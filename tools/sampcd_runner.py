#!/usr/bin/env python
"""Docstring code-sample runner — the repo's equivalent of the reference's
``tools/sampcd_processor.py`` (which extracts ``>>> `` example blocks from
API docstrings and executes them as CI; see reference
tools/sampcd_processor.py:1 "Sample code check").

TPU-first redesign: samples run on CPU (main() pins JAX_PLATFORMS=cpu
before jax is imported, so doc snippets never claim a chip), each
docstring's block executes in a fresh namespace with ``paddle``
pre-imported, and failures report module:qualname so the sample is
findable. Output matching is NOT enforced (array reprs are
device/precision-dependent); a sample passes iff it executes without
raising — the same contract the reference applies to non-deterministic
samples via its SKIP directives.

Usage:
  python tools/sampcd_runner.py            # whole package
  python tools/sampcd_runner.py nn jit     # only these subpackage prefixes
"""
import doctest
import os
import sys
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PKG = "paddle_tpu"


def iter_sample_blocks(prefixes=()):
    """Yield (location, sample_source) for every ``>>>`` block in package
    docstrings, found by scanning source files (import-free discovery —
    importing every module to inspect it would execute heavyweight module
    bodies twice and hide import-order bugs)."""
    parser = doctest.DocTestParser()
    pkg_root = os.path.join(REPO, PKG)
    for dirpath, _dirnames, filenames in os.walk(pkg_root):
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, REPO)
            mod_rel = os.path.relpath(path, pkg_root)
            if prefixes and not any(
                    mod_rel.startswith(p) for p in prefixes):
                continue
            try:
                src = open(path, encoding="utf-8").read()
            except OSError:
                continue
            if ">>> " not in src:
                continue
            import ast as _ast
            try:
                tree = _ast.parse(src)
            except SyntaxError:
                continue
            for node in _ast.walk(tree):
                if not isinstance(node, (_ast.Module, _ast.ClassDef,
                                         _ast.FunctionDef,
                                         _ast.AsyncFunctionDef)):
                    continue
                doc = _ast.get_docstring(node, clean=True)
                if not doc or ">>>" not in doc:
                    continue
                name = getattr(node, "name", "<module>")
                try:
                    examples = parser.get_examples(doc)
                except ValueError as e:
                    # malformed sample (inconsistent indentation etc.):
                    # report it as a failing block with attribution
                    # instead of killing the whole discovery walk
                    yield (f"{rel}:{name}",
                           f"raise ValueError({str(e)[:120]!r})")
                    continue
                if not examples:
                    continue
                block = "".join(e.source for e in examples)
                yield f"{rel}:{name}", block


def run_block(location, source):
    ns = {}
    preamble = ("import numpy as np\n"
                "import paddle_tpu as paddle\n")
    try:
        exec(preamble + source, ns)  # noqa: S102 — that IS the check
        return None
    except Exception:
        return traceback.format_exc(limit=3)


def main():
    # doc snippets must never claim a chip: nothing has imported jax yet
    os.environ["JAX_PLATFORMS"] = "cpu"
    prefixes = tuple(sys.argv[1:])
    blocks = list(iter_sample_blocks(prefixes))
    if not blocks:
        print("no docstring samples found", file=sys.stderr)
        return 1
    failures = []
    for loc, src in blocks:
        err = run_block(loc, src)
        status = "ok" if err is None else "FAIL"
        print(f"  [{status}] {loc} ({len(src.splitlines())} lines)")
        if err:
            failures.append((loc, err))
    print(f"{len(blocks) - len(failures)}/{len(blocks)} sample blocks pass")
    for loc, err in failures:
        print(f"--- {loc} ---\n{err}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
