#!/usr/bin/env python
"""Dump the public API surface as signature fingerprints.

Reference parity: ``/root/reference/tools/print_signatures.py`` → the
``paddle/fluid/API.spec`` CI gate — the reference hashes every public
callable's signature so a silent argument rename/reorder fails CI. Here:
one line per public callable, ``<dotted name> <signature>``, sorted;
the checked-in ``API.spec`` is diffed by ``tests/test_api_fingerprint.py``
(and ``tools/check_parity.sh``).

Regenerate after an intentional API change:
    python tools/print_signatures.py > API.spec
"""
from __future__ import annotations

import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Namespaces whose __all__ constitutes the fingerprinted surface. Chosen to
# match the reference's API.spec scope: everything a user program imports.
NAMESPACES = [
    "paddle_tpu",
    "paddle_tpu.nn",
    "paddle_tpu.nn.functional",
    "paddle_tpu.nn.initializer",
    "paddle_tpu.optimizer",
    "paddle_tpu.optimizer.lr",
    "paddle_tpu.distributed",
    "paddle_tpu.distributed.fleet",
    "paddle_tpu.distributed.ps",
    "paddle_tpu.amp",
    "paddle_tpu.autograd",
    "paddle_tpu.jit",
    "paddle_tpu.static",
    "paddle_tpu.static.nn",
    "paddle_tpu.io",
    "paddle_tpu.vision.models",
    "paddle_tpu.vision.transforms",
    "paddle_tpu.vision.ops",
    "paddle_tpu.models",
    "paddle_tpu.metric",
    "paddle_tpu.metrics",
    "paddle_tpu.faults",
    "paddle_tpu.checkpoint",
    "paddle_tpu.analysis",
    "paddle_tpu.distribution",
    "paddle_tpu.sparse",
    "paddle_tpu.fft",
    "paddle_tpu.signal",
    "paddle_tpu.onnx",
    "paddle_tpu.inference",
    "paddle_tpu.serving",
    "paddle_tpu.loadgen",
    "paddle_tpu.quantization",
    "paddle_tpu.profiler",
    "paddle_tpu.incubate.nn",
    "paddle_tpu.incubate.optimizer",
    "paddle_tpu.incubate.autograd",
]


def _sig_of(obj) -> str:
    """Signature string, or a stable fallback class for uninspectables."""
    target = obj
    if inspect.isclass(obj):
        target = obj.__init__
    try:
        sig = inspect.signature(target)
    except (ValueError, TypeError):
        return "(*uninspectable*)"
    parts = []
    for p in sig.parameters.values():
        if p.name == "self":
            continue
        s = p.name
        if p.kind == inspect.Parameter.VAR_POSITIONAL:
            s = "*" + s
        elif p.kind == inspect.Parameter.VAR_KEYWORD:
            s = "**" + s
        if p.default is not inspect.Parameter.empty:
            d = repr(p.default)
            if " object at 0x" in d:  # unstable instance repr
                d = f"<{type(p.default).__name__}>"
            s += f"={d}"
        parts.append(s)
    return "(" + ", ".join(parts) + ")"


def fingerprint_lines() -> list:
    import importlib
    import types

    # import everything FIRST: for namespaces without __all__ the dir()
    # fallback must not depend on which submodules a prior test imported
    mods = {}
    for ns in NAMESPACES:
        try:
            mods[ns] = importlib.import_module(ns)
        except ImportError as e:  # a namespace vanishing IS a finding
            mods[ns] = e

    lines = []
    for ns, mod in mods.items():
        if isinstance(mod, ImportError):
            lines.append(f"{ns} <IMPORT ERROR: {type(mod).__name__}>")
            continue
        names = getattr(mod, "__all__", None)
        if names is None:
            names = [n for n in dir(mod) if not n.startswith("_")]
        for name in sorted(set(names)):
            obj = getattr(mod, name, None)
            if isinstance(obj, types.ModuleType):
                continue  # submodule attrs aren't signatures (and their
                # presence depends on import order)
            if obj is None:
                lines.append(f"{ns}.{name} <MISSING>")
            elif callable(obj):
                lines.append(f"{ns}.{name} {_sig_of(obj)}")
            else:
                lines.append(f"{ns}.{name} <{type(obj).__name__}>")
    return sorted(set(lines))


if __name__ == "__main__":
    print("\n".join(fingerprint_lines()))
