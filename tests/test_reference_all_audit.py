"""Exhaustive __all__ audit: EVERY reference namespace with a module-level
__all__ must resolve name-for-name in this package (r5 session 3: this
sweep found 18 namespaces the per-namespace parity gates missed —
callbacks facade, quantization/sparse submodule layout, utils helpers,
inference extras, device/cuda|xpu facades, fleet role makers/data
generators, functional optimizers...; all closed). The skip list is the
reference's internal/legacy machinery with no public contract; the
allowed-gaps list is the documented descopes (README).
"""
import importlib
import os
import re

import pytest

REF = "/root/reference/python/paddle"

# reference-internal trees with no public API contract (legacy fluid,
# meta-optimizer program rewrites, transpilers, launch plugins) — the
# public surfaces they back are covered via their paddle.* facades
SKIP_PREFIXES = (
    "fluid", "incubate/fleet", "distributed/fleet/meta_optimizers",
    "distributed/transpiler", "distributed/ps", "distributed/passes",
    "incubate/distributed", "distributed/launch/plugins",
)

# documented descopes (README "Documented descopes"): IPU-hardware trio
ALLOWED_GAPS = {
    "static": {"ipu_shard_guard", "IpuCompiledProgram", "IpuStrategy",
               "set_ipu_shard"},
}


def _iter_reference_alls():
    for dirpath, _dirs, files in os.walk(REF):
        rel = os.path.relpath(dirpath, REF)
        if any(rel == p or rel.startswith(p + "/") for p in SKIP_PREFIXES):
            continue
        for fn in files:
            if fn != "__init__.py" and not (fn.endswith(".py")
                                            and dirpath == REF):
                continue
            src = open(os.path.join(dirpath, fn), encoding="utf-8",
                       errors="ignore").read()
            m = re.search(r"^__all__ = \[(.*?)\]", src, re.S | re.M)
            if not m:
                continue
            names = re.findall(r'["\']([^"\']+)["\']', m.group(1))
            if not names:
                continue
            mod_rel = (rel if fn == "__init__.py"
                       else (fn[:-3] if rel == "." else rel + "/" + fn[:-3]))
            yield mod_rel, names


@pytest.mark.needs_reference
def test_every_reference_all_resolves():
    failures = {}
    for mod_rel, names in _iter_reference_alls():
        mod_path = ("paddle_tpu" if mod_rel in (".", "")
                    else "paddle_tpu." + mod_rel.replace("/", "."))
        try:
            mod = importlib.import_module(mod_path)
        except Exception as e:
            failures[mod_rel] = f"MODULE MISSING ({type(e).__name__}: {e})"
            continue
        allowed = ALLOWED_GAPS.get(mod_rel, set())
        miss = [n for n in names if n not in allowed
                and not hasattr(mod, n)]
        if miss:
            failures[mod_rel] = miss
    assert not failures, "\n".join(f"{k}: {v}"
                                   for k, v in sorted(failures.items()))
