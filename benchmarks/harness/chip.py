"""The device: which chip this is, what it can do at best, what it holds.

Peaks are published figures, keyed by ``device_kind`` as JAX reports it. A
kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import os
import sys
from typing import Dict

from .spec import ROOT

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB HBM per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"no published peak for device kind {device_kind!r}: add it to "
            f"benchmarks/harness/chip.py PEAKS with its source") from None


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a FIXED path inside the
    checkout (the path is part of the key). Where JAX_COMPILATION_CACHE_DIR
    is set JAX already uses it and nothing is set in code."""
    import jax

    # every program, however small: a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_chips(n: int):
    """The ``n`` TPU chips this cell asks for, or exit 2 with nothing on
    stdout. A run measures the chip or nothing."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"platform is {devs[0].platform!r}, not 'tpu': this benchmark "
              "measures the chip or nothing", file=sys.stderr)
        sys.exit(2)
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1":
        print("PADDLE_TPU_PALLAS_INTERPRET=1 on a TPU would interpret the "
              "Pallas kernels: unset it", file=sys.stderr)
        sys.exit(2)
    if len(devs) < n:
        print(f"cell needs {n} chip(s), JAX reports {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    peaks(devs[0].device_kind)
    return devs[:n]


def describe(devices) -> Dict[str, object]:
    """The ``device`` object of the result line (memory peak: the fullest
    chip; None where the backend reports none, as the CPU does)."""
    peak = None
    limit = None
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peak = max(peak or 0, int(st["peak_bytes_in_use"]))
            limit = int(st.get("bytes_limit", 0)) or limit
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak,
            "memory_limit_bytes": limit}
