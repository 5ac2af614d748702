"""Shared plumbing for everything that runs on the chip: bench.py,
chip_smoke.py and the kernel A/B tools.

One process owns the chip, so every check here runs in-process: the
platform is read from ``jax.devices()`` and a run that does not find a TPU
exits non-zero instead of measuring something else. Timings are host-clock
spans around work that ends in ``block_until_ready``.
"""
from __future__ import annotations

import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def require_tpu(log=None):
    """The first device, which must be a TPU running compiled kernels.

    Exits 2 (permanent wrong-environment) on any other platform, and when
    PADDLE_TPU_PALLAS_INTERPRET=1 would swap the Pallas kernels for the
    interpreter on the chip."""
    import jax

    _log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _log(f"platform is {dev.platform!r}, not 'tpu' — this run measures "
             "the chip or nothing (exit 2)")
        sys.exit(2)
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1":
        _log("PADDLE_TPU_PALLAS_INTERPRET=1 on a TPU would run the Pallas "
             "kernels in the interpreter — unset it (exit 2)")
        sys.exit(2)
    return dev


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache before the first compile.

    Where JAX_COMPILATION_CACHE_DIR is set JAX already reads it and nothing
    is set in code; otherwise the cache lives at a FIXED path inside the
    checkout (the path is part of the cache key — a directory that moves
    never hits). Returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def iter_notes_rows(path):
    """Yield parsed rows from a BENCH_NOTES jsonl file, skipping unreadable
    lines — the one shared parser for every tool's banked-row resume logic
    (bench_decode._already_banked, bench_flash resume)."""
    import json
    try:
        with open(path) as f:
            for ln in f:
                try:
                    yield json.loads(ln)
                except ValueError:
                    continue
    except OSError:
        return


def bench_chained(step, carry, consts, iters=32, reps=3, donate=False):
    """Time `step(carry, *consts) -> carry` chained ITERS times in one jit.

    `carry` may be any pytree; returns (seconds_per_iter, final_carry) —
    final_carry matters when the caller donates buffers into the chain
    (donate=True aliases the carry in-place; required when the carry is a
    multi-GB state that would otherwise double in HBM). Best of `reps`
    host-clock spans, each closed by ``block_until_ready``.
    """
    import functools

    import jax

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def many(carry, *consts):
        def body(_, c):
            return step(c, *consts)
        return jax.lax.fori_loop(0, iters, body, carry)

    out = jax.block_until_ready(many(carry, *consts))  # compile + settle
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(many(out, *consts))
        best = min(best, time.perf_counter() - t0)
    return best / iters, out
