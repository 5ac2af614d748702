#!/usr/bin/env python
"""A/B: fused AdamW Pallas kernel vs XLA elementwise update (VERDICT r2 #6).

Run ON the TPU. 355M-param-scale flat buffers (the bench model's size).
Appends the result to BENCH_NOTES_r05.json.

Timing: chained data-dependent iterations inside one jit, closed by
`block_until_ready` (_bench_timing.bench_chained). Correctness is checked
at small N first;
the timed run holds only one (w, m, v) chain to stay inside HBM
(355M x 4 states x f32 in+out with both impls' outputs live OOMed r4).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

_NOTES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                      "BENCH_NOTES_r05.json")


from _bench_timing import bench_chained  # noqa: E402  (shared clock — both
#   A/B harnesses must time identically; see _bench_timing.py)


def _bench(update, w, m, v, g, lr, t, iters=20, reps=3):
    # donate=True: the loop carry aliases the (w, m, v) state — without it,
    # inputs + carry + outputs tripled the 4.3GB state and OOMed a 16GB
    # chip (measured r4). The final carry is handed back so the next impl
    # can be benchmarked on the same buffers.
    def step(c, g):
        return update(c[0], c[1], c[2], g, lr, t)

    return bench_chained(step, (w, m, v), (g,), iters=iters, reps=reps,
                         donate=True)


def main():
    from _bench_timing import require_tpu

    # the pallas A/B side has no CPU-interpret path — a CPU "run" only
    # ever produced a mid-sweep crash
    dev = require_tpu()
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_adamw import (fused_adamw_flat,
                                                   xla_adamw_flat)

    n = int(os.environ.get("BENCH_ADAMW_N", 355_000_000))
    # align to the LARGEST swept blocking (256*1024): the kernel's pad
    # path would otherwise copy all four flat buffers every loop
    # iteration, and a rows count not divisible by block_rows makes
    # fused_adamw_flat halve its block (8192-alignment benched a crippled
    # 16x1024 blocking in r4 — every sweep point must run at its stated
    # blocking)
    n -= n % (256 * 1024)
    print(f"device={dev.platform} n={n}", file=sys.stderr)
    rng = np.random.default_rng(0)
    lr = jnp.float32(1e-4)
    t = jnp.float32(10.0)

    # correctness first, at a size where both impls' outputs fit comfortably
    ns = min(n, 2_000_000)
    ws = jnp.asarray(rng.standard_normal(ns), jnp.float32)
    ms = jnp.zeros(ns, jnp.float32)
    vs = jnp.zeros(ns, jnp.float32)
    gs = jnp.asarray(rng.standard_normal(ns), jnp.float32) * 1e-3
    o_pl = jax.jit(fused_adamw_flat)(ws, ms, vs, gs, lr, t)
    o_x = jax.jit(xla_adamw_flat)(ws, ms, vs, gs, lr, t)
    for a, b in zip(o_pl, o_x):
        np.testing.assert_allclose(np.asarray(a[:4096]), np.asarray(b[:4096]),
                                   rtol=1e-6, atol=1e-7)
    del o_pl, o_x, ws, ms, vs, gs
    print("numerics match", file=sys.stderr)

    w = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32) * 1e-3

    # blocking sweep: 128 is the largest block that fits v5e's 16MB scoped
    # VMEM (measured r5: the original 256 design point needs 16.79M and
    # fails to compile); 256 stays in the sweep to document exactly that,
    # and in case future hardware fits it
    import functools

    pallas_rows = {}
    for br in (128, 256):
        try:
            t_br, (w, m, v) = _bench(
                functools.partial(fused_adamw_flat, block_rows=br),
                w, m, v, g, lr, t)
            pallas_rows[br] = round(t_br * 1e3, 3)
        except Exception as e:
            # a compile/runtime resource failure is DATA (the 256-row
            # point is expected to exceed v5e's scoped VMEM); anything
            # else is a bug in the harness/kernel and must surface
            msg = f"{type(e).__name__}: {e}"
            # match on resource-exhaustion STATUS text, not wrapper type
            # names — jaxlib wraps every runtime error in XlaRuntimeError
            # and swallowing those would bank wrong verdicts
            if not any(s in msg for s in
                       ("RESOURCE_EXHAUSTED", "ResourceExhausted",
                        "vmem", "VMEM")):
                raise
            pallas_rows[br] = f"compile-fail: {msg[:80]}"
            print(f"pallas block_rows={br}: {pallas_rows[br]}",
                  file=sys.stderr)
            # only a runtime failure lands after the carry was donated;
            # a compile-time failure leaves the buffers alive — skip the
            # ~4.3GB rebuild then
            if w.is_deleted():
                w = jnp.asarray(rng.standard_normal(n), jnp.float32)
                m = jnp.zeros(n, jnp.float32)
                v = jnp.zeros(n, jnp.float32)
    timed = [v_ for v_ in pallas_rows.values() if isinstance(v_, float)]
    if not timed:
        print("no pallas blocking compiled; XLA wins by default",
              file=sys.stderr)
    t_pl = min(timed) / 1e3 if timed else float("inf")
    t_x, _ = _bench(xla_adamw_flat, w, m, v, g, lr, t)
    gb = n * 4 * 7 / 1e9  # r: w,m,v,g  w: w,m,v
    rec = {
        "metric": "fused_adamw_ab", "n_params": n,
        "pallas_ms": round(t_pl * 1e3, 3) if timed else None,
        "pallas_ms_by_block_rows": pallas_rows,
        "xla_ms": round(t_x * 1e3, 3),
        "pallas_gbps": round(gb / t_pl, 1) if timed else None,
        "xla_gbps": round(gb / t_x, 1),
        "pallas_wins": bool(t_pl < t_x), "device": str(dev.platform),
    }
    print(json.dumps(rec))
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
