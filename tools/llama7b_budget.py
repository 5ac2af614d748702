#!/usr/bin/env python
"""Llama-2 7B compile-only memory budget (BASELINE.md config 4).

Proves the 7B flagship FITS and COMPILES on a v5e-8-shaped mesh without
needing 8 real chips: builds the real ``LlamaForCausalLM`` at full 7B
shapes (zero-init — no RNG cost; values never matter because nothing
executes), applies the production recipe (ZeRO-3 ``p_g_os`` sharding +
per-layer recompute + fused chunked linear+CE + bf16 O2 master weights),
AOT-lowers the FULL train step through ``StaticFunction.lower()`` on an
8-virtual-device CPU mesh, and reads XLA's own buffer-assignment peak
(``compiled.memory_analysis().peak_memory_in_bytes`` — per device under
SPMD) plus a closed-form analytic table.

Reference counterpart: the reference proves 7B feasibility by running it
(Fleet 4D, BASELINE.md item 4); on TPU the compile-only route is exact
for the memory question because XLA's buffer assignment IS the runtime
allocator (no dynamic allocation at step time).

Usage (re-execs itself pinned to the CPU):
    python tools/llama7b_budget.py              # full 7B, ~8 virtual chips
    python tools/llama7b_budget.py --smoke      # tiny shapes, CI-speed
Writes LLAMA7B_BUDGET.md + prints one JSON line; exits nonzero if the
per-chip peak exceeds --hbm-gb (default 16, v5e).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

V5E_HBM_GB = 16.0


def _reexec_cpu(n_devices: int) -> None:
    from _budget_common import reexec_cpu
    reexec_cpu("_LLAMA7B_BUDGET_CHILD", n_devices)


def _zero_init_parameters() -> None:
    from _budget_common import zero_init_parameters
    zero_init_parameters()


def _analytic_rows(n_params: int, n_layers: int, hidden: int, batch: int,
                   seq: int, shards: int):
    """Closed-form per-chip budget for ZeRO-3 + bf16 O2 + recompute.
    Activation term: recompute stores only per-layer residual-stream
    boundaries (B*S*H bf16 each) + one layer's working set at backward."""
    rows = [
        ("params (fp32 master, ZeRO-3 sharded)", 4 * n_params / shards),
        ("params (bf16 compute copy, sharded)", 2 * n_params / shards),
        ("grads (bf16, reduce-scattered)", 2 * n_params / shards),
        ("adam m+v (fp32, sharded)", 8 * n_params / shards),
        ("residual boundaries (recompute)", 2 * batch * seq * hidden
         * n_layers),
        ("one-layer recompute working set (~6 B*S*H)",
         6 * 2 * batch * seq * hidden),
        ("all-gather buffer (largest layer, bf16)",
         2 * max(3 * hidden * 11008, 4 * hidden * hidden)),
    ]
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI validation of the flow)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--hbm-gb", type=float, default=V5E_HBM_GB)
    ap.add_argument("--no-write", action="store_true",
                    help="don't write LLAMA7B_BUDGET.md (smoke/CI)")
    args = ap.parse_args()
    _reexec_cpu(args.devices)

    import numpy as np

    _zero_init_parameters()

    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 1, "pp_degree": 1, "sep_degree": 1,
        "sharding_degree": args.devices,
    }
    fleet.init(is_collective=True, strategy=strategy)

    if args.smoke:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128, num_layers=2,
                          num_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256,
                          recompute=True, fused_loss=True)
        batch, seq = 2, 128
    else:
        # Llama-2 7B (reference: llama-2-7b config.json — 32L/4096H/32H,
        # intermediate 11008, vocab 32000, ctx 4096)
        cfg = LlamaConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                          num_heads=32, num_key_value_heads=32,
                          intermediate_size=11008,
                          max_position_embeddings=args.seq,
                          recompute=True, fused_loss=True)
        batch, seq = args.batch, args.seq

    print(f"[budget] building model (zero-init, {args.devices}-dev mesh, "
          f"B{batch} S{seq})", flush=True)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    print(f"[budget] params: {n_params/1e9:.3f} B", flush=True)

    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.1)
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    group_sharded_parallel(model, opt, "p_g_os")

    def train_fn(ids, labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            _, loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = jit.StaticFunction(train_fn, observe=[model, opt], warmup=False)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)))
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)))

    print("[budget] AOT lowering full train step (no execution)...",
          flush=True)
    lowered = step.lower(ids, labels)
    print("[budget] compiling (XLA buffer assignment)...", flush=True)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}

    peak = int(ma.peak_memory_in_bytes)
    gb = 1024 ** 3
    record = {
        "metric": "llama7b_per_chip_peak_hbm_gb" if not args.smoke
        else "llama_budget_smoke_peak_gb",
        "value": round(peak / gb, 2),
        "unit": "GiB/chip",
        "params_b": round(n_params / 1e9, 3),
        "config": f"zero3+recompute+fused_ce b{batch} s{seq} "
                  f"x{args.devices}dev",
        "argument_gb": round(ma.argument_size_in_bytes / gb, 2),
        "output_gb": round(ma.output_size_in_bytes / gb, 2),
        "temp_gb": round(ma.temp_size_in_bytes / gb, 2),
        "alias_gb": round(ma.alias_size_in_bytes / gb, 2),
        "flops_per_step": cost.get("flops"),
        "hbm_limit_gb": args.hbm_gb,
        "fits": peak / gb < args.hbm_gb,
    }
    print(json.dumps(record), flush=True)

    if not args.smoke and not args.no_write:
        rows = _analytic_rows(n_params, cfg.num_layers, cfg.hidden_size,
                              batch, seq, args.devices)
        lines = [
            "# Llama-2 7B per-chip memory budget (v5e-8, compile-only)",
            "",
            f"Recipe: ZeRO-3 (`p_g_os`) over sharding={args.devices}, "
            "per-layer recompute, fused chunked linear+CE (no [B*S,V] "
            f"logits), bf16 O2 master weights. B={batch}, S={seq}.",
            "",
            "## XLA buffer assignment (ground truth, per chip)",
            "",
            "| stat | GiB |",
            "|---|---|",
            f"| **peak** | **{peak/gb:.2f}** |",
            f"| arguments (params+opt state) | "
            f"{ma.argument_size_in_bytes/gb:.2f} |",
            f"| temps (activations, gathers) | "
            f"{ma.temp_size_in_bytes/gb:.2f} |",
            f"| outputs | {ma.output_size_in_bytes/gb:.2f} |",
            f"| aliased (donated state) | {ma.alias_size_in_bytes/gb:.2f} |",
            "",
            f"v5e HBM/chip: {args.hbm_gb:.0f} GiB -> "
            f"**{'FITS' if record['fits'] else 'DOES NOT FIT'}** "
            f"(headroom {args.hbm_gb - peak/gb:.1f} GiB).",
            "",
            "## Analytic cross-check (closed form)",
            "",
            "| component | GiB/chip |",
            "|---|---|",
        ]
        total = 0
        for name, b in rows:
            total += b
            lines.append(f"| {name} | {b/gb:.2f} |")
        lines += [
            f"| **sum** | **{total/gb:.2f}** |",
            "",
            "The analytic sum is the everything-live-at-once worst case; "
            "XLA's buffer liveness typically lands the true peak well "
            "below it (transient bf16 copies, grad buffers aliasing into "
            "the optimizer update). Temps total counts every temp "
            "allocation over the step, not the concurrent peak.",
            "",
            f"Params: {n_params/1e9:.3f} B. Generated by "
            "`tools/llama7b_budget.py` (StaticFunction.lower -> "
            "compiled.memory_analysis; per-device under SPMD).",
        ]
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "LLAMA7B_BUDGET.md")
        with open(out, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"[budget] wrote {out}", flush=True)

    return 0 if record["fits"] else 1


if __name__ == "__main__":
    sys.exit(main())
