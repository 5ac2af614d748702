#!/usr/bin/env python
"""GPT-3 1.3B single-chip memory-budget sweep (BASELINE.json north star).

The north-star config is GPT-3 1.3B (h2048 l24 heads16 — the GPT-3 paper's
"XL" row, d_head 128) at >=40% MFU. One v5e chip has 16 GiB; with fp32
master weights AdamW state alone is ~18.4 GiB (14 B/param), so the fit
depends on which levers are on. This tool AOT-lowers the REAL train step
(StaticFunction.lower -> compiled.memory_analysis, the same flow as
tools/llama7b_budget.py) for each lever combo on one virtual CPU device
and prints XLA's per-chip peak, worst-first-screened so the bench ladder
(bench.py --model gpt13) ranks only configs that actually fit.

Levers swept:
  master  — amp O2 fp32 master weights on/off. Off (paddle's
            multi_precision default) the accumulators are zeros_like(param)
            — bf16 params give bf16 m/v: 6 B/param, ~7.3 GiB state at
            1.3B (the sweep's measured argument_gb = 7.34 = 3 x 2.45
            confirms all three are bf16)
  rc      — recompute off / 'dots' (save MXU outputs) / full
  fce     — fused chunked linear+CE (never materializes [B*S, 50304])
  B       — per-chip batch at S=1024

Usage:
    python tools/gpt13_budget.py            # full sweep, writes GPT13_BUDGET.md
    python tools/gpt13_budget.py --smoke    # tiny shapes, CI-speed
Prints one JSON line per combo + a final summary line.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

V5E_HBM_GB = 16.0
GB = 1024 ** 3


def _reexec_cpu() -> None:
    from _budget_common import reexec_cpu
    reexec_cpu("_GPT13_BUDGET_CHILD")


def _zero_init_parameters() -> None:
    from _budget_common import zero_init_parameters
    zero_init_parameters()


def measure(combo: dict, smoke: bool) -> dict:
    """Build + AOT-lower one lever combo; returns the budget record.
    Runs in a child process (caller) so 13-GiB host buffers are freed
    between combos."""
    import numpy as np

    _zero_init_parameters()

    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if smoke:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_position_embeddings=256,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        recompute=combo["rc"] is not None,
                        recompute_policy=combo["rc"],
                        fused_loss=combo["fce"])
        B, S = 2, 128
    else:
        S = combo.get("S", 1024)
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                        num_heads=16, max_position_embeddings=max(S, 1024),
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        recompute=combo["rc"] is not None,
                        recompute_policy=(None if combo["rc"] == "full"
                                          else combo["rc"]),
                        fused_loss=combo["fce"])
        B = combo["B"]

    model = GPTForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16",
                              master_weight=combo["master"])

    def train_fn(ids, labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            _, loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = jit.StaticFunction(train_fn, observe=[model, opt], warmup=False)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (B, S)))
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (B, S)))

    t0 = time.time()
    compiled = step.lower(ids, labels).compile()
    ma = compiled.memory_analysis()
    peak = int(ma.peak_memory_in_bytes)
    import jax
    on_cpu = jax.devices()[0].platform == "cpu"
    return {
        "metric": "gpt13_budget_peak_gb",
        "value": round(peak / GB, 2),
        "unit": "GiB/chip",
        "combo": combo["tag"],
        "params_b": round(n_params / 1e9, 3),
        "argument_gb": round(ma.argument_size_in_bytes / GB, 2),
        "temp_gb": round(ma.temp_size_in_bytes / GB, 2),
        "alias_gb": round(ma.alias_size_in_bytes / GB, 2),
        # CPU buffer assignment does not liveness-schedule temps (the
        # llama smoke row's peak reads 0.0 on CPU) — a CPU "peak" can
        # only certify structure, never fit. Authoritative fit = the
        # TPU bench ladder (each rung OOMs in its own subprocess).
        "fits": (peak / GB < V5E_HBM_GB) if not on_cpu else None,
        "cpu_aot": on_cpu,
        "compile_s": round(time.time() - t0, 1),
    }


COMBOS = [
    # tag, master, rc, fce, B  (S defaults 1024)
    {"tag": "b8-dots-fce-nomaster", "master": False, "rc": "dots",
     "fce": True, "B": 8},
    {"tag": "b8-fce-nomaster", "master": False, "rc": None,
     "fce": True, "B": 8},
    {"tag": "b4-fce-nomaster", "master": False, "rc": None,
     "fce": True, "B": 4},
    {"tag": "b16-dots-fce-nomaster", "master": False, "rc": "dots",
     "fce": True, "B": 16},
    {"tag": "b8-full-fce-nomaster", "master": False, "rc": "full",
     "fce": True, "B": 8},
    # the master-weights control: expected NOT to fit (18.4 GB state)
    {"tag": "b4-dots-fce-master", "master": True, "rc": "dots",
     "fce": True, "B": 4},
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--combo", help="run ONE combo by tag (child mode)")
    ap.add_argument("--no-write", action="store_true")
    args = ap.parse_args()
    _reexec_cpu()

    if args.combo:  # child: measure one combo, print one JSON line
        combo = next(c for c in COMBOS if c["tag"] == args.combo)
        print(json.dumps(measure(combo, args.smoke)), flush=True)
        return 0

    import subprocess
    results = []
    combos = COMBOS[:2] if args.smoke else COMBOS
    for combo in combos:
        print(f"[gpt13-budget] {combo['tag']}...", file=sys.stderr,
              flush=True)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--combo", combo["tag"]]
        if args.smoke:
            cmd.append("--smoke")
        # own process group + group kill on timeout (a plain subprocess
        # kill leaves grandchildren parked in backend init — the exact
        # orphaned-claim wedge bench.py _launch_banked guards against),
        # and a slow combo must cost only itself, not the sweep
        import signal
        p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True,
                             start_new_session=True)
        try:
            out, err = p.communicate(timeout=3600)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                p.kill()
            p.communicate()
            print(f"[gpt13-budget] {combo['tag']} TIMED OUT (killed group)",
                  file=sys.stderr, flush=True)
            continue
        line = next((ln for ln in reversed(out.splitlines())
                     if ln.startswith("{")), None)
        if line is None:
            print(f"[gpt13-budget] {combo['tag']} FAILED rc={p.returncode}: "
                  f"{err[-300:]}", file=sys.stderr, flush=True)
            continue
        rec = json.loads(line)
        print(json.dumps(rec), flush=True)
        results.append(rec)

    cpu_aot = any(r.get("cpu_aot") for r in results)
    fitting = [r for r in results if r["fits"]]
    summary = {
        "metric": "gpt13_budget_summary",
        "value": len(results) if cpu_aot else len(fitting),
        "unit": "compiled_configs" if cpu_aot else "fitting_configs",
        "vs_baseline": 1.0,
        "fitting": [r["combo"] for r in fitting],
        "peaks_gb": {r["combo"]: r["value"] for r in results},
        "cpu_aot": cpu_aot,
    }
    print(json.dumps(summary), flush=True)

    if not args.smoke and not args.no_write and results:
        lines = [
            "# GPT-3 1.3B single-chip memory budget (v5e, compile-only)",
            "",
            "North-star config (BASELINE.json): GPT-3 1.3B, h2048 l24 "
            "heads16 (d_head 128), S=1024, AdamW. Per-chip peak from XLA "
            "buffer assignment (StaticFunction.lower -> memory_analysis) "
            "on one virtual device — same flow as LLAMA7B_BUDGET.md.",
            "",
            "`nomaster` = amp O2 with master_weight=False (paddle's "
            "multi_precision default): accumulators are zeros_like(param), "
            "so bf16 params give bf16 m+v = 6 B/param (~7.3 GiB state — "
            "the measured argument_gb 7.34 = 3 x 2.45 GiB bf16 buffers) "
            "vs ~18.4 GiB with fp32 masters+moments, which cannot fit "
            "one 16 GiB chip.",
            "",
            "| combo | peak GiB | args GiB | temps GiB | fits 16 GiB |",
            "|---|---|---|---|---|",
        ]
        for r in results:
            fit = ("n/a (cpu aot)" if r["fits"] is None
                   else ("yes" if r["fits"] else "NO"))
            lines.append(
                f"| {r['combo']} | {r['value']:.2f} | {r['argument_gb']:.2f}"
                f" | {r['temp_gb']:.2f} | {fit} |")
        lines += [
            "",
            "CPU AOT caveat: CPU buffer assignment does not "
            "liveness-schedule temps, so a CPU 'peak' certifies structure "
            "and argument (param+opt-state) size only. Authoritative fit "
            "is the TPU bench ladder — each rung claims the chip in its "
            "own subprocess and an OOM fails only that rung "
            "(bench.py _LADDERS['gpt13']).",
            "",
            f"Params: {results[0]['params_b']} B. Generated by "
            "`tools/gpt13_budget.py`."]
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "GPT13_BUDGET.md")
        with open(out, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"[gpt13-budget] wrote {out}", file=sys.stderr, flush=True)
    # on CPU AOT 'fits' is unknowable (None) — success = every combo
    # compiled; on TPU success = at least one fitting config
    if cpu_aot:
        return 0 if len(results) == len(combos) else 1
    return 0 if fitting else 1


if __name__ == "__main__":
    sys.exit(main())
