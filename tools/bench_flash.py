#!/usr/bin/env python
"""Flash-attention A/B: Pallas kernel (block-size sweep) vs XLA fused
attention, fwd and fwd+bwd, S ∈ {512, 1024, 2048, 4096} (VERDICT r2 #2).

Run ON the TPU. Appends one JSON line per (S, impl,
blocks, direction) to BENCH_NOTES_r05.json and prints a summary table to
stderr, plus a final recommendation line: the measured per-S dispatch
threshold for nn/functional/attention.py's pallas_flash_min_seq.

Usage: python tools/bench_flash.py [--quick]
"""
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

_NOTES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                      "BENCH_NOTES_r05.json")


def _log(m):
    print(m, file=sys.stderr, flush=True)


def _persist(rec):
    rec = dict(rec, ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


from _bench_timing import bench_chained  # noqa: E402  (shared clock — both
#   A/B harnesses must time identically; see _bench_timing.py)


def _bench(step, q, k, v, iters=32, reps=3):
    """Time `step` (a (q,k,v)->array-of-q's-shape fn); see _bench_timing."""
    t, _ = bench_chained(lambda qq, k, v: step(qq, k, v), q, (k, v),
                         iters=iters, reps=reps)
    return t


def _load_banked(notes_path, D):
    """Banked flash_ab_summary entries for head dim D from the notes file:
    (banked_rec {str(S): entry}, banked_reps {int(S): reps}). Newest row
    wins per S — a --force re-measure deliberately supersedes older rows —
    and rows without a reps field gate at 0 (never satisfy a skip)."""
    from _bench_timing import iter_notes_rows

    banked_rec, banked_reps = {}, {}
    for row in iter_notes_rows(notes_path):
        if (row.get("metric") == "flash_ab_summary"
                and row.get("device") == "tpu"
                and row.get("D", 64) == D):
            for s, entry in row.get("per_seq", {}).items():
                banked_rec[s] = entry
                banked_reps[int(s)] = row.get("reps", 0)
    return banked_rec, banked_reps


def _summarize_s(results, S):
    """Best-pallas-vs-xla summary entry for one S from the timing dict, or
    None when either side is missing (e.g. every pallas block failed)."""
    xla = results.get((S, "xla", None))
    if xla is None:
        return None
    pl_best = None
    for (s2, impl, blk), (tf, tb) in results.items():
        if s2 == S and impl == "pallas" and (
                pl_best is None or tb < pl_best[1][1]):
            pl_best = (blk, (tf, tb))
    if pl_best is None:
        return None
    win = pl_best[1][1] < xla[1]
    return {"xla_ms": round(xla[1] * 1e3, 2),
            "pallas_ms": round(pl_best[1][1] * 1e3, 2),
            "best_blocks": list(pl_best[0]), "pallas_wins": bool(win)}


def main():
    from _bench_timing import require_tpu

    # a CPU sweep would produce numbers meaningless for dispatch thresholds
    dev = require_tpu(log=_log)
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa

    quick = "--quick" in sys.argv
    argv = sys.argv
    # tie-break mode: --s 1024 --reps 9 restricts the sweep and raises
    # repetitions (the r4 sweeps' large-block S=1024 configs differed by
    # less than run-to-run noise at reps=3)
    only_s = (int(argv[argv.index("--s") + 1]) if "--s" in argv else None)
    reps = (int(argv[argv.index("--reps") + 1]) if "--reps" in argv else 3)
    # --d 128: the gpt13/llama head geometry (16 heads x 128) — block
    # timings at D=64 don't transfer (VMEM tile footprint doubles)
    only_d = (int(argv[argv.index("--d") + 1]) if "--d" in argv else None)
    _log(f"device: {dev.platform} {dev.device_kind}")

    H, D = 16, 64  # flagship head geometry (GPT-355M: 16 heads x 64)
    if only_d is not None:
        D = only_d
    seqs = [1024] if quick else [512, 1024, 2048, 4096]
    if only_s is not None:
        seqs = [only_s]

    # resume: a re-run after a cut sweep must not re-measure (and
    # duplicate) already-banked S values — summary rows checkpoint PER S
    # as each completes; skip semantics live in _load_banked's docstring
    banked_rec, banked_reps = (
        _load_banked(_NOTES, D) if "--force" not in argv else ({}, {}))
    skip_s = {s for s, r in banked_reps.items() if r >= reps}
    if skip_s & set(seqs):
        _log(f"banked this round at reps>={reps} (skipping, --force to "
             f"re-measure): {sorted(skip_s & set(seqs))}")
    blocks = [(256, 512), (512, 512), (1024, 512), (512, 1024),
              (1024, 1024), (256, 1024)]
    causal, scale = True, 1.0 / np.sqrt(D)

    def xla_attn(q, k, v):
        # The PRODUCTION XLA path (attention._sdpa_ref): bf16 logits on the
        # MXU, f32 softmax. fa._ref_attention_bshd casts everything to f32 —
        # that is a numerics oracle, not a fair perf baseline (and its bwd
        # OOMs at S=2048: f32 [B,H,S,S] temps — measured r4).
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
        sq_, sk_ = logits.shape[-2], logits.shape[-1]
        cm = np.tril(np.ones((sq_, sk_), bool), sk_ - sq_)
        logits = jnp.where(jnp.asarray(cm), logits,
                           jnp.asarray(-1e30, logits.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
        return jnp.swapaxes(out, 1, 2)

    results = {}
    for S in seqs:
        if S in skip_s:
            continue
        B = max(1, 8 * 1024 // S)  # constant token budget ~8k
        rng = np.random.default_rng(0)
        mk = lambda: jnp.asarray(
            rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()

        def _chain_fwd(attn):
            def step(qq, k, v):
                o = attn(qq, k, v)
                return o / (jnp.max(jnp.abs(o.astype(jnp.float32)))
                            + 1e-6).astype(o.dtype)
            return step

        def _chain_bwd(attn):
            g = jax.grad(lambda qq, k, v: jnp.sum(
                attn(qq, k, v).astype(jnp.float32)), argnums=(0, 1, 2))

            def step(qq, k, v):
                # mix all three grads into the carry so none of the bwd
                # computation is dead code the compiler can strip
                dq, dk, dv = g(qq, k, v)
                mix = dq + 0.0625 * (dk + dv)
                return mix / (jnp.max(jnp.abs(mix.astype(jnp.float32)))
                              + 1e-6).astype(mix.dtype)
            return step

        # XLA reference, fwd and fwd+bwd
        t_fwd = _bench(_chain_fwd(xla_attn), q, k, v, reps=reps)
        t_bwd = _bench(_chain_bwd(xla_attn), q, k, v, reps=reps)
        results[(S, "xla", None)] = (t_fwd, t_bwd)
        _log(f"S={S} B={B} xla          fwd {t_fwd*1e3:7.2f}ms  "
             f"fwd+bwd {t_bwd*1e3:7.2f}ms")
        _persist({"metric": "flash_ab", "impl": "xla", "S": S, "B": B,
                  "H": H, "D": D, "fwd_ms": round(t_fwd * 1e3, 2),
                  "fwdbwd_ms": round(t_bwd * 1e3, 2),
                  "device": dev.platform})

        for bq, bk in blocks:
            if bq > S or bk > S:
                continue

            def pallas_attn(q, k, v, _bq=bq, _bk=bk):
                return fa._flash_attention(q, k, v, jnp.float32(0), causal, scale, _bq, _bk)

            try:
                t_fwd = _bench(_chain_fwd(pallas_attn), q, k, v, reps=reps)
                t_bwd = _bench(_chain_bwd(pallas_attn), q, k, v, reps=reps)
            except Exception as e:
                _log(f"S={S} pallas bq{bq}/bk{bk} FAILED: "
                     f"{type(e).__name__}: {str(e)[:160]}")
                _persist({"metric": "flash_ab", "impl": "pallas",
                          "S": S, "bq": bq, "bk": bk,
                          "error": f"{type(e).__name__}: {str(e)[:300]}",
                          "device": dev.platform})
                continue
            results[(S, "pallas", (bq, bk))] = (t_fwd, t_bwd)
            _log(f"S={S} B={B} pallas {bq:4d}/{bk:<4d} fwd {t_fwd*1e3:7.2f}ms"
                 f"  fwd+bwd {t_bwd*1e3:7.2f}ms")
            _persist({"metric": "flash_ab", "impl": "pallas", "S": S,
                      "B": B, "H": H, "D": D, "bq": bq, "bk": bk,
                      "fwd_ms": round(t_fwd * 1e3, 2),
                      "fwdbwd_ms": round(t_bwd * 1e3, 2),
                      "device": dev.platform})

        # checkpoint THIS S the moment it completes: a run cut mid-sweep
        # must not cost the next call the S values already measured
        entry = _summarize_s(results, S)
        if entry is not None:
            _persist({"metric": "flash_ab_summary", "per_seq": {S: entry},
                      "D": D, "reps": reps, "device": dev.platform})

    # recommendation: per S, best pallas config vs xla on fwd+bwd.
    # (The durable record is the per-S checkpoint rows persisted above —
    # nothing more is persisted here, so carried entries are never
    # re-dated and a partial run banks exactly what it measured.)
    _log("\n=== summary (fwd+bwd) ===")
    rec = {}
    for S in seqs:
        if S in skip_s:  # carry the banked row into this run's summary
            rec[S] = banked_rec[str(S)]
            _log(f"S={S}: (banked) xla {rec[S]['xla_ms']}ms vs pallas "
                 f"{rec[S]['pallas_ms']}ms @bq/bk={rec[S]['best_blocks']}")
            continue
        entry = _summarize_s(results, S)
        if entry is None:
            continue
        rec[S] = entry
        _log(f"S={S}: xla {entry['xla_ms']}ms vs pallas "
             f"{entry['pallas_ms']}ms @bq/bk={entry['best_blocks']} "
             f"-> {'PALLAS' if entry['pallas_wins'] else 'XLA'}")
    wins = sorted(s for s, r in rec.items() if r["pallas_wins"])
    threshold = wins[0] if wins else None
    _log(f"recommended pallas_flash_min_seq = {threshold}")
    print(json.dumps({"metric": "flash_ab_summary", "per_seq": rec,
                      "recommended_min_seq": threshold}))


if __name__ == "__main__":
    main()
