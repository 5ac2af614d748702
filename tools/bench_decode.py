#!/usr/bin/env python
"""Autoregressive decode throughput (KV-cache, device-side while_loop).

Greedy decode on one chip: B8, prompt 128, 128 new tokens — the whole
decode is ONE compiled program (models/generation.py device loop), so
the measurement is device time, not one host round trip per token.
Covers GPT-355M and Llama-0.76B (set BENCH_DECODE_MODELS to a
comma list to narrow). Appends each row to BENCH_NOTES_r05.json.

``--paged``: continuous-batching engine sweep (paddle_tpu.serving) —
engine tokens/s vs this dense loop at batch {1, 8, 32}, one JSON row per
(mode, batch) in the same record shape as the dense rows
(``*_paged_decode_tokens_per_sec_per_chip`` vs
``*_decode_tokens_per_sec_per_chip``).

``--shared-prefix``: prefix-cache scenario (ISSUE 8) — N requests
(BENCH_SHARED_N, default 100) sharing a BENCH_SHARED_PREFIX-token
(default 1024) common prefix with unique 16-token suffixes. Reports
``prefill_tokens_saved_total`` (expect ~(N-1) x prefix), cold-vs-warm
prefill wall time, TTFT p50/p95, a bit-identity check of a warm stream
against a cache-off cold run, and the unified-step compile count (one
per token-grid bucket).

``--mixed``: long-prompt-admission scenario (ISSUE 11) — N decoding
tenants (BENCH_MIXED_TENANTS, default 3) while one
BENCH_MIXED_PROMPT-token (default 10000) prompt admits and
chunk-prefills under BENCH_MIXED_BUDGET tokens/step through the unified
ragged step with a pinned grid. Reports tenants' p50/p95/p99 ITL before
vs during admission (asserts p95 within 15%), the long prompt's TTFT, a
zero-recompile assert over the admission, and a bit-identity check of
every stream against admission-free runs — BENCH_MIXED row.

``--spec``: speculative decoding (ISSUE 14) — BENCH_SPEC_BATCH greedy
decoders with period-3 repeating prompts run spec-off then spec-on
(NGramDrafter, k=BENCH_SPEC_K). Drafts ride the unified step as extra
grid rows (data, not programs) and verification reuses the
per-position sampling keys, so the row asserts every stream
bit-identical spec on vs off and reports tokens/s for both modes plus
the drafted/accepted acceptance rate. Also emits a cold-vs-warm
engine start-up row: a first engine compiles fresh into a persistent
compile-cache dir, a second identical engine (in-process memory layer
dropped) must materialize every program from disk and start faster.

``--host-tier``: KV-memory-economics sweep (ISSUE 18) — bf16 vs int8
KV pages at the SAME fixed HBM budget (BENCH_KV_HBM_KIB, head_dim 128
so the int8 page-byte ratio is (2*hd)/(hd+4) = 1.94x). Per dtype the
sweep sizes the pool with ``pages_for_hbm_budget``, actually serves
that many concurrent users, and measures p95 ITL both at capacity and
at a MATCHED batch (the apples-to-apples 1.15x guard), plus the spec
acceptance rate per dtype (the quantized-attention tolerance guard),
an int8+host-offload park/prefetch phase whose parked stream must be
bit-identical to an uncontended run, and a full-arm compile pin
(int8 + host tier + spec + grammar on one engine, step ==
step_buckets, zero steady-state recompiles). Emits ONE ``BENCH_KV``
row; ``--kv-out BENCH_KV.json`` commits it (the artifact comes from
the CPU smoke, like BENCH_LOAD.json — tests/test_bench_tools.py pins
its SCHEMA, never host-dependent values).

``--kv-dtype {bf16,int8}``: page dtype for the ``--paged`` engine rows
(config tag gains ``-kv<dtype>``) — ``--paged --kv-dtype int8`` is the
acceptance-criterion spelling for the users/chip claim on silicon.
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

# BENCH_KV schema (ISSUE 18) — tests/test_bench_tools.py pins these key
# sets against the committed BENCH_KV.json exactly like BENCH_LOAD:
# values are host-dependent, keys (and the determinism-contract booleans)
# are the contract
KV_ROW_KEYS = ("metric", "value", "unit", "vs_baseline", "config",
               "device", "report")
KV_REPORT_KEYS = ("hbm_budget_kib", "page_size", "head_dim", "n_kv_heads",
                  "num_layers", "prompt_tokens", "new_tokens",
                  "users_ratio", "itl_p95_ratio", "spec_acceptance_delta",
                  "tiers", "host_tier", "full_arm")
KV_TIER_KEYS = ("kv_dtype", "page_bytes", "num_pages", "users_per_chip",
                "tokens_per_sec", "itl_ms", "itl_matched_p95_ms",
                "spec_acceptance_rate", "peak_pages", "step_compiles",
                "step_buckets")
KV_HOST_KEYS = ("offload_pages", "prefetch_pages", "prefetch_late",
                "parked_seen", "round_trip_bit_exact")
KV_ARM_KEYS = ("features", "step_compiles", "step_buckets",
               "extra_jit_compiles")


def build_kv_row(report: dict, config_label: str, device: str) -> dict:
    """The one BENCH_KV row, schema-pinned: headline value is the
    users/chip ratio int8 vs bf16 at the same HBM budget; the per-dtype
    evidence rides under ``report`` trimmed to the schema-stable keys."""
    rep = {k: report[k] for k in KV_REPORT_KEYS}
    rep["tiers"] = {name: {k: tier[k] for k in KV_TIER_KEYS}
                    for name, tier in report["tiers"].items()}
    rep["host_tier"] = {k: report["host_tier"][k] for k in KV_HOST_KEYS}
    rep["full_arm"] = {k: report["full_arm"][k] for k in KV_ARM_KEYS}
    return {
        "metric": "BENCH_KV",
        "value": round(float(report["users_ratio"]), 3),
        "unit": "ratio",
        "vs_baseline": 1.0,
        "config": config_label,
        "device": device,
        "report": rep,
    }


def _build(model_name, prompt, new, small):
    import paddle_tpu as paddle

    if model_name == "gpt":
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        cfg = GPTConfig(vocab_size=128 if small else 50304,
                        hidden_size=64 if small else 1024,
                        num_layers=2 if small else 24,
                        num_heads=4 if small else 16,
                        max_position_embeddings=prompt + new,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        paddle.seed(0)
        return GPTForCausalLM(cfg), cfg.vocab_size, "gpt-355m"
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=128 if small else 32000,
                      hidden_size=64 if small else 2048,
                      num_layers=2 if small else 12,
                      num_heads=4 if small else 16,
                      num_key_value_heads=4 if small else 16,
                      max_position_embeddings=prompt + new)
    paddle.seed(0)
    return LlamaForCausalLM(cfg), cfg.vocab_size, "llama-0.76b"


def _already_banked(metric, B, prompt, new, tag=""):
    """Resume safety: a partial failure exits 1, the battery re-runs the
    whole tool, and append-only notes would duplicate the model that
    succeeded — skip rows already banked on silicon this round. Keyed by
    the (B, prompt, new) geometry too: decode is memory-bound, so batch
    probes (battery step 8b, B=32) are distinct measurements, not
    re-runs of the b8 row. ``tag`` is an extra config discriminator
    (the paged rows' ``-kv<dtype>`` — an int8 row must not skip on a
    banked bf16 row at the same geometry)."""
    from _bench_timing import iter_notes_rows
    suffix = tag + _geometry(B, prompt, new)
    return any(rec.get("metric") == metric
               and rec.get("device") == "tpu"
               and str(rec.get("config", "")).endswith(suffix)
               for rec in iter_notes_rows(_NOTES))


def _geometry(B, prompt, new):
    """One source of truth for the config-label geometry suffix — the
    banked-row skip matches on exactly this string, so the two sites
    cannot drift."""
    return f"-decode-b{B}-p{prompt}-n{new}-greedy"


def _bench_one(model_name, B, prompt, new, dev, small):
    import paddle_tpu as paddle

    metric = f"{model_name}_decode_tokens_per_sec_per_chip"
    if not small and _already_banked(metric, B, prompt, new):
        print(f"decode[{model_name}]: b{B}-p{prompt}-n{new} already banked "
              "this round — skipping", file=sys.stderr)
        return
    model, vocab, label = _build(model_name, prompt, new, small)
    model.eval()
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, vocab, (B, prompt)))

    t0 = time.time()
    model.generate(ids, max_new_tokens=new, temperature=0.0,
                   device_loop=True)
    compile_s = time.time() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        model.generate(ids, max_new_tokens=new, temperature=0.0,
                       device_loop=True)
        best = min(best, time.perf_counter() - t0)
    # generate() fetches the result (host concat) — already synced
    tok_s = B * new / best
    rec = {
        "metric": metric,
        "value": round(tok_s, 1), "unit": "tokens/s", "vs_baseline": 1.0,
        "config": label + _geometry(B, prompt, new),
        "total_s": round(best, 3), "compile_s": round(compile_s, 1),
        "per_token_ms": round(1e3 * best / new, 2),
        "device": str(dev.platform),
    }
    print(json.dumps(rec))
    if small:
        return  # CPU smoke: never pollute the round's evidence file
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _latency_percentiles():
    """TTFT / inter-token-latency p50/p95/p99 (ms) from the serving
    histograms — the latency half of the paged row (ISSUE 2): BENCH
    rows carry SLO percentiles next to the throughput number."""
    from paddle_tpu import metrics

    reg = metrics.get_registry()
    out = {}
    for key, name in (("ttft_ms", "paddle_tpu_serving_ttft_seconds"),
                      ("itl_ms",
                       "paddle_tpu_serving_inter_token_seconds")):
        h = reg.get(name)
        if h is None or h.count == 0:
            continue
        out[key] = {f"p{int(q * 100)}": round(h.quantile(q) * 1e3, 3)
                    for q in (0.5, 0.95, 0.99)}
    return out


def _bench_paged_one(model_name, B, prompt, new, dev, small,
                     kv_dtype=None):
    """Engine (paged, continuous-batching) throughput at batch B — same
    record shape as _bench_one so BENCH digests treat both alike.
    ``kv_dtype`` (``--kv-dtype``) selects the KV page dtype; the config
    tag carries it so bf16/int8 rows bank separately."""
    import paddle_tpu as paddle  # noqa: F401  (model seed side effect)
    from paddle_tpu import metrics
    from paddle_tpu.serving import ServingEngine

    kvtag = f"-kv{kv_dtype}" if kv_dtype else ""
    metric = f"{model_name}_paged_decode_tokens_per_sec_per_chip"
    if not small and _already_banked(metric, B, prompt, new, tag=kvtag):
        print(f"paged[{model_name}]: {kvtag}b{B}-p{prompt}-n{new} already "
              "banked this round — skipping", file=sys.stderr)
        return
    model, vocab, label = _build(model_name, prompt, new, small)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (prompt,)) for _ in range(B)]
    engine = ServingEngine(
        model, page_size=16, max_batch_slots=B,
        token_budget=max(B * prompt, 1024),
        kv_dtype=kv_dtype or "f32")

    def run_once():
        for p in prompts:
            engine.add_request(p, max_new_tokens=new, temperature=0.0)
        engine.run()

    t0 = time.time()
    run_once()  # compile prefill bucket + the single decode program
    compile_s = time.time() - t0
    # isolate the measured runs' latency histograms from the compile
    # pass: a compile-inflated TTFT p99 would be nonsense
    metrics.get_registry().reset()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run_once()
        best = min(best, time.perf_counter() - t0)
    tok_s = B * new / best
    rec = {
        "metric": metric,
        "value": round(tok_s, 1), "unit": "tokens/s", "vs_baseline": 1.0,
        "config": label + "-paged" + kvtag + _geometry(B, prompt, new),
        "total_s": round(best, 3), "compile_s": round(compile_s, 1),
        "per_token_ms": round(1e3 * best / new, 2),
        "step_compiles": engine.compile_counts()["step"],
        "peak_pages": engine.pool.peak_used,
        "device": str(dev.platform),
    }
    rec.update(_latency_percentiles())
    print(json.dumps(rec))
    if small:
        return  # CPU smoke: never pollute the round's evidence file
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _bench_shared_prefix(model_name, prefix_len, new, dev, small):
    """Prefix-cache proof: N requests over one shared prefix. The first
    request prefills the whole prompt (cold, and seeds the radix cache);
    every later one matches the cached prefix pages and prefills only
    its 16-token unique suffix — the saved-tokens counter and the
    cold/warm wall-clock ratio are the row's payload."""
    import paddle_tpu as paddle  # noqa: F401  (model seed side effect)
    from paddle_tpu import metrics
    from paddle_tpu.serving import ServingEngine

    n_req = int(os.environ.get("BENCH_SHARED_N", "6" if small else "100"))
    if small:
        prefix_len = min(prefix_len, 48)
    suffix = 16
    metric = f"{model_name}_shared_prefix_prefill_tokens_saved"
    cfg_tag = f"-shared-prefix-b{n_req}-p{prefix_len}-n{new}-greedy"
    if not small:
        from _bench_timing import iter_notes_rows
        if any(rec.get("metric") == metric
               and rec.get("device") == "tpu"
               and str(rec.get("config", "")).endswith(cfg_tag)
               for rec in iter_notes_rows(_NOTES)):
            print(f"shared-prefix[{model_name}]: b{n_req}-p{prefix_len}-"
                  f"n{new} already banked this round — skipping",
                  file=sys.stderr)
            return
    model, vocab, label = _build(model_name, prefix_len + suffix, new,
                                 small)
    model.eval()
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, vocab, (prefix_len,))
    prompts = [np.concatenate([prefix, rng.integers(0, vocab, (suffix,))])
               for _ in range(n_req)]

    # bit-identity oracle: one prompt end-to-end on a CACHE-OFF engine
    off = ServingEngine(model, page_size=16, max_batch_slots=2,
                        token_budget=prefix_len + suffix,
                        prefix_cache=False)
    ref_id = off.add_request(prompts[1], max_new_tokens=new,
                             temperature=0.8, seed=11)
    ref = list(off.run()[ref_id].token_ids)

    engine = ServingEngine(model, page_size=16,
                           max_batch_slots=min(n_req, 8),
                           token_budget=prefix_len + suffix)
    # compile pass: one cold + one warm request builds the full-prefill
    # AND suffix-prefill programs plus the single decode program, so the
    # measured section below times serving, not XLA
    wid = engine.add_request(prompts[0], max_new_tokens=1)
    engine.run()
    engine.add_request(prompts[1], max_new_tokens=1)
    engine.run()
    del wid

    reg = metrics.get_registry()

    def saved():
        fam = reg.get("paddle_tpu_serving_prefill_tokens_saved_total")
        return 0.0 if fam is None else fam.value

    # cold measurement on the SAME engine via the per-request opt-out
    # (programs already compiled; prefix_cache=False forces the full
    # prefill a pre-cache engine would run) — apples-to-apples against
    # the warm sweep below
    t0 = time.perf_counter()
    engine.add_request(prompts[0], max_new_tokens=new,
                       prefix_cache=False)
    engine.run()
    cold_s = time.perf_counter() - t0

    # isolate the measured warm section: reset zeroes every series
    # (families and label children stay registered), THEN snapshot the
    # compile counter so extra_jit_compiles counts only warm-sweep builds
    metrics.get_registry().reset()
    jit0 = _counter_value("paddle_tpu_jit_compiles_total",
                          fn="serving_step")
    s0 = saved()
    warm_tokens = {}
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        warm_tokens[engine.add_request(
            p, max_new_tokens=new, temperature=0.8, seed=11 if i == 1
            else i)] = i
    outs = engine.run()
    warm_s = time.perf_counter() - t0
    tokens_saved = saved() - s0
    warm_ref_id = next(r for r, i in warm_tokens.items() if i == 1)
    warm_equals_cold = list(outs[warm_ref_id].token_ids) == ref

    h = reg.get("paddle_tpu_serving_ttft_seconds")
    ttft = ({f"p{int(q * 100)}": round(h.quantile(q) * 1e3, 3)
             for q in (0.5, 0.95)} if h is not None and h.count else {})
    rec = {
        "metric": metric,
        "value": round(tokens_saved, 1), "unit": "tokens",
        "vs_baseline": 1.0,
        "config": label + cfg_tag,
        "requests": n_req, "prefix_len": prefix_len,
        "expected_saved": (n_req - 1) * (prefix_len // 16) * 16,
        "cold_run_s": round(cold_s, 3),
        "warm_total_s": round(warm_s, 3),
        "warm_per_req_s": round(warm_s / max(n_req, 1), 4),
        "warm_equals_cold": bool(warm_equals_cold),
        "step_compiles": engine.compile_counts()["step"],
        "extra_jit_compiles": _counter_value(
            "paddle_tpu_jit_compiles_total", fn="serving_step") - jit0,
        "ttft_ms": ttft,
        "device": str(dev.platform),
    }
    print(json.dumps(rec))
    if not warm_equals_cold:
        raise AssertionError(
            "warm-cache stream diverged from the cache-off cold run")
    if rec["extra_jit_compiles"]:
        raise AssertionError("step recompiled during the warm sweep")
    if small:
        return  # CPU smoke: never pollute the round's evidence file
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _bench_mixed(model_name, dev, small):
    """Long-prompt-admission scenario (ISSUE 11): N decoding tenants +
    one 10k-token prompt through the unified ragged step. The engine is
    pinned to ONE step shape (``min_step_tokens=token_budget``), so a
    prompt chunk rides grid rows a decode-only step already pays for —
    the measured claim is that the decoding tenants' p95/p99 ITL stays
    flat (within 15%) while the long prompt admits and chunk-prefills,
    with ZERO recompiles during admission and every stream bit-identical
    to an admission-free run (the determinism contract: chunking and
    batch composition never change a token)."""
    import paddle_tpu as paddle  # noqa: F401  (model seed side effect)
    from paddle_tpu import metrics
    from paddle_tpu.serving import ServingEngine

    prompt_len = int(os.environ.get("BENCH_MIXED_PROMPT", "10000"))
    tenants = int(os.environ.get("BENCH_MIXED_TENANTS", "3"))
    budget = int(os.environ.get("BENCH_MIXED_BUDGET",
                                "64" if small else "256"))
    new = int(os.environ.get("BENCH_MIXED_NEW", "64"))
    long_new = 4
    metric = f"{model_name}_mixed_admission_itl_p95_ratio"
    cfg_tag = (f"-mixed-t{tenants}-p{prompt_len}-budget{budget}-n{new}"
               f"-sampled")
    if not small:
        from _bench_timing import iter_notes_rows
        if any(rec.get("metric") == metric
               and rec.get("device") == "tpu"
               and str(rec.get("config", "")).endswith(cfg_tag)
               for rec in iter_notes_rows(_NOTES)):
            print(f"mixed[{model_name}]: {cfg_tag} already banked this "
                  "round — skipping", file=sys.stderr)
            return
    if small:
        # CPU smoke: a 1-layer trunk keeps the 10k-token page-gather
        # tractable while exercising the full scheduler/step machinery
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=1,
                          num_heads=2, num_key_value_heads=1,
                          max_position_embeddings=prompt_len + new + 8)
        paddle.seed(0)
        model, vocab, label = LlamaForCausalLM(cfg), 128, "llama-smoke"
    else:
        model, vocab, label = _build(model_name, prompt_len, new, small)
    model.eval()
    rng = np.random.default_rng(0)
    tenant_prompts = [rng.integers(0, vocab, (16,)) for _ in range(tenants)]
    long_prompt = rng.integers(0, vocab, (prompt_len,))
    spec = dict(max_new_tokens=new, temperature=0.9)

    def build_engine():
        # min_step_tokens == token_budget pins the compiled grid: every
        # step (decode-only or mixed) is ONE shape, so ITL flatness is
        # the design's to lose, not the bucket set's
        return ServingEngine(model, page_size=64,
                             max_batch_slots=tenants + 1,
                             max_model_len=prompt_len + new + 8,
                             token_budget=budget,
                             min_step_tokens=budget)

    def drive(eng, admit_long):
        """Run N tenants; optionally admit the long prompt after two
        steps. Returns (per-tenant token (timestamp, id) lists, long
        prompt (ttft_s, token_ids))."""
        stamps = {i: [] for i in range(tenants)}

        def cb(i):
            return (lambda r, tok, fin, seq:
                    stamps[i].append((time.perf_counter(), tok))
                    if tok is not None else None)

        for i, p in enumerate(tenant_prompts):
            eng.add_request(p, stream_cb=cb(i), seed=100 + i, **spec)
        eng.step()
        eng.step()
        long_info = {}
        if admit_long:
            # the zero-recompile window is THE ADMISSION: the engine
            # compiled its one pinned grid bucket while the tenants
            # started decoding above; from here to drain, the long
            # prompt's chunks must add nothing
            jit0 = _counter_value("paddle_tpu_jit_compiles_total",
                                  fn="serving_step")
            t0 = time.perf_counter()
            long_first = []

            def long_cb(r, tok, fin, seq):
                if tok is not None and not long_first:
                    long_first.append(time.perf_counter() - t0)

            rid = eng.add_request(long_prompt, max_new_tokens=long_new,
                                  temperature=0.9, seed=7,
                                  stream_cb=long_cb)
            outs = eng.run()
            long_info = {"ttft_s": long_first[0],
                         "tokens": list(outs[rid].token_ids),
                         "extra_compiles": _counter_value(
                             "paddle_tpu_jit_compiles_total",
                             fn="serving_step") - jit0}
        else:
            eng.run()
        return stamps, long_info

    def itl_ms(stamps):
        gaps = sorted(g for s in stamps.values()
                      for g in np.diff([t for t, _ in s]))
        if not gaps:
            return {}
        q = lambda f: round(1e3 * gaps[min(int(f * len(gaps)),
                                           len(gaps) - 1)], 3)
        return {"p50": q(0.50), "p95": q(0.95), "p99": q(0.99)}

    # no separate compile pass: the compiled program cache is
    # per-engine, so each phase's engine warms its one pinned grid
    # bucket during its own first tenant steps — BEFORE any measured
    # quantity (ITL gaps are between tokens, which all land after the
    # first step's compile; the long prompt's TTFT clock starts at its
    # enqueue, two steps after the grid compiled)

    # phase A — no-admission baseline
    base_stamps, _ = drive(build_engine(), admit_long=False)
    base = itl_ms(base_stamps)
    # long-prompt oracle: the same config, ALONE — batch composition
    # must not change a single token of anyone's stream
    _, long_alone = drive(build_engine(), admit_long=True)

    # phase B — the measured admission run, zero-recompile asserted
    eng = build_engine()
    mixed_stamps, long_info = drive(eng, admit_long=True)
    extra_compiles = long_info["extra_compiles"]
    during = itl_ms(mixed_stamps)

    streams_identical = (
        long_info["tokens"] == long_alone["tokens"]
        and all([t for _, t in mixed_stamps[i]]
                == [t for _, t in base_stamps[i]]
                for i in range(tenants)))
    ratio = (during["p95"] / base["p95"]) if base.get("p95") else 0.0
    rec = {
        "metric": metric,
        "value": round(ratio, 3), "unit": "ratio", "vs_baseline": 1.0,
        "config": label + cfg_tag,
        "tenants": tenants, "long_prompt_tokens": prompt_len,
        "token_budget": budget,
        "itl_before_ms": base, "itl_during_ms": during,
        "ttft_long_ms": round(1e3 * long_info["ttft_s"], 1),
        "extra_jit_compiles": extra_compiles,
        "streams_identical": bool(streams_identical),
        "step_compiles": eng.compile_counts()["step"],
        "device": str(dev.platform),
    }
    print(json.dumps(rec))
    if extra_compiles:
        raise AssertionError(
            "the unified step recompiled during long-prompt admission")
    if not streams_identical:
        raise AssertionError(
            "a stream diverged under admission — chunking/batch "
            "composition leaked into sampling")
    if ratio > 1.15:
        raise AssertionError(
            f"decoding tenants' p95 ITL degraded {ratio:.2f}x during "
            f"admission (budget {budget}) — exceeds the 15% bound")
    if small:
        return  # CPU smoke: never pollute the round's evidence file
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _bench_spec(model_name, dev, small):
    """Speculative-decoding scenario (ISSUE 14): B greedy decoders with
    period-3 repeating prompts — an n-gram drafter's best case — run
    spec-off then spec-on through the unified ragged step. Drafts enter
    as extra grid rows of programs the engine already compiled, and
    acceptance compares drafts against the per-position sampled targets,
    so every stream must be bit-identical to the spec-off run; the row
    reports tokens/s for both modes and the drafted/accepted counters'
    acceptance rate. Both phases share one persistent compile-cache dir
    so each timed engine materializes its programs from cache, keeping
    XLA out of the throughput window."""
    import tempfile

    import paddle_tpu as paddle  # noqa: F401  (model seed side effect)
    from paddle_tpu.serving import ServingEngine

    B = int(os.environ.get("BENCH_SPEC_BATCH", "4"))
    new = int(os.environ.get("BENCH_SPEC_NEW", "32" if small else "128"))
    spec_k = int(os.environ.get("BENCH_SPEC_K", "4"))
    metric = f"{model_name}_spec_decode_speedup_ratio"
    cfg_tag = f"-spec-b{B}-k{spec_k}-n{new}-greedy"
    if not small:
        from _bench_timing import iter_notes_rows
        if any(rec.get("metric") == metric
               and rec.get("device") == "tpu"
               and str(rec.get("config", "")).endswith(cfg_tag)
               for rec in iter_notes_rows(_NOTES)):
            print(f"spec[{model_name}]: {cfg_tag} already banked this "
                  "round — skipping", file=sys.stderr)
            return
    model, vocab, label = _build(model_name, 64, new + spec_k + 2, small)
    model.eval()
    # period-3 prompts: the suffix always recurs earlier, so the n-gram
    # drafter proposes from step one — and greedy decode tends to lock
    # into the cycle, giving real (not vacuous) acceptance
    prompts = [np.tile((np.arange(3) + 5 * i) % vocab, 8).astype(np.int64)
               for i in range(B)]
    cache_dir = tempfile.mkdtemp(prefix="bench_spec_jitcache_")

    def run(spec_on):
        eng = ServingEngine(
            model, page_size=16, max_batch_slots=B,
            max_model_len=int(prompts[0].size) + new + spec_k + 2,
            spec_k=spec_k if spec_on else 0,
            compile_cache_dir=cache_dir)
        stamps = []

        def cb(r, tok, fin, seq):
            if tok is not None:
                stamps.append(time.perf_counter())

        for i, p in enumerate(prompts):
            eng.add_request(p, max_new_tokens=new, temperature=0.0,
                            seed=11 + i, stream_cb=cb)
        eng.step()  # prefill (and its compile) outside the timed window
        eng.step()  # first decode step: materialize the decode bucket
        t0 = time.perf_counter()
        outs = eng.run()
        dt = time.perf_counter() - t0
        toks = [list(outs[r].token_ids) for r in sorted(outs)]
        tps = sum(1 for t in stamps if t >= t0) / dt if dt else 0.0
        return eng, toks, tps

    # warmup pass per mode seeds the persistent cache; the timed pass's
    # engine then materializes from memory/disk instead of compiling
    run(False)
    _, toks_off, tps_off = run(False)
    run(True)
    d0 = _counter_value("paddle_tpu_serving_spec_drafted_tokens_total")
    a0 = _counter_value("paddle_tpu_serving_spec_accepted_tokens_total")
    eng_on, toks_on, tps_on = run(True)
    drafted = _counter_value(
        "paddle_tpu_serving_spec_drafted_tokens_total") - d0
    accepted = _counter_value(
        "paddle_tpu_serving_spec_accepted_tokens_total") - a0
    streams_identical = toks_on == toks_off
    ratio = tps_on / tps_off if tps_off else 0.0
    rec = {
        "metric": metric,
        "value": round(ratio, 3), "unit": "ratio", "vs_baseline": 1.0,
        "config": label + cfg_tag,
        "batch": B, "spec_k": spec_k, "new_tokens": new,
        "tokens_per_sec_spec_off": round(tps_off, 1),
        "tokens_per_sec_spec_on": round(tps_on, 1),
        "drafted_tokens": int(drafted), "accepted_tokens": int(accepted),
        "acceptance_rate": (round(accepted / drafted, 3)
                            if drafted else 0.0),
        "streams_identical": bool(streams_identical),
        "step_compiles": eng_on.compile_counts()["step"],
        "device": str(dev.platform),
    }
    print(json.dumps(rec))
    if not streams_identical:
        raise AssertionError(
            "a stream diverged with speculation on — drafting leaked "
            "into sampling")
    if not drafted:
        raise AssertionError(
            "drafter proposed nothing on period-3 prompts — the suffix "
            "match is broken")
    if not small and ratio <= 1.0:
        raise AssertionError(
            f"speculation did not improve decode throughput "
            f"({ratio:.2f}x at k={spec_k}, "
            f"acceptance {rec['acceptance_rate']})")
    if small:
        return  # CPU smoke: never pollute the round's evidence file
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _bench_cache_startup(model_name, dev, small):
    """Cold-vs-warm engine start-up (ISSUE 14): the first engine in a
    fresh compile-cache dir compiles from XLA (source="fresh") and
    serializes each executable; a second identical engine — with the
    in-process memory layer dropped — must materialize every step
    program from disk (source="disk", zero fresh) and produce
    bit-identical tokens. The row reports both wall times and the
    per-source jit_compiles_total deltas."""
    import tempfile

    import paddle_tpu as paddle  # noqa: F401  (model seed side effect)
    from paddle_tpu import jit
    from paddle_tpu.serving import ServingEngine

    new = 8
    metric = f"{model_name}_engine_startup_warm_vs_cold_ratio"
    cfg_tag = f"-cachestart-n{new}"
    if not small:
        from _bench_timing import iter_notes_rows
        if any(rec.get("metric") == metric
               and rec.get("device") == "tpu"
               and str(rec.get("config", "")).endswith(cfg_tag)
               for rec in iter_notes_rows(_NOTES)):
            print(f"cache-startup[{model_name}]: {cfg_tag} already "
                  "banked this round — skipping", file=sys.stderr)
            return
    model, vocab, label = _build(model_name, 32, new + 2, small)
    model.eval()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, vocab, (16,))
    cache_dir = tempfile.mkdtemp(prefix="bench_jitcache_")
    sources = ("fresh", "disk", "memory")

    def serve():
        src0 = {s: _counter_value("paddle_tpu_jit_compiles_total",
                                  source=s) for s in sources}
        t0 = time.perf_counter()
        eng = ServingEngine(model, page_size=16, max_batch_slots=1,
                            max_model_len=int(prompt.size) + new + 2,
                            compile_cache_dir=cache_dir)
        rid = eng.add_request(prompt, max_new_tokens=new, temperature=0.0,
                              seed=5)
        toks = list(eng.run()[rid].token_ids)
        dt = time.perf_counter() - t0
        srcs = {s: int(_counter_value("paddle_tpu_jit_compiles_total",
                                      source=s) - src0[s])
                for s in sources}
        return dt, srcs, toks

    cold_dt, cold_src, cold_toks = serve()
    jit.clear_compile_cache(memory=True)  # force the disk layer
    warm_dt, warm_src, warm_toks = serve()
    ratio = warm_dt / cold_dt if cold_dt else 0.0
    rec = {
        "metric": metric,
        "value": round(ratio, 3), "unit": "ratio", "vs_baseline": 1.0,
        "config": label + cfg_tag,
        "cold_start_s": round(cold_dt, 3), "warm_start_s": round(warm_dt, 3),
        "cold_sources": cold_src, "warm_sources": warm_src,
        "streams_identical": bool(warm_toks == cold_toks),
        "device": str(dev.platform),
    }
    print(json.dumps(rec))
    if warm_toks != cold_toks:
        raise AssertionError(
            "warm (disk-cached) engine's stream diverged from the cold "
            "compile's — serialization changed the program")
    if not cold_src["fresh"]:
        raise AssertionError("cold start compiled nothing fresh — the "
                             "cache dir was not cold")
    if warm_src["fresh"] or not warm_src["disk"]:
        raise AssertionError(
            f"warm start did not come from disk: {warm_src}")
    if small:
        return  # CPU smoke: never pollute the round's evidence file
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _bench_kv_tiers(dev, small, out_path):
    """KV-memory-economics sweep (ISSUE 18): bf16 vs int8 KV pages at
    ONE fixed HBM budget. head_dim is 128 so the int8 page-byte ratio is
    (2*128)/(128+4) = 1.94x — the users/chip claim is sizing math that
    the sweep then PROVES by serving that many concurrent users per
    dtype. The ITL guard compares p95 at a MATCHED batch (bf16's
    capacity) so int8's extra users don't masquerade as per-token cost;
    the quantization-quality guard is the spec acceptance rate (a
    toleranced contract — quantized attention is NOT bit-checked); the
    host-tier phase parks a low-priority int8 stream under page
    pressure and requires its tokens bit-identical to an uncontended
    run; the full-arm phase pins the compile surface with quantization
    + host tier + spec + grammar armed at once."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (GrammarFSM, ServingEngine,
                                    page_bytes, pages_for_hbm_budget,
                                    toy_tokenizer)

    budget_kib = int(os.environ.get("BENCH_KV_HBM_KIB", "256"))
    page, prompt_t, new = 16, 16, 16
    n_layers, n_kv, hd = 2, 1, 128
    metric = "BENCH_KV"
    cfg_tag = (f"-kvtiers-hbm{budget_kib}kib-hd{hd}-p{prompt_t}-n{new}"
               f"-greedy")
    if not small:
        from _bench_timing import iter_notes_rows
        if any(rec.get("metric") == metric
               and rec.get("device") == "tpu"
               and str(rec.get("config", "")).endswith(cfg_tag)
               for rec in iter_notes_rows(_NOTES)):
            print(f"kv-tiers: {cfg_tag} already banked this round — "
                  "skipping", file=sys.stderr)
            return
    cfg = LlamaConfig(vocab_size=128, hidden_size=256,
                      num_layers=n_layers, num_heads=2,
                      num_key_value_heads=n_kv,
                      max_position_embeddings=64)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    vocab = cfg.vocab_size
    label = "llama-kv128" + cfg_tag
    need = -(-(prompt_t + new) // page)  # pages one user reserves

    sizing = {kv: pages_for_hbm_budget(budget_kib * 1024, page, n_kv, hd,
                                       n_layers, kv_dtype=kv)
              for kv in ("bf16", "int8")}
    users = {kv: max((p - 1) // need, 1) for kv, p in sizing.items()}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (prompt_t,))
               for _ in range(max(users.values()))]

    def drive(eng, n_users):
        """Serve n_users greedy decoders to drain; returns (wall_s,
        sorted inter-token gaps across all streams)."""
        stamps = [[] for _ in range(n_users)]

        def cb(i):
            return (lambda r, tok, fin, seq:
                    stamps[i].append(time.perf_counter())
                    if tok is not None else None)

        t0 = time.perf_counter()
        for i in range(n_users):
            eng.add_request(prompts[i], max_new_tokens=new,
                            temperature=0.0, seed=i, stream_cb=cb(i))
        eng.run()
        dt = time.perf_counter() - t0
        return dt, sorted(g for s in stamps for g in np.diff(s))

    def pq(gaps, q):
        return gaps[min(int(q * len(gaps)), len(gaps) - 1)] if gaps else 0.0

    # per-dtype capacity phase: the pool is sized by the budget and the
    # engine must actually hold that many users resident at once
    # (prefix_cache off — shared pages would flatter the capacity claim)
    tiers, engines = {}, {}
    for kv in ("bf16", "int8"):
        eng = ServingEngine(model, page_size=page, num_pages=sizing[kv],
                            max_batch_slots=users[kv],
                            max_model_len=prompt_t + new,
                            token_budget=max(users[kv] * prompt_t, 64),
                            prefix_cache=False, kv_dtype=kv)
        drive(eng, users[kv])            # compile pass
        dt, gaps = drive(eng, users[kv])
        cc = eng.compile_counts()
        tiers[kv] = {
            "kv_dtype": kv,
            "page_bytes": page_bytes(page, n_kv, hd, n_layers,
                                     kv_dtype=kv),
            "num_pages": sizing[kv], "users_per_chip": users[kv],
            "tokens_per_sec": round(users[kv] * new / dt, 1),
            "itl_ms": {f"p{int(q * 100)}": round(1e3 * pq(gaps, q), 3)
                       for q in (0.5, 0.95)},
            "peak_pages": eng.pool.peak_used,
            "step_compiles": cc["step"], "step_buckets": cc["step_buckets"],
        }
        engines[kv] = eng

    # matched-batch ITL: both dtypes at bf16's capacity AND bf16's slot
    # count, best-of-3 p95 — the capacity engines differ in
    # max_batch_slots (the compiled step's row grid), so the bf16 one is
    # reused while int8 gets a fresh equal-slot engine; the 1.15x guard
    # must compare equal work, not 15 padded rows against 7
    for kv in ("bf16", "int8"):
        eng = engines[kv]
        if users[kv] != users["bf16"]:
            eng = ServingEngine(model, page_size=page,
                                num_pages=sizing[kv],
                                max_batch_slots=users["bf16"],
                                max_model_len=prompt_t + new,
                                token_budget=max(
                                    users["bf16"] * prompt_t, 64),
                                prefix_cache=False, kv_dtype=kv)
            drive(eng, users["bf16"])    # compile pass
        best = float("inf")
        for _ in range(3):
            _, gaps = drive(eng, users["bf16"])
            best = min(best, pq(gaps, 0.95))
        tiers[kv]["itl_matched_p95_ms"] = round(1e3 * best, 3)

    # spec-acceptance guard: period-3 prompts, greedy, k=3 — acceptance
    # on quantized pages may not fall more than the documented 0.25
    # tolerance below bf16 (docs/SERVING.md "KV page tiers")
    for kv in ("bf16", "int8"):
        eng = ServingEngine(model, page_size=page, num_pages=64,
                            max_batch_slots=4,
                            max_model_len=24 + 24 + 5,
                            spec_k=3, kv_dtype=kv)
        d0 = _counter_value("paddle_tpu_serving_spec_drafted_tokens_total")
        a0 = _counter_value("paddle_tpu_serving_spec_accepted_tokens_total")
        for i in range(4):
            eng.add_request(np.tile((np.arange(3) + 5 * i) % vocab, 8),
                            max_new_tokens=24, temperature=0.0, seed=11 + i)
        eng.run()
        drafted = _counter_value(
            "paddle_tpu_serving_spec_drafted_tokens_total") - d0
        accepted = _counter_value(
            "paddle_tpu_serving_spec_accepted_tokens_total") - a0
        tiers[kv]["spec_acceptance_rate"] = (
            round(accepted / drafted, 3) if drafted else 0.0)

    # host-tier phase: int8 + host_offload under real page pressure — a
    # priority-5 stream is parked for a priority-0 arrival, round-trips
    # through the HostPageStore, and must finish bit-identical to an
    # uncontended solo run (the offload tier's warm_equals_cold contract)
    lo_p, hi_p = np.arange(1, 9), np.arange(2, 10)
    solo = ServingEngine(model, page_size=4, num_pages=64,
                         max_batch_slots=2, max_model_len=18,
                         kv_dtype="int8")
    r_ref = solo.add_request(lo_p, max_new_tokens=10, temperature=0.0,
                             seed=5)
    ref = list(solo.run()[r_ref].token_ids)
    eng = ServingEngine(model, page_size=4, num_pages=8,
                        max_batch_slots=3, max_model_len=18,
                        kv_dtype="int8", host_offload=True)
    c0 = {n: _counter_value(f"paddle_tpu_serving_kv_{n}")
          for n in ("offload_pages_total", "prefetch_pages_total",
                    "prefetch_late_total")}
    lo = eng.add_request(lo_p, max_new_tokens=10, temperature=0.0,
                         seed=5, priority=5)
    eng.step()
    eng.step()  # lo decoding and holding worst-case pages
    hi = eng.add_request(hi_p, max_new_tokens=4, temperature=0.0,
                         seed=6, priority=0)
    outs = eng.run()
    dc = {n: int(_counter_value(f"paddle_tpu_serving_kv_{n}") - v)
          for n, v in c0.items()}
    host = {
        "offload_pages": dc["offload_pages_total"],
        "prefetch_pages": dc["prefetch_pages_total"],
        "prefetch_late": dc["prefetch_late_total"],
        "parked_seen": dc["offload_pages_total"] > 0,
        "round_trip_bit_exact": (list(outs[lo].token_ids) == ref
                                 and len(outs[hi].token_ids) == 4),
    }

    # full-arm compile pin: quantization + host tier + spec + grammar on
    # ONE engine; a second identical traffic pass must compile nothing
    eng = ServingEngine(model, page_size=4, num_pages=64,
                        max_batch_slots=4, max_model_len=40,
                        kv_dtype="int8", host_offload=True, spec_k=3)
    fsm = GrammarFSM.compile("[ab]{1,6}", toy_tokenizer(vocab))

    def arm_traffic(seed0):
        eng.add_request(np.tile(np.arange(3) + 1, 6), max_new_tokens=8,
                        temperature=0.0, seed=seed0)
        eng.add_request(prompts[0], max_new_tokens=6, temperature=0.9,
                        seed=seed0 + 1, grammar=fsm)
        eng.add_request(prompts[1], max_new_tokens=8, temperature=0.7,
                        seed=seed0 + 2)
        eng.run()

    arm_traffic(0)  # compile pass
    jit0 = _counter_value("paddle_tpu_jit_compiles_total",
                          fn="serving_step")
    arm_traffic(10)
    cc = eng.compile_counts()
    arm = {
        "features": ["int8", "host_offload", "spec", "grammar"],
        "step_compiles": cc["step"], "step_buckets": cc["step_buckets"],
        "extra_jit_compiles": int(_counter_value(
            "paddle_tpu_jit_compiles_total", fn="serving_step") - jit0),
    }

    report = {
        "hbm_budget_kib": budget_kib, "page_size": page, "head_dim": hd,
        "n_kv_heads": n_kv, "num_layers": n_layers,
        "prompt_tokens": prompt_t, "new_tokens": new,
        "users_ratio": round(users["int8"] / users["bf16"], 3),
        "itl_p95_ratio": round(
            tiers["int8"]["itl_matched_p95_ms"]
            / max(tiers["bf16"]["itl_matched_p95_ms"], 1e-9), 3),
        "spec_acceptance_delta": round(
            tiers["int8"]["spec_acceptance_rate"]
            - tiers["bf16"]["spec_acceptance_rate"], 3),
        "tiers": tiers, "host_tier": host, "full_arm": arm,
    }
    rec = build_kv_row(report, label, str(dev.platform))
    print(json.dumps(rec))
    if report["users_ratio"] < 1.9:
        raise AssertionError(
            f"int8 sustains only {report['users_ratio']:.2f}x users/chip "
            f"vs bf16 at {budget_kib} KiB — below the 1.9x bar")
    for kv in ("bf16", "int8"):
        if tiers[kv]["peak_pages"] < users[kv]:
            raise AssertionError(
                f"{kv} never held its {users[kv]} users resident at once "
                f"(peak_pages {tiers[kv]['peak_pages']})")
        if tiers[kv]["step_compiles"] != tiers[kv]["step_buckets"]:
            raise AssertionError(f"{kv} compile surface unpinned: "
                                 f"{tiers[kv]}")
    # the latency bound is a silicon claim (decode is memory-bound on
    # TPU, where int8's halved page traffic pays for the dequant; a CPU
    # smoke measures interpreter overhead) — same gating as _bench_spec's
    # speedup assert
    if not small and report["itl_p95_ratio"] > 1.15:
        raise AssertionError(
            f"int8 p95 ITL is {report['itl_p95_ratio']:.2f}x bf16 at the "
            f"matched batch — exceeds the 15% bound")
    if (tiers["int8"]["spec_acceptance_rate"]
            < tiers["bf16"]["spec_acceptance_rate"] - 0.25):
        raise AssertionError(
            f"quantized spec acceptance fell past the 0.25 tolerance: "
            f"{report['spec_acceptance_delta']}")
    if not (host["parked_seen"] and host["round_trip_bit_exact"]):
        raise AssertionError(f"host-tier phase failed: {host}")
    if host["prefetch_late"]:
        raise AssertionError(
            f"{host['prefetch_late']} late prefetches — the scheduler "
            "let a step block on a host→HBM copy")
    if arm["extra_jit_compiles"] or arm["step_compiles"] != arm[
            "step_buckets"]:
        raise AssertionError(f"full-arm compile surface unpinned: {arm}")
    if out_path:
        # the committed artifact (BENCH_KV.json): overwrite-whole like
        # BENCH_LOAD.json — written even from the CPU smoke, because the
        # schema test pins keys and determinism booleans, never timings
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
    if small:
        return  # CPU smoke: never pollute the round's evidence file
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(_NOTES, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _counter_value(name, **labels):
    from paddle_tpu import metrics

    fam = metrics.get_registry().get(name)
    if fam is None:
        return 0.0
    if labels and set(labels) != set(fam.label_names):
        # partial label set: aggregate the unnamed dimensions (e.g.
        # jit_compiles_total{fn=...} summed across its source split)
        return fam.sum_labels(**labels)
    return (fam.labels(**labels) if labels else fam).value


def _parse_args(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench_decode",
        description="Decode benchmarks: dense while_loop decode by "
                    "default; flags select engine scenarios (combinable "
                    "— each selected scenario emits its own BENCH rows).",
        epilog="Geometry via env: BENCH_BATCH, BENCH_PROMPT, "
               "BENCH_NEW_TOKENS, BENCH_DECODE_MODELS (comma list of "
               "gpt,llama), BENCH_DECODE_SMALL=1 for a CPU smoke that "
               "never writes the notes file. Per-scenario knobs: "
               "BENCH_PAGED_BATCHES, BENCH_SHARED_N/BENCH_SHARED_PREFIX, "
               "BENCH_MIXED_*, BENCH_SPEC_BATCH/BENCH_SPEC_K/"
               "BENCH_SPEC_NEW.")
    ap.add_argument("--paged", action="store_true",
                    help="continuous-batching engine sweep vs the dense "
                         "loop at BENCH_PAGED_BATCHES")
    ap.add_argument("--shared-prefix", action="store_true",
                    dest="shared_prefix",
                    help="prefix-cache scenario (ISSUE 8): N requests "
                         "sharing one common prefix")
    ap.add_argument("--mixed", action="store_true",
                    help="long-prompt-admission scenario (ISSUE 11): "
                         "tenant ITL flatness under chunked prefill")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding (ISSUE 14): spec on/off "
                         "tokens/s + acceptance rate, plus a cold-vs-"
                         "warm compile-cache start-up row")
    ap.add_argument("--host-tier", action="store_true", dest="host_tier",
                    help="KV-memory-economics sweep (ISSUE 18): bf16 vs "
                         "int8 users/chip at one HBM budget "
                         "(BENCH_KV_HBM_KIB) + host-offload round-trip "
                         "+ full-arm compile pin — one BENCH_KV row")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"), default=None,
                    help="KV page dtype for the --paged engine rows "
                         "(rows tag their config with -kv<dtype>)")
    ap.add_argument("--kv-out", default=None,
                    help="write the BENCH_KV row to this file (e.g. "
                         "BENCH_KV.json); stdout always gets it")
    return ap.parse_args(argv)


def main():
    args = _parse_args()
    from _bench_timing import require_tpu

    # decode numbers describe the chip; BENCH_DECODE_SMALL=1 is the
    # explicit CPU smoke size
    small = os.environ.get("BENCH_DECODE_SMALL") == "1"
    if small:
        import jax

        dev = jax.devices()[0]
    else:
        dev = require_tpu()

    B = int(os.environ.get("BENCH_BATCH", 8))
    prompt = int(os.environ.get("BENCH_PROMPT", 128))
    new = int(os.environ.get("BENCH_NEW_TOKENS", 128))
    models = [m.strip() for m in
              os.environ.get("BENCH_DECODE_MODELS", "gpt,llama").split(",")
              if m.strip()]
    known = {"gpt", "llama"}
    if not models or not set(models) <= known:
        print(f"BENCH_DECODE_MODELS must name models from {sorted(known)}; "
              f"got {models!r}", file=sys.stderr)
        sys.exit(2)
    failures = 0

    def attempt(tag, fn, *fargs):
        # one scenario's OOM/regression must not lose the others' rows
        nonlocal failures
        try:
            fn(*fargs)
        except Exception as e:
            failures += 1
            print(f"{tag}: {type(e).__name__}: {str(e)[:160]}",
                  file=sys.stderr)

    if args.host_tier:
        attempt("kv-tiers", _bench_kv_tiers, dev, small, args.kv_out)
    if args.spec:
        for name in models:
            attempt(f"spec[{name}]", _bench_spec, name, dev, small)
            attempt(f"cache-startup[{name}]", _bench_cache_startup,
                    name, dev, small)
    if args.mixed:
        for name in models:
            attempt(f"mixed[{name}]", _bench_mixed, name, dev, small)
    if args.shared_prefix:
        shared_prefix = int(os.environ.get("BENCH_SHARED_PREFIX", "1024"))
        for name in models:
            attempt(f"shared-prefix[{name}]", _bench_shared_prefix,
                    name, shared_prefix, new, dev, small)
    if args.paged:
        # engine-vs-dense sweep: one dense and one paged row per batch
        batches = [int(b) for b in os.environ.get(
            "BENCH_PAGED_BATCHES", "1,8,32").split(",") if b.strip()]
        for name in models:
            for b in batches:
                attempt(f"decode[{name}] b{b}", _bench_one,
                        name, b, prompt, new, dev, small)
                attempt(f"paged[{name}] b{b}", _bench_paged_one,
                        name, b, prompt, new, dev, small,
                        args.kv_dtype)
    if not (args.spec or args.mixed or args.shared_prefix or args.paged
            or args.host_tier):
        for name in models:
            attempt(f"decode[{name}]", _bench_one,
                    name, B, prompt, new, dev, small)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
