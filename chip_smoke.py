#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: device → train → serve
    python chip_smoke.py --chips 4   # four chips: dp2×mp2 train vs one device

One process drives the main path once at the full width of GPT-3 1.3B
(h2048, 24 layers, 16 heads, head_dim 128, vocab 50304; random weights
from a seed) through the entry points a user calls:

- *train*: ``amp.decorate(O2, bf16, master_weight=False)`` + AdamW + fused
  chunked CE under ``jit.StaticFunction`` at B4 S1024 on a fixed batch.
  Pass = finite falling loss, ONE compile, flash kernel in the program.
- *serve*: the same weights behind ``Router.add_model`` → ``ServingEngine``
  (bf16 pool sized by ``pages_for_hbm_budget``), more greedy requests than
  batch slots: two warm-up passes, one ``CompletionAPI`` call, a third pass.
  Pass = every request finishes, compiles == step buckets, the third pass
  compiles nothing, the Pallas ragged kernel is in every step program, no
  page leaks, and every served token is a dense-forward argmax to within a
  stated logit tolerance (exact equality is unsound under bf16 ties).
  Before it, *paged_kernel* holds the ragged kernel to
  ``ref_paged_attention`` on seeded inputs at the same widths.
- ``--chips 4``: ONLY the dp2×mp2 train step (``fleet.init``) and the
  one-device run of the same batch it is compared with.

Each phase is a function of its sizes, so tests/test_chip_smoke.py runs
them tiny on the CPU; ``main()`` always runs full width and refuses any
platform but ``tpu``. Any failed check raises — the exit code is non-zero
and the result line is never printed. The last stdout line on success is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import gc
import json
import os
import re
import statistics
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(_HERE, "tools"))

# one fixed serving traffic mix: prompt lengths spread over 32–1024 tokens,
# 32–64 new tokens each, more requests than batch slots
SERVE_PROMPTS = (32, 96, 200, 384, 640, 1024)
SERVE_NEW = (32, 48, 64, 32, 48, 64)
# |dense logit of the served token − dense max logit| allowed, as a share
# of the largest |logit| in that row: 2^-5 is 4 bf16 ulps of the top logit
DENSE_RTOL = 2.0 ** -5
# |Pallas paged kernel − ref_paged_attention| allowed on unit-normal bf16
# inputs: 4 bf16 ulps of an O(1) output (the kernel rounds p to bf16)
PAGED_KERNEL_ATOL = 2.0 ** -5
# per-step |loss(dp2×mp2) − loss(one device)| allowed, bf16 params
SHARDED_LOSS_ATOL = 0.05


def _say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def device_phase(want_chips: int):
    """The chip, or a non-zero exit before anything else runs."""
    import jax

    from _bench_timing import require_tpu

    dev = require_tpu()  # its refusal goes to stderr: stdout stays empty
    n = len(jax.devices())
    _say(f"device: platform={dev.platform} kind={dev.device_kind} count={n} "
         f"bytes_limit={dev.memory_stats()['bytes_limit']}")
    _check(n == want_chips, f"need {want_chips} chip(s), jax reports {n}")
    return dev


def _peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _train_steps(cfg, batch: int, seq: int, steps: int, wrap=None):
    """The r5 recipe (bench.py bench_gpt13): returns (model, step fn,
    losses, seconds per call — the first includes the compile)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.models import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16",
                              master_weight=False)
    net = wrap(model) if wrap is not None else model

    def train_fn(ids, labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            _, loss = net(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = jit.StaticFunction(train_fn, observe=[model, opt], warmup=False)
    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, cfg.vocab_size, (batch, seq))
    ids = paddle.to_tensor(ids_np)
    labels = paddle.to_tensor(np.roll(ids_np, -1, axis=1))
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step(ids, labels)
        jax.block_until_ready(loss.value)
        secs.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(loss.numpy(), dtype="float32")))
    return model, step, losses, secs


def _check_losses(tag, losses):
    _check(all(np.isfinite(losses)), f"{tag}: non-finite loss {losses}")
    _check(losses[-1] < losses[0],
           f"{tag}: loss did not fall on a fixed batch: {losses}")


def train_phase(cfg, batch: int, seq: int, steps: int, dev):
    """``steps`` (>= 3) compiled train steps on a fixed batch; returns the
    trained model (bf16 weights) for the serve phase."""
    import jax

    from paddle_tpu.nn.functional import attention

    model, step, losses, secs = _train_steps(cfg, batch, seq, steps)
    _check_losses("train", losses)
    _check(len(step._cache) == 1,
           f"train: {len(step._cache)} compiled signatures, expected 1")
    # _use_pallas decides under trace from the backend and the length, so
    # the kernel's presence is asserted on the program, not assumed
    want_kernel = (jax.default_backend() == "tpu"
                   and seq >= attention.pallas_flash_min_seq)
    has_kernel = "tpu_custom_call" in step.program_text()
    _check(has_kernel == want_kernel,
           f"train: flash kernel in program = {has_kernel}, "
           f"expected {want_kernel}")
    _say(f"train[{dev.device_kind}]: losses={[round(x, 4) for x in losses]} "
         f"compile_s={secs[0]:.1f} "
         f"step_ms={1e3 * statistics.median(secs[1:]):.1f} "
         f"(median of {len(secs) - 1}, B{batch} S{seq}) "
         f"flash_kernel={has_kernel} peak_bytes_in_use={_peak_bytes(dev)}")
    return model


def _dense_agreement(model, outs, rtol: float):
    """Teacher-forced check of served tokens against the DENSE forward
    (``model(ids)``: flash/XLA attention over the whole sequence, no pages):
    at every generated position the served token's dense logit must be
    within ``rtol × max|logit|`` of the dense maximum. Returns
    (tokens checked, exact argmax matches, worst gap / max|logit|)."""
    import paddle_tpu as paddle
    from paddle_tpu import jit

    seqs = [np.concatenate([o.prompt_token_ids, np.asarray(o.token_ids)])
            for o in outs]
    width = -(-max(s.size for s in seqs) // 128) * 128  # one padded shape
    width = min(width, int(model.config.max_position_embeddings))
    ids = np.zeros((len(seqs), width), np.int64)
    for i, s in enumerate(seqs):
        ids[i, :s.size] = s  # right-padded: causal rows never see the pad

    def dense_fn(x):
        with paddle.no_grad():
            return model(x)

    dense = jit.StaticFunction(dense_fn, observe=[model], warmup=False)
    logits = np.asarray(dense(paddle.to_tensor(ids)).numpy(), np.float32)
    n = exact = 0
    worst = 0.0
    for i, o in enumerate(outs):
        p = int(o.prompt_token_ids.size)
        for t, tok in enumerate(o.token_ids):
            row = logits[i, p + t - 1]
            gap = float(row.max() - row[int(tok)]) / float(np.abs(row).max())
            worst = max(worst, gap)
            exact += int(np.argmax(row) == int(tok))
            n += 1
    _check(worst <= rtol,
           f"serve: a served token sits {worst:.4f} × max|logit| below the "
           f"dense argmax (tolerance {rtol:.4f}; {exact}/{n} exact)")
    return n, exact, worst


def paged_kernel_phase(nh: int, nkv: int, hd: int, page_size: int,
                       pages_per_seq: int, dev):
    """The Pallas ragged kernel against ``ref_paged_attention`` on this
    device at the engine's widths, bf16 and int8 pages, seeded inputs —
    the serve phase's token check cannot see attention through near-random
    weights, so the kernel's numbers are checked here directly."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.quantization.observers import quantize_kv

    rng = np.random.default_rng(2)
    rows, num_pages = 16, 2 * pages_per_seq
    max_len = pages_per_seq * page_size
    q = jnp.asarray(rng.standard_normal((rows, nh, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((num_pages, nkv, page_size, hd)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((num_pages, nkv, page_size, hd)),
                    jnp.bfloat16)
    bt = jnp.asarray(rng.integers(1, num_pages, (rows, pages_per_seq)),
                     jnp.int32)
    lens = jnp.asarray(np.linspace(1, max_len, rows).astype(np.int32))
    worst = {}
    for name in ("bf16", "int8"):
        kw = {}
        kp, vp = k, v
        if name == "int8":
            kp, ks = quantize_kv(k)
            vp, vs = quantize_kv(v)
            kw = dict(k_scale=ks, v_scale=vs)
        out = pa.ragged_paged_attention(q, kp, vp, bt, lens,
                                        use_kernel=True, **kw)
        ref = pa.ref_paged_attention(q, kp, vp, bt, lens, **kw)
        out, ref = (np.asarray(x, np.float32) for x in (out, ref))
        _check(out.shape == (rows, nh, hd) and np.isfinite(out).all(),
               f"paged kernel ({name}): bad output")
        worst[name] = float(np.abs(out - ref).max())
        _check(worst[name] <= PAGED_KERNEL_ATOL,
               f"paged kernel ({name}) differs from ref_paged_attention by "
               f"{worst[name]:.4f} > {PAGED_KERNEL_ATOL}")
    _say(f"paged_kernel[{dev.device_kind}]: nh{nh} nkv{nkv} hd{hd} "
         f"page{page_size} pages{pages_per_seq} rows{rows}: "
         f"max|kernel - ref|={worst} (tol {PAGED_KERNEL_ATOL})")


def serve_phase(model, *, kv_pool_bytes: int, page_size: int,
                max_model_len: int, max_batch_slots: int, token_budget: int,
                prompt_lens, new_tokens, dev):
    """Paged serving of ``model`` through Router → ServingEngine →
    CompletionAPI; see the module docstring for what passes."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving import (CompletionAPI, Router,
                                    pages_for_hbm_budget, tracing)

    t_begin = time.perf_counter()  # the tracer's clock
    n_layers, n_kv, head_dim = model._cache_spec()
    num_pages = pages_for_hbm_budget(kv_pool_bytes, page_size, n_kv,
                                     head_dim, n_layers, kv_dtype="bfloat16")
    router = Router()
    router.add_model("gpt", model, kv_dtype=jnp.bfloat16,
                     page_size=page_size, max_model_len=max_model_len,
                     num_pages=num_pages, max_batch_slots=max_batch_slots,
                     token_budget=token_budget)
    engine = router.engines("gpt")[0]
    vocab = int(model.config.vocab_size)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, vocab, (n,)) for n in prompt_lens]
    _check(len(prompts) > max_batch_slots,
           "serve: traffic must exceed the batch slots so that a request "
           "is admitted mid-decode")

    steps = []  # engine steps each pass took

    def one_pass():
        t0, s0 = time.perf_counter(), engine.stats["steps"]
        rids = [router.submit(p, model="gpt", max_new_tokens=n,
                              temperature=0.0)
                for p, n in zip(prompts, new_tokens)]
        outs = router.run()
        steps.append(int(engine.stats["steps"] - s0))
        return [outs[r] for r in rids], time.perf_counter() - t0

    # warm with two passes (prefix-cache hits on the second can legitimately
    # form a bucket the cold pass never did) and the CompletionAPI call (a
    # lone warm request can form one more); the third pass compiles nothing
    passes, secs = [], []
    for _ in range(2):
        outs, dt = one_pass()
        passes.append(outs)
        secs.append(dt)
    resp = CompletionAPI(router).create_completion(
        prompts[0].tolist(), max_tokens=new_tokens[0], temperature=0.0,
        model="gpt")
    warm = engine.compile_counts()
    outs, dt = one_pass()
    passes.append(outs)
    secs.append(dt)
    final = engine.compile_counts()
    _check(final == warm,
           f"serve: third identical pass compiled: {warm} -> {final}")
    _check(final["step"] == final["step_buckets"],
           f"serve: compiles != step buckets: {final}")
    for outs in passes:
        for o, n in zip(outs, new_tokens):
            _check(o.finish_reason in ("length", "stop"),
                   f"serve: request finished {o.finish_reason!r}: {o.error}")
            _check(o.n_gen == n, f"serve: {o.n_gen} tokens, asked {n}")
    for later in passes[1:]:
        _check([o.token_ids for o in later]
               == [o.token_ids for o in passes[0]],
               "serve: greedy streams differ between identical passes")
    choice = resp["choices"][0]
    _check(choice["finish_reason"] in ("length", "stop"),
           f"serve: completion finished {choice['finish_reason']!r}")
    _check(choice["token_ids"] == passes[0][0].token_ids,
           "serve: CompletionAPI stream differs from the routed request")

    texts = engine.step_program_texts()
    _check(len(texts) == final["step"], "serve: a step program has no text")
    on_tpu = jax.default_backend() == "tpu"
    has_kernel = all("tpu_custom_call" in t for t in texts)
    _check(has_kernel == on_tpu,
           f"serve: Pallas ragged kernel in every step program = "
           f"{has_kernel}, expected {on_tpu}")
    # the step updates the pool where it lies: every pool parameter of every
    # bucket aliases its own output (`nxt, fin, k0', v0', ...`); on the chip
    # the compiled program holds no copy of a pool-shaped array and updates
    # at least the pool's bytes in place. PADDLE_TPU_NO_DONATE=1 is the
    # bisect axis that turns all of it off.
    donating = os.environ.get("PADDLE_TPU_NO_DONATE") != "1"
    pool = engine.pool
    n_pool = pool.step_stride * pool.num_layers
    want_alias = [f"tf.aliasing_output = {2 + i} : i32" for i in range(n_pool)]
    for t in texts:
        sig = t[t.index("func.func public @main("):].split(") -> (", 1)[0]
        params = re.split(r", (?=%arg\d+:)", sig)[-n_pool:]
        aliases = all(w in p for w, p in zip(want_alias, params))
        _check(aliases == donating,
               f"serve: every pool parameter of the step aliases its own "
               f"output = {aliases}, expected {donating}")
    if on_tpu and donating:
        pool_bytes = sum(t._value.nbytes for li in range(pool.num_layers)
                         for t in pool.step_arrays(li))
        aliased = engine.step_aliased_bytes()
        _check(all(a is not None and a >= pool_bytes for a in aliased),
               f"serve: step buckets alias {aliased} bytes, the pool holds "
               f"{pool_bytes}")
        dims = ",".join(str(d) for d in pool.k_pools[0].shape)
        pool_copy = re.compile(r"= \w+\[%s\]\S* copy\(" % re.escape(dims))
        n_copies = [len(pool_copy.findall(t))
                    for t in engine.step_program_texts(compiled=True)]
        _check(not any(n_copies),
               f"serve: pool-shaped copies per compiled step bucket: "
               f"{n_copies}")
    _check(engine.pool.used_pages == 0,
           f"serve: {engine.pool.used_pages} pool pages leaked")

    # wall time of the first call of each bucket (compile + one run), from
    # the engine's own req.compile events (one per rider: dedupe by time)
    compile_s = sorted({(e["t"], e["arg"])
                        for e in tracing.get_tracer().events()
                        if e["name"] == "req.compile" and e["t"] >= t_begin})
    compile_s = [round(a, 1) for _, a in compile_s]
    _check(len(compile_s) == final["step"],
           f"serve: {len(compile_s)} compile events for {final} programs")

    # the two shortest streams, against the dense forward
    short = sorted(passes[0], key=lambda o: o.prompt_token_ids.size
                   + o.n_gen)[:2]
    t0 = time.perf_counter()
    n, exact, worst = _dense_agreement(model, short, DENSE_RTOL)
    dense_s = time.perf_counter() - t0
    _say(f"serve[{dev.device_kind}]: requests={len(prompts)} "
         f"slots={max_batch_slots} pool_pages={num_pages} "
         f"({kv_pool_bytes / 2**30:.2f} GiB bf16) compiles={final} "
         f"bucket_first_call_s={compile_s} (compile + one step) "
         f"cold_pass_s={secs[0]:.1f} (first calls included) "
         f"warm_pass_s={secs[1]:.1f} third_pass_s={secs[2]:.1f} "
         f"engine_steps_per_pass={steps} "
         f"third_pass_ms_per_step={1e3 * secs[2] / steps[2]:.1f} "
         f"ragged_kernel={has_kernel} leaked_pages=0 "
         f"dense_check: {exact}/{n} exact argmax, worst gap "
         f"{worst:.5f} x max|logit| (tol {DENSE_RTOL:.5f}, {dense_s:.1f}s) "
         f"peak_bytes_in_use={_peak_bytes(dev)}")


def sharded_train_phase(cfg, batch: int, seq: int, steps: int, dp: int,
                        mp: int):
    """The dp×mp train step (``fleet.init``) against the one-device run of
    the same batch and seed, in this process; proves the work is spread."""
    import jax

    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    devs = jax.devices()
    _check(len(devs) == dp * mp, f"need {dp * mp} devices, have {len(devs)}")
    # keep the losses only: the one-device model and optimizer state must
    # leave device 0 before the mesh run is weighed against the others
    ref_losses, ref_secs = _train_steps(cfg, batch, seq, steps)[2:]
    gc.collect()
    _say(f"one-device: losses={[round(x, 4) for x in ref_losses]} "
         f"compile_s={ref_secs[0]:.1f} "
         f"step_ms={1e3 * statistics.median(ref_secs[1:]):.1f}")
    _check_losses("one-device", ref_losses)

    tag = f"dp{dp}xmp{mp}"
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                               "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        model, step, losses, secs = _train_steps(
            cfg, batch, seq, steps, wrap=fleet.distributed_model)
        diffs = [abs(a - b) for a, b in zip(losses, ref_losses)]
        _say(f"{tag}[{devs[0].device_kind} x{len(devs)}]: "
             f"losses={[round(x, 4) for x in losses]} "
             f"max|diff vs one device|={max(diffs):.4f} "
             f"(tol {SHARDED_LOSS_ATOL}) compile_s={secs[0]:.1f} "
             f"step_ms={1e3 * statistics.median(secs[1:]):.1f}")
        _check_losses(tag, losses)
        _check(max(diffs) <= SHARDED_LOSS_ATOL,
               f"{tag} losses differ from one device by {max(diffs):.4f}")
        _check(len(step._cache) == 1,
               f"{tag}: {len(step._cache)} compiled signatures")

        # proof that the work is really spread
        sharded = [p for p in model.parameters()
                   if any(a is not None for a in p.value.sharding.spec)]
        _check(sharded, "no parameter carries a non-trivial PartitionSpec")
        for p in sharded:
            on = {s.device for s in p.value.addressable_shards}
            _check(on == set(devs),
                   f"parameter {p.name} {p.shape} has shards on "
                   f"{len(on)} of {len(devs)} devices")
        # virtual CPU devices (the rehearsal) report no memory stats
        in_use = [d.memory_stats()["bytes_in_use"] for d in devs
                  if d.platform == "tpu"]
        _say(f"{tag}: sharded_params={len(sharded)} on {len(devs)} devices "
             f"bytes_in_use={in_use}")
        _check(not in_use or max(in_use) <= 2 * min(in_use),
               "device memory is not spread evenly")
        hlo = step.program_text(compiled=True)
        ops = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
               for op in ("all-reduce", "all-gather", "reduce-scatter",
                          "collective-permute", "all-to-all")}
        has_kernel = "tpu_custom_call" in hlo
        _say(f"{tag}: collectives={ops} flash_kernel={has_kernel}")
        _check(ops["all-reduce"] > 0,
               "no all-reduce in the compiled step (TP row-parallel and DP "
               "gradients both imply one)")
        want_kernel = jax.default_backend() == "tpu"
        _check(has_kernel == want_kernel,
               f"flash kernel in the sharded program = {has_kernel}, "
               f"expected {want_kernel}")
    finally:
        fleet.fleet._is_initialized = False
        dist.set_mesh(None)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the dp2×mp2 train phase and its "
                         "one-device comparison")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    dev = device_phase(args.chips)

    import jax

    from _bench_timing import enable_compile_cache
    from paddle_tpu.models.gpt import gpt3_1_3b

    _say(f"compile cache: {enable_compile_cache()}")
    cfg = gpt3_1_3b(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    fused_loss=True)
    if args.chips == 4:
        sharded_train_phase(cfg, batch=4, seq=1024, steps=4, dp=2, mp=2)
    else:
        model = train_phase(cfg, batch=4, seq=1024, steps=4, dev=dev)
        gc.collect()  # the optimizer state leaves HBM before the pool lands
        _say(f"after train: bytes_in_use="
             f"{dev.memory_stats()['bytes_in_use']}")
        paged_kernel_phase(cfg.num_heads, cfg.num_heads,
                           cfg.hidden_size // cfg.num_heads, page_size=16,
                           pages_per_seq=2048 // 16, dev=dev)
        serve_phase(model, kv_pool_bytes=3 << 30, page_size=16,
                    max_model_len=2048, max_batch_slots=4, token_budget=64,
                    prompt_lens=SERVE_PROMPTS, new_tokens=SERVE_NEW, dev=dev)
    _say(f"all phases passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
