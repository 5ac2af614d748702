#!/usr/bin/env python
"""Benchmarks: GPT pretraining (flagship), BERT-base finetune, ResNet-50.

One process, one model per invocation, on the chip:

    python bench.py --model gpt13|gpt|bert|resnet50|llama|llama7b|yoloe|ocr

Prints ONE JSON line on stdout: {"metric", "value", "unit", "vs_baseline"}
(+extras, including the device it ran on). All diagnostics go to stderr.
The reference publishes no numbers (BASELINE.md) — each config's first TPU
measurement IS the baseline.

The platform comes from ``jax.devices()`` in this process. Anything but a
TPU is a non-zero exit with no result line, unless BENCH_SMALL=1 asks for
the tiny CPU smoke size explicitly. Timings are host-clock spans closed by
``block_until_ready``; compile time is reported apart.

Model selection: ``--model ...`` or ``BENCH_MODEL`` env (default gpt13 — the
BASELINE.json north-star GPT-3 1.3B config).

Env knobs: BENCH_SMALL=1 (tiny config for a CPU smoke), BENCH_STEPS,
BENCH_BATCH, BENCH_SEQ, BENCH_RECOMPUTE=1, BENCH_RC_POLICY, BENCH_FUSED_CE,
BENCH_MASTER, BENCH_MODEL.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))

import numpy as np

# bf16 peak TFLOP/s of one chip, keyed by jax's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s). A kind that is not here is an error, never a default.
_PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
}


def _log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _emit(record: dict):
    print(json.dumps(record), flush=True)


def _time_steps(step, args, steps, reps=3):
    """Block timing: `steps` back-to-back calls (successive train steps are
    data-dependent through the donated optimizer state, so none can be
    elided or reordered) closed by ONE ``block_until_ready``; best of
    `reps` blocks. The first call's wall time is the compile time."""
    import jax

    def sync(out):
        jax.block_until_ready(_first_leaf(out).value)

    _log("compiling...")
    t0 = time.perf_counter()
    out = step(*args)
    sync(out)
    compile_s = time.perf_counter() - t0
    _log(f"compiled in {compile_s:.1f}s; warming 2 steps...")
    for _ in range(2):
        out = step(*args)
    sync(out)
    _log(f"timing {reps}x{steps} steps")
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step(*args)
        sync(out)
        block = time.perf_counter() - t0
        _log(f"block: {block:.3f}s ({block/steps*1e3:.1f}ms/step)")
        best = min(best, block)
    return best / steps, compile_s, out


def _first_leaf(out):
    return out[0] if isinstance(out, (tuple, list)) else out


def _mfu(achieved_tflops, dev):
    """Model FLOP/s utilization against the chip's published bf16 peak;
    None on the CPU smoke (a CPU number is never a device metric)."""
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in _PEAK_BF16_TFLOPS:
        raise KeyError(
            f"no published bf16 peak for device_kind {dev.device_kind!r}; "
            f"add it to _PEAK_BF16_TFLOPS with its source "
            f"(known: {sorted(_PEAK_BF16_TFLOPS)})")
    return round(achieved_tflops / _PEAK_BF16_TFLOPS[dev.device_kind], 4)


def _device_fields(dev):
    import jax

    return {"device": str(dev.platform), "device_kind": str(dev.device_kind),
            "device_count": len(jax.devices())}


# ------------------------------------------------------------------- GPT

def bench_gpt(dev, small):
    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if small:
        # scale position table with BENCH_SEQ: ids past a fixed 512-row
        # embedding would be silently clamped by XLA gather — a
        # numerically bogus long-seq row
        S = int(os.environ.get("BENCH_SEQ", 256))
        cfg = GPTConfig(vocab_size=2048, hidden_size=256, num_layers=4,
                        num_heads=8, max_position_embeddings=max(S, 512),
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        B = int(os.environ.get("BENCH_BATCH", 4))
        steps = int(os.environ.get("BENCH_STEPS", 5))
    else:
        # GPT-medium-scale: ~355M params — saturates one v5e chip in bf16
        S = int(os.environ.get("BENCH_SEQ", 1024))
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                        num_heads=16, max_position_embeddings=max(S, 1024),
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        recompute=os.environ.get("BENCH_RECOMPUTE") == "1",
                        recompute_policy=os.environ.get("BENCH_RC_POLICY")
                        or None,
                        fused_loss=os.environ.get("BENCH_FUSED_CE") == "1")
        B = int(os.environ.get("BENCH_BATCH", 8))
        steps = int(os.environ.get("BENCH_STEPS", 10))

    _log(f"gpt config: h{cfg.hidden_size} l{cfg.num_layers} B{B} S{S} "
         f"steps={steps} recompute={cfg.recompute} device={dev.platform}")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

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
    labels = paddle.to_tensor(np.roll(np.asarray(ids.numpy()), -1, axis=1))

    dt, compile_s, loss = _time_steps(step, (ids, labels), steps)
    tokens_per_s = B * S / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = 6 * n_params  # fwd+bwd dense-transformer convention
    achieved = flops_per_token * tokens_per_s / 1e12
    _emit({
        "metric": "gpt_tokens_per_sec_per_chip",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": 1.0,  # reference publishes no numbers (BASELINE.md)
        "config": f"gpt-h{cfg.hidden_size}-l{cfg.num_layers}-b{B}-s{S}-bf16"
                  + (("-rc" + (f":{cfg.recompute_policy}"
                               if cfg.recompute_policy else ""))
                     if cfg.recompute else "")
                  + ("-fce" if cfg.fused_loss else ""),
        "params_m": round(n_params / 1e6, 1),
        "loss": float(np.asarray(loss.numpy(), dtype="float32")),
        "step_ms": round(1000 * dt, 1),
        "compile_s": round(compile_s, 1),
        "achieved_tflops_per_s": round(achieved, 2),
        "mfu_vs_v5e_peak": _mfu(achieved, dev),
        **_device_fields(dev),
    })


# ------------------------------------------------------------ GPT-3 1.3B

def bench_gpt13(dev, small):
    """GPT-3 1.3B (BASELINE.json north star: h2048 l24 heads16, the GPT-3
    paper's "XL" row — d_head 128) single-chip training step at S=1024.

    Fit (GPT13_BUDGET.md): fp32 master weights put AdamW state at
    ~18.4 GiB > 16 GiB HBM, so this config runs amp O2 with
    master_weight=False (paddle's own multi_precision default): the
    accumulators are zeros_like(param), so bf16 params carry bf16 m/v —
    6 B/param, ~7.3 GiB persistent state (measured: the AOT sweep's
    argument_gb 7.34 = 3 bf16 param-sized buffers) + fused chunked CE.
    B4 without recompute is the best of the configurations measured on
    2026-08-01 (BENCH_NOTES_r05.json: b4 50.68% MFU, b8 47.42%, b8-dots
    46.55%). Override with BENCH_MASTER=1 to run the (non-fitting)
    master-weights control."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if small:
        S = int(os.environ.get("BENCH_SEQ", 256))
        cfg = GPTConfig(vocab_size=2048, hidden_size=256, num_layers=4,
                        num_heads=2,  # d_head 128 — same head geometry
                        max_position_embeddings=max(S, 512),
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        fused_loss=True)
        B = int(os.environ.get("BENCH_BATCH", 2))
        steps = int(os.environ.get("BENCH_STEPS", 3))
    else:
        S = int(os.environ.get("BENCH_SEQ", 1024))
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                        num_heads=16, max_position_embeddings=max(S, 1024),
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        recompute=os.environ.get("BENCH_RECOMPUTE") == "1",
                        recompute_policy=os.environ.get("BENCH_RC_POLICY")
                        or None,
                        fused_loss=os.environ.get("BENCH_FUSED_CE", "1")
                        == "1")
        B = int(os.environ.get("BENCH_BATCH", 4))
        steps = int(os.environ.get("BENCH_STEPS", 10))
    master = os.environ.get("BENCH_MASTER") == "1"

    _log(f"gpt13 config: h{cfg.hidden_size} l{cfg.num_layers} B{B} S{S} "
         f"steps={steps} recompute={cfg.recompute} "
         f"policy={cfg.recompute_policy} fce={cfg.fused_loss} "
         f"master={master} device={dev.platform}")
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16",
                              master_weight=master)

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
    labels = paddle.to_tensor(np.roll(np.asarray(ids.numpy()), -1, axis=1))

    dt, compile_s, loss = _time_steps(step, (ids, labels), steps)
    tokens_per_s = B * S / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    achieved = 6 * n_params * tokens_per_s / 1e12
    _emit({
        "metric": "gpt13_tokens_per_sec_per_chip",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": 1.0,
        "config": f"gpt13-h{cfg.hidden_size}-l{cfg.num_layers}-b{B}-s{S}"
                  f"-bf16" + (("-rc" + (f":{cfg.recompute_policy}"
                                        if cfg.recompute_policy else ""))
                              if cfg.recompute else "")
                  + ("-fce" if cfg.fused_loss else "")
                  + ("" if master else "-nomaster"),
        "params_m": round(n_params / 1e6, 1),
        "loss": float(np.asarray(loss.numpy(), dtype="float32")),
        "step_ms": round(1000 * dt, 1),
        "compile_s": round(compile_s, 1),
        "achieved_tflops_per_s": round(achieved, 2),
        "mfu_vs_v5e_peak": _mfu(achieved, dev),
        **_device_fields(dev),
    })


# ------------------------------------------------------------------ BERT

def bench_bert(dev, small):
    """BERT-base MLM+NSP pretraining-style step (BASELINE.md config 2)."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.models import BertForPretraining, bert_base, bert_tiny

    if small:
        # scale the position table with BENCH_SEQ: ids past it are
        # silently clamped by XLA gather (degenerate embeddings -> NaN
        # MLM loss, observed at S=512 against the 128-row tiny default)
        S = int(os.environ.get("BENCH_SEQ", 128))
        cfg = bert_tiny(max_position_embeddings=max(S, 128))
        B = int(os.environ.get("BENCH_BATCH", 4))
        steps = int(os.environ.get("BENCH_STEPS", 5))
    else:
        S = int(os.environ.get("BENCH_SEQ", 128))
        cfg = bert_base(max_position_embeddings=max(S, 512))
        B = int(os.environ.get("BENCH_BATCH", 32))
        steps = int(os.environ.get("BENCH_STEPS", 10))

    _log(f"bert config: h{cfg.hidden_size} l{cfg.num_layers} "
         f"B{B} S{S} steps={steps} device={dev.platform}")
    paddle.seed(0)
    model = BertForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

    def train_fn(ids, mlm_labels, nsp_labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            _, loss = model(ids, masked_lm_labels=mlm_labels,
                            next_sentence_labels=nsp_labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = jit.StaticFunction(train_fn, observe=[model, opt], warmup=False)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (B, S)))
    mlm = np.asarray(ids.numpy()).copy()
    keep = rng.random((B, S)) > 0.15
    mlm[keep] = -100  # ignore index: loss on the 15% masked positions
    mlm_labels = paddle.to_tensor(mlm)
    nsp = paddle.to_tensor(rng.integers(0, 2, (B,)))

    dt, compile_s, loss = _time_steps(step, (ids, mlm_labels, nsp), steps)
    tokens_per_s = B * S / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    achieved = 6 * n_params * tokens_per_s / 1e12
    _emit({
        "metric": "bert_tokens_per_sec_per_chip",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": 1.0,
        "config": f"bert-h{cfg.hidden_size}-l{cfg.num_layers}"
                  f"-b{B}-s{S}-bf16",
        "params_m": round(n_params / 1e6, 1),
        "loss": float(np.asarray(loss.numpy(), dtype="float32")),
        "step_ms": round(1000 * dt, 1),
        "compile_s": round(compile_s, 1),
        "achieved_tflops_per_s": round(achieved, 2),
        "mfu_vs_v5e_peak": _mfu(achieved, dev),
        **_device_fields(dev),
    })


# --------------------------------------------------------------- ResNet-50

def bench_resnet50(dev, small):
    """ResNet-50 ImageNet-shape training step (BASELINE.md config 1).
    FLOPs/step come from XLA's own cost analysis of the compiled program
    (StaticFunction.cost_analysis) — convs don't fit the 6N convention."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import amp, jit
    from paddle_tpu.vision.models import resnet18, resnet50

    if small:
        model_fn, name = resnet18, "resnet18"
        B = int(os.environ.get("BENCH_BATCH", 2))
        H = 64
        steps = int(os.environ.get("BENCH_STEPS", 3))
    else:
        model_fn, name = resnet50, "resnet50"
        B = int(os.environ.get("BENCH_BATCH", 64))
        H = 224
        steps = int(os.environ.get("BENCH_STEPS", 10))

    _log(f"{name} config: B{B} {H}x{H} steps={steps} device={dev.platform}")
    paddle.seed(0)
    model = model_fn(num_classes=1000)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

    def train_fn(images, labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            logits = model(images)
            loss = F.cross_entropy(logits, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = jit.StaticFunction(train_fn, observe=[model, opt], warmup=False)
    rng = np.random.default_rng(0)
    images = paddle.to_tensor(
        rng.standard_normal((B, 3, H, H)).astype("float32"))
    labels = paddle.to_tensor(rng.integers(0, 1000, (B,)))

    dt, compile_s, loss = _time_steps(step, (images, labels), steps)
    imgs_per_s = B / dt

    flops_per_step = None
    flops_source = "analytic"
    try:
        cost = step.cost_analysis()
        if cost and cost.get("flops"):
            flops_per_step = float(cost["flops"])
            flops_source = "xla_cost_analysis"
    except Exception as e:  # pragma: no cover
        _log(f"cost_analysis unavailable: {type(e).__name__}: {e}")
    if flops_per_step is None:
        # analytic fallback: ~4.1 GFLOPs fwd @224 x3 for fwd+bwd
        flops_per_step = (12.3e9 if name == "resnet50" else 5.4e9) \
            * B * (H / 224.0) ** 2
    achieved = flops_per_step * (1.0 / dt) / 1e12
    _emit({
        "metric": f"{name}_images_per_sec_per_chip",
        "value": round(imgs_per_s, 1),
        "unit": "imgs/s",
        "vs_baseline": 1.0,
        "config": f"{name}-b{B}-{H}x{H}-bf16",
        "loss": float(np.asarray(loss.numpy(), dtype="float32")),
        "step_ms": round(1000 * dt, 1),
        "compile_s": round(compile_s, 1),
        "achieved_tflops_per_s": round(achieved, 2),
        "mfu_vs_v5e_peak": _mfu(achieved, dev),
        "flops_source": flops_source,
        **_device_fields(dev),
    })


# -------------------------------------------------- dynamic-shape vision

def _time_stream(step, batches, reps):
    """Chained timing over a HETEROGENEOUS batch stream (the dynamic-shape
    benches): every batch every rep, ONE terminal ``block_until_ready`` per
    rep — same methodology as _time_steps."""
    import jax

    def sync(out):
        jax.block_until_ready(_first_leaf(out).value)

    _log(f"warmup pass over {len(batches)} batches (compiles each bucket)")
    t0 = time.perf_counter()
    out = None
    for b in batches:
        out = step(*b)
    sync(out)
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for b in batches:
            out = step(*b)
        sync(out)
        best = min(best, time.perf_counter() - t0)
        _log(f"stream pass: {best:.3f}s")
    return best, compile_s


def bench_yoloe(dev, small):
    """PP-YOLOE-s dynamic-shape training (BASELINE.md config 5): images
    arrive at varying resolutions and gt counts; jit.BucketedFunction pads
    onto a bucket ladder so XLA compiles once per bucket, not per shape.
    Reports imgs/s + the recompile count on the shape stream."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.vision.models import ppyoloe_s

    if small:
        sizes, B, M_max, reps = [64, 96], 2, 8, 2
    else:
        sizes, B, M_max, reps = [320, 416, 512], 8, 16, 3
    B = int(os.environ.get("BENCH_BATCH", B))

    paddle.seed(0)
    model = ppyoloe_s(num_classes=80)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

    def train_fn(imgs, gt_boxes, gt_labels, gt_mask):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = model.loss(model(imgs), gt_boxes, gt_labels, gt_mask)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    mladder = [M_max // 2, M_max]
    step = jit.BucketedFunction(
        train_fn,
        axes={0: {2: sizes, 3: sizes},
              1: {1: mladder}, 2: {1: mladder}, 3: {1: mladder}},
        pad_values={1: 0.0, 2: 0, 3: 0.0},
        observe=[model, opt])

    # a seeded stream of 8 batches at varied (H, W, M) — the dynamic-shape
    # workload the reference feeds PP-YOLOE (multi-scale training)
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(8):
        H = int(rng.choice(sizes))
        W = int(rng.choice(sizes))
        M = int(rng.integers(2, M_max))
        imgs = paddle.to_tensor(
            rng.standard_normal((B, 3, H, W)).astype("float32"))
        xy = rng.uniform(0, min(H, W) * 0.6, (B, M, 2)).astype("float32")
        wh = rng.uniform(8, min(H, W) * 0.3, (B, M, 2)).astype("float32")
        boxes = np.concatenate([xy, xy + wh], -1)
        batches.append((imgs, paddle.to_tensor(boxes),
                        paddle.to_tensor(rng.integers(0, 80, (B, M))),
                        paddle.to_tensor(np.ones((B, M), "float32"))))
    distinct = len({tuple(b[0].shape) + tuple(b[1].shape) for b in batches})

    stream_s, compile_s = _time_stream(step, batches, reps)
    imgs_per_s = len(batches) * B / stream_s
    _emit({
        "metric": "yoloe_images_per_sec_per_chip",
        "value": round(imgs_per_s, 1),
        "unit": "imgs/s",
        "vs_baseline": 1.0,
        "config": f"ppyoloe_s-b{B}-sizes{sizes}-bf16-bucketed",
        "distinct_input_shapes": distinct,
        "recompiles": step.compile_count,
        "stream_batches": len(batches),
        "compile_s": round(compile_s, 1),
        "mfu_vs_v5e_peak": None,
        **_device_fields(dev),
    })


def bench_ocr(dev, small):
    """PP-OCR CRNN recognition training (BASELINE.md config 5's second
    half): variable-width text crops + variable-length labels, bucket-
    padded (CTC ignores padded frames via the blank path). imgs/s +
    recompile count."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.vision.models import CRNN

    if small:
        widths, B, L_max, reps = [64, 96], 4, 8, 2
    else:
        widths, B, L_max, reps = [96, 160, 256, 320], 32, 24, 3
    B = int(os.environ.get("BENCH_BATCH", B))

    paddle.seed(0)
    model = CRNN(num_classes=97)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

    def train_fn(imgs, labels, label_lengths):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            log_probs = model(imgs)
            loss = model.loss(log_probs, labels, label_lengths)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    lladder = [L_max // 2, L_max]
    step = jit.BucketedFunction(
        train_fn,
        axes={0: {3: widths}, 1: {1: lladder}},
        pad_values={1: 0},
        observe=[model, opt])

    rng = np.random.default_rng(0)
    batches = []
    for _ in range(8):
        W = int(rng.choice(widths))
        L = int(rng.integers(2, L_max))
        imgs = paddle.to_tensor(
            rng.standard_normal((B, 3, 32, W)).astype("float32"))
        labels = paddle.to_tensor(rng.integers(1, 97, (B, L)))
        lengths = paddle.to_tensor(np.full((B,), L, "int64"))
        batches.append((imgs, labels, lengths))
    distinct = len({tuple(b[0].shape) + tuple(b[1].shape) for b in batches})

    stream_s, compile_s = _time_stream(step, batches, reps)
    imgs_per_s = len(batches) * B / stream_s
    _emit({
        "metric": "ocr_images_per_sec_per_chip",
        "value": round(imgs_per_s, 1),
        "unit": "imgs/s",
        "vs_baseline": 1.0,
        "config": f"crnn-b{B}-w{widths}-bf16-bucketed",
        "distinct_input_shapes": distinct,
        "recompiles": step.compile_count,
        "stream_batches": len(batches),
        "compile_s": round(compile_s, 1),
        "mfu_vs_v5e_peak": None,
        **_device_fields(dev),
    })


# ----------------------------------------------------------------- Llama

def bench_llama(dev, small):
    """Llama-family single-chip training step (BASELINE.md config 4's
    family at a size one v5e chip holds: ~0.76B params + AdamW fp32
    state ~= 10.6 GB, headroom for activations at B8 S1024)."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, jit
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, llama_tiny

    if small:
        # no position-table scaling needed here: llama is RoPE-only (the
        # rotary tables are computed from the actual sequence length;
        # max_position_embeddings only caps generate()/export)
        cfg = llama_tiny(recompute=False, fused_loss=True)
        B = int(os.environ.get("BENCH_BATCH", 2))
        S = int(os.environ.get("BENCH_SEQ", 128))
        steps = int(os.environ.get("BENCH_STEPS", 3))
    else:
        S = int(os.environ.get("BENCH_SEQ", 1024))
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, num_layers=12,
                          num_heads=16, num_key_value_heads=16,
                          max_position_embeddings=max(S, 1024),
                          # default ON: the fitting, proven config — plain
                          # b8-norc OOM'd in r4, so a bare run must not
                          # land on it by default
                          recompute=os.environ.get("BENCH_RECOMPUTE", "1")
                          == "1",
                          recompute_policy=os.environ.get("BENCH_RC_POLICY")
                          or None,
                          fused_loss=os.environ.get("BENCH_FUSED_CE", "1")
                          == "1")
        B = int(os.environ.get("BENCH_BATCH", 8))
        steps = int(os.environ.get("BENCH_STEPS", 10))

    _log(f"llama config: h{cfg.hidden_size} l{cfg.num_layers} B{B} S{S} "
         f"steps={steps} recompute={cfg.recompute} "
         f"fused_loss={cfg.fused_loss} device={dev.platform}")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

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
    labels = paddle.to_tensor(np.roll(np.asarray(ids.numpy()), -1, axis=1))

    dt, compile_s, loss = _time_steps(step, (ids, labels), steps)
    tokens_per_s = B * S / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    achieved = 6 * n_params * tokens_per_s / 1e12
    _emit({
        "metric": "llama_tokens_per_sec_per_chip",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": 1.0,
        "config": f"llama-h{cfg.hidden_size}-l{cfg.num_layers}-b{B}-s{S}"
                  f"-bf16" + (("-rc" + (f":{cfg.recompute_policy}"
                                        if cfg.recompute_policy else ""))
                              if cfg.recompute else "")
                  + ("-fce" if cfg.fused_loss else ""),
        "params_m": round(n_params / 1e6, 1),
        "loss": float(np.asarray(loss.numpy(), dtype="float32")),
        "step_ms": round(1000 * dt, 1),
        "compile_s": round(compile_s, 1),
        "achieved_tflops_per_s": round(achieved, 2),
        "mfu_vs_v5e_peak": _mfu(achieved, dev),
        **_device_fields(dev),
    })


def bench_llama7b(dev, small):
    """Llama-2 7B (BASELINE.md config 4). Needs >= 8 chips. On fewer
    devices it runs the compile-only budget (tools/llama7b_budget.py, a
    CPU-pinned child that never needs the chip) and emits the staged row
    LOUDLY marked compile_only."""
    import subprocess

    import jax

    n = len(jax.devices())
    if n >= 8 and not small:
        # real 8-chip run: ZeRO-3 + recompute + fused CE, B8 S4096
        os.environ.setdefault("BENCH_BATCH", "8")
        os.environ.setdefault("BENCH_SEQ", "4096")
        os.environ.setdefault("BENCH_RECOMPUTE", "1")
        raise NotImplementedError(
            "llama7b 8-chip bench: attach a pod slice and wire the mesh "
            "config here (tools/llama7b_budget.py has the exact recipe)")
    _log(f"llama7b: {n} device(s) < 8 — running compile-only budget")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "llama7b_budget.py")
    args = [sys.executable, tool, "--no-write"]
    if small:
        args.append("--smoke")
    r = subprocess.run(args, capture_output=True, text=True, timeout=7200)
    line = next((ln for ln in reversed(r.stdout.splitlines())
                 if ln.startswith("{")), None)
    if r.returncode not in (0, 1) or line is None:
        raise RuntimeError(f"budget tool failed rc={r.returncode}: "
                           f"{r.stderr[-500:]}")
    rec = json.loads(line)
    rec.update({"compile_only": True, **_device_fields(dev),
                "vs_baseline": 1.0,
                "note": "7B needs an 8-chip slice; this certifies fit+compile"})
    _emit(rec)


_MODELS = {"gpt": bench_gpt, "gpt13": bench_gpt13, "bert": bench_bert,
           "resnet50": bench_resnet50, "llama": bench_llama,
           "llama7b": bench_llama7b, "yoloe": bench_yoloe,
           "ocr": bench_ocr}


def main():
    model = os.environ.get("BENCH_MODEL", "gpt13")
    if "--model" in sys.argv:
        model = sys.argv[sys.argv.index("--model") + 1]
    if model not in _MODELS:
        _log(f"unknown model {model!r}; choose from {sorted(_MODELS)}")
        sys.exit(2)

    from _bench_timing import enable_compile_cache, require_tpu

    small = os.environ.get("BENCH_SMALL") == "1"
    if small:
        import jax

        dev = jax.devices()[0]
    else:
        dev = require_tpu(log=_log)
    _log(f"device: {dev.platform} {dev.device_kind}; "
         f"compile cache: {enable_compile_cache()}")
    _MODELS[model](dev, small)


if __name__ == "__main__":
    main()
