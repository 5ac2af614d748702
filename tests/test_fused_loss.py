"""Fused chunked linear+CE (ops/fused_loss.py): numerics vs the dense path,
ignore_index, bf16, and the GPTConfig.fused_loss integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.ops.fused_loss import fused_linear_cross_entropy


def _dense_ref(h, w, y, ignore=-100):
    logits = h.astype(np.float64) @ w.astype(np.float64).T
    m = logits.max(-1, keepdims=True)
    lse = (m + np.log(np.exp(logits - m).sum(-1, keepdims=True))).squeeze(-1)
    valid = y != ignore
    yy = np.where(valid, y, 0)
    per = lse - logits[np.arange(len(y)), yy]
    return float((per * valid).sum() / max(valid.sum(), 1))


def test_matches_dense_loss_and_grads():
    rng = np.random.RandomState(0)
    N, H, V = 64, 32, 512
    h = rng.randn(N, H).astype(np.float32)
    w = rng.randn(V, H).astype(np.float32) * 0.1
    y = rng.randint(0, V, (N,))

    loss = fused_linear_cross_entropy(jnp.asarray(h), jnp.asarray(w),
                                      jnp.asarray(y), 128)
    np.testing.assert_allclose(float(loss), _dense_ref(h, w, y), rtol=1e-5)

    # grads vs jax AD of the dense formulation
    def dense(hh, ww):
        logits = hh @ ww.T
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.asarray(y)[:, None],
                                     axis=1)[:, 0]
        return jnp.mean(lse - picked)

    gd_h, gd_w = jax.grad(dense, argnums=(0, 1))(jnp.asarray(h),
                                                 jnp.asarray(w))
    gf_h, gf_w = jax.grad(
        lambda hh, ww: fused_linear_cross_entropy(
            hh, ww, jnp.asarray(y), 128), argnums=(0, 1))(
        jnp.asarray(h), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(gf_h), np.asarray(gd_h),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gf_w), np.asarray(gd_w),
                               rtol=1e-4, atol=1e-6)


def test_ignore_index():
    rng = np.random.RandomState(1)
    N, H, V = 32, 16, 256
    h = rng.randn(N, H).astype(np.float32)
    w = rng.randn(V, H).astype(np.float32) * 0.1
    y = rng.randint(0, V, (N,))
    y[::3] = -100
    loss = fused_linear_cross_entropy(jnp.asarray(h), jnp.asarray(w),
                                      jnp.asarray(y), 64)
    np.testing.assert_allclose(float(loss), _dense_ref(h, w, y), rtol=1e-5)
    # ignored rows contribute no grad
    g = jax.grad(lambda hh: fused_linear_cross_entropy(
        hh, jnp.asarray(w), jnp.asarray(y), 64))(jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(g)[::3], 0.0, atol=1e-8)


def test_bf16_inputs_finite_and_close():
    rng = np.random.RandomState(2)
    N, H, V = 32, 32, 384
    h = rng.randn(N, H).astype(np.float32)
    w = (rng.randn(V, H) * 0.1).astype(np.float32)
    y = rng.randint(0, V, (N,))
    loss16 = fused_linear_cross_entropy(
        jnp.asarray(h, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16),
        jnp.asarray(y), 128)
    assert np.isfinite(float(loss16))
    np.testing.assert_allclose(float(loss16), _dense_ref(h, w, y),
                               rtol=3e-2, atol=3e-2)


def test_odd_vocab_falls_back_to_valid_chunking():
    rng = np.random.RandomState(3)
    h = rng.randn(8, 8).astype(np.float32)
    w = rng.randn(300, 8).astype(np.float32) * 0.1  # 300 not divisible by 128
    y = rng.randint(0, 300, (8,))
    loss = fused_linear_cross_entropy(jnp.asarray(h), jnp.asarray(w),
                                      jnp.asarray(y), 128)
    np.testing.assert_allclose(float(loss), _dense_ref(h, w, y), rtol=1e-5)


def test_gpt_fused_loss_matches_dense_path():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    kw = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
              max_position_embeddings=32, hidden_dropout_prob=0.0,
              attention_dropout_prob=0.0)
    rng = np.random.RandomState(4)
    ids = rng.randint(0, 512, (2, 32))
    labels = np.roll(ids, -1, axis=1)

    pt.seed(0)
    dense = GPTForCausalLM(GPTConfig(**kw))
    _, dense_loss = dense(pt.to_tensor(ids), labels=pt.to_tensor(labels))

    pt.seed(0)
    fused = GPTForCausalLM(GPTConfig(fused_loss=True, **kw))
    none_logits, fused_loss = fused(pt.to_tensor(ids),
                                    labels=pt.to_tensor(labels))
    assert none_logits is None
    np.testing.assert_allclose(float(np.asarray(fused_loss.numpy())),
                               float(np.asarray(dense_loss.numpy())),
                               rtol=1e-4)
    # trains: backward reaches the tied embedding
    fused_loss.backward()
    assert fused.gpt.embeddings.weight.grad is not None


def test_llama_fused_loss_matches_dense_path():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    kw = dict(vocab_size=384, hidden_size=64, num_layers=2, num_heads=4,
              num_key_value_heads=2, max_position_embeddings=32)
    rng = np.random.RandomState(5)
    ids = rng.randint(0, 384, (2, 32))
    labels = np.roll(ids, -1, axis=1)

    pt.seed(0)
    dense = LlamaForCausalLM(LlamaConfig(**kw))
    _, dense_loss = dense(pt.to_tensor(ids), labels=pt.to_tensor(labels))

    pt.seed(0)
    fused = LlamaForCausalLM(LlamaConfig(fused_loss=True, **kw))
    none_logits, fused_loss = fused(pt.to_tensor(ids),
                                    labels=pt.to_tensor(labels))
    assert none_logits is None
    np.testing.assert_allclose(float(np.asarray(fused_loss.numpy())),
                               float(np.asarray(dense_loss.numpy())),
                               rtol=1e-4)
    fused_loss.backward()
    assert fused.lm_head.weight.grad is not None


def test_llama_fused_loss_tied_embeddings():
    """The tied-embedding branch uses the [V, H] table without transpose."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    kw = dict(vocab_size=384, hidden_size=64, num_layers=2, num_heads=4,
              num_key_value_heads=2, max_position_embeddings=32,
              tie_word_embeddings=True)
    rng = np.random.RandomState(6)
    ids = rng.randint(0, 384, (2, 32))
    labels = np.roll(ids, -1, axis=1)

    pt.seed(0)
    dense = LlamaForCausalLM(LlamaConfig(**kw))
    _, dense_loss = dense(pt.to_tensor(ids), labels=pt.to_tensor(labels))

    pt.seed(0)
    fused = LlamaForCausalLM(LlamaConfig(fused_loss=True, **kw))
    _, fused_loss = fused(pt.to_tensor(ids), labels=pt.to_tensor(labels))
    np.testing.assert_allclose(float(np.asarray(fused_loss.numpy())),
                               float(np.asarray(dense_loss.numpy())),
                               rtol=1e-4)
    fused_loss.backward()
    assert fused.llama.embed_tokens.weight.grad is not None


# -- the products' precision and the chunk rule (PR 36) ---------------------

def _dense_f32(y, ignore=-100):
    """The dense formulation in float32 at the package's matmul precision:
    jax AD of it is the reference for loss and both gradients."""
    yj = jnp.asarray(y)
    valid = yj != ignore

    def dense(hh, ww):
        logits = hh.astype(jnp.float32) @ ww.astype(jnp.float32).T
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.where(valid, yj, 0)[:, None], axis=1)[:, 0]
        return jnp.sum(jnp.where(valid, lse - picked, 0.0)) \
            / jnp.maximum(jnp.sum(valid), 1)

    return dense


def _loss_and_grads(fn, h, w):
    loss, (gh, gw) = jax.value_and_grad(fn, argnums=(0, 1))(h, w)
    return float(loss), np.asarray(gh, np.float32), np.asarray(gw, np.float32)


# half a unit in the last place of a bfloat16, relative: the most that
# rounding a value to bf16 moves it
_BF16_HALF_ULP = 2.0 ** -9


def test_bf16_loss_and_both_grads_match_dense_f32():
    """bf16 operands multiply as bf16 and accumulate in f32. The loss is the
    dense float32 loss of the same (bf16-valued) inputs: a bf16 x bf16
    product is exact in the accumulator. Each gradient is that reference's
    within what the two bf16 roundings of the backward may cost an element:
    d = p - onehot in front of its products, and the output."""
    rng = np.random.RandomState(7)
    N, H, V = 256, 128, 1000
    h = jnp.asarray(rng.randn(N, H), jnp.bfloat16)
    w = jnp.asarray(rng.randn(V, H) * 0.1, jnp.bfloat16)
    y = rng.randint(0, V, (N,))
    y[::5] = -100

    loss, gh, gw = _loss_and_grads(
        lambda hh, ww: fused_linear_cross_entropy(hh, ww, jnp.asarray(y), 256),
        h, w)
    h32, w32 = h.astype(jnp.float32), w.astype(jnp.float32)
    ref_loss, rh, rw = _loss_and_grads(_dense_f32(y), h32, w32)
    np.testing.assert_allclose(loss, ref_loss, rtol=2e-6)

    # |d| from the equations, for the bound on what rounding d moves
    hn, wn = np.asarray(h32, np.float64), np.asarray(w32, np.float64)
    logits = hn @ wn.T
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    valid = y != -100
    p[np.arange(N), np.where(valid, y, 0)] -= 1.0
    d = np.abs(p) * valid[:, None] / valid.sum()
    for got, ref, d_moves in ((gh, rh, d @ np.abs(wn)),
                              (gw, rw, d.T @ np.abs(hn))):
        assert np.isfinite(got).all()
        room = _BF16_HALF_ULP * (d_moves + 2 * np.abs(ref)) + 1e-9
        worst = np.max(np.abs(got - ref) / room)
        assert worst <= 1.0, worst
        # as a whole: each of the two roundings moves the norm by at most
        # half a unit in the last place
        rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
        assert rel < 2 * _BF16_HALF_ULP, rel
    np.testing.assert_array_equal(gh[::5], 0.0)


def _dot_generals(jaxpr):
    """Every dot_general equation of a jaxpr, scan bodies and the custom
    vjp's inner jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found.extend(_dot_generals(inner))
    return found


@pytest.mark.parametrize("dtypes, operand", [
    (("bfloat16", "bfloat16"), "bfloat16"),
    (("float32", "float32"), "float32"),
    (("bfloat16", "float32"), "float32"),
    (("float64", "float64"), "float32"),
], ids=["bf16", "f32", "mixed", "f64"])
def test_products_multiply_in_the_operands_dtype(dtypes, operand):
    """The choice is made at trace time, so it is read from the program: in
    forward and backward every product's operands have the promoted dtype of
    hidden and weight (no float32 operand under bf16 inputs) and every
    product accumulates in float32."""
    h = jnp.zeros((16, 8), dtypes[0])
    w = jnp.zeros((300, 8), dtypes[1])
    y = jnp.zeros((16,), jnp.int32)

    def f(hh, ww):
        return fused_linear_cross_entropy(hh, ww, y, 128)

    fwd = _dot_generals(jax.make_jaxpr(f)(h, w).jaxpr)
    both = _dot_generals(
        jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(h, w).jaxpr)
    assert len(fwd) == 1 and len(both) == 4, (len(fwd), len(both))
    for eqn in fwd + both:
        assert [str(v.aval.dtype) for v in eqn.invars] == [operand] * 2, eqn
        assert eqn.params["preferred_element_type"] == jnp.float32, eqn
        assert str(eqn.outvars[0].aval.dtype) == "float32", eqn


@pytest.mark.parametrize("v, chunk, rows, n", [
    (50304, 8192, 7296, 7),    # GPT-3's padded vocab: 1.5% padding, was 14%
    (8192, 8192, 8192, 1),     # fits one chunk
    (300, 8192, 300, 1),       # fits one chunk: V itself, not a multiple
    (16384, 8192, 8192, 2),    # an exact multiple of the chunk
    (50257, 8192, 7296, 7),    # an odd vocab
    (32000, 8192, 8064, 4),    # ceil(V / n) = 8000 rounds up to 63 x 128
    (8193, 8192, 4224, 2),     # ceil(V / n) = 4097 rounds up to 33 x 128
    (1001, 200, 167, 6),       # a small chunk: 256 would pass it, so 167
    (300, 128, 128, 3),
    (384, 128, 128, 3),
    (513, 512, 384, 2),
], ids=lambda x: str(x))
def test_chunk_rule(v, chunk, rows, n):
    from paddle_tpu.ops.fused_loss import _chunks, _pick_chunk

    c = _pick_chunk(v, chunk)
    assert c == rows and c <= chunk
    wch, c2, v2 = _chunks(jnp.zeros((v, 2), jnp.bfloat16), chunk)
    assert wch.shape == (n, rows, 2) and (c2, v2) == (rows, v)
    assert n == -(-v // chunk)          # no chunk more than the cap asks for
    assert n * rows - v < n * 128       # padding under 128 rows a chunk
    assert (n - 1) * rows < v           # and no chunk wholly padding


@pytest.mark.parametrize("v, chunk", [
    (1572, 256),   # GPT-3's shape in small: 7 chunks, 1,792 rows, was 14%
    (200, 256),    # V <= chunk: one chunk of V
    (1001, 200),   # an odd V, a chunk no multiple of 128
    (1024, 256),   # an exact multiple
    (257, 256),    # one row over
], ids=lambda x: str(x))
def test_chunked_loss_and_grads_match_dense(v, chunk):
    rng = np.random.RandomState(v)
    N, H = 48, 24
    h = jnp.asarray(rng.randn(N, H), jnp.float32)
    w = jnp.asarray(rng.randn(v, H) * 0.1, jnp.float32)
    y = rng.randint(0, v, (N,))
    y[0], y[1], y[2] = v - 1, 0, -100    # the last row of the last chunk

    loss, gh, gw = _loss_and_grads(
        lambda hh, ww: fused_linear_cross_entropy(
            hh, ww, jnp.asarray(y), chunk), h, w)
    ref_loss, rh, rw = _loss_and_grads(_dense_f32(y), h, w)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    assert gw.shape == (v, H)
    np.testing.assert_allclose(gh, rh, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gw, rw, rtol=1e-4, atol=1e-6)


def test_float16_gradients_do_not_underflow_with_the_loss_scale():
    """The loss's 1 / count multiplies the products' float32 results and not
    d: at 4,096 rows p / 4096 would sit among float16's subnormals."""
    rng = np.random.RandomState(8)
    N, H, V = 4096, 8, 300
    h = jnp.asarray(rng.randn(N, H), jnp.float16)
    w = jnp.asarray(rng.randn(V, H) * 0.1, jnp.float16)
    y = rng.randint(0, V, (N,))
    _, gh, gw = _loss_and_grads(
        lambda hh, ww: fused_linear_cross_entropy(hh, ww, jnp.asarray(y), 128),
        h, w)
    _, rh, rw = _loss_and_grads(
        _dense_f32(y), h.astype(jnp.float32), w.astype(jnp.float32))
    # dh's own elements are subnormal in float16 (|dh| ~ 1e-5), so it is dw,
    # summed over the 4,096 rows, that shows whether d kept its digits
    assert np.linalg.norm(gw - rw) / np.linalg.norm(rw) < 2.0 ** -11
    assert np.linalg.norm(gh - rh) / np.linalg.norm(rh) < 2.0 ** -6
