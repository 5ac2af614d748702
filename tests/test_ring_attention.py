"""Ring attention: exact parity with dense attention over the 'sep' axis
+ fused incubate layers (reference gap: SURVEY §2.3 — no SP/CP in the
reference; fused_transformer.py:192,497,725)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet, ring_attention

pytestmark = pytest.mark.slow  # fast lane: -m 'not slow'


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    dist.set_mesh(None)
    fleet.fleet._is_initialized = False


def _init_sep(sep=4, dp=1):
    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": dp, "sep_degree": sep}
    fleet.fleet._is_initialized = False
    fleet.init(strategy=s)


def _qkv(B=2, S=32, H=4, D=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.standard_normal((B, S, H, D)).astype("float32")
    return mk(), mk(), mk()


def _dense_ref(q, k, v, causal):
    qh, kh, vh = [np.swapaxes(x, 1, 2) for x in (q, k, v)]
    s = np.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(q.shape[-1])
    if causal:
        S = q.shape[1]
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask, s, -1e9)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.swapaxes(np.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        _init_sep(sep=4)
        q, k, v = _qkv()
        out = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                             paddle.to_tensor(v), causal=causal)
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   _dense_ref(q, k, v, causal),
                                   rtol=1e-4, atol=1e-5)

    def test_gradients_match_dense(self):
        _init_sep(sep=4)
        q, k, v = _qkv(seed=1)

        def grads(use_ring):
            qt, kt, vt = (paddle.to_tensor(x) for x in (q, k, v))
            for t in (qt, kt, vt):
                t.stop_gradient = False
            if use_ring:
                out = ring_attention(qt, kt, vt, causal=True)
            else:
                dist.set_mesh(None)
                out = F.scaled_dot_product_attention(qt, kt, vt,
                                                     is_causal=True)
            (out * out).sum().backward()
            return [np.asarray(t.grad.numpy()) for t in (qt, kt, vt)]

        g_ring = grads(True)
        dist.set_mesh(None)
        g_ref = grads(False)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)

    def test_fallback_without_mesh(self):
        dist.set_mesh(None)
        q, k, v = _qkv(S=16, seed=2)
        out = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                             paddle.to_tensor(v))
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   _dense_ref(q, k, v, False),
                                   rtol=1e-4, atol=1e-5)

    def test_indivisible_seq_raises(self):
        _init_sep(sep=4)
        q, k, v = _qkv(S=30, seed=3)
        with pytest.raises(ValueError):
            ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                           paddle.to_tensor(v))

    def test_composes_with_dp(self):
        _init_sep(sep=2, dp=4)
        q, k, v = _qkv(seed=4)
        out = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                             paddle.to_tensor(v), causal=True)
        np.testing.assert_allclose(np.asarray(out.numpy()),
                                   _dense_ref(q, k, v, True),
                                   rtol=1e-4, atol=1e-5)


class TestFusedLayers:
    def test_fused_linear(self):
        from paddle_tpu.incubate.nn import FusedLinear

        paddle.seed(0)
        fl = FusedLinear(6, 3)
        x = np.random.default_rng(0).standard_normal((4, 6)).astype("float32")
        out = fl(paddle.to_tensor(x))
        ref = x @ np.asarray(fl.weight.numpy()) + np.asarray(fl.bias.numpy())
        np.testing.assert_allclose(np.asarray(out.numpy()), ref, rtol=1e-5)

    def test_fused_dropout_add_eval(self):
        from paddle_tpu.incubate.nn import FusedDropoutAdd

        fda = FusedDropoutAdd(p=0.5)
        fda.eval()
        x = np.ones((2, 3), "float32")
        out = fda(paddle.to_tensor(x), paddle.to_tensor(2 * x))
        np.testing.assert_allclose(np.asarray(out.numpy()), 3 * x)

    def test_fused_mha_matches_unfused_math(self):
        from paddle_tpu.incubate.nn import FusedMultiHeadAttention

        paddle.seed(1)
        E, H = 16, 4
        mha = FusedMultiHeadAttention(E, H, dropout_rate=0.0,
                                      attn_dropout_rate=0.0,
                                      normalize_before=True)
        mha.eval()
        x = np.random.default_rng(1).standard_normal(
            (2, 8, E)).astype("float32")
        out = mha(paddle.to_tensor(x))
        assert list(out.shape) == [2, 8, E]
        # manual recomputation with the same params
        ln = (x - x.mean(-1, keepdims=True)) / np.sqrt(
            x.var(-1, keepdims=True) + 1e-5)
        w = np.asarray(mha.qkv_weight.numpy()).reshape(3 * E, E)
        qkv = (ln @ w.T).reshape(2, 8, 3, H, E // H) \
            + np.asarray(mha.qkv_bias.numpy()).reshape(1, 1, 3, H, E // H)
        ctx = _dense_ref(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], False)
        ref = ctx.reshape(2, 8, E) @ np.asarray(
            mha.linear_weight.numpy()) + np.asarray(
            mha.linear_bias.numpy()) + x
        np.testing.assert_allclose(np.asarray(out.numpy()), ref, rtol=1e-3,
                                   atol=1e-4)

    def test_fused_ffn_and_encoder_layer_train(self):
        from paddle_tpu.incubate.nn import FusedTransformerEncoderLayer

        paddle.seed(2)
        layer = FusedTransformerEncoderLayer(16, 4, 32, dropout_rate=0.0)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=layer.parameters())
        x = paddle.to_tensor(np.random.default_rng(2)
                             .standard_normal((2, 8, 16)).astype("float32"))
        losses = []
        for _ in range(5):
            out = layer(x)
            loss = (out * out).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]

    def test_fused_multi_transformer(self):
        from paddle_tpu.incubate.nn import FusedMultiTransformer

        paddle.seed(3)
        mt = FusedMultiTransformer(16, 4, 32, num_layers=2)
        mt.eval()
        x = paddle.to_tensor(np.random.default_rng(3)
                             .standard_normal((2, 6, 16)).astype("float32"))
        out = mt(x)
        assert list(out.shape) == [2, 6, 16]

    def test_fused_bias_dropout_residual_ln(self):
        from paddle_tpu.incubate.nn import FusedBiasDropoutResidualLayerNorm

        paddle.seed(4)
        layer = FusedBiasDropoutResidualLayerNorm(8, dropout_rate=0.0)
        layer.eval()
        rng = np.random.default_rng(4)
        x = rng.standard_normal((2, 8)).astype("float32")
        res = rng.standard_normal((2, 8)).astype("float32")
        out = layer(paddle.to_tensor(x), paddle.to_tensor(res))
        h = x + np.asarray(layer.linear_bias.numpy()) + res
        ref = (h - h.mean(-1, keepdims=True)) / np.sqrt(
            h.var(-1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(np.asarray(out.numpy()), ref, rtol=1e-4,
                                   atol=1e-5)

    def test_fused_multi_transformer_kv_cache_decoding(self):
        """Incremental decoding with caches matches full-sequence forward."""
        from paddle_tpu.incubate.nn import FusedMultiTransformer

        paddle.seed(6)
        E, H = 16, 4
        mt = FusedMultiTransformer(E, H, 32, num_layers=2)
        mt.eval()
        rng = np.random.default_rng(6)
        x = rng.standard_normal((1, 5, E)).astype("float32")
        # full pass needs an explicit causal mask to match step decoding
        # (which is causal by construction)
        causal = np.triu(np.full((5, 5), -1e9, "float32"), k=1)
        full = np.asarray(mt(paddle.to_tensor(x),
                             attn_mask=paddle.to_tensor(causal)).numpy())

        # decode token by token with caches
        empty = paddle.to_tensor(np.zeros((1, 0, H, E // H), "float32"))
        caches = [(empty, empty) for _ in range(2)]
        outs = []
        for t in range(5):
            step = paddle.to_tensor(x[:, t:t + 1])
            out, caches = mt(step, caches=caches)
            outs.append(np.asarray(out.numpy()))
        np.testing.assert_allclose(np.concatenate(outs, axis=1), full,
                                   rtol=1e-3, atol=1e-4)

    def test_ring_attention_custom_scale_fallback_parity(self):
        import paddle_tpu.distributed as dist

        dist.set_mesh(None)
        q, k, v = _qkv(S=16, seed=7)
        out_fb = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                paddle.to_tensor(v), scale=0.5)
        _init_sep(sep=4)
        out_ring = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                  paddle.to_tensor(v), scale=0.5)
        np.testing.assert_allclose(np.asarray(out_fb.numpy()),
                                   np.asarray(out_ring.numpy()),
                                   rtol=1e-4, atol=1e-5)


def test_flash_block_path_matches_einsum(monkeypatch):
    """The flash-block ring path (interpret mode) must match the einsum
    ring path — fwd and grads (bwd recomputes via the einsum VJP)."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")

    _init_sep(sep=2)
    # C = S/2 = 128 per device with D=64: flash-eligible block shape
    q, k, v = _qkv(B=1, S=256, H=2, D=64, seed=3)

    def run(flag, causal):
        monkeypatch.setenv("PADDLE_TPU_RING_FLASH", flag)
        qt, kt, vt = (paddle.to_tensor(x) for x in (q, k, v))
        for t in (qt, kt, vt):
            t.stop_gradient = False
        out = ring_attention(qt, kt, vt, causal=causal)
        (out * out).sum().backward()
        return (np.asarray(out.numpy()),
                [np.asarray(t.grad.numpy()) for t in (qt, kt, vt)])

    for causal in (False, True):
        ref, gref = run("0", causal)
        out, gout = run("1", causal)
        np.testing.assert_allclose(out, ref, atol=5e-3, rtol=5e-3)
        # the flash path's custom bwd (einsum VJP) vs the einsum path —
        # all three grads (dq, dk, dv order through the vjp tuple)
        for ga, gb, nm in zip(gout, gref, "qkv"):
            np.testing.assert_allclose(ga, gb, atol=5e-3, rtol=5e-3,
                                       err_msg=f"d{nm} (causal={causal})")
