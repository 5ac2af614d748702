"""static legacy tail + incubate ops/fused-functional + amp/jit tail,
with parity gates for static (modulo IPU) / incubate / incubate.nn."""
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, static

t = paddle.to_tensor


def _ref_all(path):
    src = open(path).read()
    block = re.search(r"__all__ = \[(.*?)\]", src, re.S).group(1)
    return re.findall(r"'([^']+)'", block)


@pytest.mark.needs_reference
def test_static_parity_modulo_ipu():
    names = _ref_all("/root/reference/python/paddle/static/__init__.py")
    # IPU hardware support is deliberately absent (loud, not stubbed)
    names = [n for n in names if "ipu" not in n.lower() and "Ipu" not in n]
    missing = [n for n in names if not hasattr(static, n)]
    assert missing == [], missing


@pytest.mark.needs_reference
@pytest.mark.parametrize("path,mod", [
    ("/root/reference/python/paddle/incubate/__init__.py", paddle.incubate),
    ("/root/reference/python/paddle/incubate/nn/__init__.py",
     paddle.incubate.nn),
    ("/root/reference/python/paddle/incubate/nn/functional/__init__.py",
     paddle.incubate.nn.functional),
    ("/root/reference/python/paddle/amp/__init__.py", paddle.amp),
    ("/root/reference/python/paddle/jit/__init__.py", paddle.jit),
], ids=["incubate", "incubate.nn", "incubate.nn.functional", "amp", "jit"])
def test_more_parity_gates(path, mod):
    missing = [n for n in _ref_all(path) if not hasattr(mod, n)]
    assert missing == [], missing


# -------------------------------------------------------------- static


def test_gradients_and_append_backward():
    x = t(np.array([3.0], np.float32))
    x.stop_gradient = False
    y = (x ** 2).sum()
    (g,) = static.gradients(y, [x])
    np.testing.assert_allclose(np.asarray(g.numpy()), [6.0], rtol=1e-6)


def test_scope_and_name_scope_and_compiled_program():
    from paddle_tpu.static.legacy import _Scope

    with static.scope_guard(_Scope()):
        with static.name_scope("block1"):
            pass
    prog = static.Program()
    cp = static.CompiledProgram(prog, static.BuildStrategy())
    assert cp.global_block() is prog  # delegation


def test_print_and_py_func(capsys):
    x = t(np.array([1.0, 2.0], np.float32))
    y = static.Print(x, message="dbg")
    out = capsys.readouterr().out
    assert "dbg" in out and "shape=[2]" in out
    np.testing.assert_array_equal(np.asarray(y.numpy()), [1.0, 2.0])

    class _Spec:
        shape = (2,)
        dtype = "float32"

    r = static.py_func(lambda v: v * 3, x, _Spec())
    np.testing.assert_allclose(np.asarray(r.numpy()), [3.0, 6.0])


def test_exponential_moving_average():
    lin = nn.Linear(2, 2, bias_attr=False)
    ema = static.ExponentialMovingAverage(decay=0.5)
    ema.update(list(lin.parameters()))
    w0 = np.asarray(lin.weight._value).copy()
    lin.weight._set_value(lin.weight._value * 0.0)
    ema.update()
    trained = np.asarray(lin.weight._value).copy()
    with ema.apply():
        ema_w = np.asarray(lin.weight._value)
        assert not np.allclose(ema_w, trained)  # EMA differs from current
    np.testing.assert_array_equal(np.asarray(lin.weight._value), trained)
    del w0


def test_create_global_var_and_device_guard():
    v = static.create_global_var([2, 3], 1.5, "float32", name="gv")
    np.testing.assert_array_equal(np.asarray(v.numpy()),
                                  np.full((2, 3), 1.5))
    with static.device_guard("cpu"):
        w = paddle.ones([2])
    np.testing.assert_array_equal(np.asarray(w.numpy()), [1, 1])


def test_static_save_load_roundtrip(tmp_path):
    with static.program_guard(static.Program()):
        x = static.data("x", [4, 2], "float32")
        lin = nn.Linear(2, 1)
        loss = (lin(x) ** 2).mean()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        opt.minimize(loss)
        prog = static.default_main_program()
        before = np.asarray(lin.weight._value).copy()
        static.save(prog, str(tmp_path / "m"))
        lin.weight._set_value(lin.weight._value * 0.0)
        static.load(prog, str(tmp_path / "m"))
        np.testing.assert_array_equal(np.asarray(lin.weight._value), before)
        state = static.load_program_state(str(tmp_path / "m"))
        assert len(state) == len(list(lin.parameters()))
        # loading a state with a bogus key must fail loudly
        state["not_a_param"] = np.zeros((1,), np.float32)
        with pytest.raises(ValueError, match="not matched"):
            static.set_program_state(prog, state)


def test_static_accuracy_auc_metric_bundle():
    pred = t(np.array([[0.1, 0.9], [0.8, 0.2]], np.float32))
    lab = t(np.array([[1], [0]]))
    acc = static.accuracy(pred, lab)
    np.testing.assert_allclose(float(acc.numpy()), 1.0)
    scores = t(np.array([0.9, 0.1, 0.8, 0.2], np.float32))
    labels = t(np.array([1, 0, 1, 0], np.int64))
    a = static.auc(scores, labels)
    assert float(a.numpy()) == pytest.approx(1.0, abs=1e-3)
    bundle = static.ctr_metric_bundle(scores, labels)
    assert len(bundle) == 7


# ------------------------------------------------------------ incubate


def test_softmax_mask_fuse_ops():
    x = t(np.random.default_rng(0).standard_normal((1, 1, 3, 3)
                                                   ).astype(np.float32))
    mask = t(np.zeros((1, 1, 3, 3), np.float32))
    out = np.asarray(paddle.incubate.softmax_mask_fuse(x, mask).numpy())
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-5)
    tri = np.asarray(
        paddle.incubate.softmax_mask_fuse_upper_triangle(x).numpy())
    assert tri[0, 0, 0, 1] == 0.0 and tri[0, 0, 0, 0] == pytest.approx(1.0)


def test_incubate_segment_and_identity_loss():
    data = t(np.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]], np.float32))
    seg = t(np.array([0, 0, 1]))
    out = np.asarray(paddle.incubate.segment_sum(data, seg).numpy())
    np.testing.assert_array_equal(out, [[4, 6], [5, 6]])
    x = t(np.array([1.0, 2.0], np.float32))
    assert float(paddle.incubate.identity_loss(x, "sum").numpy()) == 3.0
    assert float(paddle.incubate.identity_loss(x, "mean").numpy()) == 1.5


def test_fused_functional_matmul_bias_and_ffn():
    FF = paddle.incubate.nn.functional
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 4)).astype(np.float32)
    w = rng.standard_normal((4, 3)).astype(np.float32)
    b = rng.standard_normal((3,)).astype(np.float32)
    out = np.asarray(FF.fused_matmul_bias(t(x), t(w), t(b)).numpy())
    np.testing.assert_allclose(out, x @ w + b, rtol=1e-5)

    h = 4
    x2 = rng.standard_normal((2, 5, h)).astype(np.float32)
    w1 = rng.standard_normal((h, 8)).astype(np.float32)
    w2 = rng.standard_normal((8, h)).astype(np.float32)
    out2 = FF.fused_feedforward(t(x2), t(w1), t(w2), dropout1_rate=0.0,
                                dropout2_rate=0.0, pre_layer_norm=True)
    assert tuple(out2.shape) == (2, 5, h)

    qkvw = rng.standard_normal((3, 2, 2, h)).astype(np.float32) * 0.1
    lw = rng.standard_normal((h, h)).astype(np.float32) * 0.1
    attn = FF.fused_multi_head_attention(
        t(x2), t(qkvw), t(lw), pre_layer_norm=True, dropout_rate=0.0,
        attn_dropout_rate=0.0)
    assert tuple(attn.shape) == (2, 5, h)


def test_fused_ec_moe_layer():
    paddle.seed(0)
    moe = paddle.incubate.nn.FusedEcMoe(hidden_size=8, inter_size=16,
                                        num_experts=4)
    rng = np.random.default_rng(2)
    x = t(rng.standard_normal((2, 6, 8)).astype(np.float32))
    gate = t(rng.standard_normal((2, 6, 4)).astype(np.float32))
    out = moe(x, gate)
    assert tuple(out.shape) == (2, 6, 8)
    loss = (out ** 2).mean()
    loss.backward()
    assert moe.bmm_weight0.grad is not None


def test_amp_supported_flags_and_jit_verbosity():
    assert paddle.amp.is_bfloat16_supported() is True
    assert isinstance(paddle.amp.is_float16_supported(), bool)
    paddle.jit.set_code_level(2)
    paddle.jit.set_verbosity(3)


def test_fused_mha_kv_cache_round():
    FF = paddle.incubate.nn.functional
    rng = np.random.default_rng(3)
    h, H, D = 4, 2, 2
    x = rng.standard_normal((1, 2, h)).astype(np.float32)
    qkvw = rng.standard_normal((3, H, D, h)).astype(np.float32) * 0.1
    lw = rng.standard_normal((h, h)).astype(np.float32) * 0.1
    cache = np.zeros((2, 1, 0, H, D), np.float32)  # empty BSHD cache
    out, new_cache = FF.fused_multi_head_attention(
        t(x), t(qkvw), t(lw), pre_layer_norm=True, dropout_rate=0.0,
        attn_dropout_rate=0.0, cache_kv=t(cache))
    assert tuple(out.shape) == (1, 2, h)
    assert tuple(new_cache.shape) == (2, 1, 2, H, D)  # cache grew by S


def test_fused_ec_moe_reference_contract():
    FF = paddle.incubate.nn.functional
    rng = np.random.default_rng(4)
    B, S, Dm, E, I = 1, 3, 4, 2, 8
    x = rng.standard_normal((B, S, Dm)).astype(np.float32)
    gate = rng.standard_normal((B, S, E)).astype(np.float32)
    w0 = rng.standard_normal((E, Dm, I)).astype(np.float32) * 0.1
    b0 = np.zeros((E, I), np.float32)
    w1 = rng.standard_normal((E, I, Dm)).astype(np.float32) * 0.1
    b1 = np.zeros((E, Dm), np.float32)
    out = FF.fused_ec_moe(t(x), t(gate), t(w0), t(b0), t(w1), t(b1), "gelu")
    assert tuple(out.shape) == (B, S, Dm)
    # layer form takes (x, gate) like the reference
    paddle.seed(1)
    moe = paddle.incubate.nn.FusedEcMoe(hidden_size=Dm, inter_size=I,
                                        num_experts=E)
    out2 = moe(t(x), t(gate))
    assert tuple(out2.shape) == (B, S, Dm)


def test_graph_khop_sampler_contract():
    # chain graph 0→1→2→3 in CSC (colptr over dst, row = src ids)
    row = t(np.array([0, 1, 2], np.int64))      # edges (0→1),(1→2),(2→3)
    colptr = t(np.array([0, 0, 1, 2, 3], np.int64))
    src, dst, sample_index, reindex = paddle.incubate.graph_khop_sampler(
        row, colptr, t(np.array([3], np.int64)), [1, 1])
    si = np.asarray(sample_index.numpy())
    assert si[0] == 3  # input nodes first
    # edges are local ids into sample_index
    s_l, d_l = np.asarray(src.numpy()), np.asarray(dst.numpy())
    assert len(s_l) == len(d_l) >= 1
    orig_edges = {(int(si[a]), int(si[b])) for a, b in zip(s_l, d_l)}
    assert (2, 3) in orig_edges  # hop-1 samples 3's in-neighbor 2
    with pytest.raises(NotImplementedError):
        paddle.incubate.graph_khop_sampler(row, colptr,
                                           t(np.array([3], np.int64)),
                                           [1], return_eids=True)


def test_print_summarize_all():
    x = t(np.array([1.0], np.float32))
    static.Print(x, summarize=-1)  # must include the lone element
