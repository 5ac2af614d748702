"""static.nn: data-dependent control flow (cond/while_loop/case/switch_case
eager + compiled), static layers, sequence ops, StaticRNN-as-scan, and the
parity gate over the reference's static/nn/__init__.py __all__."""
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, static

nn = static.nn
t = paddle.to_tensor


def _ref_all(path):
    src = open(path).read()
    block = re.search(r"__all__ = \[(.*?)\]", src, re.S).group(1)
    return re.findall(r"'([^']+)'", block)


@pytest.mark.needs_reference
def test_static_nn_parity_gate():
    names = _ref_all("/root/reference/python/paddle/static/nn/__init__.py")
    missing = [n for n in names if not hasattr(nn, n)]
    assert missing == [], missing


# ------------------------------------------------------------- cond (eager)

def test_cond_eager_and_grad():
    x = t(np.array([2.0], np.float32))
    x.stop_gradient = False
    out = nn.cond(t(np.array(True)), lambda: x * 2, lambda: x * 3)
    np.testing.assert_allclose(out.numpy(), [4.0])
    out.backward()
    np.testing.assert_allclose(x.grad.numpy(), [2.0])
    out2 = nn.cond(t(np.array(False)), lambda: x * 2, lambda: x * 3)
    np.testing.assert_allclose(out2.numpy(), [6.0])


def test_cond_structure_mismatch_raises():
    x = t(np.array([1.0], np.float32))

    def fn(p):
        return nn.cond(p > 0, lambda: (x, x), lambda: x)

    with pytest.raises(ValueError):
        jit.to_static(fn, warmup=False)(t(np.array(1.0, np.float32)))


# ---------------------------------------------------------- cond (compiled)

def test_cond_compiled_with_gradients():
    """VERDICT r2 #3: a cond whose predicate is a traced tensor, compiled to
    lax.cond, with gradients to the branch captures via jax AD."""
    w = t(np.array([2.0, 3.0], np.float32))
    w.stop_gradient = False
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w])

    def step(x):
        pred = x.sum() > 0
        loss = nn.cond(pred, lambda: (x * w).sum(), lambda: (x - w).sum())
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    sf = jit.to_static(step, warmup=False)
    w0 = np.asarray(w.numpy()).copy()
    loss = sf(t(np.array([1.0, 2.0], np.float32)))  # true branch: dw = x
    np.testing.assert_allclose(float(np.asarray(loss.numpy())), 8.0,
                               rtol=1e-6)
    np.testing.assert_allclose(w.numpy(), w0 - 0.1 * np.array([1.0, 2.0]),
                               rtol=1e-5)
    w1 = np.asarray(w.numpy()).copy()
    sf(t(np.array([-1.0, -2.0], np.float32)))  # false branch: dw = -1
    np.testing.assert_allclose(w.numpy(), w1 + 0.1, rtol=1e-5)


def test_cond_compiled_both_branches_in_one_program():
    calls = []

    def fn(x):
        return nn.cond(x.sum() > 0, lambda: x * 10.0, lambda: x * 100.0)

    sf = jit.to_static(fn, warmup=False)
    np.testing.assert_allclose(
        sf(t(np.array([1.0], np.float32))).numpy(), [10.0])
    # second call, opposite branch, same compiled program (no retrace)
    np.testing.assert_allclose(
        sf(t(np.array([-1.0], np.float32))).numpy(), [-100.0])
    assert len(sf._cache) == 1
    del calls


# --------------------------------------------------------------- while_loop

def test_while_loop_eager_grad_through_dynamic_trip_count():
    x = t(np.array([1.5], np.float32))
    x.stop_gradient = False
    i = t(np.array(0, np.int64))
    v0 = t(np.array([1.0], np.float32))

    iv, v = nn.while_loop(lambda i, v: i < 3, lambda i, v: [i + 1, v * x],
                          [i, v0])
    np.testing.assert_allclose(v.numpy(), [1.5 ** 3], rtol=1e-6)
    v.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [3 * 1.5 ** 2], rtol=1e-5)
    assert int(np.asarray(iv.numpy())) == 3


def test_while_loop_compiled():
    """VERDICT r2 #3: a tensor-valued while loop compiling under to_static
    (lowers to lax.while_loop inside one XLA program)."""
    def fn(x, n):
        i0 = paddle.to_tensor(np.array(0, np.int32))

        def c(i, v):
            return i < n

        def b(i, v):
            return [i + 1, v * 1.5]

        _, v = nn.while_loop(c, b, [i0, x])
        return v

    sf = jit.to_static(fn, warmup=False)
    out = sf(t(np.array([1.0], np.float32)), t(np.array(5, np.int32)))
    np.testing.assert_allclose(out.numpy(), [1.5 ** 5], rtol=1e-6)
    # trip count is DATA: same compiled program, different n
    out = sf(t(np.array([1.0], np.float32)), t(np.array(2, np.int32)))
    np.testing.assert_allclose(out.numpy(), [1.5 ** 2], rtol=1e-6)
    assert len(sf._cache) == 1


def test_while_loop_errors():
    with pytest.raises(TypeError):
        nn.while_loop(None, lambda i: [i], [t(np.array(0))])
    with pytest.raises(ValueError):
        nn.while_loop(lambda: True, lambda: [], [])


# ------------------------------------------------------- case / switch_case

def test_case_eager_first_true_wins():
    x = t(np.array([1.0], np.float32))
    r = nn.case([(t(np.array(True)), lambda: x + 1),
                 (t(np.array(True)), lambda: x + 2)],
                default=lambda: x)
    np.testing.assert_allclose(r.numpy(), [2.0])
    r = nn.case([(t(np.array(False)), lambda: x + 1),
                 (t(np.array(False)), lambda: x + 2)],
                default=lambda: x + 9)
    np.testing.assert_allclose(r.numpy(), [10.0])
    # no default: last fn is the fallback
    r = nn.case([(t(np.array(False)), lambda: x + 1),
                 (t(np.array(False)), lambda: x + 2)])
    np.testing.assert_allclose(r.numpy(), [3.0])


def test_case_compiled():
    def fn(a, x):
        return nn.case([(a > 3, lambda: x + 100.0),
                        (a > 1, lambda: x + 10.0)],
                       default=lambda: x)

    sf = jit.to_static(fn, warmup=False)
    for av, want in [(2.0, 11.0), (5.0, 101.0), (0.0, 1.0)]:
        got = sf(t(np.array(av, np.float32)),
                 t(np.array([1.0], np.float32))).numpy()
        np.testing.assert_allclose(got, [want])
    assert len(sf._cache) == 1


def test_switch_case_eager_and_compiled():
    x = t(np.array([2.0], np.float32))
    fns = {0: lambda: x * 1.0, 1: lambda: x * 10.0, 3: lambda: x * 30.0}
    np.testing.assert_allclose(
        nn.switch_case(t(np.array(1)), fns).numpy(), [20.0])
    np.testing.assert_allclose(  # no match -> max-index fn
        nn.switch_case(t(np.array(7)), fns).numpy(), [60.0])

    def fn(idx, v):
        return nn.switch_case(idx, [lambda: v * 1.0, lambda: v * 10.0,
                                    lambda: v * 20.0])

    sf = jit.to_static(fn, warmup=False)
    np.testing.assert_allclose(
        sf(t(np.array(2)), t(np.array([1.0], np.float32))).numpy(), [20.0])
    np.testing.assert_allclose(
        sf(t(np.array(0)), t(np.array([1.0], np.float32))).numpy(), [1.0])
    assert len(sf._cache) == 1


def test_switch_case_duplicate_index_raises():
    with pytest.raises(ValueError):
        nn.switch_case(t(np.array(0)), [(0, lambda: None), (0, lambda: None)])


# ------------------------------------------------------------- static layers

def test_fc_and_minimize_collects_params():
    with static.program_guard(static.Program()):
        x = static.data("x", [None, 4], "float32")
        y = nn.fc(x, 3, activation="relu")
        loss = y.sum()
        opt = paddle.optimizer.SGD(learning_rate=0.1)
        opt.minimize(loss)
        exe = static.Executor()
        feed = {"x": np.random.RandomState(0).randn(5, 4).astype(np.float32)}
        l0 = exe.run(feed=feed, fetch_list=[loss])[0]
        for _ in range(5):
            l1 = exe.run(feed=feed, fetch_list=[loss])[0]
        assert float(l1) <= float(l0) + 1e-6


def test_layers_shapes():
    rng = np.random.RandomState(0)
    img = t(rng.randn(2, 3, 8, 8).astype(np.float32))
    assert nn.conv2d(img, 4, 3, padding=1).shape == [2, 4, 8, 8]
    assert nn.batch_norm(img).shape == [2, 3, 8, 8]
    assert nn.group_norm(img, 3).shape == [2, 3, 8, 8]
    assert nn.instance_norm(img).shape == [2, 3, 8, 8]
    assert nn.prelu(img, "channel").shape == [2, 3, 8, 8]
    assert nn.conv2d_transpose(img, 4, filter_size=2,
                               stride=2).shape == [2, 4, 16, 16]
    vol = t(rng.randn(2, 3, 4, 8, 8).astype(np.float32))
    assert nn.conv3d(vol, 4, 3, padding=1).shape == [2, 4, 4, 8, 8]
    x2 = t(rng.randn(4, 6).astype(np.float32))
    assert nn.layer_norm(x2).shape == [4, 6]
    assert nn.data_norm(t(np.abs(rng.randn(4, 6)).astype(
        np.float32))).shape == [4, 6]
    assert nn.fc(img, 10).shape == [2, 10]
    assert nn.embedding(t(np.array([[1, 2]])), (10, 6)).shape == [1, 2, 6]
    assert nn.sparse_embedding(t(np.array([[1, 2]])),
                               (10, 6)).shape == [1, 2, 6]
    assert nn.bilinear_tensor_product(
        t(rng.randn(2, 3).astype(np.float32)),
        t(rng.randn(2, 4).astype(np.float32)), 5).shape == [2, 5]
    assert nn.row_conv(t(rng.randn(2, 6, 4).astype(np.float32)),
                       2).shape == [2, 6, 4]
    assert nn.nce(t(rng.randn(4, 8).astype(np.float32)),
                  t(np.array([[1], [2], [3], [0]])), 20,
                  num_neg_samples=5).shape == [4, 1]
    assert nn.continuous_value_model(
        t(rng.randn(4, 6).astype(np.float32)), None,
        use_cvm=False).shape == [4, 4]


def test_spectral_norm_unit_sigma():
    w = t(np.random.RandomState(0).randn(6, 4).astype(np.float32))
    wn = nn.spectral_norm(w, power_iters=20)
    s = np.linalg.svd(np.asarray(wn.numpy()), compute_uv=False)
    np.testing.assert_allclose(s[0], 1.0, rtol=1e-3)


# -------------------------------------------------------------- sequence ops

def test_sequence_ops_numerics():
    rng = np.random.RandomState(0)
    xv = rng.randn(2, 5, 3).astype(np.float32)
    s = t(xv)
    np.testing.assert_allclose(nn.sequence_pool(s, "sum").numpy(),
                               xv.sum(1), rtol=1e-6)
    np.testing.assert_allclose(nn.sequence_pool(s, "sqrt").numpy(),
                               xv.sum(1) / np.sqrt(5), rtol=1e-6)
    np.testing.assert_allclose(nn.sequence_first_step(s).numpy(), xv[:, 0])
    np.testing.assert_allclose(nn.sequence_last_step(s).numpy(), xv[:, -1])
    np.testing.assert_allclose(nn.sequence_reverse(s).numpy(),
                               xv[:, ::-1], rtol=1e-6)
    sm = np.asarray(nn.sequence_softmax(s).numpy())
    np.testing.assert_allclose(sm.sum(1), np.ones((2, 3)), rtol=1e-5)
    padded, lens = nn.sequence_pad(s, t(np.float32(0)), maxlen=7)
    assert padded.shape == [2, 7, 3]
    assert np.asarray(padded.numpy())[:, 5:].sum() == 0
    np.testing.assert_allclose(np.asarray(lens.numpy()), [5, 5])
    up = nn.sequence_unpad(padded, t(np.array([3, 5])))
    upv = np.asarray(up.numpy())
    assert up.shape == [2, 5, 3]
    assert upv[0, 3:].sum() == 0  # masked past row length
    np.testing.assert_allclose(upv[1], xv[1], rtol=1e-6)


def test_sequence_conv_matches_manual():
    rng = np.random.RandomState(1)
    xv = rng.randn(1, 4, 2).astype(np.float32)
    out = nn.sequence_conv(t(xv), 3, filter_size=3, bias_attr=False)
    assert out.shape == [1, 4, 3]


# ---------------------------------------------------------------- StaticRNN

def test_static_rnn_cumsum_and_grad():
    rng = np.random.RandomState(0)
    xv = rng.randn(5, 3, 4).astype(np.float32)
    x = t(xv)
    x.stop_gradient = False
    rnn = nn.StaticRNN()
    with rnn.step():
        xt = rnn.step_input(x)
        prev = rnn.memory(shape=[-1, 4], batch_ref=xt, init_value=0.0)
        h = prev + xt
        rnn.update_memory(prev, h)
        rnn.step_output(h)
    out = rnn()
    np.testing.assert_allclose(out.numpy(), np.cumsum(xv, axis=0), rtol=1e-5)
    out.sum().backward()
    # x[t] contributes to steps t..T-1 -> grad = T - t
    g = np.asarray(x.grad.numpy())
    np.testing.assert_allclose(g[0], np.full((3, 4), 5.0), rtol=1e-6)
    np.testing.assert_allclose(g[4], np.full((3, 4), 1.0), rtol=1e-6)


def test_static_rnn_with_parameters_trains():
    rng = np.random.RandomState(0)
    x = t(rng.randn(4, 2, 3).astype(np.float32))
    rnn = nn.StaticRNN()
    with rnn.step():
        xt = rnn.step_input(x)
        prev = rnn.memory(shape=[-1, 6], batch_ref=xt, init_value=0.0)
        h = nn.fc(paddle.concat([xt, prev], axis=-1), 6, activation="tanh")
        rnn.update_memory(prev, h)
        rnn.step_output(h)
    out = rnn()
    assert out.shape == [4, 2, 6]
    loss = (out * out).sum()
    loss.backward()
    from paddle_tpu.static import _collect_parameters
    params = _collect_parameters(loss)
    assert params and all(p.grad is not None for p in params)


def test_static_rnn_misuse_raises():
    rnn = nn.StaticRNN()
    with pytest.raises(RuntimeError):
        rnn.step_input(t(np.zeros((2, 2), np.float32)))
    with pytest.raises(RuntimeError):
        rnn()


def test_sequence_conv_padding_start_window():
    """padding_start=1, filter_size=1 is a pure one-step lookahead: output t
    must equal input t+1 (review finding: positive starts were clamped)."""
    xv = np.arange(8, dtype=np.float32).reshape(1, 8, 1)
    out = nn.sequence_conv(t(xv), 1, filter_size=1, padding_start=1,
                           bias_attr=False,
                           param_attr=paddle.ParamAttr(
                               initializer=paddle.nn.initializer.Constant(1.0)))
    got = np.asarray(out.numpy())[0, :, 0]
    want = np.concatenate([xv[0, 1:, 0], [0.0]])  # shifted left, zero tail
    np.testing.assert_allclose(got, want)


def test_static_rnn_correct_under_no_grad():
    """Regression: the step block's tape recording must survive no_grad —
    the replayed scan body used to degenerate to step-0 constants and
    silently broadcast h0 over time (found exporting StaticRNN to ONNX)."""
    rng = np.random.RandomState(0)
    xv = rng.randn(5, 3, 4).astype(np.float32)
    with paddle.no_grad():
        rnn = nn.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(t(xv))
            prev = rnn.memory(shape=[-1, 4], batch_ref=xt, init_value=0.0)
            h = prev + xt
            rnn.update_memory(prev, h)
            rnn.step_output(h)
        out = rnn()
    np.testing.assert_allclose(out.numpy(), np.cumsum(xv, axis=0), rtol=1e-5)


def test_while_loop_passthrough_carry_slot():
    """A body may return one of its CARRY ARG tensors in a different
    output slot (e.g. `return h+1, s2, h`): the returned slot must hold
    the substituted trace value, not the tensor object's stale pre-loop
    payload (r4 bug: _run_substituted restored payloads before the
    caller read the outputs — the for-range loop target came back as its
    seed)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.static.nn import while_loop
    from paddle_tpu.tensor import Tensor

    def fn(n):
        h = Tensor(jnp.asarray(0, jnp.int32), stop_gradient=True)
        s = paddle.to_tensor(np.float32(0.0))
        i = Tensor(jnp.asarray(0, jnp.int32), stop_gradient=True)

        def cond(h, s, i):
            return h < n

        def body(h, s, i):
            return (h + 1, s + 1.0, h)  # slot 2 passes the carry arg through

        _, s2, i2 = while_loop(cond, body, (h, s, i))
        return s2 + 0, i2 + 0

    f = jit.StaticFunction(fn, warmup=False)
    for _ in range(2):
        s, i = f(paddle.to_tensor(np.int64(4)))
        assert float(np.asarray(s.numpy())) == 4.0
        assert int(np.asarray(i.numpy())) == 3


def test_while_loop_carry_aliased_with_closure_capture():
    """An initial carry value identity-aliased with a tensor the body
    reads through its CLOSURE must keep its own value (r5: payload
    substitution turned `s + x` into `s + s` — 1,2,4,8,16 doubling).
    Compiled must match eager, where the cell is never mutated."""
    import jax.numpy as jnp

    from paddle_tpu.jit.dy2static import ast_transform

    def loop(x, n):
        s = x            # s IS x (same Tensor object) at loop entry
        i = paddle.to_tensor(np.int64(0))
        while i < n:
            s = s + x    # closure read of x must stay the INITIAL x
            i = i + 1
        return s

    x = paddle.to_tensor(np.array([1.0], np.float32))
    eager = float(np.asarray(
        ast_transform(loop)(x, paddle.to_tensor(np.int64(4))).numpy()))
    assert eager == 5.0, eager
    sf = jit.StaticFunction(ast_transform(loop), warmup=False)
    got = float(np.asarray(
        sf(x, paddle.to_tensor(np.int64(4))).numpy()))
    assert got == 5.0, got
