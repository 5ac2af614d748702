"""paddle_tpu.jit: trace/compile parity with eager execution.

Mirrors the reference's dy2static test strategy (SURVEY.md §4: run the same
nn code eagerly and compiled, compare outputs — test/dygraph_to_static/).
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import jit
from paddle_tpu.jit import static_function


def _make_model_and_data(seed=7):
    paddle.seed(seed)
    model = nn.Sequential(
        nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4),
    )
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((32, 8)).astype("float32")
    y = rng.integers(0, 4, (32,))
    return model, x, y


class TestToStaticForward:
    def test_forward_matches_eager(self):
        model, x, _ = _make_model_and_data()
        eager_out = model(paddle.to_tensor(x)).numpy()

        fwd = jit.to_static(lambda t: model(t))
        t = paddle.to_tensor(x)
        out1 = fwd(t).numpy()          # warm-up (eager)
        out2 = fwd(paddle.to_tensor(x)).numpy()  # compiled
        np.testing.assert_allclose(out1, eager_out, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out2, eager_out, rtol=1e-5, atol=1e-5)

    def test_retrace_on_new_shape(self):
        model, x, _ = _make_model_and_data()
        fwd = jit.to_static(lambda t: model(t))
        fwd(paddle.to_tensor(x))                   # warmup
        fwd(paddle.to_tensor(x))                   # compile @32
        out = fwd(paddle.to_tensor(x[:8])).numpy() # compile @8
        assert out.shape == (8, 4)
        assert len(fwd._cache) == 2

    def test_layer_decoration(self):
        model, x, _ = _make_model_and_data()
        ref = model(paddle.to_tensor(x)).numpy()
        model = jit.to_static(model)
        out = model(paddle.to_tensor(x))
        out = model(paddle.to_tensor(x))
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-5)


class TestCompiledTrainStep:
    def test_train_step_matches_eager(self):
        """Two models, same init: one trained eagerly, one with a compiled
        step (forward+backward+adam update in one XLA program)."""
        model_a, x, y = _make_model_and_data(seed=3)
        model_b, _, _ = _make_model_and_data(seed=3)
        for pa, pb in zip(model_a.parameters(), model_b.parameters()):
            np.testing.assert_array_equal(pa.numpy(), pb.numpy())

        opt_a = paddle.optimizer.Adam(learning_rate=1e-2, parameters=model_a.parameters())
        opt_b = paddle.optimizer.Adam(learning_rate=1e-2, parameters=model_b.parameters())

        def eager_step(xb, yb):
            loss = F.cross_entropy(model_a(xb), yb)
            loss.backward()
            opt_a.step()
            opt_a.clear_grad()
            return loss

        @jit.to_static
        def compiled_step(xb, yb):
            loss = F.cross_entropy(model_b(xb), yb)
            loss.backward()
            opt_b.step()
            opt_b.clear_grad()
            return loss

        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        losses_a = [float(eager_step(xt, yt).numpy()) for _ in range(5)]
        losses_b = [float(compiled_step(xt, yt).numpy()) for _ in range(5)]
        np.testing.assert_allclose(losses_a, losses_b, rtol=1e-4, atol=1e-5)
        for pa, pb in zip(model_a.parameters(), model_b.parameters()):
            np.testing.assert_allclose(pa.numpy(), pb.numpy(), rtol=1e-4, atol=1e-5)
        assert losses_a[-1] < losses_a[0]

    def test_lr_scheduler_no_retrace(self):
        model, x, y = _make_model_and_data()
        sched = paddle.optimizer.lr.StepDecay(learning_rate=0.1, step_size=1, gamma=0.5)
        opt = paddle.optimizer.SGD(learning_rate=sched, parameters=model.parameters())

        @jit.to_static
        def step(xb, yb):
            loss = F.cross_entropy(model(xb), yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        xt, yt = paddle.to_tensor(x), paddle.to_tensor(y)
        step(xt, yt)  # warmup
        before = [p.numpy().copy() for p in model.parameters()]
        step(xt, yt)  # compiled, lr=0.1 (after 0 sched steps... first call already stepped? no: sched.step() is manual)
        sched.step()
        step(xt, yt)  # compiled, lr=0.05 — must NOT retrace
        assert len(step._cache) == 1
        after = [p.numpy() for p in model.parameters()]
        assert any(not np.allclose(b, a) for b, a in zip(before, after))

    def test_dropout_rng_advances(self):
        paddle.seed(11)
        drop = nn.Dropout(0.5)

        @jit.to_static
        def f(t):
            return drop(t)

        x = paddle.to_tensor(np.ones((64, 64), "float32"))
        f(x)  # warmup
        a = f(x).numpy()
        b = f(x).numpy()
        assert not np.array_equal(a, b), "PRNG key must advance between compiled calls"
        assert abs(a.mean() - 1.0) < 0.2  # inverted dropout scaling

    def test_batchnorm_stats_update(self):
        bn = nn.BatchNorm1D(8)

        @jit.to_static
        def f(t):
            return bn(t)

        x = np.random.default_rng(0).standard_normal((16, 8)).astype("float32") * 3 + 5
        f(paddle.to_tensor(x))  # warmup (eager) updates stats once
        m1 = bn._mean.numpy().copy()
        f(paddle.to_tensor(x))  # compiled
        m2 = bn._mean.numpy()
        assert not np.allclose(m1, m2), "running mean must update inside compiled step"
        assert m2.mean() > 0.8  # moving toward true mean 5 (≈5·(1−0.9²) after 2 steps)


class TestSaveLoad:
    def test_save_load_roundtrip(self, tmp_path):
        model, x, _ = _make_model_and_data()
        model.eval()
        ref = model(paddle.to_tensor(x)).numpy()
        path = str(tmp_path / "infer/model")
        jit.save(model, path, input_spec=[jit.InputSpec([32, 8], "float32")])

        loaded = jit.load(path)
        out = loaded(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_save_load_dynamic_batch(self, tmp_path):
        model, x, _ = _make_model_and_data()
        model.eval()
        path = str(tmp_path / "model_dyn")
        jit.save(model, path, input_spec=[jit.InputSpec([None, 8], "float32")])
        loaded = jit.load(path)
        for n in (4, 32):
            out = loaded(paddle.to_tensor(x[:n])).numpy()
            ref = model(paddle.to_tensor(x[:n])).numpy()
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_program_text_and_cost_analysis_leave_state_usable():
    """Both introspection paths re-lower a compiled signature from its
    recorded abstract arguments; a re-trace must not leave tracers in the
    state slots the next real call reads."""
    model, x, y = _make_model_and_data()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    def train_fn(xb, yb):
        loss = F.cross_entropy(model(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = jit.StaticFunction(train_fn, observe=[model, opt], warmup=False)
    assert step.program_text() is None and step.cost_analysis() is None
    xb, yb = paddle.to_tensor(x), paddle.to_tensor(y)
    first = float(step(xb, yb).numpy())
    text = step.program_text()
    assert "func.func" in text and "tpu_custom_call" not in text
    assert "HloModule" in step.program_text(compiled=True)
    assert step.cost_analysis()["flops"] > 0
    assert float(step(xb, yb).numpy()) < first  # still trains
    assert len(step._cache) == 1


# ------------------------------------------------ launch plans (ISSUE 33)
def _main_head(text):
    """The one line that holds ``@main``'s parameters and results."""
    head = text[text.index("func.func public @main("):]
    return head[:head.index("\n")]


def test_read_only_state_is_neither_donated_nor_returned():
    """A function that reads its layer's parameters and never writes them
    compiles a program with no state outputs, and calling it leaves every
    parameter the same array in the same buffer."""
    model, x, _ = _make_model_and_data()
    ref = model(paddle.to_tensor(x)).numpy()
    fwd = jit.StaticFunction(lambda t: model(t), observe=[model],
                             warmup=False)
    held = [(p._value, p._value.unsafe_buffer_pointer())
            for p in model.parameters()]
    for _ in range(4):
        out = fwd(paddle.to_tensor(x))
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-5)
    assert fwd._plan().written == ()
    head = _main_head(fwd.program_text())
    assert "tf.aliasing_output" not in head
    assert head.count("jax.result_info") == 1
    for p, (array, ptr) in zip(model.parameters(), held):
        assert p._value is array and not array.is_deleted()
        assert array.unsafe_buffer_pointer() == ptr


def test_slot_written_under_one_signature_and_read_under_another():
    """Which slots a program writes is a fact of ONE signature: the same
    function counts under `bump=True` and only reads the count under
    `bump=False`; interleaved, both are right."""
    count = paddle.to_tensor(np.zeros((2,), np.float32))

    def fn(x, bump):
        if bump:
            paddle.add_(count, paddle.to_tensor(np.ones((2,), np.float32)))
        return x + count

    sf = jit.StaticFunction(fn, observe=[count], warmup=False)
    x = paddle.to_tensor(np.full((2,), 10.0, np.float32))
    want = 0.0
    for bump in (True, False, False, True, True, False, True):
        want += bump
        held = count._value
        np.testing.assert_allclose(sf(x, bump).numpy(), 10.0 + want)
        np.testing.assert_allclose(count.numpy(), want)
        assert held.is_deleted() == bump  # consumed only where written
    assert len(sf._cache) == 2
    slot = [i for i, s in enumerate(sf._slots)
            if getattr(s, "t", None) is count]
    assert sorted(list(p.written) for p in sf._cache.values()) == [[], slot]


@pytest.mark.parametrize("depth", [1, 6])
def test_second_call_does_not_ask_about_read_only_slots(monkeypatch, depth):
    """On a call that reuses its plan nothing is rebuilt from the signature:
    no ShapeDtypeStruct, and buffer pointers are asked of the one given-up
    leaf and the one fresh argument — however many parameters the function
    only reads."""
    paddle.seed(3)
    model = nn.Sequential(*[nn.Linear(4, 4) for _ in range(depth)])
    sf = jit.StaticFunction(lambda x, scratch: (model(x), scratch * 2.0),
                            observe=[model], warmup=False,
                            donate_argnums=(1,))

    def call():
        return sf(paddle.to_tensor(np.ones((2, 4), np.float32)),
                  paddle.to_tensor(np.ones((3, 4), np.float32)))

    call()
    asked = []
    ptr, sds = static_function._buffer_ptr, jax.ShapeDtypeStruct
    monkeypatch.setattr(static_function, "_buffer_ptr",
                        lambda v: asked.append("ptr") or ptr(v))
    monkeypatch.setattr(jax, "ShapeDtypeStruct",
                        lambda *a, **k: asked.append("sds") or sds(*a, **k))
    out, doubled = call()
    assert asked == ["ptr", "ptr"]
    np.testing.assert_allclose(doubled.numpy(), 2.0)
    # a parameter that is replaced is looked at again, once
    first = next(iter(model.parameters()))
    first._value = first._value + 0.0
    del asked[:]
    call()
    call()
    assert asked == ["ptr"] * 3 + ["ptr"] * 2


def test_introspection_answers_for_the_latest_key_after_plan_calls():
    model, x, _ = _make_model_and_data()
    fwd = jit.StaticFunction(lambda t: model(t), observe=[model],
                             warmup=False)
    big, small = paddle.to_tensor(x), paddle.to_tensor(x[:8])
    fwd(big)
    key_big = fwd._latest_key()
    fwd(small)
    key_small = fwd._latest_key()
    assert key_big != key_small and list(fwd._cache) == [key_big, key_small]
    for _ in range(2):  # plan calls: no build, and the order still follows
        fwd(big)
        assert fwd._latest_key() == key_big
        assert "tensor<32x8xf32>" in fwd.program_text()
    fwd(small)
    assert fwd._latest_key() == key_small
    assert "tensor<8x8xf32>" in fwd.program_text()
    assert "tensor<32x8xf32>" in fwd.program_text(key_big)
    assert fwd.cost_analysis()["flops"] < fwd.cost_analysis(key_big)["flops"]
    assert "tensor<8x8xf32>" in fwd.lower(small).as_text()
    assert len(fwd._cache) == 2


def test_the_trace_runs_in_one_roomy_chunk_of_the_frame_stack():
    """``_with_room`` is an ordinary call whose frame is large enough for
    the interpreter to open one chunk that holds every frame below it
    (PERF.md section 6, PR 33), and the trace goes through it."""
    import sys

    assert static_function._with_room.__code__.co_stacksize >= 1 << 16
    assert static_function._with_room(lambda: 7) == 7
    seen = []

    def fn(t):
        f, names = sys._getframe(), []
        while f is not None:
            names.append(f.f_code.co_name)
            f = f.f_back
        seen.append(names)
        return t * 2.0

    sf = jit.StaticFunction(fn, warmup=False, dy2static=False)
    sf(paddle.to_tensor(np.ones((2,), np.float32)))
    sf(paddle.to_tensor(np.ones((2,), np.float32)))
    assert len(seen) == 1 and "_with_room" in seen[0]  # traced once, in it
