"""The serving step updates the KV pool where it lies (ISSUE 30): the pool's
arrays are given up to the compiled step (``StaticFunction(donate_argnums=)``)
and the K/V write keeps the pool's layout (``ops/paged_cache.write_step_kv``).

On the CPU jax honours donation (a donated array reads ``is_deleted()``), so
ownership is testable here; that XLA:TPU leaves no pool-shaped copy in the
compiled step is chip_smoke.py's to assert on the chip.
"""
import re

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, metrics, nn
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM, gpt_tiny,
                               llama_tiny)
from paddle_tpu.ops.paged_cache import write_step_kv
from paddle_tpu.quantization.observers import quantize_kv
from paddle_tpu.serving import ServingEngine

pytestmark = pytest.mark.serving


def _llama():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_key_value_heads=2, max_position_embeddings=64))


def _gpt():
    paddle.seed(0)
    return GPTForCausalLM(gpt_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))


_ENGINES = [pytest.param(_gpt, "bfloat16", id="gpt-bf16"),
            pytest.param(_gpt, "int8", id="gpt-int8"),
            pytest.param(_llama, "float32", id="llama-f32"),
            pytest.param(_llama, "int8", id="llama-int8")]


def _engine(make_model, kv_dtype):
    eng = ServingEngine(make_model(), kv_dtype=kv_dtype, page_size=8,
                        max_model_len=64, num_pages=24, max_batch_slots=2,
                        token_budget=16)
    rng = np.random.RandomState(3)
    for n in (11, 5):
        eng.add_request(rng.randint(0, 128, (n,)), max_new_tokens=4,
                        temperature=0.0)
    return eng


def _pool_arrays(pool):
    return [t._value for li in range(pool.num_layers)
            for t in pool.step_arrays(li)]


def _main_signature(text):
    """(aliasing_output or None) per parameter of the lowered ``@main``, and
    its number of results."""
    head = text[text.index("func.func public @main("):]
    params, results = head[:head.index(") {\n")].split(") -> (", 1)
    alias = []
    for p in re.split(r", (?=%arg\d+:)", params):
        m = re.search(r"tf\.aliasing_output = (\d+)", p)
        alias.append(int(m.group(1)) if m else None)
        assert "jax.buffer_donor" not in p, p  # donated, but jax paired none
    return alias, results.count("jax.result_info")


def _aliased_bytes(fn):
    fam = metrics.get_registry().get("paddle_tpu_jit_aliased_bytes")
    return 0.0 if fam is None else fam.sum_labels(fn=fn)


@pytest.mark.parametrize("make_model,kv_dtype", _ENGINES)
def test_step_aliases_every_pool_array_to_its_own_output(make_model,
                                                         kv_dtype):
    """Every bucket's lowered step marks each pool parameter (the trailing
    parameters: the given-up leaves ride last) as aliasing ITS output —
    `nxt, fin, k0', v0', ...` — and the executable aliases at least the
    pool's bytes."""
    eng = _engine(make_model, kv_dtype)
    pool_bytes = sum(a.nbytes for a in _pool_arrays(eng.pool))
    while eng.has_work:
        eng.step()
        assert _aliased_bytes("serving_step") >= pool_bytes
    texts = eng.step_program_texts()
    assert len(texts) >= 2  # the 16-row chunk bucket and the slot grid
    n_pool = eng.pool.step_stride * eng.pool.num_layers
    for text in texts:
        alias, _ = _main_signature(text)
        assert alias[-n_pool:] == [2 + i for i in range(n_pool)]


def _jit_calls(fn, path):
    fam = metrics.get_registry().get("paddle_tpu_jit_calls_total")
    return 0.0 if fam is None else fam.sum_labels(fn=fn, path=path)


@pytest.mark.parametrize("make_model,kv_dtype", _ENGINES)
def test_step_donates_the_pool_and_nothing_else(make_model, kv_dtype,
                                                monkeypatch):
    """The serving step only READS the model: of its operands the pool's
    arrays alone are donated, and they alone ride back beside `nxt, fin`.
    Every bucket is built by one call and launched from its plan by every
    other (`paddle_tpu_jit_calls_total{fn="serving_step", path}`)."""
    calls = []
    call = jit.StaticFunction.__call__
    monkeypatch.setattr(
        jit.StaticFunction, "__call__",
        lambda self, *a, **k: calls.append(self.__name__) or call(self, *a, **k))
    before = {p: _jit_calls("serving_step", p) for p in ("build", "plan")}
    eng = _engine(make_model, kv_dtype)
    params = [p._value for p in eng.model.parameters()]
    while eng.has_work:
        eng.step()
    pool_bytes = sum(a.nbytes for a in _pool_arrays(eng.pool))
    n_pool = eng.pool.step_stride * eng.pool.num_layers
    assert len(eng.step_aliased_bytes()) >= 2
    assert set(eng.step_aliased_bytes()) == {pool_bytes}
    for text in eng.step_program_texts():
        alias, n_results = _main_signature(text)
        assert n_results == 2 + n_pool
        assert [a for a in alias if a is not None] == [
            2 + i for i in range(n_pool)]
    assert all(p._value is v and not v.is_deleted()
               for p, v in zip(eng.model.parameters(), params))
    built = _jit_calls("serving_step", "build") - before["build"]
    reused = _jit_calls("serving_step", "plan") - before["plan"]
    assert built == eng.compile_counts()["step"] >= 2
    assert calls.count("serving_step") == built + reused and reused >= 1


@pytest.mark.parametrize("make_model,kv_dtype", _ENGINES)
def test_step_consumes_the_pool_arrays(make_model, kv_dtype):
    """After a call that launched a step the arrays the pool held before
    are deleted and the pool's new ones are live; a call that only lands
    the step in flight leaves them as they are."""
    eng = _engine(make_model, kv_dtype)
    launched = 0
    while eng.has_work:
        before = _pool_arrays(eng.pool)
        eng.step()
        after = _pool_arrays(eng.pool)
        if all(a is b for a, b in zip(after, before)):
            assert not eng._flight
        else:
            assert all(a.is_deleted() for a in before)
            launched += 1
        assert not any(a.is_deleted() for a in after)
        assert np.isfinite(np.asarray(after[0], np.float32)).all()
    assert launched >= 2


@pytest.mark.parametrize("make_model,kv_dtype", _ENGINES[:2])
def test_no_donate_env_declares_nothing(monkeypatch, make_model, kv_dtype):
    """PADDLE_TPU_NO_DONATE=1 (the bisect axis) turns the pool's donation
    off with the state's: nothing is marked, nothing is deleted, and the
    tokens are those of the donated run."""
    def run():
        eng = _engine(make_model, kv_dtype)
        outs, held = {}, []
        while eng.has_work:
            held.append(_pool_arrays(eng.pool))
            for o in eng.step():
                outs[len(outs)] = list(o.token_ids)
        return eng, outs, held

    _, want, _ = run()
    monkeypatch.setenv("PADDLE_TPU_NO_DONATE", "1")
    eng, got, held = run()
    assert got == want
    assert not any(a.is_deleted() for arrays in held for a in arrays)
    for text in eng.step_program_texts():
        assert set(_main_signature(text)[0]) == {None}


def _grid(rng, n_pages, page, width):
    """A step's rows as the engine packs them: two decode rows, a chunk of
    five consecutive positions that crosses a page seam, a decode row with
    two draft rows behind it, and three padding rows (zero table, position
    0) that collide on the null page."""
    tables = rng.permutation(np.arange(1, n_pages))[:4 * width].reshape(
        4, width)
    rows = [(0, 13), (1, 6)]
    rows += [(2, p) for p in range(page - 2, page + 3)]
    rows += [(3, 9), (3, 10), (3, 11)]
    bt = np.zeros((len(rows) + 3, width), np.int32)
    pos = np.zeros(len(rows) + 3, np.int32)
    for r, (seq, p) in enumerate(rows):
        bt[r], pos[r] = tables[seq], p
    return jnp.asarray(bt), jnp.asarray(pos)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_write_step_kv_equals_the_indexed_set(dtype):
    """The layout-keeping write against the write it replaced (kept here as
    the oracle), everywhere but the null page's slot 0, where a bucket's
    padding rows collide and any of them may win."""
    rng = np.random.default_rng(5)
    n_pages, heads, page, hd, width = 16, 2, 8, 16, 3
    bt, pos = _grid(rng, n_pages, page, width)
    T = pos.shape[0]
    k = jnp.asarray(rng.standard_normal((T, heads, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, heads, hd)), jnp.float32)
    shape = (n_pages, heads, page, hd)
    cache = tuple(jnp.asarray(rng.integers(-5, 5, shape), dtype)
                  for _ in range(2))
    rows = (k, v)
    if dtype == "int8":
        cache += tuple(jnp.asarray(rng.random(shape[:3]), jnp.float32)
                       for _ in range(2))
        (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
        rows = (kq, vq, ks, vs)
    page_ids = bt[jnp.arange(T), pos // page]
    offs = pos % page
    want = [a.at[page_ids, :, offs].set(r.astype(a.dtype))
            for a, r in zip(cache, rows)]
    got = write_step_kv(cache, k, v, bt, pos)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.array(g, np.float32), np.array(w, np.float32)
        g[0, :, 0] = w[0, :, 0] = 0
        np.testing.assert_array_equal(g, w)


# ------------------------------------------------- StaticFunction(donate_argnums)
def _affine():
    paddle.seed(1)
    lin = nn.Linear(4, 4)

    def fn(x, scratch, y):
        return lin(x) + y, scratch * 2.0

    return lin, fn


def _x(v=1.0, rows=2):
    return paddle.to_tensor(np.full((rows, 4), v, np.float32))


def _s(v=2.0):
    """The scratch argument: no other operand or result has its shape, so
    jax can pair it with the doubled scratch alone."""
    return _x(v, rows=3)


def test_no_declared_argument_lowers_to_the_same_program():
    """No declaration, no change of program: the argument leaves ride as
    before and an empty declaration is the same text. The function only
    READS its state (the layer's parameters; the RNG key it never touches is
    not even a parameter of the program), so nothing is marked and the
    state adds no result."""
    lin, fn = _affine()
    texts = []
    for kw in ({}, {"donate_argnums": ()}):
        sf = jit.StaticFunction(fn, observe=[lin], warmup=False, **kw)
        x, s, y = _x(), _x(2.0), _x(3.0)
        sf(x, s, y)
        assert not any(t._value.is_deleted() for t in (x, s, y))
        texts.append(sf.program_text())
    assert texts[0] == texts[1]
    alias, n_results = _main_signature(texts[0])
    n_params = len(list(lin.parameters()))
    assert len(sf._slots) == n_params + 1  # and the RNG key
    assert len(alias) == n_params + 3 and n_results == 2
    assert alias == [None] * (n_params + 3)
    assert sf.aliased_bytes() == 0


def test_a_written_slot_is_the_one_marked_and_returned():
    """The twin: the same function also counts its calls in a tensor it
    observes. That one slot is written, so it alone is donated (the first
    parameter, aliasing its output behind the two results) and returned;
    the parameters beside it stay read-only."""
    lin, fn = _affine()
    calls = paddle.to_tensor(np.zeros((3, 5), np.float32))

    def counting(x, scratch, y):
        paddle.add_(calls, paddle.to_tensor(np.ones((3, 5), np.float32)))
        return fn(x, scratch, y)

    sf = jit.StaticFunction(counting, observe=[lin, calls], warmup=False)
    weight = lin.weight._value
    for n in (1, 2, 3):
        held = calls._value
        sf(_x(), _x(2.0), _x(3.0))
        np.testing.assert_allclose(calls.numpy(), float(n))
        assert held.is_deleted() and lin.weight._value is weight
    alias, n_results = _main_signature(sf.program_text())
    n_params = len(list(lin.parameters()))
    assert n_results == 3 and len(alias) == 1 + n_params + 3
    assert alias == [2] + [None] * (n_params + 3)
    assert sf.aliased_bytes() == held.nbytes


def test_declared_argument_is_consumed_and_rides_last():
    lin, fn = _affine()
    sf = jit.StaticFunction(fn, observe=[lin], warmup=False,
                            donate_argnums=(1,))
    for _ in range(2):  # the build, then the cached program
        x, s, y = _x(), _s(), _x(3.0)
        out, doubled = sf(x, s, y)
        np.testing.assert_allclose(doubled.numpy(), 4.0)
        assert s._value.is_deleted()
        assert not (x._value.is_deleted() or y._value.is_deleted())
    alias, _ = _main_signature(sf.program_text())
    assert alias[-1] == 1 and alias[-3:-1] == [None, None]
    assert _aliased_bytes(fn.__name__) >= s._value.nbytes


@pytest.mark.parametrize("twin", ["kept_argument", "state_buffer"])
def test_declared_argument_that_is_another_operand_is_copied(twin):
    """A given-up buffer that is also a kept argument, or a state buffer,
    is copied — XLA rejects a donated buffer passed twice — and the call
    succeeds with the other operand intact."""
    lin, fn = _affine()
    sf = jit.StaticFunction(fn, observe=[lin], warmup=False,
                            donate_argnums=(1,))
    if twin == "kept_argument":
        s = y = _x(2.0)
    else:
        s, y = lin.weight, _x(2.0)
    want = np.asarray(s.numpy()) * 2.0
    out, doubled = sf(_x(), s, y)
    np.testing.assert_allclose(doubled.numpy(), want)
    assert not y._value.is_deleted()
    assert not lin.weight._value.is_deleted()
    np.testing.assert_allclose(sf(_x(), _x(1.0), _x(2.0))[1].numpy(), 2.0)


def test_no_donate_env_marks_nothing(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_NO_DONATE", "1")
    lin, fn = _affine()
    sf = jit.StaticFunction(fn, observe=[lin], warmup=False,
                            donate_argnums=(1,))
    s = _x(2.0)
    sf(_x(), s, _x(3.0))
    assert not s._value.is_deleted()
    assert set(_main_signature(sf.program_text())[0]) == {None}


def test_executable_mismatch_degrades_before_any_buffer_is_consumed():
    """A call the built executable refuses (a calling-convention mismatch)
    fails BEFORE execution: the given-up buffers are intact for the
    jax.jit retry, and the signature stays on that path."""
    lin, fn = _affine()
    sf = jit.StaticFunction(fn, observe=[lin], warmup=False,
                            donate_argnums=(1,))
    sf(_x(), _x(2.0), _x(3.0))
    (compiled,) = sf._cache.values()

    def refuses(*operands):
        assert not any(a.is_deleted() for a in operands[-1])
        raise TypeError("compiled for another signature")

    compiled.aot = refuses
    s = _x(5.0)
    np.testing.assert_allclose(sf(_x(), s, _x(3.0))[1].numpy(), 10.0)
    assert s._value.is_deleted() and compiled.aot is None


def test_old_convention_cache_entry_is_a_miss(tmp_path):
    """A `.jitcache` entry as the all-state-donated convention stored it
    (no record of the written slots) under this signature's file name is not
    loaded and does not crash: the program is built fresh, and the key a new
    entry is stored under names the convention."""
    import pickle

    lin, fn = _affine()

    def build():
        jit.clear_compile_cache(memory=True)
        sf = jit.StaticFunction(fn, observe=[lin], warmup=False,
                                cache_dir=str(tmp_path))
        return sf(_x(), _x(2.0), _x(3.0))[0].numpy()

    fam = metrics.get_registry().counter(
        "paddle_tpu_jit_compiles_total", labels=("fn", "source"))
    fresh = lambda: fam.sum_labels(fn=fn.__name__, source="fresh")  # noqa: E731
    try:
        want = build()
        [path] = tmp_path.glob("*.jitcache")
        entry = pickle.loads(path.read_bytes())
        assert "written-state-donated" in entry["key"]
        assert entry.pop("written") == ()
        path.write_bytes(pickle.dumps(entry))
        n = fresh()
        np.testing.assert_array_equal(build(), want)
        assert fresh() == n + 1
        assert "written" in pickle.loads(path.read_bytes())  # stored anew
        build()
        assert fresh() == n + 1  # and that one loads
    finally:
        jit.clear_compile_cache(memory=True)
