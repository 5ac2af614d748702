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


@pytest.mark.parametrize("make_model,kv_dtype", _ENGINES)
def test_step_consumes_the_pool_arrays(make_model, kv_dtype):
    """After a step the arrays the pool held before are deleted and the
    pool's new ones are live."""
    eng = _engine(make_model, kv_dtype)
    while eng.has_work:
        before = _pool_arrays(eng.pool)
        eng.step()
        assert all(a.is_deleted() for a in before)
        after = _pool_arrays(eng.pool)
        assert not any(a.is_deleted() for a in after)
        assert np.isfinite(np.asarray(after[0], np.float32)).all()


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
    """No declaration, no change of program: only the state is marked, the
    argument leaves ride as before, and an empty declaration is the same
    text."""
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
    n_state = len(sf._slots)  # the layer's parameters and the RNG key
    assert len(alias) == n_state + 3 and n_results == 2 + n_state
    assert all(a is not None for a in alias[:n_state])
    assert alias[n_state:] == [None] * 3


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
        assert not any(a.is_deleted() for a in operands[3])
        raise TypeError("compiled for another signature")

    compiled.aot = refuses
    s = _x(5.0)
    np.testing.assert_allclose(sf(_x(), s, _x(3.0))[1].numpy(), 10.0)
    assert s._value.is_deleted() and compiled.aot is None
