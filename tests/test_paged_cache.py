"""A layer's paged cache has one owner (ISSUE 32): ``ops/paged_cache.
paged_attend`` is the one traced function that opens it, the trunks hand it
through, the pool regroups the step's operands, and nothing under ``models/``
or ``ops/`` imports ``serving/``.

Run as a script this file prints the sha256 of every bucket's lowered step
text of the GPT-bf16 tiny engine: the other process of
``test_step_text_is_the_same_in_another_process``.
"""
import ast
import hashlib
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, REPO)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM,  # noqa: E402
                               gpt_tiny, llama_tiny)
from paddle_tpu.models import gpt as gpt_mod, llama as llama_mod  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    ragged_paged_attention)
from paddle_tpu.ops.paged_cache import paged_attend  # noqa: E402
from paddle_tpu.quantization.observers import quantize_kv  # noqa: E402
from paddle_tpu.serving import PagedKVCachePool, ServingEngine  # noqa: E402
from paddle_tpu.tensor import Tensor  # noqa: E402

pytestmark = pytest.mark.serving


def _gpt():
    paddle.seed(0)
    return GPTForCausalLM(gpt_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))


def _llama():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_key_value_heads=2, max_position_embeddings=64))


# ------------------------------------------- (i) one text in every process
def _step_text_hashes():
    """tests/test_step_donation.py's GPT-bf16 engine, served to the end:
    sha256 of each bucket's lowered step, in the order they compiled."""
    eng = ServingEngine(_gpt(), kv_dtype="bfloat16", page_size=8,
                        max_model_len=64, num_pages=24, max_batch_slots=2,
                        token_budget=16)
    rng = np.random.RandomState(3)
    for n in (11, 5):
        eng.add_request(rng.randint(0, 128, (n,)), max_new_tokens=4,
                        temperature=0.0)
    while eng.has_work:
        eng.step()
    return [hashlib.sha256(t.encode()).hexdigest()
            for t in eng.step_program_texts()]


def test_step_text_is_the_same_in_another_process():
    """The lowered step is a function of the code, not of the process: a
    process with another ``PYTHONHASHSEED`` lowers the same text, so JAX's
    persistent cache hits from run to run and a refactor can be held to
    "the parent's program, byte for byte" (PERF.md, PR 32)."""
    mine = _step_text_hashes()
    assert len(mine) == 2
    seed = "2" if os.environ.get("PYTHONHASHSEED") == "1" else "1"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], check=True,
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "PYTHONHASHSEED": seed})
    assert json.loads(out.stdout.strip().splitlines()[-1]) == mine


# --------------------------------- (ii) the trunks pass the cache unopened
class _Opaque:
    """A cache kind no trunk has heard of: nothing to index or measure."""


def _caches(kind, n_layers):
    shape = (6, 2, 8, 16)
    if kind == "opaque":
        return [_Opaque() for _ in range(n_layers)]
    arrays = (jnp.zeros(shape, jnp.int8 if kind == "kv+scales"
                        else jnp.float32),) * 2
    if kind == "kv+scales":
        arrays += (jnp.ones(shape[:3], jnp.float32),) * 2
    return [tuple(Tensor(a, stop_gradient=True) for a in arrays)
            for _ in range(n_layers)]


@pytest.mark.parametrize("kind", ["kv", "kv+scales", "opaque"])
@pytest.mark.parametrize("make_model,module", [(_gpt, gpt_mod),
                                               (_llama, llama_mod)],
                         ids=["gpt", "llama"])
def test_trunk_hands_the_cache_through(monkeypatch, make_model, module,
                                       kind):
    """Model, decoder layer and attention give ``paged_attend`` the very
    ``cache`` value their layer was given, whatever it is, and return the
    very value it returned."""
    model = make_model()
    trunk = model._decode_trunk()
    T, n_layers = 5, len(trunk.layers)
    seen, handed_back = [], []

    def spy(cache, q, k, v, block_tables, positions, scale):
        assert q.shape[0] == k.shape[0] == v.shape[0] == T
        assert q.shape[2] == k.shape[2] == v.shape[2]  # [T, heads, hd]
        assert scale == pytest.approx(q.shape[2] ** -0.5)
        seen.append(cache)
        handed_back.append(_Opaque())
        return Tensor(jnp.zeros(q.shape, q._value.dtype)), handed_back[-1]

    monkeypatch.setattr(module, "paged_attend", spy)
    caches = _caches(kind, n_layers)
    ids = Tensor(jnp.arange(T, dtype=jnp.int32).reshape(T, 1))
    pos = Tensor(jnp.arange(T, dtype=jnp.int32))
    bt = Tensor(jnp.ones((T, 2), jnp.int32))
    with paddle.no_grad():
        hidden, new = trunk.forward_paged(ids, pos, bt, caches)
    assert tuple(hidden.shape) == (T, 1, 64)
    assert len(seen) == n_layers
    assert all(s is c for s, c in zip(seen, caches))
    assert len(new) == n_layers
    assert all(n is h for n, h in zip(new, handed_back))


def _grid(rng, n_pages, page, width):
    """A step's rows as the engine packs them: two decode rows, a chunk of
    five consecutive positions that crosses a page seam, and three padding
    rows (zero table, position 0) that collide on the null page. Returns
    the tables, the positions and the number of live rows."""
    tables = rng.permutation(np.arange(1, n_pages))[:3 * width].reshape(
        3, width)
    rows = [(0, 13), (1, 6)] + [(2, p) for p in range(page - 2, page + 3)]
    bt = np.zeros((len(rows) + 3, width), np.int32)
    pos = np.zeros(len(rows) + 3, np.int32)
    for r, (seq, p) in enumerate(rows):
        bt[r], pos[r] = tables[seq], p
    return jnp.asarray(bt), jnp.asarray(pos), len(rows)


def _old_paged_step(cache, qh, kh, vh, bt, pos, scale):
    """What each trunk's ``paged_step`` closure did before the seam, with
    the K/V write in its first, indexed form: the oracle."""
    page = cache[0].shape[2]
    page_ids = bt[jnp.arange(pos.shape[0]), pos // page]
    rows = (kh, vh)
    if len(cache) == 4:
        (kq, ks), (vq, vs) = quantize_kv(kh), quantize_kv(vh)
        rows = (kq, vq, ks, vs)
    cache = tuple(a.at[page_ids, :, pos % page].set(r.astype(a.dtype))
                  for a, r in zip(cache, rows))
    k_sc, v_sc = cache[2:] if len(cache) == 4 else (None, None)
    ctx = ragged_paged_attention(qh, cache[0], cache[1], bt, pos + 1,
                                 scale=scale, k_scale=k_sc, v_scale=v_sc)
    return ctx, cache


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_paged_attend_equals_the_old_paged_step(dtype):
    """Everywhere but the null page's slot 0, where a bucket's padding rows
    collide and any of them may win (so their own context is not compared
    either)."""
    rng = np.random.default_rng(7)
    n_pages, nh, nkv, page, hd, width = 16, 4, 2, 8, 16, 3
    bt, pos, live = _grid(rng, n_pages, page, width)
    T = pos.shape[0]
    q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
    shape = (n_pages, nkv, page, hd)
    cache = tuple(jnp.asarray(rng.integers(-5, 5, shape), dtype)
                  for _ in range(2))
    if dtype == "int8":
        cache += tuple(jnp.asarray(rng.random(shape[:3]), jnp.float32)
                       for _ in range(2))
    want_ctx, want = _old_paged_step(cache, q, k, v, bt, pos, hd ** -0.5)
    ctx, got = paged_attend(tuple(Tensor(a) for a in cache), Tensor(q),
                            Tensor(k), Tensor(v), Tensor(bt), Tensor(pos),
                            hd ** -0.5)
    np.testing.assert_array_equal(np.asarray(ctx._value[:live]),
                                  np.asarray(want_ctx[:live]))
    assert isinstance(got, tuple) and len(got) == len(want)
    for g, w in zip(got, want):
        g = g._value
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.array(g, np.float32), np.array(w, np.float32)
        g[0, :, 0] = w[0, :, 0] = 0
        np.testing.assert_array_equal(g, w)


# ---------------------------------------- (iii) ops <- models <- serving
def _modules(package):
    root = os.path.join(REPO, "paddle_tpu", package)
    for dirpath, _, names in os.walk(root):
        for name in sorted(names):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                with open(path) as f:
                    yield path, ast.parse(f.read(), path)


def _imported(path, tree):
    """Absolute dotted names of everything a module imports, lazy imports
    inside function bodies included."""
    pkg = os.path.relpath(os.path.dirname(path), REPO).split(os.sep)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - node.level + 1] if node.level else []
            base = ".".join(base + ([node.module] if node.module else []))
            yield from (f"{base}.{a.name}" for a in node.names)


@pytest.mark.parametrize("package", ["models", "ops"])
def test_lower_layers_do_not_import_serving(package):
    found = [(os.path.relpath(path, REPO), name)
             for path, tree in _modules(package)
             for name in _imported(path, tree)
             if name.startswith("paddle_tpu.serving")]
    assert not found


def test_the_seam_has_one_caller_per_trunk_and_the_engine_no_stride():
    """``paged_attend`` is named in ``models/`` by an attention's
    ``forward_paged`` and nowhere else; ``engine.py`` leaves the operands'
    grouping to the pool."""
    callers = []
    for path, tree in _modules("models"):
        for cls in (n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)):
            for fn in (n for n in cls.body if isinstance(n, ast.FunctionDef)):
                if any(isinstance(n, ast.Name) and n.id == "paged_attend"
                       for n in ast.walk(fn)):
                    callers.append((cls.name, fn.name))
        outside = [n for n in tree.body
                   if not isinstance(n, (ast.ClassDef, ast.ImportFrom))]
        assert not any(isinstance(n, ast.Name) and n.id == "paged_attend"
                       for top in outside for n in ast.walk(top)), path
    assert sorted(callers) == [("GPTAttention", "forward_paged"),
                               ("LlamaAttention", "forward_paged")]
    with open(os.path.join(REPO, "paddle_tpu", "serving", "engine.py")) as f:
        assert "step_stride" not in f.read()


# ------------------------------------- (iv) the pool hands over its operands
@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_pool_operands_and_their_regrouping_are_inverse(dtype):
    pool = PagedKVCachePool(num_layers=3, num_pages=8, page_size=4,
                            n_kv_heads=2, head_dim=8, dtype=dtype)
    flat = pool.step_flat()
    per_layer = [pool.step_arrays(li) for li in range(pool.num_layers)]
    assert len(flat) == pool.step_stride * pool.num_layers
    assert len(flat) == (12 if dtype == "int8" else 6)
    caches = pool.layer_caches(flat)
    assert len(caches) == pool.num_layers
    assert all(isinstance(c, tuple) and len(c) == len(w)
               and all(a is b for a, b in zip(c, w))
               for c, w in zip(caches, per_layer))
    assert all(a is b for a, b in zip(pool.step_flat(caches), flat))
    # pure Python on any sequence: names stand in for the step's tracers
    names = tuple(f"t{i}" for i in range(len(flat)))
    assert pool.step_flat(pool.layer_caches(names)) == list(names)
    # and the way back puts every array where it came from
    pool.set_step_flat(tuple(flat))
    assert all(a is b for a, b in zip(pool.step_flat(), flat))


if __name__ == "__main__":
    print(json.dumps(_step_text_hashes()))
