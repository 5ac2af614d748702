"""Olmo-Hybrid through the serving path on the CPU at a tiny size: gated
delta-rule layers whose state lives per slot beside the full layers' paged
K/V (``ops/paged_cache.state_attend``, ``ops/pallas/gated_delta.py``), the
``tok_slot`` grid, the spare slot, and the refusals of what would move a
sequence's cache without its state.

The program runs in float32 on seeded weights and is compared with the
benchmark family's plain float32 reference (``benchmarks/families/
olmo_hybrid/reference.py``: a ``lax.scan`` of the published equation, one
layer at a time, no cache) by its LOGITS at every served position: the
final hidden state of each row is read off the step as it runs, and the
logits are that times the head.
"""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models import olmo_hybrid as oh  # noqa: E402
from paddle_tpu.ops import paged_cache  # noqa: E402
from paddle_tpu.ops.pallas import gated_delta as gd  # noqa: E402
from paddle_tpu.serving import (PagedKVCachePool, ServingEngine,  # noqa: E402
                                SlotState)
from benchmarks.families.olmo_hybrid import model as fam  # noqa: E402
from benchmarks.families.olmo_hybrid import reference as ref  # noqa: E402
from benchmarks.families.olmo_hybrid import weights as W  # noqa: E402

pytestmark = pytest.mark.serving

# 4 layers L L L F, 2 linear heads of d_k 16 and d_v 32, 4 full heads of 16
CFG = {"vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
       "num_hidden_layers": 4, "num_attention_heads": 4,
       "num_key_value_heads": 4,
       "layer_types": ["linear_attention"] * 3 + ["full_attention"],
       "linear_num_key_heads": 2, "linear_num_value_heads": 2,
       "linear_key_head_dim": 16, "linear_value_head_dim": 32,
       "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
       "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
       "rope_parameters": {"rope_theta": None},
       "tie_word_embeddings": False, "attention_bias": False}
SEED = 7
# The program in float32 against the float32 reference differs only by the
# order of float32 sums: 3.2e-6 of a row's logit spread here, 30x under the
# tolerance. The same program with its state kept in bfloat16 between steps
# reads 0.011, 110x over it (the test below); bf16 weights and activations
# read 0.04.
TOL = 1e-4


@pytest.fixture(scope="module")
def model():
    m = oh.OlmoHybridForCausalLM(fam.olmo_config(CFG, param_dtype="float32"))
    fam.load_weights(m, CFG, SEED)
    return m


@pytest.fixture
def rows(monkeypatch):
    """{(slot, position): final hidden row} of every row the step runs,
    the last one written for each key."""
    seen = {}
    orig = oh.OlmoHybridModel.forward_paged

    def hooked(self, ids, pos, bt, caches, adapters=None, slots=None):
        h, c = orig(self, ids, pos, bt, caches, adapters=adapters,
                    slots=slots)

        def store(hv, pv, sv):
            for r in range(hv.shape[0]):
                seen[(int(sv[r]), int(pv[r]))] = np.asarray(hv[r, 0],
                                                            np.float32)
        jax.debug.callback(store, h._value, pos._value, slots._value)
        return h, c

    monkeypatch.setattr(oh.OlmoHybridModel, "forward_paged", hooked)
    return seen


def _engine(model, **kw):
    args = dict(page_size=4, max_model_len=64, num_pages=40,
                max_batch_slots=2, token_budget=8, prefix_cache=False)
    args.update(kw)
    return ServingEngine(model, **args)


def _gap(model, seen, slot, prompt, tokens):
    """max over served positions of max |program - reference| logit over
    the reference row's standard deviation."""
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    head = np.asarray(model.lm_head.weight._value, np.float32)
    positions = range(len(prompt) - 1, len(seq) - 1)
    prog = np.stack([seen[(slot, p)] for p in positions]) @ head
    at = np.asarray([(0, p) for p in positions], np.int32)
    want = np.asarray(ref.logits_at(W.make_weights(CFG, SEED), seq[None], at,
                                    CFG))
    return float((np.abs(prog - want).max(-1) / want.std(-1)).max())


def _serve(eng, prompts, n_new, **kw):
    rids = [eng.add_request(p, max_new_tokens=n_new, temperature=0.0, **kw)
            for p in prompts]
    out = eng.run()
    return [np.asarray(out[r].token_ids) for r in rids]


PROMPTS = [np.random.RandomState(s).randint(0, 128, (n,))
           for s, n in ((1, 13), (2, 6))]


def test_prefill_then_decode_matches_the_reference(model, rows):
    eng = _engine(model)
    got = _serve(eng, PROMPTS, 10)
    for slot, (p, t) in enumerate(zip(PROMPTS, got)):
        assert t.size == 10
        assert _gap(model, rows, slot, p, t) < TOL
    assert eng.pool.has_state and eng.pool.used_pages == 0


def test_a_state_kept_in_bf16_fails_the_tolerance(model, rows, monkeypatch):
    real = paged_cache.gated_delta

    def bf16_state(state, *a, **k):
        o, st = real(state.astype(jnp.bfloat16).astype(jnp.float32), *a, **k)
        return o, st.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(paged_cache, "gated_delta", bf16_state)
    eng = _engine(model)
    t = _serve(eng, PROMPTS[:1], 10)[0]
    assert _gap(model, rows, 0, PROMPTS[0], t) > 10 * TOL


@pytest.mark.parametrize("budget", [3, 5, 16])
def test_a_prompt_chunked_at_any_budget_gives_the_same_logits(model, rows,
                                                              budget):
    """13 prompt rows in chunks of 3, 5 or all at once: the state and the
    convolution's tail carry across steps exactly."""
    eng = _engine(model, token_budget=budget, max_batch_slots=1)
    t = _serve(eng, PROMPTS[:1], 6)[0]
    assert _gap(model, rows, 0, PROMPTS[0], t) < TOL


def test_a_slot_reused_after_an_ended_sequence_starts_afresh(model, rows):
    """The first request ends on an eos with its next row in flight (that
    row runs and advances slot 0's state, then is thrown away); the second
    takes slot 0 and must read nothing of it: position 0 resets."""
    from paddle_tpu.serving import tracing

    first = _serve(_engine(model, max_batch_slots=1), PROMPTS[:1], 6)[0]
    eos = int(first[2])
    eng = _engine(model, max_batch_slots=1)
    tr = tracing.get_tracer()
    tr.reset()
    a = eng.add_request(PROMPTS[0], max_new_tokens=6, temperature=0.0,
                        eos_token_id=eos)
    b = eng.add_request(PROMPTS[1], max_new_tokens=8, temperature=0.0)
    out = eng.run()
    assert out[a].finish_reason == "stop" and len(out[a].token_ids) == 3
    counts = [e["counts"] for e in tr.events()
              if e["name"] == "step" and "counts" in e]
    assert sum(c["discarded"] for c in counts) >= 1
    assert _gap(model, rows, 0, PROMPTS[1], np.asarray(out[b].token_ids)) \
        < TOL


def test_a_preempted_sequence_is_recomputed(model, rows):
    """Taken off its engine mid-decode (the journal of its tokens, as
    migration and brownout preemption take it) and served again from
    position 0 on another engine: the same stream and logits."""
    src, dst = _engine(model), _engine(model)
    rid = src.add_request(PROMPTS[0], max_new_tokens=10, temperature=0.0)
    while not src.slots[0] or len(src.slots[0].gen) < 4:
        src.step()
    (req,) = src.export_inflight()
    dst.adopt_request(req)
    got = np.asarray(dst.run()[rid].token_ids)
    assert _gap(model, rows, 0, PROMPTS[0], got) < TOL
    np.testing.assert_array_equal(
        got, _serve(_engine(model), PROMPTS[:1], 10)[0])


def test_the_step_counts_the_rows_that_advance_a_state(model):
    from paddle_tpu.serving import tracing

    tr = tracing.get_tracer()
    tr.reset()
    eng = _engine(model)
    _serve(eng, PROMPTS, 4)
    counts = [e["counts"] for e in tr.events()
              if e["name"] == "step" and "counts" in e]
    assert sum(c["state_rows"] for c in counts) == \
        sum(c["rows"] for c in counts)
    assert sum(c["state_chunk_rows"] for c in counts) == \
        sum(c["chunk_rows"] for c in counts) == 13 + 6
    assert sum(c["state_segments"] for c in counts) == \
        sum(c["seqs"] for c in counts)


def test_the_step_counts_the_rows_the_kernel_advances_in_blocks(monkeypatch):
    """4 linear heads of d_v 32 fill a 128-lane tile, so the kernel's block
    is 32 rows: a 40-row prompt in one chunk advances in blocks, a 13-row
    one and the decode rows do not. The ``step`` counter is the kernel
    module's rule applied to each slot's run of packed rows."""
    from paddle_tpu.serving import tracing

    cfg = dict(CFG, linear_num_key_heads=4, linear_num_value_heads=4)
    m = oh.OlmoHybridForCausalLM(fam.olmo_config(cfg, param_dtype="float32"))
    fam.load_weights(m, cfg, SEED)
    block = gd.state_block(4, cfg["linear_value_head_dim"])
    assert block == 32
    runs = []
    pack = ServingEngine._pack

    def packed(self, rows, t0):
        runs.append([r[2].size for r in rows])
        return pack(self, rows, t0)

    monkeypatch.setattr(ServingEngine, "_pack", packed)
    tr = tracing.get_tracer()
    tr.reset()
    eng = _engine(m, token_budget=64)
    long = np.random.RandomState(3).randint(0, 128, (40,))
    _serve(eng, [long, PROMPTS[0]], 4)
    counts = [e["counts"] for e in tr.events()
              if e["name"] == "step" and "counts" in e]
    want = sum(gd.block_rows(c, block) for step in runs for c in step)
    assert want == 40
    assert sum(c["state_block_rows"] for c in counts) == want


# ------------------------------------------------------------ the kernel
def test_the_kernel_matches_the_plain_path_on_ragged_rows(monkeypatch):
    """Interpret mode: a decode singleton mid-sequence, a chunk carried in
    from an earlier step, a chunk from position 0 and three padding rows on
    the spare slot; an untouched slot stays as it was."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    H, dk, dv, S = 4, 16, 32, 5
    rng = np.random.default_rng(0)
    slots = np.array([2, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 4, 4, 4], np.int32)
    pos = np.array([7, 3, 4, 5, 6, 7, 8, 0, 1, 2, 3, 0, 0, 0], np.int32)
    T = slots.size

    def f(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True)
            for x in (f(T, H, dk), f(T, H, dk)))
    v, lg = f(T, H, dv), -jnp.abs(f(T, H))
    b = 2 * jax.nn.sigmoid(f(T, H))
    g = gd.state_head_group(H, dv)
    state = f(S, H // g, dk, g * dv)
    want = gd.gated_delta(state, q, k, v, lg, b, slots, pos, use_kernel=False)
    got = gd.gated_delta(state, q, k, v, lg, b, slots, pos, use_kernel=True)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1][:4], want[1][:4], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[1][3], state[3])


# 4 heads of d_v 32 fill one 128-lane tile, so a block is 32 rows
BLOCK_H, BLOCK_DK, BLOCK_DV, BLOCK_SLOTS = 4, 16, 32, 6
C = gd.state_block(BLOCK_H, BLOCK_DV)


@pytest.mark.parametrize("runs,inputs", [
    ([(2, 5, C - 1)], "random"),       # one row short: the row path
    ([(2, 0, C)], "random"),           # one whole block from position 0
    ([(2, 5, C + 1)], "random"),       # a 1-row window, then a whole one
    ([(2, 5, 2 * C + 5)], "random"),
    ([(2, 5, C + 1), (4, 0, 2 * C + 5)], "random"),  # blocks back to back
    ([(2, 5, 2 * C + 5)], "steep decay"),
    ([(2, 5, 2 * C + 5)], "beta 2, one key"),
], ids=["C-1", "C", "C+1", "2C+5", "two runs", "g -30", "beta 2"])
def test_the_kernel_advances_a_long_run_in_blocks(monkeypatch, runs, inputs):
    """Interpret mode, against the scan: decode singletons before and after
    the long runs (which start at row 2, off the 8-row tiling), three
    padding rows on the spare. "steep decay": head 1's ``g`` is about -30 a
    row, so exp of a block's cumulative decay underflows and only log-space
    ratios stay finite. "beta 2": every key of head 0 is one unit vector
    plus 1e-3 noise and ``beta`` is 1.99, the UT transform's worst case
    (``(I - beta k k^T)`` flips the key's component every row). Row path
    and block path are both held to 1e-5; an untouched slot and the
    spare stay bit-identical."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    H, dk, dv, S = BLOCK_H, BLOCK_DK, BLOCK_DV, BLOCK_SLOTS
    rng = np.random.default_rng(len(runs) * 100 + runs[-1][2])
    slots = [0, 1]
    pos = [7, 9]
    for slot, p0, n in runs:
        slots += [slot] * n
        pos += list(range(p0, p0 + n))
    slots += [3, 5, 5, 5]
    pos += [11, 0, 0, 0]
    slots, pos = np.asarray(slots, np.int32), np.asarray(pos, np.int32)
    T = slots.size

    def f(*s):
        return rng.standard_normal(s).astype(np.float32)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q, k, v = unit(f(T, H, dk)), unit(f(T, H, dk)), f(T, H, dv)
    lg = -np.abs(f(T, H)) * 0.5
    b = 2 / (1 + np.exp(-f(T, H)))
    if inputs == "steep decay":
        lg[:, 1] = -30 + f(T)
    if inputs == "beta 2":
        k[:, 0] = unit(unit(f(dk)) + 1e-3 * f(T, dk))
        b[:, 0] = 1.99
    g = gd.state_head_group(H, dv)
    state = jnp.asarray(f(S, H // g, dk, g * dv))
    args = [jnp.asarray(x) for x in (q, k, v, lg, b)] + [slots, pos]
    want = gd.gated_delta(state, *args, use_kernel=False)
    got = gd.gated_delta(state, *args, use_kernel=True)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[1][5], state[5])
    np.testing.assert_array_equal(got[0][-3:], 0.0)
    if 4 not in slots:
        np.testing.assert_array_equal(got[1][4], state[4])


def test_the_state_layout_round_trips():
    x = jnp.arange(2 * 6 * 3 * 4, dtype=jnp.float32).reshape(2, 6, 3, 4)
    for g in (1, 2, 3):
        np.testing.assert_array_equal(
            gd.heads_view(gd.grouped_view(x, g), 4), x)
    assert gd.state_head_group(30, 192) == 2
    assert gd.state_head_group(2, 32) == 2


def test_conv_rows_carries_the_tail_across_steps():
    """A slot's rows split over two steps convolve as one sequence."""
    rng = np.random.default_rng(1)
    C, K, n = 6, 4, 9
    x = jnp.asarray(rng.standard_normal((n, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((C, K)), jnp.float32)
    whole = ref.causal_conv(x[None], w)[0]
    tail = jnp.zeros((3, K - 1, C), jnp.float32)
    y1, tail = paged_cache.conv_rows(tail, x[:5], w, jnp.zeros(5, jnp.int32),
                                     jnp.arange(5))
    y2, _ = paged_cache.conv_rows(tail, x[5:], w, jnp.zeros(4, jnp.int32),
                                  jnp.arange(5, 9))
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), whole, rtol=1e-6,
                               atol=1e-6)


# ------------------------------------------------------------ refusals
def test_the_engine_refuses_what_would_leave_the_state_behind(model):
    for kw, why in ((dict(prefix_cache=True), "prefix_cache=True"),
                    (dict(spec_k=2), "speculative decoding"),
                    (dict(host_offload=True), "host_offload=True")):
        with pytest.raises(ValueError, match=why):
            _engine(model, **kw)


def test_the_pool_refuses_what_would_leave_the_state_behind():
    kinds = [SlotState(heads=2, key_dim=16, value_dim=32, conv_channels=128),
             "kv"]
    pool = PagedKVCachePool(1, 8, 4, 2, 8, dtype="bfloat16",
                            layer_kinds=kinds, state_slots=3)
    pool.allocate("a", 5)
    for op, call in (("truncate", lambda: pool.truncate("a", 2)),
                     ("offload", lambda: pool.offload_seq("a")),
                     ("prefetch", lambda: pool.prefetch_seq("a")),
                     ("fork", lambda: pool.fork("a", "b")),
                     ("prefix adoption",
                      lambda: pool.allocate("c", 8, prefix_pages=[1],
                                            prefix_tokens=4))):
        with pytest.raises(ValueError, match=f"{op}: this pool keeps"):
            call()
    assert pool.state_bytes == 3 * (4 * 2 * 16 * 32 + 2 * 3 * 128)
    # the pool's operands: a state layer's (state, tail), then K/V
    flat = pool.step_flat()
    assert [tuple(t.shape) for t in flat] == [(3, 1, 16, 64), (3, 3, 128),
                                              (8, 2, 4, 8), (8, 2, 4, 8)]
    names = tuple(f"t{i}" for i in range(4))
    assert pool.layer_caches(names) == [("t0", "t1"), ("t2", "t3")]
    pool.set_step_flat(tuple(flat))
    assert all(a is b for a, b in zip(pool.step_flat(), flat))


# ------------------------------------------- the GPT step is as it was
# sha256 of each bucket's lowered step of tests/test_paged_cache.py's tiny
# GPT-bf16 engine, taken at the commit before per-slot state existed: a pool
# of K/V alone lowers the same program, byte for byte
GPT_STEP_SHA256 = [
    "45ab81e06ae61d78be5cfac68090f9906f65416536609ea5ffec6a4d8f2e5373",
    "f278b8acf47060d2f8ea246f6c831ba6c802ef60d5f6dcf20636d4759e6af3a3"]


def test_the_gpt_step_lowers_byte_identical():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    eng = ServingEngine(GPTForCausalLM(gpt_tiny(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0)), kv_dtype="bfloat16", page_size=8,
        max_model_len=64, num_pages=24, max_batch_slots=2, token_budget=16)
    rng = np.random.RandomState(3)
    for n in (11, 5):
        eng.add_request(rng.randint(0, 128, (n,)), max_new_tokens=4,
                        temperature=0.0)
    eng.run()
    assert [hashlib.sha256(t.encode()).hexdigest()
            for t in eng.step_program_texts()] == GPT_STEP_SHA256
