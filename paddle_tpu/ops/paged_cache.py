"""A layer's cache as ONE opaque value, and the functions that open it
inside the compiled serving step, one for each kind the pool holds.

``cache`` is whatever :meth:`PagedKVCachePool.layer_caches` groups for a
layer, by the layer's kind:

- paged K/V: ``(k, v)`` page pools ``[pages, n_kv_heads, page_size,
  head_dim]`` or, for int8 pages, ``(k, v, k_scales, v_scales)``, opened by
  :func:`paged_attend`;
- per-slot state: ``(state, tail)``, a gated delta-rule state ``[slots, H/G,
  d_k, G*d_v]`` float32 and the short convolution's tail ``[slots, taps,
  channels]`` of the last pre-conv inputs, opened by :func:`state_attend`.

A trunk's ``forward_paged`` projects its rows and calls one of them; it
never indexes, measures or unpacks the value, so a new cache kind (a sliding
window, a latent cache) is a change here and in the pool, not in every model
(docs/SERVING.md "Adding a trunk").
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..quantization.observers import quantize_kv
from ._apply import apply_op, ensure_tensor
from .pallas.gated_delta import gated_delta, state_head_group
from .pallas.paged_attention import ragged_paged_attention

__all__ = ["SlotState", "paged_attend", "state_attend", "write_step_kv"]


@dataclasses.dataclass(frozen=True)
class SlotState:
    """The kind of a layer that keeps per-slot state instead of pages: a
    gated delta-rule state of ``heads`` x ``key_dim`` x ``value_dim`` in
    float32 (it multiplies into itself every token) and the short
    convolution's tail, the last ``conv_taps`` pre-conv inputs of
    ``conv_channels`` channels in ``tail_dtype`` (the step's activations'),
    per slot. The state's heads lie side by side on its lane axis as
    ops/pallas/gated_delta.py lays them."""
    heads: int
    key_dim: int
    value_dim: int
    conv_channels: int
    conv_taps: int = 3
    tail_dtype: str = "bfloat16"

    def shapes(self, slots: int):
        g = state_head_group(self.heads, self.value_dim)
        return ((slots, self.heads // g, self.key_dim, g * self.value_dim),
                (slots, self.conv_taps, self.conv_channels))

    def bytes_per_slot(self) -> int:
        tail = jnp.dtype(self.tail_dtype).itemsize
        return (4 * self.heads * self.key_dim * self.value_dim
                + tail * self.conv_taps * self.conv_channels)


def _write_rows(pool, slots, rows):
    """``rows`` ``[T, heads, ...]`` into ``pool`` ``[pages, heads, page,
    ...]``, row ``(t, h)`` at flat slot ``slots[t, h]`` of the pool's
    ``[pages * heads * page, ...]`` view: ONE row scatter. The flat view
    keeps the array's own layout, so XLA updates a donated pool where it
    lies; ``pool.at[page_ids, :, offs].set(rows)`` made it re-lay the WHOLE
    array so that a token's ``[heads, hd]`` window was contiguous, and lay
    it back for the kernel: two pool-sized copies per array per step
    (PERF.md, PR 30). Indices are NOT marked unique: a bucket's padding
    rows all name (page 0, offset 0), the reserved null page."""
    tail = pool.shape[3:]
    flat = pool.reshape((-1,) + tail)
    flat = flat.at[slots.reshape(-1)].set(
        rows.reshape((-1,) + tail).astype(pool.dtype))
    return flat.reshape(pool.shape)


def write_step_kv(cache, k_rows, v_rows, block_tables, positions):
    """The compiled step's KV write, on raw arrays: row ``t``'s K and V
    ``[T, n_kv_heads, head_dim]`` land in page ``block_tables[t,
    positions[t] // page_size]``, slot ``positions[t] % page_size`` of
    ``cache``. int8 pages quantize on write (per-slot absmax) and write the
    scales beside the codes. Returns the updated tuple in the same order.
    Inactive rows carry all-zero block tables and positions, landing their
    writes on the pool's reserved null page 0."""
    n_heads, page_size = cache[0].shape[1:3]
    t = jnp.arange(positions.shape[0], dtype=jnp.int32)
    page_ids = block_tables[t, positions // page_size]
    slots = ((page_ids[:, None] * n_heads
              + jnp.arange(n_heads, dtype=jnp.int32)) * page_size
             + (positions % page_size)[:, None])
    rows = (k_rows, v_rows)
    if len(cache) == 4:
        (k_rows, k_sc), (v_rows, v_sc) = quantize_kv(k_rows), quantize_kv(v_rows)
        rows = (k_rows, v_rows, k_sc, v_sc)
    return tuple(_write_rows(a, slots, r) for a, r in zip(cache, rows))


def paged_attend(cache, q_rows, k_rows, v_rows, block_tables, positions,
                 scale):
    """Write this step's rows, then attend: the paged half of every trunk's
    attention, one row per QUERY TOKEN (decode tokens, prompt-chunk tokens
    and draft tokens alike; ops/pallas/paged_attention.py "Ragged form").

    ``q_rows`` ``[T, n_heads, head_dim]``, ``k_rows``/``v_rows`` ``[T,
    n_kv_heads, head_dim]`` are the trunk's projected rows (rotary already
    applied where the trunk has one); ``block_tables`` ``[T, pages]`` and
    ``positions`` ``[T]`` say where each row lies. Every row's K/V is
    written first (:func:`write_step_kv`), then each row attends over its
    page list masked at its own position, so a chunk's rows are causal
    over their chunk-mates. Returns ``(ctx [T, n_heads, head_dim], cache)``
    with ``cache`` updated, in the pool's order."""
    def fn(q, k, v, bt, pos, *arrays):
        pos = pos.astype(jnp.int32).reshape(-1)
        bt = bt.astype(jnp.int32)
        arrays = write_step_kv(arrays, k, v, bt, pos)
        k_sc, v_sc = arrays[2:] if len(arrays) == 4 else (None, None)
        ctx = ragged_paged_attention(q, arrays[0], arrays[1], bt, pos + 1,
                                     scale=scale, k_scale=k_sc, v_scale=v_sc)
        return (ctx, *arrays)

    ctx, *cache = apply_op(
        fn, [ensure_tensor(t) for t in (q_rows, k_rows, v_rows, block_tables,
                                        positions, *cache)],
        name="paged_attend")
    return ctx, tuple(cache)


def _segments(slots):
    """Per row: the row that starts its slot's run of rows, and whether it
    ends one (a slot's rows are consecutive: the engine packs them so)."""
    t = jnp.arange(slots.shape[0], dtype=jnp.int32)
    new = slots[1:] != slots[:-1]
    first = jnp.concatenate([jnp.ones((1,), bool), new])
    last = jnp.concatenate([new, jnp.ones((1,), bool)])
    return jax.lax.cummax(jnp.where(first, t, 0)), last


def conv_rows(tail, x, w, slots, positions):
    """The causal depthwise convolution of kernel ``K`` over a step's rows:
    row ``t`` at position ``p`` takes its own input and the ``K - 1`` before
    it in its sequence, from this step's rows of its slot where they are
    here and from the slot's ``tail`` where they came in an earlier step,
    and zeros before position 0. ``x`` ``[T, C]``, ``w`` ``[C, K]`` (tap
    ``K - 1`` multiplies the row's own input), ``tail`` ``[slots, K - 1,
    C]`` oldest first. Returns (y ``[T, C]`` float32, the tail with each
    slot's last ``K - 1`` inputs, written at the row that ends its run; the
    other rows' writes go to the last slot, the engine's spare)."""
    taps = w.shape[-1] - 1
    start, last = _segments(slots)
    t = jnp.arange(x.shape[0], dtype=jnp.int32)
    back = [x]                                  # back[j]: input at p - j
    for j in range(1, taps + 1):
        src = t - j
        here = x[jnp.maximum(src, 0)]
        before = tail[slots, jnp.clip(taps + src - start, 0, taps - 1)]
        got = jnp.where((src >= start)[:, None], here, before)
        back.append(jnp.where((positions >= j)[:, None], got,
                              jnp.zeros((), x.dtype)))
    wf = w.astype(jnp.float32)
    y = sum(back[j].astype(jnp.float32) * wf[:, taps - j]
            for j in range(taps + 1))
    new = jnp.stack(back[taps - 1::-1], axis=1)    # p-taps+1 .. p
    dest = jnp.where(last, slots, tail.shape[0] - 1)
    return y, tail.at[dest].set(new.astype(tail.dtype))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def state_attend(cache, q_rows, k_rows, v_rows, log_decay, beta, conv_w,
                 slots, positions):
    """The short-convolution gated delta rule of a linear-attention layer,
    one row per query token like :func:`paged_attend`: the per-slot half of
    the layer, which opens the layer's ``(state, tail)``.

    ``q_rows``/``k_rows`` ``[T, H, d_k]`` and ``v_rows`` ``[T, H, d_v]`` are
    the trunk's projections before the convolution; ``conv_w`` their
    depthwise kernels ``(w_q, w_k, w_v)``, each ``[channels, K]``;
    ``log_decay`` ``[T, H]`` is ``g`` (the decay is ``exp(g)``) and ``beta``
    ``[T, H]`` the write strength, both float32; ``slots`` ``[T]`` is each
    row's owner (the spare slot for padding rows) and ``positions`` ``[T]``
    its position, 0 starting a sequence afresh. Each of q, k and v passes
    the convolution (:func:`conv_rows`) and SiLU; q and k are L2-normalised
    per head, q scaled by ``d_k ** -0.5``; then the state advances over
    each slot's rows, a long run a block of rows at a time
    (ops/pallas/gated_delta.py). Returns (o ``[T, H, d_v]`` float32,
    cache) with ``cache`` updated, in the pool's order."""
    def fn(q, k, v, g, b, wq, wk, wv, sl, pos, state, tail):
        T, H, dk = q.shape
        sl = sl.astype(jnp.int32).reshape(-1)
        pos = pos.astype(jnp.int32).reshape(-1)
        x = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                             v.reshape(T, -1)], axis=-1)
        y, tail = conv_rows(tail, x, jnp.concatenate([wq, wk, wv], axis=0),
                            sl, pos)
        y = jax.nn.silu(y)
        nq = H * dk
        qc = _l2norm(y[:, :nq].reshape(T, H, dk)) * (dk ** -0.5)
        kc = _l2norm(y[:, nq:2 * nq].reshape(T, H, dk))
        vc = y[:, 2 * nq:].reshape(T, H, -1)
        o, state = gated_delta(state, qc, kc, vc, g, b, sl, pos)
        return o, state, tail

    o, *cache = apply_op(
        fn, [ensure_tensor(t) for t in (q_rows, k_rows, v_rows, log_decay,
                                        beta, *conv_w, slots, positions,
                                        *cache)],
        name="state_attend")
    return o, tuple(cache)
