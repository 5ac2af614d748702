"""A layer's paged KV cache as ONE opaque value, and the one function that
opens it inside the compiled serving step.

``cache`` is whatever :meth:`PagedKVCachePool.layer_caches` groups for a
layer: today ``(k, v)`` page pools ``[pages, n_kv_heads, page_size,
head_dim]`` or, for int8 pages, ``(k, v, k_scales, v_scales)``. A trunk's
``forward_paged`` projects its rows and calls :func:`paged_attend`; it never
indexes, measures or unpacks the value, so a new cache kind (a sliding
window, a latent cache, a recurrent state) is a change here and in the pool,
not in every model (docs/SERVING.md "Adding a trunk").
"""
from __future__ import annotations

import jax.numpy as jnp

from ..quantization.observers import quantize_kv
from ._apply import apply_op, ensure_tensor
from .pallas.paged_attention import ragged_paged_attention

__all__ = ["paged_attend", "write_step_kv"]


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
