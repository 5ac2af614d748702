"""Ragged paged-attention decode kernel (Pallas TPU) + pure-jnp fallback.

TPU-native kernel for continuous-batching decode (PAPERS.md: "Ragged
Paged Attention", arxiv 2604.15464): each live sequence owns a list of
fixed-size KV pages scattered through a shared pool, described by a
per-sequence block table. One query token per sequence attends over its
own ragged page list — no per-sequence dense cache, no re-layout when
sequences join or retire mid-decode.

Layout (serving/kv_cache.py owns the pool):

- ``q``            [B, num_heads, head_dim]      — one decode token per seq
- ``k/v pool``     [num_pages, num_kv_heads, page_size, head_dim]
- ``block_tables`` [B, pages_per_seq] int32      — page ids, 0-padded (page 0
  is the pool's reserved null page, never allocated to a sequence)
- ``seq_lens``     [B] int32                     — tokens written so far

Kernel shape (style of ops/pallas/flash_attention.py): grid
``(B, num_kv_heads, pages_per_seq)`` with the page axis innermost carrying
the online-softmax state in VMEM scratch; the block table and seq lens ride
in as SCALAR-PREFETCH operands (``pltpu.PrefetchScalarGridSpec``) so the
k/v BlockSpec index maps can DMA exactly the pages each sequence names —
the "ragged" part: no dense [B, max_len] gather ever materializes.

The pure-jnp fallback (``ref_paged_attention``) is the same math as the
dense decode path (models/llama.py cached_attn): softmax in f32 over the
gathered pages with masked lanes at -1e30 — tier-1 CPU tests drive the
engine through this path and assert token-for-token equality with dense
``generate()``. Set PADDLE_TPU_PALLAS_INTERPRET=1 to run the real kernel
on CPU (interpret mode), as the flash kernels do.

**Ragged (mixed query-length) form** — ``ragged_paged_attention``: the
unified serving step (engine.py) batches decode slots (q_len 1) and
prompt chunks (q_len up to the token budget) in ONE launch by
flattening every query token into a row of a ``[T, ...]`` grid: a
slot's chunk contributes one row per token, each carrying the slot's
block table and its own absolute position. Per-row ``seq_lens`` =
position + 1 masks later keys, so a chunk token attends to the shared
pool's KV — its own earlier chunk tokens included, because the step
scatters the whole chunk's KV before the gather — exactly causally.
Raggedness is therefore DATA (row→table mapping), not shape: one
compiled program per token-grid bucket serves every prefill/decode mix
(PAPERS.md, arXiv 2604.15464 — the same "queries of every length in
one kernel" contract, expressed on the decode kernel's grid).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _i32  # int32 index-map literals under x64

__all__ = ["paged_attention", "ragged_paged_attention",
           "ref_paged_attention"]

NEG_INF = -1e30
LANES = 128
# v5e SMEM is 1 MiB (the TPU compiler's own report: "Used 1.01M of 1.00M
# smem" at 2048 rows × 128 pages); the scalar-prefetched table and row lens
# live there whole, beside ~1.1 KiB the kernel itself uses. Compiled for
# the described v5e: 2024 × 128 fits, 2040 × 128 does not.
SMEM_PREFETCH_LIMIT_BYTES = (1 << 20) - 4096


def _interpret() -> bool:
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1"


# ───────────────────────── pure-jnp fallback ─────────────────────────


def ref_paged_attention(q, k_pool, v_pool, block_tables, seq_lens,
                        scale: float = None, k_scale=None, v_scale=None):
    """Gather-based paged attention, pure jnp — the CPU/equivalence path.

    Math-identical to the dense cached_attn (einsum in f32, -1e30 masked
    lanes, softmax over the key axis): a masked key contributes exactly 0
    to every sum, so outputs match the dense decode bit-for-bit on the
    positions both paths share.

    ``k_scale``/``v_scale`` (``[num_pages, nkv, page_size]`` f32, both or
    neither) arm int8-page dequantization: gathered blocks are widened
    per-block (``q * scale``) right here in the reduction — the full
    bf16/f32 page array is never materialized, mirroring the in-kernel
    dequant of the Pallas path.
    """
    B, nh, hd = q.shape
    nkv = k_pool.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    groups = nh // nkv
    # [B, pages_per_seq, nkv, page, hd] -> [B, K, nkv, hd]
    k = jnp.swapaxes(k_pool[block_tables], 2, 3).reshape(B, -1, nkv, hd)
    v = jnp.swapaxes(v_pool[block_tables], 2, 3).reshape(B, -1, nkv, hd)
    if k_scale is not None:
        ks = jnp.swapaxes(k_scale[block_tables], 2, 3).reshape(B, -1, nkv)
        vs = jnp.swapaxes(v_scale[block_tables], 2, 3).reshape(B, -1, nkv)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    if groups > 1:  # GQA: repeat kv per query group (same as dense path)
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", qf, k.astype(jnp.float32)) * scale
    pos = jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]  # [1, K]
    valid = pos < seq_lens.astype(jnp.int32)[:, None]       # [B, K]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ───────────────────────── pallas kernel ─────────────────────────


def _paged_attn_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                       scale: float, page_size: int,
                       quantized: bool = False):
    """One (row b, kv head h, page j) step of online-softmax decode.

    bt_ref/len_ref are the scalar-prefetched (flattened) block table and
    row lens — the table already consumed by the k/v index maps; len_ref
    masks the tail of the last live page here. q block is the head group
    [groups, hd], k/v blocks one head's page [page, hd]; scratch carries
    (acc, m, l) across the page axis (innermost, 'arbitrary').

    ``quantized`` (a Python-time flag) threads two extra per-page scale
    blocks (``ks_ref``/``vs_ref``, [nkv, page] — every head's row; this
    head's is picked by a dynamic sublane slice) and applies the dequant
    to the SCORE and PROBABILITY columns instead of the k/v rows: a
    slot's scale is one number per key, so ``(q·k_int)·ks == q·(k_int·ks)``
    — the same sums with ``groups`` instead of ``hd`` multiplies per key,
    and the scale row stays in its lane-major [1, page] layout.
    """
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        o_ref, acc_ref, m_ref, l_ref = rest[2:]
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    npages = pl.num_programs(2)

    neg_inf = jnp.float32(NEG_INF)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])
        m_ref[...] = jnp.full_like(m_ref[...], neg_inf)
        l_ref[...] = jnp.zeros_like(l_ref[...])

    seq_len = len_ref[b]
    # ragged early-out: pages past the sequence's length are dead weight
    # (their block-table entries are the null page) — skip the whole block
    @pl.when(j * page_size < seq_len)
    def _body():
        q = q_ref[...]  # [groups, hd]
        k = k_ref[...]  # [page, hd]
        v = v_ref[...]
        if quantized:  # int8 codes are exact in bf16 and f32 alike
            k = k.astype(jnp.float32).astype(q.dtype)
            v = v.astype(jnp.float32).astype(q.dtype)
        # the package default ("highest") asks Mosaic for an fp32 contract,
        # which it refuses on bf16 operands; f32 operands keep it
        prec = (jax.lax.Precision.DEFAULT if q.dtype == jnp.bfloat16
                else None)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        if quantized:
            s = s * ks_ref[pl.ds(h, 1), :]
        # mask the tail of the last live page
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], page_size), 1)
        mask = pos < seq_len
        s = jnp.where(mask, s, neg_inf)

        m_prev = m_ref[...]  # [groups, LANES] replicated
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        if quantized:
            p = p * vs_ref[pl.ds(h, 1), :]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
        m_ref[...] = m_new

    @pl.when(j == npages - 1)
    def _finish():
        l_fin = jnp.maximum(l_ref[...], jnp.float32(1e-30))
        o_ref[...] = (acc_ref[...] / l_fin[:, :1]).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pool, v_pool, block_tables, seq_lens,
                            scale: float, k_scale=None, v_scale=None):
    B, nh, hd = q.shape
    _, nkv, page_size, _ = k_pool.shape
    groups = nh // nkv
    pages_per_seq = block_tables.shape[1]
    quantized = k_scale is not None
    smem_bytes = 4 * B * (pages_per_seq + 1)
    if smem_bytes > SMEM_PREFETCH_LIMIT_BYTES:
        raise ValueError(
            f"paged attention: the scalar-prefetched block table "
            f"[{B} rows, {pages_per_seq} pages] + row lens need "
            f"{smem_bytes} bytes of SMEM, over the "
            f"{SMEM_PREFETCH_LIMIT_BYTES}-byte limit this kernel compiles "
            f"under — lower the engine's token_budget (rows) or "
            f"max_model_len / page_size (pages)")
    # q regrouped so each kv head's query group is one contiguous block
    qg = q.reshape(B, nkv, groups, hd)

    # flat 1-D table: a 2-D SMEM array pads its minor dim to 128 words
    bt = block_tables.astype(jnp.int32).reshape(-1)
    sl = seq_lens.astype(jnp.int32)

    def q_map(b, h, j, bt_ref, len_ref):
        return (b, h, _i32(0), _i32(0))

    def kv_map(b, h, j, bt_ref, len_ref):
        return (bt_ref[b * pages_per_seq + j], h, _i32(0), _i32(0))

    # the block's last two dims ARE the pool's last two (page_size, hd):
    # the shape the TPU lowering accepts at any page_size
    kv_spec = pl.BlockSpec((None, None, page_size, hd), kv_map)
    q_spec = pl.BlockSpec((None, None, groups, hd), q_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qg, k_pool, v_pool]
    if quantized:
        # per-slot scale blocks ride the same page-indexed DMA pattern;
        # the block spans every head's row (last two dims = the array's)
        def sc_map(b, h, j, bt_ref, len_ref):
            return (bt_ref[b * pages_per_seq + j], _i32(0), _i32(0))

        sc_spec = pl.BlockSpec((None, nkv, page_size), sc_map)
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, seq_lens
        grid=(B, nkv, pages_per_seq),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((groups, hd), jnp.float32),
            pltpu.VMEM((groups, LANES), jnp.float32),
            pltpu.VMEM((groups, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale=scale,
                          page_size=page_size, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, groups, hd), q.dtype),
        interpret=_interpret(),
        name="paged_attention",
    )(bt, sl, *operands)
    return out.reshape(B, nh, hd)


# ───────────────────────── public op ─────────────────────────


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens,
                    scale: float = None, use_kernel: bool = None,
                    k_scale=None, v_scale=None):
    """Ragged paged-attention decode: one query token per sequence over its
    page list. ``use_kernel=None`` picks the Pallas kernel on TPU backends
    (or under PADDLE_TPU_PALLAS_INTERPRET=1) and the jnp gather fallback
    elsewhere — both compute the identical masked-softmax math, so the
    serving engine's numerics don't depend on the backend.

    ``k_scale``/``v_scale`` (pass both or neither; f32
    ``[num_pages, nkv, page_size]``) switch the pools to int8 pages with
    per-slot dequant applied inside the reduction on BOTH backends."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if use_kernel is None:
        use_kernel = _interpret() or jax.default_backend() == "tpu"
    if use_kernel:
        return _paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                       seq_lens, scale,
                                       k_scale=k_scale, v_scale=v_scale)
    return ref_paged_attention(q, k_pool, v_pool, block_tables, seq_lens,
                               scale, k_scale=k_scale, v_scale=v_scale)


def ragged_paged_attention(q, k_pool, v_pool, row_block_tables, row_lens,
                           scale: float = None, use_kernel: bool = None,
                           k_scale=None, v_scale=None):
    """Mixed query-length paged attention over a FLATTENED token grid
    (module docstring, "Ragged form"): ``q`` is ``[T, nh, hd]`` — one
    row per query token across every slot this step, decode tokens and
    prompt-chunk tokens alike. ``row_block_tables`` ``[T, pages]``
    repeats a slot's block table for each of its rows; ``row_lens``
    ``[T]`` is each row's absolute position + 1 (keys at or past the
    row's own position are masked, which is what makes an in-chunk
    token causal over its chunk-mates' freshly scattered KV).

    Contract: the caller has ALREADY scattered this step's KV for every
    row into the pool (the unified step writes first, attends second —
    the decode step's own idiom, generalized). Each row then reduces
    over its named pages exactly like a decode query, so the kernel grid
    (``(T, kv_heads, pages)``, scalar-prefetched tables, online-softmax
    scratch) serves the ragged batch unchanged — per-row early-out over
    ``row_lens`` is what keeps a 1-token decode row from paying a long
    prompt's page walk."""
    return paged_attention(q, k_pool, v_pool, row_block_tables, row_lens,
                           scale=scale, use_kernel=use_kernel,
                           k_scale=k_scale, v_scale=v_scale)
