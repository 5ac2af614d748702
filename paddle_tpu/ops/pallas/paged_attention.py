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

Kernel shape: grid ``(B, pages_per_seq / P)`` — one grid step holds one
row, EVERY kv head and a block of ``P`` pages, the block axis innermost
carrying the online-softmax state of all heads in VMEM scratch. In the
pool's layout a whole page, all heads, is one contiguous run (64 KB at
GPT-3 1.3B's 16 × 16 × 128 bf16), so a page is one DMA: the block table and
seq lens ride in as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``), the pools stay in HBM, and the kernel
copies exactly the pages each sequence names (``pltpu.make_async_copy``)
into one of two VMEM slots, the next live block's copies in flight while
this block computes — the "ragged" part: no dense [B, max_len] gather ever
materializes, and a block past a row's last live page starts no copy.
``P`` is what a fixed VMEM budget for the two slots buys at the pool's
shapes and dtype (``_pages_per_block``: 8 pages at GPT-3 1.3B, 16 at the
Llama trunk's 8 kv heads or at int8 pages); a table that is no multiple of
``P`` is padded with the null page. Scores and PV are products batched over
the head axis, operands in the pool's dtype, accumulation and softmax in
float32.

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
# VMEM for the kernel's double buffer of K and V page blocks; what it buys
# of whole pages is a grid step's block (_pages_per_block)
KV_BLOCK_VMEM_BYTES = 2 << 20


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


def _pages_per_block(pages_per_seq: int, nkv: int, page_size: int, hd: int,
                     itemsize: int) -> int:
    """Pages one grid step holds: what KV_BLOCK_VMEM_BYTES buys of whole
    pages (every kv head) for K and V, two slots each — rounded down to a
    power of two so the block's key axis stays lane-aligned, and never
    wider than the table. int8 pages' scale rows (4/hd of the bytes
    again) ride outside the budget."""
    page_bytes = nkv * page_size * hd * itemsize
    fit = max(1, KV_BLOCK_VMEM_BYTES // (4 * page_bytes))
    return min(1 << (fit.bit_length() - 1), pages_per_seq)


def _paged_attn_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                       scale: float, page_size: int, pages_per_block: int,
                       quantized: bool = False):
    """One (row b, page block j) step of online-softmax decode over EVERY
    kv head: the block's ``pages_per_block`` pages — whole pages, all heads,
    one contiguous run of the pool each — arrive by ``make_async_copy`` in
    one of two VMEM slots, the next live block's copies started before this
    block's compute (across rows too, so only the call's first block waits
    unhidden).

    bt_ref/len_ref are the scalar-prefetched (flattened, block-padded) table
    and row lens. q block is every head group [nkv, groups, hd]; scratch
    carries (acc, m, l) for all heads across the block axis (innermost,
    'arbitrary') and the slot in use across the whole grid. A row's block 0
    is always live (a length-0 row masks all of it), so the chain of
    prefetches never has to search for the next row that has one.

    ``quantized`` (a Python-time flag) opens ``rest`` with the block's scale
    pages, K's then V's ([nkv, page] each: too narrow for a hand-written
    copy, so they come as blocks), and applies the dequant to the SCORE and
    PROBABILITY columns instead of the k/v rows: a slot's scale is one
    number per key, so ``(q·k_int)·ks == q·(k_int·ks)`` — the same sums with
    ``groups`` instead of ``hd`` multiplies per key.
    """
    n_scale_refs = 2 * pages_per_block if quantized else 0
    ks_refs = rest[:pages_per_block]
    vs_refs = rest[pages_per_block:n_scale_refs]
    (o_ref, k_buf, v_buf, sems, slot_ref,
     acc_ref, m_ref, l_ref) = rest[n_scale_refs:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    nrows = pl.num_programs(0)
    nblocks = pl.num_programs(1)
    block_keys = pages_per_block * page_size

    neg_inf = jnp.float32(NEG_INF)

    def copies(hbm, buf, kv, row, blk, slot):
        """The copies of block ``blk`` of ``row`` from K's pool (``kv`` 0) or
        V's (1) into ``slot``, as started and as waited for."""
        base = (row * nblocks + blk) * pages_per_block
        return [pltpu.make_async_copy(hbm.at[bt_ref[base + p]],
                                      buf.at[slot, _i32(p)],
                                      sems.at[slot, _i32(kv)])
                for p in range(pages_per_block)]

    def start(row, blk, slot):
        # K's pages first: the scores want them first
        for cp in (copies(k_hbm, k_buf, 0, row, blk, slot)
                   + copies(v_hbm, v_buf, 1, row, blk, slot)):
            cp.start()

    def block(page):
        # the block's pages side by side along the key axis
        return jnp.concatenate(
            [page(p) for p in range(pages_per_block)], axis=1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])
        m_ref[...] = jnp.full_like(m_ref[...], neg_inf)
        l_ref[...] = jnp.zeros_like(l_ref[...])

    @pl.when((b == 0) & (j == 0))
    def _first_block():
        slot_ref[0] = _i32(0)
        start(b, j, _i32(0))

    seq_len = len_ref[b]
    # ragged early-out: a block past the row's last live page does nothing
    # and starts no copy (its table entries are the null page)
    @pl.when((j == 0) | (j * block_keys < seq_len))
    def _body():
        slot = slot_ref[0]
        slot_ref[0] = 1 - slot
        same_row = (j + 1 < nblocks) & ((j + 1) * block_keys < seq_len)
        next_row = jnp.where(same_row, b, b + 1)

        @pl.when(next_row < nrows)
        def _prefetch():
            start(next_row, jnp.where(same_row, j + 1, _i32(0)), 1 - slot)

        q = q_ref[...]  # [nkv, groups, hd]
        for cp in copies(k_hbm, k_buf, 0, b, j, slot):
            cp.wait()
        k = block(lambda p: k_buf[slot, _i32(p)])  # [nkv, block_keys, hd]
        if quantized:  # int8 codes are exact in bf16 and f32 alike
            k = k.astype(jnp.float32).astype(q.dtype)
        # the package default ("highest") asks Mosaic for an fp32 contract,
        # which it refuses on bf16 operands; f32 operands keep it
        prec = (jax.lax.Precision.DEFAULT if q.dtype == jnp.bfloat16
                else None)
        s = jax.lax.dot_general(  # [nkv, groups, block_keys]
            q, k, (((2,), (2,)), ((0,), (0,))), precision=prec,
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        if quantized:
            s = s * block(lambda p: ks_refs[p][...])[:, None, :]
        # mask the tail of the row's last live page, and the pages after it
        pos = j * block_keys + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        mask = pos < seq_len
        s = jnp.where(mask, s, neg_inf)

        m_prev = m_ref[...]  # [nkv, groups, LANES] replicated
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :, :1])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=2, keepdims=True), l_prev.shape)
        if quantized:
            p = p * block(lambda p: vs_refs[p][...])[:, None, :]
        for cp in copies(v_hbm, v_buf, 1, b, j, slot):
            cp.wait()
        v = block(lambda p: v_buf[slot, _i32(p)])
        if quantized:
            v = v.astype(jnp.float32).astype(q.dtype)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            precision=prec, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :, :1] + pv
        m_ref[...] = m_new

    @pl.when(j == nblocks - 1)
    def _finish():
        l_fin = jnp.maximum(l_ref[...], jnp.float32(1e-30))
        o_ref[...] = (acc_ref[...] / l_fin[:, :, :1]).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pool, v_pool, block_tables, seq_lens,
                            scale: float, k_scale=None, v_scale=None):
    B, nh, hd = q.shape
    _, nkv, page_size, _ = k_pool.shape
    groups = nh // nkv
    pages_per_seq = block_tables.shape[1]
    quantized = k_scale is not None
    ppb = _pages_per_block(pages_per_seq, nkv, page_size, hd,
                           k_pool.dtype.itemsize)
    nblocks = pl.cdiv(pages_per_seq, ppb)
    width = nblocks * ppb
    smem_bytes = 4 * B * (width + 1)
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

    # flat 1-D table: a 2-D SMEM array pads its minor dim to 128 words. A
    # last block that overhangs the table names the null page, which the
    # row's length masks like any page past its end
    bt = jnp.pad(block_tables.astype(jnp.int32),
                 ((0, 0), (0, width - pages_per_seq))).reshape(-1)
    sl = seq_lens.astype(jnp.int32)

    def q_map(b, j, bt_ref, len_ref):
        return (b, _i32(0), _i32(0), _i32(0))

    q_spec = pl.BlockSpec((None, nkv, groups, hd), q_map)
    # the pools stay where they are; the kernel copies the pages it names.
    # A slot holds a block as the pool does, page by page: each copy's two
    # ends have one shape, [nkv, page_size, hd], at any page_size and dtype
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, ppb, nkv, page_size, hd), k_pool.dtype)
    in_specs = [q_spec, pool_spec, pool_spec]
    operands = [qg, k_pool, v_pool]
    if quantized:
        # a page's scales [nkv, page_size] are narrower than a lane tile,
        # which a hand-written copy cannot slice: they come as blocks whose
        # last two dims ARE the array's, one per page of the block
        def scale_map(p):
            def index_map(b, j, bt_ref, len_ref):
                # a block past the row's last live one names that one's
                # pages again: an unchanged block is not copied again
                live = jax.lax.div(jnp.maximum(len_ref[b] - 1, 0),
                                   _i32(ppb * page_size))
                return (bt_ref[b * width + jnp.minimum(j, live) * ppb + p],
                        _i32(0), _i32(0))
            return index_map

        scale_specs = [pl.BlockSpec((None, nkv, page_size), scale_map(p))
                       for p in range(ppb)]
        in_specs += 2 * scale_specs
        operands += ppb * [k_scale.astype(jnp.float32)]
        operands += ppb * [v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, seq_lens
        grid=(B, nblocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            kv_buf, kv_buf,
            pltpu.SemaphoreType.DMA((2, 2)),  # [slot, K | V]
            pltpu.SMEM((1,), jnp.int32),      # the slot in use
            pltpu.VMEM((nkv, groups, hd), jnp.float32),
            pltpu.VMEM((nkv, groups, LANES), jnp.float32),
            pltpu.VMEM((nkv, groups, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale=scale,
                          page_size=page_size, pages_per_block=ppb,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, groups, hd), q.dtype),
        # a block's copies are started by the block before it, in grid order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
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
    (``(T, page blocks)``, scalar-prefetched tables, online-softmax
    scratch) serves the ragged batch unchanged — per-row early-out over
    ``row_lens`` is what keeps a 1-token decode row from paying a long
    prompt's page walk."""
    return paged_attention(q, k_pool, v_pool, row_block_tables, row_lens,
                           scale=scale, use_kernel=use_kernel,
                           k_scale=k_scale, v_scale=v_scale)
