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

Kernel shape: ONE invocation walks the whole batch — a loop over query
blocks and, in each, a loop over the block's live blocks of ``P`` pages,
every kv head at once, the online-softmax state in VMEM scratch. In the
pool's layout a whole page, all heads, is one contiguous run (64 KB at
GPT-3 1.3B's 16 × 16 × 128 bf16), so a page is one DMA: the block table and
row lens ride in as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``), the pools stay in HBM, and the kernel
copies exactly the pages each block names (``pltpu.make_async_copy``) into
one of two VMEM slots, the next live page block's copies in flight while
this one computes — the "ragged" part: no dense [B, max_len] gather ever
materializes, a page block past a block's last live page is never visited,
and a row that names no page (an engine bucket's padding) starts no copy.
``P`` is what a fixed VMEM budget for the two slots buys at the pool's
shapes and dtype (``_pages_per_block``: 8 pages at GPT-3 1.3B, 16 at the
Llama trunk's 8 kv heads or at int8 pages); a table that is no multiple of
``P`` is padded with the null page. Scores and PV are products batched over
the head axis, operands in the pool's dtype, accumulation and softmax in
float32.

**Query blocks** (``_query_blocks``): a block is up to ``Q`` consecutive
rows that name the same block table at consecutive positions — one
sequence's prompt chunk, or a decode row with its draft rows. Identical
tables mean identical keys, so the grouping is exact for any caller, and
two sequences that share prefix pages have different tables and never
merge. A block's pages are copied once per page block and multiplied
against all of its rows, each masked at its own length; a decode row alone
is a block of one. ``Q`` is what a fixed VMEM budget for the block's
float32 working set buys at the pool's shapes (``query_block_rows``: 24
rows at GPT-3 1.3B, 8 at the Llama trunk's 32 heads over 8), and
``walked_keys`` is the count of keys that walk reads, which the serving
engine's ``kv_walked`` counter uses.

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

__all__ = ["paged_attention", "query_block_rows", "ragged_paged_attention",
           "ref_paged_attention", "walked_keys"]

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
# VMEM for a query block's float32 working set; what it buys of rows is the
# block's bound (query_block_rows)
QUERY_BLOCK_VMEM_BYTES = 1 << 20
# the compiler's VMEM limit beside the resident q and output
VMEM_LIMIT_BYTES = 16 << 20
SUBLANES = 8


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
    """Pages one page block holds: what KV_BLOCK_VMEM_BYTES buys of whole
    pages (every kv head) for K and V, two slots each — rounded down to a
    power of two so the block's key axis stays lane-aligned, and never
    wider than the table. int8 pages' scale rows (4/hd of the bytes
    again) ride outside the budget."""
    page_bytes = nkv * page_size * hd * itemsize
    fit = max(1, KV_BLOCK_VMEM_BYTES // (4 * page_bytes))
    return min(1 << (fit.bit_length() - 1), pages_per_seq)


def query_block_rows(pages_per_seq: int, nkv: int, page_size: int, hd: int,
                     itemsize: int, num_heads: int) -> int:
    """Rows one query block holds at most: what QUERY_BLOCK_VMEM_BYTES buys
    of a row's float32 working set — scores and probabilities over a page
    block's keys, the accumulator, the running max and sum, for each of
    its ``num_heads`` query heads — rounded down to a sublane multiple."""
    keys = _pages_per_block(pages_per_seq, nkv, page_size, hd,
                            itemsize) * page_size
    row_bytes = 4 * num_heads * (2 * keys + hd + 2 * LANES)
    fit = QUERY_BLOCK_VMEM_BYTES // row_bytes
    return max(SUBLANES, fit // SUBLANES * SUBLANES)


def walked_keys(first_len: int, n_rows: int, q_rows: int) -> int:
    """Keys the kernel walks for ``n_rows`` consecutive rows of one
    sequence (one block table, consecutive positions) whose first row
    attends ``first_len`` keys: each query block of up to ``q_rows`` of
    them walks its last row's keys once."""
    return sum(first_len + min(b + q_rows, n_rows) - 1
               for b in range(0, n_rows, q_rows))


def _query_blocks(block_tables, seq_lens, q_rows: int):
    """Row lens marked with the query blocks (module docstring, "Query
    blocks"): a row that continues the block of the row before it — the
    same table, its length one more, and the block not yet ``q_rows``
    long — carries its length negated; a row that names no key (its table
    starts at the null page, or its length is 0) carries 0 and is a block
    of its own that walks nothing."""
    empty = (block_tables[:, 0] == 0) | (seq_lens <= 0)
    same = (jnp.all(block_tables[1:] == block_tables[:-1], axis=1)
            & (seq_lens[1:] == seq_lens[:-1] + 1)
            & ~empty[1:] & ~empty[:-1])
    cont = jnp.concatenate([jnp.zeros((1,), bool), same])
    rows = jnp.arange(seq_lens.shape[0], dtype=jnp.int32)
    run_start = jax.lax.cummax(jnp.where(cont, 0, rows))
    cont &= (rows - run_start) % q_rows != 0
    return jnp.where(empty, 0, jnp.where(cont, -seq_lens, seq_lens))


def _lane_padded(scales):
    """``scales`` ``[pages, nkv, n]`` as float32 with ``n`` padded to a
    multiple of LANES (no copy where it is one already)."""
    pad = -scales.shape[-1] % LANES
    return jnp.pad(scales.astype(jnp.float32), ((0, 0), (0, 0), (0, pad)))


def _paged_attn_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                       scale: float, page_size: int, pages_per_block: int,
                       nblocks: int, q_rows: int, dtype,
                       quantized: bool = False, interpret: bool = False):
    """The whole ragged batch in one invocation: a loop over query blocks,
    and in each a loop over its live page blocks, the online softmax of
    every kv head and every row of the block carried in VMEM scratch.

    A page block's ``pages_per_block`` pages — whole pages, all heads, one
    contiguous run of the pool each — arrive by ``make_async_copy`` in one
    of two VMEM slots; the next live page block's copies, of this query
    block or of the next, start before this one's compute, so only the
    call's first copies wait unhidden. A query block's rows are one window
    of ``q_rows`` rows of the resident q, ``[nkv, groups, rows, hd]``, so a
    block of many rows and a block of one run the same products: scores
    ``[nkv, groups * q_rows, block_keys]``, each row masked at its own
    length and the window's rows outside the block at zero keys. Only the
    block's rows are stored.

    bt_ref/len_ref are the scalar-prefetched (flattened, block-padded) table
    and the marked row lens (``_query_blocks``). ``quantized`` (a
    Python-time flag) opens ``rest`` with K's and V's scale pools
    ``[pages, nkv, lanes]`` (``_lane_padded``), copied page by page beside
    the codes, and applies the dequant to the SCORE and PROBABILITY
    columns instead of the k/v rows: a slot's scale is one number per key, so ``(q·k_int)·ks ==
    q·(k_int·ks)`` — the same sums with ``groups`` instead of ``hd``
    multiplies per key.
    """
    if quantized:
        ks_hbm, vs_hbm, *rest = rest
    (o_ref, k_buf, v_buf, *rest) = rest
    if quantized:
        ks_buf, vs_buf, *rest = rest
    sems, acc_ref, m_ref, l_ref = rest
    nkv, groups, n_rows, hd = q_ref.shape
    block_keys = pages_per_block * page_size
    width = nblocks * pages_per_block
    M = groups * q_rows
    neg_inf = jnp.float32(NEG_INF)
    # the package default ("highest") asks Mosaic for an fp32 contract,
    # which it refuses on bf16 operands; f32 operands keep it
    prec = jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None

    pools = [(k_hbm, k_buf), (v_hbm, v_buf)]
    if quantized:
        pools += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]

    def copies(kind, row, blk, slot):
        """The copies of page block ``blk`` of the table of ``row`` from
        pool ``kind`` (K, V, then their scales) into ``slot``."""
        hbm, buf = pools[kind]
        base = row * width + blk * pages_per_block
        return [pltpu.make_async_copy(hbm.at[bt_ref[base + p]],
                                      buf.at[slot, _i32(p)],
                                      sems.at[slot, _i32(kind)])
                for p in range(pages_per_block)]

    def start(row, blk, slot):
        # K's pages first: the scores want them first
        for kind in (0, 2, 1, 3) if quantized else (0, 1):
            for cp in copies(kind, row, blk, slot):
                cp.start()

    def wait(kind, row, blk, slot):
        for cp in copies(kind, row, blk, slot):
            cp.wait()

    def block(page):
        # the block's pages side by side along the key axis
        return jnp.concatenate(
            [page(p) for p in range(pages_per_block)], axis=1)

    def window(w):
        # the interpreter slices at its literals' index dtype (i64 under
        # x64), Mosaic at i32
        if interpret:
            w = w.astype(jnp.int_)
        return slice(None), slice(None), pl.ds(w, q_rows)

    def marked(r):
        return len_ref[jnp.minimum(r, _i32(n_rows - 1))]

    def next_block(r):
        """The first row at or after ``r`` that starts a block with keys."""
        return jax.lax.while_loop(
            lambda r: (r < n_rows) & (marked(r) == 0), lambda r: r + 1, r)

    def query_block(carry):
        s, slot = carry
        # the block's rows: s and the rows after it that continue it
        n = jax.lax.while_loop(
            lambda c: (c < q_rows) & (s + c < n_rows) & (marked(s + c) < 0),
            lambda c: c + 1, _i32(1))
        first = len_ref[s]
        live = jnp.minimum(pl.cdiv(first + n - 1, _i32(block_keys)),
                           _i32(nblocks))
        nxt = next_block(s + n)
        # the window of q_rows rows that holds the block, inside the grid
        w = jnp.minimum(s, _i32(n_rows - q_rows))
        row = w + jax.lax.broadcasted_iota(
            jnp.int32, (nkv, groups, q_rows, block_keys), 2)
        in_block = (row >= s) & (row < s + n)
        # row r attends keys [0, first + r - s); rows outside none
        limit = jnp.where(in_block, first + row - s, _i32(0)).reshape(
            nkv, M, block_keys)
        q = q_ref[window(w)].reshape(nkv, M, hd).astype(dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref[...])
        m_ref[...] = jnp.full_like(m_ref[...], neg_inf)
        l_ref[...] = jnp.zeros_like(l_ref[...])

        def page_block(j, slot):
            same = j + 1 < live

            @pl.when(same)
            def _prefetch_here():
                start(s, j + 1, 1 - slot)

            @pl.when(~same & (nxt < n_rows))
            def _prefetch_next():
                start(nxt, _i32(0), 1 - slot)

            wait(0, s, j, slot)
            k = block(lambda p: k_buf[slot, _i32(p)])  # [nkv, keys, hd]
            if quantized:  # int8 codes are exact in bf16 and f32 alike
                k = k.astype(jnp.float32).astype(dtype)
            sc = jax.lax.dot_general(  # [nkv, M, keys]
                q, k, (((2,), (2,)), ((0,), (0,))), precision=prec,
                preferred_element_type=jnp.float32) * jnp.float32(scale)
            if quantized:
                wait(2, s, j, slot)
                sc = sc * block(lambda p: ks_buf[slot, _i32(p)][
                    :, :page_size])[:, None, :]
            # mask each row past its own length, and the pages after it
            pos = j * block_keys + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 2)
            mask = pos < limit
            sc = jnp.where(mask, sc, neg_inf)

            m_prev = m_ref[...]  # [nkv, M, LANES] replicated
            l_prev = l_ref[...]
            m_cur = jnp.max(sc, axis=2, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new[:, :, :1])
            p = jnp.where(mask, p, 0.0)
            l_ref[...] = alpha * l_prev + jnp.broadcast_to(
                jnp.sum(p, axis=2, keepdims=True), l_prev.shape)
            if quantized:
                wait(3, s, j, slot)
                p = p * block(lambda p: vs_buf[slot, _i32(p)][
                    :, :page_size])[:, None, :]
            wait(1, s, j, slot)
            v = block(lambda p: v_buf[slot, _i32(p)])
            if quantized:
                v = v.astype(jnp.float32).astype(dtype)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                precision=prec, preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * alpha[:, :, :1] + pv
            m_ref[...] = m_new
            return 1 - slot

        slot = jax.lax.fori_loop(_i32(0), live, page_block, slot)
        l_fin = jnp.maximum(l_ref[...], jnp.float32(1e-30))
        out = (acc_ref[...] / l_fin[:, :, :1]).reshape(
            nkv, groups, q_rows, hd)
        out_row = w + jax.lax.broadcasted_iota(jnp.int32, out.shape, 2)
        pltpu.store(o_ref.at[window(w)], out,
                    mask=(out_row >= s) & (out_row < s + n))
        return nxt, slot

    # a row with no keys reads zeros; every other row is stored by its block
    o_ref[...] = jnp.zeros_like(o_ref[...])
    s0 = next_block(_i32(0))

    @pl.when(s0 < n_rows)
    def _first_block():
        start(s0, _i32(0), _i32(0))

    jax.lax.while_loop(lambda c: c[0] < n_rows, query_block, (s0, _i32(0)))


def _paged_attention_pallas(q, k_pool, v_pool, block_tables, seq_lens,
                            scale: float, k_scale=None, v_scale=None):
    B, nh, hd = q.shape
    _, nkv, page_size, _ = k_pool.shape
    pages_per_seq = block_tables.shape[1]
    itemsize = k_pool.dtype.itemsize
    ppb = _pages_per_block(pages_per_seq, nkv, page_size, hd, itemsize)
    q_rows = min(query_block_rows(pages_per_seq, nkv, page_size, hd,
                                  itemsize, nh), B)
    width = pl.cdiv(pages_per_seq, ppb) * ppb
    smem_bytes = 4 * B * (width + 1)
    if smem_bytes > SMEM_PREFETCH_LIMIT_BYTES:
        raise ValueError(
            f"paged attention: the scalar-prefetched block table "
            f"[{B} rows, {pages_per_seq} pages] + row lens need "
            f"{smem_bytes} bytes of SMEM, over the "
            f"{SMEM_PREFETCH_LIMIT_BYTES}-byte limit this kernel compiles "
            f"under — lower the engine's token_budget (rows) or "
            f"max_model_len / page_size (pages)")
    return _paged_call(q, k_pool, v_pool, block_tables, seq_lens, k_scale,
                       v_scale, scale=scale, ppb=ppb, q_rows=q_rows,
                       interpret=_interpret())


# one trace and one lowering a shape: a step's layers call the kernel with
# the same shapes, and each call would otherwise trace and lower it anew
@functools.partial(jax.jit,
                   static_argnames=("scale", "ppb", "q_rows", "interpret"))
def _paged_call(q, k_pool, v_pool, block_tables, seq_lens, k_scale, v_scale,
                *, scale: float, ppb: int, q_rows: int, interpret: bool):
    B, nh, hd = q.shape
    _, nkv, page_size, _ = k_pool.shape
    groups = nh // nkv
    pages_per_seq = block_tables.shape[1]
    quantized = k_scale is not None
    nblocks = pl.cdiv(pages_per_seq, ppb)
    width = nblocks * ppb
    # q resident in VMEM with each kv head's query groups over the rows:
    # a query block is one window of rows at any offset, which Mosaic
    # slices dynamically in float32 only
    qg = jnp.transpose(q.reshape(B, nkv, groups, hd),
                       (1, 2, 0, 3)).astype(jnp.float32)

    # flat 1-D table: a 2-D SMEM array pads its minor dim to 128 words. A
    # last block that overhangs the table names the null page, which the
    # row's length masks like any page past its end
    block_tables = block_tables.astype(jnp.int32)
    bt = jnp.pad(block_tables,
                 ((0, 0), (0, width - pages_per_seq))).reshape(-1)
    sl = _query_blocks(block_tables, seq_lens.astype(jnp.int32), q_rows)

    # whole in VMEM for the one invocation; the index map says so in i32
    resident = pl.BlockSpec(None, lambda i, bt_ref, len_ref: (_i32(0),) * 4,
                            memory_space=pltpu.VMEM)
    # the pools stay where they are; the kernel copies the pages it names.
    # A slot holds a block as the pool does, page by page: each copy's two
    # ends have one shape, [nkv, page_size, hd], at any page_size and dtype
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, ppb, nkv, page_size, hd), k_pool.dtype)
    in_specs = [resident, pool_spec, pool_spec]
    operands = [qg, k_pool, v_pool]
    scratch = [kv_buf, kv_buf]
    if quantized:
        in_specs += [pool_spec, pool_spec]
        # a copy moves whole lane tiles: a page's scales [nkv, page_size]
        # come as rows of a multiple of 128 lanes, the page's first
        # page_size of them its own (the pool keeps them so)
        operands += [_lane_padded(s) for s in (k_scale, v_scale)]
        scratch += 2 * [pltpu.VMEM((2, ppb) + operands[-1].shape[1:],
                                   jnp.float32)]
    M = groups * q_rows
    scratch += [
        pltpu.SemaphoreType.DMA((2, 4 if quantized else 2)),  # [slot, pool]
        pltpu.VMEM((nkv, M, hd), jnp.float32),
        pltpu.VMEM((nkv, M, LANES), jnp.float32),
        pltpu.VMEM((nkv, M, LANES), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, marked row lens
        grid=(1,),
        in_specs=in_specs,
        out_specs=resident,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale=scale,
                          page_size=page_size, pages_per_block=ppb,
                          nblocks=nblocks, q_rows=q_rows, dtype=q.dtype,
                          quantized=quantized, interpret=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES + 2 * qg.size * 4),
        interpret=interpret,
        name="paged_attention",
    )(bt, sl, *operands)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(B, nh, hd).astype(q.dtype)


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
    per-slot dequant applied inside the reduction on BOTH backends.

    A row whose table starts at the null page names no keys: the kernel
    reads it as zeros where the fallback attends the null page's slots.
    The serving step's padding rows are such rows, and nothing reads
    their outputs."""
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
    the decode step's own idiom, generalized). The kernel then gathers a
    slot's consecutive rows into query blocks (module docstring, "Query
    blocks"): a chunk's keys are read once per block, not once per row,
    and each row is masked at its own length, so a 1-token decode row
    never pays a long prompt's page walk."""
    return paged_attention(q, k_pool, v_pool, row_block_tables, row_lens,
                           scale=scale, use_kernel=use_kernel,
                           k_scale=k_scale, v_scale=v_scale)
