"""paddle_tpu.serving: continuous-batching engine over the paged KV cache.

Acceptance gates (ISSUE 1): paged-fallback decode is TOKEN-IDENTICAL to
dense ``generate()`` on mixed-length prompts, with eos mid-batch and a
request admitted after step 0; retired sequences' pages are reused (pool
high-water mark < the sum of per-request dense caches on a staggered
workload); and the decode step compiles a BOUNDED number of times while
the live batch churns. The pallas kernel itself runs in interpret mode
(tests/test_flash_attention.py pattern); everything else drives the
pure-jnp fallback — the same code path a CPU build serves with.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM, gpt_tiny,
                               llama_tiny)
from paddle_tpu.serving import (CompletionAPI, FCFSScheduler,
                                PagedKVCachePool, Request, Router,
                                ServingEngine, page_bytes,
                                pages_for_hbm_budget)

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


def _dense_gen(model, prompt, n, eos=None):
    """Per-request dense reference: generated ids only."""
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None, :]),
                         max_new_tokens=n, temperature=0.0,
                         eos_token_id=eos)
    return np.asarray(out.numpy())[0, len(prompt):]


_PROMPTS = [np.random.RandomState(7).randint(0, 128, (n,))
            for n in (5, 9, 3)]


# ───────────────────────── kernel (interpret mode) ─────────────────────────


class TestPagedAttentionKernel:
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["pages-as-q", "int8"])
    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                           ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("nh,nkv", [(4, 2), (4, 4)], ids=["gqa", "mha"])
    def test_kernel_matches_fallback(self, monkeypatch, nh, nkv, dtype,
                                     tol, quantized):
        """The real pallas kernel (scalar-prefetched block tables, online
        softmax over the ragged page list) against the jnp gather
        fallback, on CPU via interpret mode — GQA and MHA (a one-row
        query group), f32 and bf16 queries, plain and int8 pages with
        the in-kernel dequant."""
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import paged_attention as pa
        from paddle_tpu.quantization.observers import quantize_kv

        rng = np.random.default_rng(0)
        B, hd, page, pages, width = 3, 64, 8, 12, 4
        q = jnp.asarray(rng.standard_normal((B, nh, hd)), dtype)
        kp = jnp.asarray(rng.standard_normal((pages, nkv, page, hd)), dtype)
        vp = jnp.asarray(rng.standard_normal((pages, nkv, page, hd)), dtype)
        bt = jnp.asarray(rng.integers(1, pages, (B, width)), jnp.int32)
        sl = jnp.asarray([1, 17, 32], jnp.int32)  # ragged, incl. 1 token
        kw = {}
        if quantized:
            kp, ks = quantize_kv(kp)
            vp, vs = quantize_kv(vp)
            kw = dict(k_scale=ks, v_scale=vs)
        ref = pa.ref_paged_attention(q, kp, vp, bt, sl, **kw)
        out = pa.paged_attention(q, kp, vp, bt, sl, use_kernel=True, **kw)
        assert out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)

    # what the (rows, page blocks) grid can get wrong. Each case is the
    # rows' lengths over a table `width` pages wide (page 8), with the
    # block budget cut so that a grid step holds `ppb` pages
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["pages-as-q", "int8"])
    @pytest.mark.parametrize("nh,nkv", [(4, 2), (4, 4)], ids=["gqa", "mha"])
    @pytest.mark.parametrize("ppb,width,lens", [
        (4, 7, [56, 29, 3]),      # table wider than a block, not a multiple
        (2, 7, [9, 56, 17]),      # three and a half blocks
        (2, 8, [8, 16, 32, 64]),  # ends on a page and on a block boundary
        (4, 8, [1, 64]),          # length 1 beside a row that fills its table
        (1, 5, [40, 1, 24]),      # one page a block: the page-at-a-time walk
        (8, 3, [24, 10]),         # the budget buys more than the table holds
    ], ids=["ragged-blocks", "half-block", "boundaries", "one-and-full",
            "page-blocks", "one-block"])
    def test_kernel_matches_fallback_over_page_blocks(
            self, monkeypatch, ppb, width, lens, nh, nkv, quantized):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import paged_attention as pa
        from paddle_tpu.quantization.observers import quantize_kv

        rng = np.random.default_rng(3)
        B, hd, page, pages = len(lens), 64, 8, 24
        kp = jnp.asarray(rng.standard_normal((pages, nkv, page, hd)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((pages, nkv, page, hd)),
                         jnp.float32)
        kw = {}
        if quantized:
            kp, ks = quantize_kv(kp)
            vp, vs = quantize_kv(vp)
            kw = dict(k_scale=ks, v_scale=vs)
        # the budget of `ppb` pages: two slots each for K and for V
        monkeypatch.setattr(pa, "KV_BLOCK_VMEM_BYTES",
                            4 * ppb * nkv * page * hd * kp.dtype.itemsize)
        assert pa._pages_per_block(width, nkv, page, hd,
                                   kp.dtype.itemsize) == min(ppb, width)
        q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
        # pages past a row's length are the null page, as the engine pads
        bt = rng.integers(1, pages, (B, width)).astype(np.int32)
        for r, n in enumerate(lens):
            bt[r, -(-n // page):] = 0
        bt, sl = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)
        ref = pa.ref_paged_attention(q, kp, vp, bt, sl, **kw)
        out = pa.paged_attention(q, kp, vp, bt, sl, use_kernel=True, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("shape,ppb", [
        ((128, 16, 16, 128, 2), 8),   # GPT-3 1.3B, bf16 pages: 64 KB each
        ((128, 8, 16, 128, 2), 16),   # the Llama trunk's 8 kv heads
        ((128, 16, 16, 128, 1), 16),  # int8 pages
        ((128, 32, 16, 128, 4), 2),   # f32 pages of 32 heads
        ((128, 64, 32, 256, 4), 1),   # a page over the budget: still one
        ((4, 16, 16, 128, 2), 4),     # never wider than the table
    ], ids=["gpt3-1.3b", "gqa-8", "int8", "f32-32", "huge-page", "narrow"])
    def test_pages_per_block_follows_the_shapes(self, shape, ppb):
        from paddle_tpu.ops.pallas import paged_attention as pa

        assert pa._pages_per_block(*shape) == ppb

    def test_block_table_over_smem_is_an_error_with_numbers(self):
        """The scalar-prefetched table must fit the chip's SMEM whole; a
        grid that cannot is refused at trace time with the sizes named,
        not left to crash the TPU compiler."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import paged_attention as pa

        T, pages = 4096, 128
        q = jax.ShapeDtypeStruct((T, 4, 64), jnp.bfloat16)
        pool = jax.ShapeDtypeStruct((16, 4, 16, 64), jnp.bfloat16)
        bt = jax.ShapeDtypeStruct((T, pages), jnp.int32)
        sl = jax.ShapeDtypeStruct((T,), jnp.int32)
        with pytest.raises(ValueError) as e:
            jax.eval_shape(
                lambda *a: pa.ragged_paged_attention(*a, use_kernel=True),
                q, pool, pool, bt, sl)
        msg = str(e.value)
        assert f"{T} rows, {pages} pages" in msg
        assert str(4 * T * (pages + 1)) in msg
        assert str(pa.SMEM_PREFETCH_LIMIT_BYTES) in msg

    # each case: the rows' groups as (table, rows, first position) — a
    # group is one slot's rows at consecutive positions under its table —
    # then bucket padding rows, and the budgets cut so that a page block
    # holds `ppb` pages and a query block at most 8 rows (`q8`)
    @pytest.mark.parametrize("groups,pad,ppb,q8,nh,quantized", [
        ([("a", 1, 12), ("b", 5, 7), ("c", 1, 0)], 0, None, False, 4, False),
        ([("a", 1, 12), ("b", 5, 7), ("c", 1, 0)], 0, 1, False, 4, False),
        ([("a", 1, 12), ("b", 5, 7), ("c", 1, 0)], 0, 2, False, 4, False),
        # a chunk of 19 rows: query blocks of 8, 8 and 3
        ([("a", 1, 30), ("b", 19, 4), ("c", 1, 2)], 0, 2, True, 4, False),
        # a decode row and its three draft rows, beside a lone decode row
        ([("a", 4, 20), ("b", 1, 9)], 0, 2, True, 4, False),
        # neighbouring slots that share their first pages, not their
        # tables, at positions that run on across the boundary
        ([("a", 4, 10), ("a+", 4, 14)], 0, 1, True, 4, False),
        # identical tables on adjacent rows at positions that do not run on
        ([("a", 1, 5), ("a", 1, 9), ("a", 1, 9), ("a", 2, 3)], 0, 2, True,
         4, False),
        # an engine bucket: real rows, then padding rows on the null page
        ([("a", 1, 12), ("b", 9, 7)], 6, 2, True, 4, False),
        ([("a", 1, 30), ("b", 19, 4), ("c", 1, 2)], 3, 2, True, 4, True),
        ([("a", 1, 30), ("b", 19, 4), ("c", 1, 2)], 3, 2, True, 8, False),
    ], ids=["one-block", "page-blocks", "two-pages", "chunk-over-q",
            "drafts", "shared-prefix", "not-consecutive", "padding",
            "int8", "gqa-4"])
    def test_ragged_flattened_rows_match_fallback(
            self, monkeypatch, groups, pad, ppb, q8, nh, quantized):
        """The unified-step contract (ISSUE 11): mixed per-slot query
        lengths ride as FLATTENED rows — a decode slot contributes one
        row, a chunk slot one row per token, each with its slot's block
        table repeated and consecutive positions. The kernel gathers a
        slot's rows into query blocks that walk its pages once, and
        matches the jnp fallback row for row (interpret mode); a padding
        row, whose table names no page, reads zeros."""
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import paged_attention as pa
        from paddle_tpu.quantization.observers import quantize_kv

        rng = np.random.default_rng(1)
        nkv, hd, page, pages, width = 2, 64, 8, 20, 4
        if ppb is not None:  # a chunk's rows name the same pages, block
            # after block, each masked at its own length
            monkeypatch.setattr(pa, "KV_BLOCK_VMEM_BYTES",
                                4 * ppb * nkv * page * hd * 4)
        if q8:
            monkeypatch.setattr(pa, "QUERY_BLOCK_VMEM_BYTES", 0)
            assert pa.query_block_rows(width, nkv, page, hd, 4, nh) == 8
        tables = {name: rng.integers(1, pages, width).astype(np.int32)
                  for name in ("a", "b", "c")}
        tables["a+"] = tables["a"].copy()
        tables["a+"][2:] = rng.integers(1, pages, width - 2)
        assert (tables["a+"] != tables["a"]).any()
        row_bt = np.concatenate(
            [np.repeat(tables[t][None], n, axis=0) for t, n, _ in groups]
            + [np.zeros((pad, width), np.int32)])
        row_lens = np.concatenate(
            [np.arange(p0, p0 + n) + 1 for _, n, p0 in groups]
            + [np.ones(pad, np.int64)]).astype(np.int32)
        T = len(row_lens)
        q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((pages, nkv, page, hd)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((pages, nkv, page, hd)),
                         jnp.float32)
        kw = {}
        if quantized:
            kp, ks = quantize_kv(kp)
            vp, vs = quantize_kv(vp)
            kw = dict(k_scale=ks, v_scale=vs)
        ref = pa.ref_paged_attention(q, kp, vp, jnp.asarray(row_bt),
                                     jnp.asarray(row_lens), **kw)
        out = pa.ragged_paged_attention(q, kp, vp, jnp.asarray(row_bt),
                                        jnp.asarray(row_lens),
                                        use_kernel=True, **kw)
        live = T - pad
        np.testing.assert_allclose(np.asarray(out)[:live],
                                   np.asarray(ref)[:live],
                                   atol=2e-5, rtol=2e-5)
        assert not np.asarray(out)[live:].any()

    @pytest.mark.parametrize("lens,tables,q_rows,marked", [
        # a run of 5 under one table, cut at 2 rows a block
        ([3, 4, 5, 6, 7], [1, 1, 1, 1, 1], 2, [3, -4, 5, -6, 7]),
        # another table, a position that does not run on, a repeat
        ([3, 4, 9, 10, 10], [1, 2, 2, 2, 2], 8, [3, 4, 9, -10, 10]),
        # padding rows name the null page: no block, nothing walked
        ([8, 9, 1, 1], [1, 1, 0, 0], 8, [8, -9, 0, 0]),
    ], ids=["cut-at-q", "breaks", "padding"])
    def test_query_blocks_mark_the_rows(self, lens, tables, q_rows, marked):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import paged_attention as pa

        bt = np.asarray(tables, np.int32)[:, None] * np.ones((1, 3),
                                                              np.int32)
        got = pa._query_blocks(jnp.asarray(bt), jnp.asarray(lens, jnp.int32),
                               q_rows)
        assert np.asarray(got).tolist() == marked

    @pytest.mark.parametrize("first,n,q_rows,walked", [
        (31, 1, 8, 31),                         # a decode row
        (21, 4, 8, 24),                         # with its three drafts
        (5, 19, 8, (5 + 7) + (5 + 15) + (5 + 18)),  # blocks of 8, 8, 3
        (100, 16, 8, 107 + 115),                # two whole blocks
    ], ids=["decode", "drafts", "chunk-over-q", "whole-blocks"])
    def test_walked_keys_is_one_walk_a_block(self, first, n, q_rows, walked):
        from paddle_tpu.ops.pallas import paged_attention as pa

        assert pa.walked_keys(first, n, q_rows) == walked

# ───────────────────────────── kv-cache pool ─────────────────────────────


class TestPagedKVCachePool:
    def _pool(self, pages=9):
        return PagedKVCachePool(num_layers=1, num_pages=pages, page_size=4,
                                n_kv_heads=2, head_dim=8)

    def test_alloc_free_reuse_and_null_page(self):
        pool = self._pool()
        t = pool.allocate("a", 6)  # 2 pages
        assert 0 not in t and len(t) == 2 and pool.used_pages == 2
        pool.allocate("b", 4)
        assert pool.used_pages == 3
        pool.free("a")
        assert pool.used_pages == 1
        t2 = pool.allocate("c", 8)
        assert set(t2) <= set(range(1, 9))  # freed pages recycled
        assert pool.peak_used == 3

    def test_lazy_extend_and_reservation_accounting(self):
        pool = self._pool(pages=5)  # 4 usable
        pool.allocate("a", 2, max_total_tokens=12)  # 1 page now, 3 reserved
        assert pool.used_pages == 1
        assert not pool.can_admit(8)  # 2 pages wanted, only 1 unreserved
        assert pool.can_admit(4)
        for _ in range(3):  # tokens 3, 4, 5 — position 4 opens page 2
            pool.append_token("a")
        assert pool.used_pages == 2

    def test_can_admit_charges_same_step_pending_pages(self):
        """Batch-mates admitted in one scheduler step reserve nothing in
        the pool until their prefill runs — can_admit must charge their
        pending pages or two big requests would jointly over-commit."""
        pool = self._pool(pages=6)  # 5 usable
        assert pool.can_admit(12)                     # 3 pages alone: fits
        assert not pool.can_admit(12, pending_pages=3)  # with a batch-mate

    def test_pool_exhaustion_raises(self):
        pool = self._pool(pages=3)
        pool.allocate("a", 8)
        with pytest.raises(RuntimeError):
            pool.allocate("b", 4)

    def test_fork_shares_everything_and_copies_on_divergent_append(self):
        """fork() shares EVERY page (full + partial tail) by refcount;
        nothing copies until a branch appends into the shared tail —
        then copy-on-write swaps in a private copy and the sibling's
        bytes are untouched."""
        import jax.numpy as jnp

        pool = self._pool()
        pool.allocate("src", 6)  # page0 full (4 tokens), page1 partial (2)
        k = jnp.arange(9 * 4 * 2 * 8, dtype=jnp.float32).reshape(9, 4, 2, 8)
        pool.set_arrays([k], [k + 1000.0])
        src_table = pool.block_table("src")
        dst_table = pool.fork("src", "dst")
        assert dst_table == src_table             # zero-copy fork
        assert pool.used_pages == 2               # no extra page yet
        src_tail_before = np.asarray(pool.k_pools[0]._value[src_table[1]])
        pool.extend("dst", 7)  # dst diverges: append into the shared tail
        dst_after = pool.block_table("dst")
        assert dst_after[0] == src_table[0]       # full page still shared
        assert dst_after[1] != src_table[1]       # tail CoW'd
        # the copy carries the shared bytes; the sibling's are untouched
        np.testing.assert_array_equal(
            np.asarray(pool.k_pools[0]._value[dst_after[1]]),
            src_tail_before)
        np.testing.assert_array_equal(
            np.asarray(pool.k_pools[0]._value[src_table[1]]),
            src_tail_before)
        pool.free("src")  # shared page must survive the src retirement
        assert pool.has_seq("dst")
        used_after = pool.used_pages
        assert used_after == 2  # shared full page + dst tail
        pool.free("dst")
        assert pool.used_pages == 0

    def test_sizing_math(self):
        # docs/SERVING.md worked example: 8 MiB/page, 10 GiB -> 1280 pages
        pb = page_bytes(page_size=16, n_kv_heads=32, head_dim=128,
                        num_layers=32, dtype_bytes=2)
        assert pb == 8 * 2 ** 20
        assert pages_for_hbm_budget(10 * 2 ** 30, 16, 32, 128, 32, 2) == 1280


# ───────────────────────────── scheduler ─────────────────────────────


class TestFCFSScheduler:
    def test_admission_ignores_prompt_length_fcfs_within_tier(self):
        """Chunked prefill (ISSUE 11): prompt LENGTH no longer gates
        admission — everything that has a slot and worst-case pages
        admits at once, FCFS within the default tier, and the prefill
        work is sliced later by plan_chunks."""
        pool = PagedKVCachePool(1, 64, 4, 2, 8)
        sched = FCFSScheduler(max_batch_slots=4, token_budget=8)
        reqs = [Request(prompt=np.arange(1, 6), max_new_tokens=2),
                Request(prompt=np.arange(1, 5), max_new_tokens=2),
                Request(prompt=np.arange(1, 3), max_new_tokens=2)]
        for r in reqs:
            sched.add(r)
        first = sched.admit(free_slots=4, pool=pool)
        assert [r.req_id for r in first] == [r.req_id for r in reqs]
        assert sched.queue_depth == 0

    def test_priority_orders_admission_within_backpressure(self):
        """SLO tiers: a lower-priority-number (more urgent) request
        enqueues ahead of every waiting request of a higher number;
        within a tier, arrival order holds."""
        pool = PagedKVCachePool(1, 64, 4, 2, 8)
        sched = FCFSScheduler(max_batch_slots=2, token_budget=64)
        batch0 = Request(prompt=np.arange(1, 4), priority=1)
        batch1 = Request(prompt=np.arange(1, 4), priority=1)
        urgent = Request(prompt=np.arange(1, 4), priority=0)
        for r in (batch0, batch1, urgent):
            sched.add(r)
        assert [r.req_id for r in sched.waiting] == [
            urgent.req_id, batch0.req_id, batch1.req_id]
        got = sched.admit(free_slots=2, pool=pool)
        assert [r.req_id for r in got] == [urgent.req_id, batch0.req_id]

    def test_plan_chunks_decode_first_and_slo_order(self):
        """The per-step token budget: decode charged FIRST (decode-first
        under load), prompt chunks fill the remainder in (priority,
        earliest-deadline, arrival) order — one slot may take the whole
        remainder, later ones wait for the next step."""
        sched = FCFSScheduler(max_batch_slots=8, token_budget=16)
        tier1 = Request(prompt=np.arange(1, 4), priority=1)
        tier0 = Request(prompt=np.arange(1, 4), priority=0)
        slo = Request(prompt=np.arange(1, 4), priority=1, deadline_s=60.0)
        # 6 decode tokens leave 10 budget; slot "a" (tier 0) takes its 8
        # remaining, slot "c" (tier 1 + deadline) beats slot "b" for the
        # last 2, "b" gets nothing this step
        plan = sched.plan_chunks(6, [("b", 9, tier1), ("a", 8, tier0),
                                     ("c", 5, slo)])
        assert plan == [("a", 8), ("c", 2)]
        # no decode load: the full budget goes to the head prefill
        plan = sched.plan_chunks(0, [("b", 40, tier1)])
        assert plan == [("b", 16)]
        # budget exhausted by decode: prefill waits (decode retirements
        # free budget in a bounded number of steps — no starvation)
        assert sched.plan_chunks(16, [("b", 9, tier1)]) == []

    def test_step_charge_counts_prompt_chunks(self):
        """pending_steps (the router's queue-side load signal) charges a
        queued prompt its CHUNK count under the token budget, not a flat
        1 — a 10k-token prompt is ~40 steps of work at budget 256 and
        least-loaded dispatch must see them."""
        sched = FCFSScheduler(max_batch_slots=2, token_budget=8)
        sched.add(Request(prompt=np.arange(1, 33), max_new_tokens=2))
        # 32 prompt tokens / budget 8 = 4 chunk steps + 2 decode steps
        assert sched.pending_steps == 6
        sched.add(Request(prompt=np.arange(1, 4), max_new_tokens=1))
        assert sched.pending_steps == 6 + 1 + 1

    def test_no_overtaking_when_pool_full(self):
        pool = PagedKVCachePool(1, 3, 4, 2, 8)  # 2 usable pages
        pool.allocate("live", 8)  # pool full
        sched = FCFSScheduler(max_batch_slots=4)
        big = Request(prompt=np.arange(1, 9), max_new_tokens=1)
        small = Request(prompt=np.arange(1, 3), max_new_tokens=1)
        sched.add(big)
        sched.add(small)
        assert sched.admit(4, pool) == []  # head blocks; no starvation
        assert sched.queue_depth == 2


# ─────────────────────────── engine acceptance ───────────────────────────


def test_engine_smoke_fast():
    """<5s tier-1 smoke: smallest viable engine pass (1-layer llama, one
    prefill-only request) — admission, page alloc, prefill program,
    retire+free. The compiled decode step is covered by the (also tier-1)
    equivalence tests; keeping it out of the smoke keeps this under 5s."""
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=1,
        num_key_value_heads=1, max_position_embeddings=16))
    engine = ServingEngine(model, page_size=4, max_batch_slots=1)
    rid = engine.add_request(np.arange(1, 5), max_new_tokens=1)
    outs = engine.run()
    assert outs[rid].n_gen == 1
    assert all(0 <= t < 32 for t in outs[rid].token_ids)
    assert engine.pool.used_pages == 0
    assert engine.stats["finished_requests"] == 1


class TestEngineEquivalence:
    def test_paged_matches_dense_mixed_lengths_eos_and_late_admission(self):
        """The ISSUE acceptance test in one workload: mixed-length
        prompts, one row stopping on eos mid-batch, and a request
        admitted after step 0 — every request token-identical to its
        dense ``generate()`` run."""
        model = _llama()
        eos_probe = int(_dense_gen(model, _PROMPTS[0], 3)[2])  # hits at t3
        dense = [
            _dense_gen(model, _PROMPTS[0], 8, eos=eos_probe),
            _dense_gen(model, _PROMPTS[1], 6),
            _dense_gen(model, _PROMPTS[2], 5),
        ]
        engine = ServingEngine(model, page_size=4, max_batch_slots=2)
        r0 = engine.add_request(_PROMPTS[0], max_new_tokens=8,
                                eos_token_id=eos_probe)
        r1 = engine.add_request(_PROMPTS[1], max_new_tokens=6)
        engine.step()  # admit + prefill r0/r1, decode step 0
        r2 = engine.add_request(_PROMPTS[2], max_new_tokens=5)  # mid-decode
        outs = engine.run()
        # dense freezes finished rows with eos padding; the engine stops
        # the row at eos — compare up to the engine's (shorter) output
        got0 = np.asarray(outs[r0].token_ids)
        np.testing.assert_array_equal(got0, dense[0][:got0.size])
        assert outs[r0].finish_reason == "stop"
        assert got0[-1] == eos_probe
        np.testing.assert_array_equal(np.asarray(outs[r1].token_ids),
                                      dense[1])
        np.testing.assert_array_equal(np.asarray(outs[r2].token_ids),
                                      dense[2])
        assert outs[r2].finish_reason == "length"
        # everything retired -> every page back on the free list
        assert engine.pool.used_pages == 0

    def test_step_compiles_bounded_across_live_batch_churn(self):
        """The unified step compiles one program per token-grid bucket
        and NOTHING else: admission, retirement, ragged prompt lengths,
        and every decode/chunk mix must never retrace a bucket (the
        ISSUE 11 compile-surface pin — `step` == `step_buckets`)."""
        model = _llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=3)
        rng = np.random.RandomState(3)
        for n, new in ((4, 2), (6, 5), (3, 3), (5, 7), (4, 1), (7, 4)):
            engine.add_request(rng.randint(0, 128, (n,)), max_new_tokens=new)
            engine.step()  # live batch size churns every step
        engine.run()
        counts = engine.compile_counts()
        assert counts["step"] == counts["step_buckets"], counts
        # buckets: the slot grid (3) for decode-only steps, 16 (the
        # floor) for mixed steps carrying prompts of 3..7 tokens
        assert counts["step_buckets"] <= 2, counts

    def test_page_reuse_staggered_high_water_mark(self):
        """Retired sequences' pages serve later requests: on a staggered
        workload the pool's high-water mark stays strictly under the sum
        of per-request dense caches (what generate() would pin)."""
        model = _llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=2)
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, 128, (6,)), 6) for _ in range(6)]
        for p, n in reqs:
            engine.add_request(p, max_new_tokens=n)
        outs = engine.run()
        assert len(outs) == 6
        dense_pages_equiv = sum(
            -(-(len(p) + n) // engine.page_size) for p, n in reqs)
        assert engine.pool.peak_used < dense_pages_equiv
        # 2 slots * 3 pages worst case -> the mark is the concurrency cap
        assert engine.pool.peak_used <= 2 * 3
        assert engine.pool.used_pages == 0

    def test_gpt_engine_smoke(self):
        """Fast CPU smoke (tier-1): the GPT adapter end-to-end — learned
        position embeddings gathered per row, fused qkv write hook."""
        model = _gpt()
        dense = _dense_gen(model, _PROMPTS[2], 4)
        engine = ServingEngine(model, page_size=4, max_batch_slots=2)
        rid = engine.add_request(_PROMPTS[2], max_new_tokens=4)
        outs = engine.run()
        np.testing.assert_array_equal(np.asarray(outs[rid].token_ids), dense)

    def test_add_request_validates_length(self):
        engine = ServingEngine(_llama(), page_size=4, max_batch_slots=1)
        with pytest.raises(ValueError):
            engine.add_request(np.arange(60), max_new_tokens=10)  # > 64

    def test_add_request_rejects_pool_impossible(self):
        """A request whose worst case exceeds the whole pool must be
        rejected at add_request — queueing it would leave run() spinning
        forever on a head request that can never pass can_admit."""
        engine = ServingEngine(_llama(), page_size=4, num_pages=3,
                               max_batch_slots=1)
        with pytest.raises(ValueError, match="usable pages"):
            engine.add_request(np.arange(8), max_new_tokens=4)  # 3 > 2

    def test_undersized_pool_serializes_not_overcommits(self):
        """Two requests that each fit alone but not together: one
        scheduler step must admit only the first (pending-page
        accounting), the second runs after its pages free — no mid-decode
        pool exhaustion."""
        model = _llama()
        # 5 usable pages; each request's worst case is 3 pages
        engine = ServingEngine(model, page_size=4, num_pages=6,
                               max_batch_slots=2)
        dense = [_dense_gen(model, _PROMPTS[1], 6),
                 _dense_gen(model, _PROMPTS[2], 6)]
        r0 = engine.add_request(_PROMPTS[1], max_new_tokens=6)
        r1 = engine.add_request(_PROMPTS[2], max_new_tokens=6)
        engine.step()
        assert engine.stats["running_seqs"] == 1  # r1 waits, not admitted
        outs = engine.run()
        np.testing.assert_array_equal(np.asarray(outs[r0].token_ids),
                                      dense[0])
        np.testing.assert_array_equal(np.asarray(outs[r1].token_ids),
                                      dense[1])
        assert engine.pool.peak_used <= 5
        assert engine.pool.used_pages == 0
        assert engine.run() == {}  # outputs drain: handed out exactly once


# ──────────────── deterministic sampling (ISSUE 7 tentpole) ────────────────


class TestDeterministicSampling:
    """A sampled request's token stream is a pure function of
    (prompt, seed, temperature): per-slot keys derive as
    fold_in(PRNGKey(req.seed), position) INSIDE the compiled decode step,
    so tokens never depend on batch composition, engine history, or a
    mid-stream migration — the property that makes in-flight failover
    token-identical."""

    _SPEC = dict(max_new_tokens=8, temperature=0.9, seed=13)

    def _alone(self, model):
        eng = ServingEngine(model, page_size=4, max_batch_slots=3)
        rid = eng.add_request(_PROMPTS[0], **self._SPEC)
        return list(eng.run()[rid].token_ids)

    def test_batch_composition_independence_and_migration(self):
        model = _llama()
        ref = self._alone(model)
        assert len(set(ref)) > 1  # sanity: actually sampling, not greedy

        # same request alongside DIFFERENT batch mates (other seeds,
        # temperatures, lengths; engine pre-warmed with unrelated work)
        eng = ServingEngine(model, page_size=4, max_batch_slots=3)
        eng.add_request(_PROMPTS[2], max_new_tokens=3, temperature=0.5,
                        seed=99)
        eng.step()  # engine history differs from the reference run
        rid = eng.add_request(_PROMPTS[0], **self._SPEC)
        eng.add_request(_PROMPTS[1], max_new_tokens=6, temperature=1.3,
                        seed=7)
        assert list(eng.run()[rid].token_ids) == ref

        # same request REPLAYED on a fresh engine: bit-identical again
        assert self._alone(model) == ref

        # migrated mid-stream: journal 3 tokens, resume on another
        # engine (ragged re-prefill of prompt + journal) — the continued
        # stream must be token-identical to the uninterrupted run
        adoptive = ServingEngine(model, page_size=4, max_batch_slots=2)
        req = Request(prompt=_PROMPTS[0], **self._SPEC)
        req.resume_tokens = ref[:3]
        adoptive.adopt_request(req)
        assert list(adoptive.run()[req.req_id].token_ids) == ref

    def test_export_inflight_journals_and_resume_is_exact(self):
        """export_inflight pops live requests with their journals; a
        sibling adopting the journal continues the stream exactly where
        the source stopped (no duplicated/missing stream chunks)."""
        model = _llama()
        ref = self._alone(model)
        src = ServingEngine(model, page_size=4, max_batch_slots=2)
        chunks = []
        rid = src.add_request(
            _PROMPTS[0],
            stream_cb=lambda r, tok, fin, seq: chunks.append((seq, tok)),
            **self._SPEC)
        src.step()  # admit + final prompt chunk -> token 0
        src.step()  # decode -> token 1
        src.step()  # decode -> token 2
        journals = src.export_inflight()
        assert [j.req_id for j in journals] == [rid]
        assert journals[0].resume_tokens == ref[:3]
        assert src.slots == [None, None]  # popped, pages freed
        assert src.pool.used_pages == 0

        dst = ServingEngine(model, page_size=4, max_batch_slots=2)
        dst.adopt_request(journals[0])
        out = dst.run()[rid]
        assert list(out.token_ids) == ref
        # exactly-once streaming across the hop: monotone seqs, no gap,
        # no repeat; terminal chunk carries the total count
        tok_chunks = [c for c in chunks if c[1] is not None]
        assert [s for s, _ in tok_chunks] == list(range(8))
        assert [t for _, t in tok_chunks] == ref
        assert chunks[-1] == (8, None)

    def test_out_of_int32_seed_is_canonicalized_not_crashing(self):
        """The compiled decode step stages seeds as int32: a 64-bit seed
        must canonicalize deterministically (low 32 bits) instead of
        letting one user request crash the decode step with an
        OverflowError — which, behind a Router, would cascade an
        engine-killing request across the fleet via migration."""
        model = _llama()
        eng = ServingEngine(model, page_size=4, max_batch_slots=1)
        rid = eng.add_request(_PROMPTS[2], max_new_tokens=4,
                              temperature=0.9, seed=2 ** 31)
        out = eng.run()[rid]
        assert out.finish_reason == "length" and out.n_gen == 4
        # canonicalization is deterministic: same wide seed, same stream
        eng2 = ServingEngine(model, page_size=4, max_batch_slots=1)
        rid2 = eng2.add_request(_PROMPTS[2], max_new_tokens=4,
                                temperature=0.9, seed=2 ** 31)
        assert eng2.run()[rid2].token_ids == out.token_ids

    def test_legacy_three_arg_stream_cb_keeps_working(self):
        """The seq number threads only into callbacks that ask for it —
        the PR 1 cb(req_id, token, finished) contract is untouched."""
        eng = ServingEngine(_llama(), page_size=4, max_batch_slots=1)
        seen = []
        rid = eng.add_request(
            _PROMPTS[2], max_new_tokens=3,
            stream_cb=lambda r, tok, fin: seen.append((tok, fin)))
        outs = eng.run()
        assert [t for t, _ in seen[:-1]] == list(outs[rid].token_ids)
        assert seen[-1] == (None, "length")

    def test_defaulted_fourth_param_cb_stays_legacy(self):
        """A legacy callback that happens to carry an unrelated
        DEFAULTED 4th parameter must not start receiving the seq int in
        it on upgrade; opting in takes *args, a required 4th positional,
        or a parameter named `seq`."""
        from paddle_tpu.serving.engine import _cb_accepts_seq

        assert not _cb_accepts_seq(lambda r, t, f: None)
        assert not _cb_accepts_seq(lambda r, t, f, logger=None: None)
        assert _cb_accepts_seq(lambda r, t, f, seq: None)
        assert _cb_accepts_seq(lambda r, t, f, seq=0: None)
        assert _cb_accepts_seq(lambda *a: None)
        eng = ServingEngine(_llama(), page_size=4, max_batch_slots=1)
        seen = []
        rid = eng.add_request(
            _PROMPTS[2], max_new_tokens=2,
            stream_cb=lambda r, t, f, logger="L": seen.append(logger))
        assert eng.run()[rid].finish_reason == "length"
        assert seen == ["L"] * 3  # default untouched: 2 tokens + terminal

    def test_migrated_admission_does_not_pollute_queue_wait(self):
        """A migrated request's SECOND admission must not observe
        queue-wait from the original enqueue — that would fold its
        decode time on the dead engine into the histogram operators
        read during exactly these incidents (same guard as TTFT)."""
        from paddle_tpu import metrics

        model = _llama()
        wait = metrics.get_registry().get(
            "paddle_tpu_serving_queue_wait_seconds")
        eng = ServingEngine(model, page_size=4, max_batch_slots=1)
        req = Request(prompt=_PROMPTS[2], max_new_tokens=4)
        req.resume_tokens = [5]
        before = wait.count
        eng.adopt_request(req)
        assert eng.run()[req.req_id].finish_reason == "length"
        assert wait.count == before


# ──────────── unified ragged step + chunked prefill (ISSUE 11) ────────────


class TestUnifiedStep:
    """The prefill/decode split is gone: one compiled ragged step serves
    decode tokens and prompt chunks together under a shared token
    budget. Properties: streams are token-identical to the pre-chunking
    engine (= dense generate / any chunking) at temperature>0 — alone,
    with batch-mates, and across chunk-size sweeps; decode is never
    starved by concurrent prefill chunks; and the compile surface stays
    pinned to the token-grid bucket set."""

    _SPEC = dict(max_new_tokens=8, temperature=0.9, seed=29)

    def test_streams_identical_across_chunk_size_sweep(self):
        """THE chunking property: (prompt, seed, temperature) fully
        determines the stream no matter how the prompt is sliced — a
        1-token-budget engine (maximal chunking), a mid-size one, and an
        unchunked one (budget >= prompt) emit bit-identical tokens, all
        equal to the dense generate() oracle."""
        model = _llama()
        prompt = np.random.RandomState(41).randint(0, 128, (23,))
        paddle.seed(0)
        ref = None
        for budget in (1, 5, 16, 1024):
            eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                                token_budget=budget)
            rid = eng.add_request(prompt, **self._SPEC)
            got = list(eng.run()[rid].token_ids)
            if ref is None:
                ref = got
                assert len(set(ref)) > 1  # sanity: actually sampling
            assert got == ref, f"stream diverged at token_budget={budget}"
        # greedy chunked == dense generate (the pre-chunking oracle)
        dense = _dense_gen(model, prompt, 6)
        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            token_budget=7)
        rid = eng.add_request(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(
            np.asarray(eng.run()[rid].token_ids), dense)

    def test_streams_identical_with_chunking_batch_mates(self):
        """A decoding request's stream is untouched by a long prompt
        chunk-prefilling beside it (and vice versa) — the ragged grid
        carries both, sampling keys are per-slot."""
        model = _llama()
        rng = np.random.RandomState(43)
        long_prompt = rng.randint(0, 128, (40,))
        ref_eng = ServingEngine(model, page_size=4, max_batch_slots=2)
        r = ref_eng.add_request(_PROMPTS[0], **self._SPEC)
        ref = list(ref_eng.run()[r].token_ids)
        long_ref_eng = ServingEngine(model, page_size=4,
                                     max_batch_slots=2)
        r = long_ref_eng.add_request(long_prompt, **self._SPEC)
        long_ref = list(long_ref_eng.run()[r].token_ids)

        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            token_budget=8)
        dec = eng.add_request(_PROMPTS[0], **self._SPEC)
        eng.step()  # decoding before the long prompt arrives
        lng = eng.add_request(long_prompt, **self._SPEC)
        outs = eng.run()
        assert list(outs[dec].token_ids) == ref
        assert list(outs[lng].token_ids) == long_ref

    def test_decode_not_starved_by_concurrent_prefill(self):
        """Decode-first under load: while a 40-token prompt trickles in
        at token_budget=8, every already-decoding tenant still lands
        EXACTLY one token per engine step — chunks only ever take the
        budget decode left over."""
        model = _llama()
        rng = np.random.RandomState(47)
        eng = ServingEngine(model, page_size=4, max_batch_slots=3,
                            token_budget=8)
        d0 = eng.add_request(_PROMPTS[0], max_new_tokens=20)
        d1 = eng.add_request(_PROMPTS[1], max_new_tokens=20)
        eng.step()  # both sampled their first token
        lng = eng.add_request(rng.randint(0, 128, (40,)),
                              max_new_tokens=2)
        gens = {d0: 1, d1: 1}
        for _ in range(5):  # the long prompt needs ceil(40/6)=7 chunks
            before = {rid: self._gen_len(eng, rid) for rid in gens}
            eng.step()
            for rid in gens:
                assert self._gen_len(eng, rid) == before[rid] + 1, (
                    "a decoding tenant was starved by a prefill chunk")
            assert self._gen_len(eng, lng) == 0  # still mid-prompt
        outs = eng.run()
        assert all(outs[r].finish_reason == "length" for r in outs)

    @staticmethod
    def _gen_len(eng, rid):
        for st in eng.slots:
            if st is not None and st.req.req_id == rid:
                return len(st.gen)
        return -1  # retired

    def test_compile_surface_pinned_to_bucket_set(self):
        """`paddle_tpu_jit_compiles_total{fn="serving_step"}` == the
        bucket-set size across an adversarial workload sweep (ragged
        prompts, churn, chunking, prefix hits): the ISSUE 11 metric
        contract, monitorable in production."""
        from paddle_tpu import metrics

        def compiles():
            # summed across the source="memory|disk|fresh" split: one
            # inc per materialized program either way
            fam = metrics.get_registry().get(
                "paddle_tpu_jit_compiles_total")
            return 0.0 if fam is None else fam.sum_labels(
                fn="serving_step")

        model = _llama()
        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            token_budget=8)
        before = compiles()
        rng = np.random.RandomState(53)
        for n, new in ((3, 2), (30, 3), (7, 6), (41, 2), (30, 1)):
            eng.add_request(rng.randint(0, 128, (n,)), max_new_tokens=new)
            eng.step()
        eng.run()
        counts = eng.compile_counts()
        assert counts["step"] == counts["step_buckets"]
        assert compiles() - before == counts["step"]
        # re-running the same mix compiles NOTHING new
        for n, new in ((30, 3), (3, 2)):
            eng.add_request(rng.randint(0, 128, (n,)), max_new_tokens=new)
        eng.run()
        assert compiles() - before == counts["step"]
        assert eng.compile_counts() == counts

    def test_priority_tier_preempts_chunk_budget(self):
        """SLO tiers at the chunk level: with two prompts mid-prefill,
        the tier-0 one takes the whole step budget and reaches its
        first token first even though the tier-1 prompt was admitted
        earlier."""
        model = _llama()
        rng = np.random.RandomState(59)
        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            token_budget=8)
        batch = eng.add_request(rng.randint(0, 128, (32,)),
                                max_new_tokens=2, priority=1)
        urgent = eng.add_request(rng.randint(0, 128, (32,)),
                                 max_new_tokens=2, priority=0)
        first = None
        for _ in range(12):
            eng.step()
            for rid in (urgent, batch):
                if first is None and self._gen_len(eng, rid) > 0:
                    first = rid
        assert first == urgent
        outs = eng.run()
        assert all(o.finish_reason == "length" for o in outs.values())


# ──────── speculative decoding on the unified step (ISSUE 14) ────────


class _OracleDrafter:
    """Proposes the reference continuation itself — 100% acceptance, so
    every decode step lands a full (k+1)-token burst; exercises the
    multi-token landing path deterministically."""

    def __init__(self, prompt_len, ref):
        self.prompt_len, self.ref = int(prompt_len), list(ref)

    def propose(self, ids, k=None):
        done = len(ids) - self.prompt_len
        return np.asarray(self.ref[done:done + (k or 1)], np.int32)


class _GarbageDrafter:
    """Proposes a fixed token the model (almost) never emits — the
    all-rejected rollback path runs on every decode step."""

    def propose(self, ids, k=None):
        return np.full(k or 1, 127, np.int32)


class TestSpeculativeDecoding:
    """ISSUE 14 tentpole: host-side drafts ride the unified ragged step
    as extra grid rows — data, not new compiled programs — and
    verification compares drafts against the per-position sampled
    targets the determinism contract already pins. So streams are
    bit-identical with speculation on or off, for ANY drafter: a good
    one only changes how many grid rows each step retires."""

    def _ref(self, model, prompt, **spec):
        eng = ServingEngine(model, page_size=4, max_batch_slots=2)
        rid = eng.add_request(prompt, **spec)
        return list(eng.run()[rid].token_ids)

    @pytest.mark.parametrize("temperature", [0.0, 0.9])
    def test_streams_bit_identical_spec_on_vs_off(self, temperature):
        """The headline property, greedy AND sampled: an n-gram-drafted
        engine emits exactly the spec-off streams for a mixed batch."""
        model = _llama()
        spec = dict(max_new_tokens=10, temperature=temperature, seed=17)
        refs = [self._ref(model, p, **spec) for p in _PROMPTS]
        if temperature:
            assert any(len(set(r)) > 1 for r in refs)  # actually sampling
        eng = ServingEngine(model, page_size=4, max_batch_slots=3,
                            spec_k=3)
        rids = [eng.add_request(p, **spec) for p in _PROMPTS]
        outs = eng.run()
        assert [list(outs[r].token_ids) for r in rids] == refs

    def test_oracle_drafter_lands_multi_token_bursts(self):
        """With a drafter proposing the true continuation every draft is
        accepted, so the request drains in ~1/(k+1) the decode steps —
        proof the accept path lands real bursts, not one token with
        extra ceremony — and the stream is still bit-identical."""
        from paddle_tpu import metrics

        model = _llama()
        spec = dict(max_new_tokens=12, temperature=0.9, seed=23)
        ref = self._ref(model, _PROMPTS[0], **spec)
        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            spec_k=3,
                            drafter=_OracleDrafter(_PROMPTS[0].size, ref))
        reg = metrics.get_registry()
        d0 = reg.get("paddle_tpu_serving_spec_drafted_tokens_total").value
        a0 = reg.get("paddle_tpu_serving_spec_accepted_tokens_total").value
        toks, done = [], []
        eng.add_request(
            _PROMPTS[0],
            stream_cb=lambda r, t, f, s: (toks.append(t) if t is not None
                                          else done.append(f)),
            **spec)
        steps = 0
        while not done:
            eng.step()
            steps += 1
            assert steps < 16  # would mean speculation stalled the drain
        assert toks == ref
        # prefill step lands token 0; 11 more at 4/step -> 4 steps total
        assert steps <= 5
        drafted = reg.get(
            "paddle_tpu_serving_spec_drafted_tokens_total").value - d0
        accepted = reg.get(
            "paddle_tpu_serving_spec_accepted_tokens_total").value - a0
        assert drafted == accepted > 0  # the oracle is never rejected

    def test_rejected_drafts_roll_back_bit_identically(self):
        """The a=0 path: a drafter proposing garbage every step forces
        the KV rollback (pool.truncate) on every burst — the stream must
        still match the spec-off run token for token."""
        from paddle_tpu import metrics

        model = _llama()
        spec = dict(max_new_tokens=8, temperature=0.9, seed=31)
        ref = self._ref(model, _PROMPTS[1], **spec)
        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            spec_k=3, drafter=_GarbageDrafter())
        reg = metrics.get_registry()
        d0 = reg.get("paddle_tpu_serving_spec_drafted_tokens_total").value
        a0 = reg.get("paddle_tpu_serving_spec_accepted_tokens_total").value
        rid = eng.add_request(_PROMPTS[1], **spec)
        assert list(eng.run()[rid].token_ids) == ref
        drafted = reg.get(
            "paddle_tpu_serving_spec_drafted_tokens_total").value - d0
        accepted = reg.get(
            "paddle_tpu_serving_spec_accepted_tokens_total").value - a0
        assert drafted > 0 and accepted < drafted

    def test_compile_surface_pinned_with_speculation(self):
        """Drafts are grid rows, not programs: with spec_k=3 armed, the
        ISSUE 11 contract still holds — jit compiles for serving_step ==
        the bucket-set size across a ragged churn sweep, and replaying
        the mix compiles nothing new."""
        from paddle_tpu import metrics

        def compiles():
            fam = metrics.get_registry().get(
                "paddle_tpu_jit_compiles_total")
            return 0.0 if fam is None else fam.sum_labels(
                fn="serving_step")

        model = _llama()
        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            token_budget=8, spec_k=3)
        before = compiles()
        rng = np.random.RandomState(61)
        for n, new in ((3, 6), (30, 3), (7, 6), (20, 2)):
            eng.add_request(rng.randint(0, 128, (n,)), max_new_tokens=new,
                            temperature=0.9, seed=n)
            eng.step()
        eng.run()
        counts = eng.compile_counts()
        assert counts["step"] == counts["step_buckets"]
        assert compiles() - before == counts["step"]
        # the same mix again — drafts and all — compiles NOTHING new
        for n, new in ((30, 3), (3, 6)):
            eng.add_request(rng.randint(0, 128, (n,)), max_new_tokens=new)
        eng.run()
        assert compiles() - before == counts["step"]
        assert eng.compile_counts() == counts

    def test_drafts_yield_to_decode_and_prefill_chunks(self):
        """Budget order is decode > chunks > drafts: while a 40-token
        prompt trickles in at token_budget=8, every decoding tenant
        still lands at least its guaranteed token per step and the
        chunk cadence is untouched (drafts take only the leftover,
        which is zero during admission)."""
        model = _llama()
        eng = ServingEngine(model, page_size=4, max_batch_slots=3,
                            token_budget=8, spec_k=3)
        d0 = eng.add_request(_PROMPTS[0], max_new_tokens=20)
        d1 = eng.add_request(_PROMPTS[1], max_new_tokens=20)
        eng.step()  # both sampled their first token
        lng = eng.add_request(np.random.RandomState(67).randint(
            0, 128, (40,)), max_new_tokens=2)
        gl = TestUnifiedStep._gen_len
        for _ in range(5):  # same cadence as the spec-off starvation test
            before = {r: gl(eng, r) for r in (d0, d1)}
            eng.step()
            for r in (d0, d1):
                assert gl(eng, r) >= before[r] + 1, (
                    "a decoding tenant was starved with speculation on")
            assert gl(eng, lng) == 0  # still mid-prompt: chunks kept pace
        outs = eng.run()
        assert all(outs[r].finish_reason == "length" for r in outs)

    def test_export_mid_burst_journals_only_committed_tokens(self):
        """Chaos contract: exporting a slot mid-speculative-run journals
        exactly the tokens already streamed — never unaccepted drafts —
        and a sibling adopting the journal (its own drafter re-drafting
        over prompt+journal) finishes the stream bit-identically with
        exactly-once chunk seqs."""
        model = _llama()
        spec = dict(max_new_tokens=10, temperature=0.9, seed=37)
        ref = self._ref(model, _PROMPTS[2], **spec)
        src = ServingEngine(model, page_size=4, max_batch_slots=2,
                            spec_k=3,
                            drafter=_OracleDrafter(_PROMPTS[2].size, ref))
        chunks = []
        rid = src.add_request(
            _PROMPTS[2],
            stream_cb=lambda r, t, f, s: chunks.append((s, t)),
            **spec)
        src.step()  # prefill -> token 0
        src.step()  # full burst: drafts 1..3 accepted + bonus -> 4 more
        [journal] = src.export_inflight()
        streamed = [t for _, t in chunks if t is not None]
        assert len(streamed) == 5  # the burst actually landed 4 tokens
        assert journal.resume_tokens == streamed == ref[:5]
        assert src.pool.used_pages == 0  # rollback/export left no pages

        dst = ServingEngine(model, page_size=4, max_batch_slots=2,
                            spec_k=3)
        dst.adopt_request(journal)
        assert list(dst.run()[rid].token_ids) == ref
        tok_chunks = [c for c in chunks if c[1] is not None]
        assert [s for s, _ in tok_chunks] == list(range(10))
        assert [t for _, t in tok_chunks] == ref

    def test_engine_seed_kwarg_deprecated(self):
        """ServingEngine(seed=...) never seeded anything (sampling is
        keyed per request); passing it now warns instead of silently
        implying a determinism knob that does not exist."""
        with pytest.warns(DeprecationWarning, match="ServingEngine"):
            ServingEngine(_llama(), page_size=4, max_batch_slots=1,
                          seed=0)


# ──────────────── prefix caching (ISSUE 8 tentpole) ────────────────


class TestPrefixCache:
    """Copy-on-write prefix caching over the paged pool: a request
    sharing a cached prompt prefix adopts the cached pages at admission
    and ragged-prefills only its uncovered suffix — with warm streams
    BIT-IDENTICAL to cold ones (the determinism contract survives the
    optimization), sibling pages immutable under divergence, and LRU
    eviction under pool pressure invisible to in-flight requests."""

    _PREFIX = np.random.RandomState(21).randint(0, 128, (24,))

    def _prompt(self, *suffix):
        return np.concatenate([self._PREFIX,
                               np.asarray(suffix, np.int32)])

    @staticmethod
    def _counter(name, eng):
        fam = __import__("paddle_tpu").metrics.get_registry().get(name)
        if fam is None:
            return 0.0
        return fam.labels(engine_id=eng.engine_id,
                          model_id=eng.model_id).value

    @staticmethod
    def _run_one(eng, prompt, **spec):
        rid = eng.add_request(prompt, **spec)
        return list(eng.run()[rid].token_ids)

    def test_warm_streams_bit_identical_and_counters(self):
        """Property (1): warm-cache streams equal cold-prefill streams
        at temperature>0 — same prompt AND shared-prefix-new-suffix —
        while hits/misses/saved counters move exactly once per event and
        decode stays at one compile."""
        model = _llama()
        off = ServingEngine(model, page_size=4, max_batch_slots=2,
                            prefix_cache=False)
        spec = dict(max_new_tokens=8, temperature=0.9, seed=13)
        pa, pb = self._prompt(1, 2, 3, 4, 5), self._prompt(9, 9)
        ref_a = self._run_one(off, pa, **spec)
        ref_b = self._run_one(off, pb, **spec)
        assert len(set(ref_a)) > 1  # sanity: actually sampling

        eng = ServingEngine(model, page_size=4, max_batch_slots=2)
        h0 = self._counter("paddle_tpu_serving_prefix_hits_total", eng)
        m0 = self._counter("paddle_tpu_serving_prefix_misses_total", eng)
        s0 = self._counter("paddle_tpu_serving_prefill_tokens_saved_total",
                           eng)
        cold = self._run_one(eng, pa, **spec)
        assert cold == ref_a  # cold through the unified program: same
        assert self._counter(
            "paddle_tpu_serving_prefix_misses_total", eng) == m0 + 1
        warm_same = self._run_one(eng, pa, **spec)
        assert warm_same == ref_a  # full-prompt hit (capped at s-1)
        warm_diverged = self._run_one(eng, pb, **spec)
        assert warm_diverged == ref_b  # shared 24-token prefix, new tail
        assert self._counter(
            "paddle_tpu_serving_prefix_hits_total", eng) == h0 + 2
        # pa is 29 tokens: the identical re-run saves 28 (7 full pages,
        # capped one short of the prompt); pb (26 tokens) shares the
        # 24-token prefix = 6 pages
        assert self._counter(
            "paddle_tpu_serving_prefill_tokens_saved_total",
            eng) == s0 + 28 + 24
        counts = eng.compile_counts()
        assert counts["step"] == counts["step_buckets"]
        assert eng.pool.used_pages == 0  # cache pages are not "used"
        assert len(eng.prefix_cache) > 0

    def test_cow_divergence_never_mutates_shared_pages(self):
        """Property (2): decoding a request that adopted cached pages —
        and a second one diverging right after the shared prefix — never
        changes a byte of the shared pages (checksummed before/after)."""
        model = _llama()
        eng = ServingEngine(model, page_size=4, max_batch_slots=2)
        spec = dict(max_new_tokens=8, temperature=0.7, seed=5)
        eng.run()  # no-op; keep shapes warm
        eng.add_request(self._prompt(1, 2, 3), **spec)
        eng.run()  # prefix now cached
        matched, pages, _ = eng.prefix_cache.match(self._prompt(7, 7, 7))
        assert matched == 24 and len(pages) == 6
        def page_bytes_snapshot():
            return [np.asarray(eng.pool.k_pools[li]._value[np.asarray(pages)])
                    .copy() for li in range(eng.n_layers)]
        before = page_bytes_snapshot()
        r1 = eng.add_request(self._prompt(7, 7, 7), **spec)
        r2 = eng.add_request(self._prompt(8, 8, 8, 8), **spec)
        outs = eng.run()
        assert outs[r1].n_gen == 8 and outs[r2].n_gen == 8
        after = page_bytes_snapshot()
        for b, a in zip(before, after):
            np.testing.assert_array_equal(b, a)
        assert eng.pool.used_pages == 0

    def test_eviction_under_pressure_never_breaks_inflight(self):
        """Property (3): a full pool evicts LRU cache nodes instead of
        failing allocation, and an in-flight request decodes through the
        eviction storm token-identical to a cache-off run."""
        model = _llama()
        rng = np.random.RandomState(31)
        inflight_p = rng.randint(0, 128, (6,))
        late_p = rng.randint(0, 128, (12,))
        off = ServingEngine(model, page_size=4, max_batch_slots=2,
                            prefix_cache=False)
        spec = dict(max_new_tokens=10, temperature=0.8, seed=3)
        ref = self._run_one(off, inflight_p, **spec)

        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            num_pages=12)  # 11 usable
        for i in range(3):  # fill the cache: 3 x 2 full pages resident
            eng.add_request(rng.randint(0, 128, (8,)), max_new_tokens=2)
        eng.run()
        assert len(eng.prefix_cache) == 6
        ev0 = self._counter("paddle_tpu_serving_prefix_evictions_total",
                            eng)
        rid = eng.add_request(inflight_p, **spec)
        eng.step()  # in-flight mid-decode, pinning its pages
        late = eng.add_request(late_p, max_new_tokens=8)
        outs = eng.run()
        assert list(outs[rid].token_ids) == ref
        assert outs[late].finish_reason == "length"
        assert self._counter(
            "paddle_tpu_serving_prefix_evictions_total", eng) > ev0
        assert eng.pool.used_pages == 0

    def test_can_admit_does_not_double_count_matched_pages(self):
        """Admission regression: a request's matched prefix pages are
        about to be PINNED by its own adoption, so they must not be
        discounted from its need AND still counted as reclaimable —
        that double-count admitted work whose fresh draws would starve
        a live sequence's reserved tail mid-decode."""
        from paddle_tpu.serving import PrefixCache

        pool = PagedKVCachePool(num_layers=1, num_pages=11, page_size=4,
                                n_kv_heads=2, head_dim=8)  # 10 usable
        cache = PrefixCache(pool)
        ids = np.arange(1, 18, dtype=np.int32)  # 17 tokens: 4 full pages
        cache.insert(ids, 17, pool.allocate("warm", 17))
        pool.free("warm")  # 4 pages stay cache-resident, 6 free
        pool.allocate("live", 8, max_total_tokens=16)  # 2 now, 2 promised
        assert pool.prefix_match_len(ids) == 16  # 4 pages would be adopted
        # worst case 8 pages, 4 matched -> 4 fresh draws; truly spare:
        # 4 free minus the live tail's 2 promised = 2 -> must NOT admit
        # (the matched pages stop being evictable the moment they're
        # adopted, so they cannot also serve as the eviction reserve)
        assert not pool.can_admit(32, cached_pages=4)
        # sanity: a cold 24-token request needs 6 fresh and CAN admit —
        # the 4 unpinned cache pages genuinely evict for it
        assert pool.can_admit(24)

    def test_opt_out_flags(self):
        """Engine-level prefix_cache=False builds no cache; the
        per-request flag skips match AND insert for that request only."""
        model = _llama()
        off = ServingEngine(model, page_size=4, max_batch_slots=1,
                            prefix_cache=False)
        assert off.prefix_cache is None
        off.add_request(self._prompt(1), max_new_tokens=2)
        off.run()

        eng = ServingEngine(model, page_size=4, max_batch_slots=1)
        h0 = self._counter("paddle_tpu_serving_prefix_hits_total", eng)
        m0 = self._counter("paddle_tpu_serving_prefix_misses_total", eng)
        eng.add_request(self._prompt(1), max_new_tokens=2,
                        prefix_cache=False)
        eng.run()
        assert len(eng.prefix_cache) == 0  # nothing indexed
        assert self._counter(
            "paddle_tpu_serving_prefix_hits_total", eng) == h0
        assert self._counter(
            "paddle_tpu_serving_prefix_misses_total", eng) == m0

    def test_chunk_budget_charges_only_uncovered_suffix(self):
        """Budget honesty under chunked prefill: admission adopts the
        cached prefix pages and sets the chunk cursor AFTER them, so a
        warm prompt's first token lands in ONE budget-bounded step while
        the identical cold prompt needs several chunk steps — the
        prefix-cache win measured in steps-to-first-token."""
        model = _llama()

        def steps_to_first_token(warm):
            eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                                token_budget=10)
            if warm:
                eng.add_request(self._PREFIX, max_new_tokens=1)
                eng.run()  # cache the 24-token prefix (5 full pages)
            rid = eng.add_request(self._PREFIX, max_new_tokens=4)
            for n in range(1, 10):
                eng.step()
                if any(st is not None and st.req.req_id == rid
                       and st.gen for st in eng.slots):
                    return n
            raise AssertionError("no first token within 9 steps")

        # cold: 24 tokens / budget 10 = 3 chunk steps to the sample;
        # warm: 20 matched (5 full pages), 4-token suffix = ONE step
        assert steps_to_first_token(warm=False) == 3
        assert steps_to_first_token(warm=True) == 1

    def test_migration_reprefill_rides_the_cache(self):
        """A journaled request adopted by an engine whose cache holds the
        prefix re-prefills only the uncovered tail (saved counter moves)
        and continues the stream token-identically — failover of
        prefix-heavy traffic is cheap (docs/RESILIENCE.md)."""
        model = _llama()
        spec = dict(max_new_tokens=8, temperature=0.9, seed=13)
        prompt = self._prompt(2, 4, 6)
        off = ServingEngine(model, page_size=4, max_batch_slots=2,
                            prefix_cache=False)
        ref = self._run_one(off, prompt, **spec)

        src = ServingEngine(model, page_size=4, max_batch_slots=2)
        rid = src.add_request(prompt, **spec)
        for _ in range(3):
            src.step()  # chunk (token 0) + two decodes -> 3 tokens
        [journal] = src.export_inflight()
        assert journal.resume_tokens == ref[:3]

        dst = ServingEngine(model, page_size=4, max_batch_slots=2)
        dst.add_request(prompt, max_new_tokens=1)  # prefix-heavy sibling
        dst.run()
        s0 = self._counter("paddle_tpu_serving_prefill_tokens_saved_total",
                           dst)
        dst.adopt_request(journal)
        out = dst.run()[rid]
        assert list(out.token_ids) == ref
        assert self._counter(
            "paddle_tpu_serving_prefill_tokens_saved_total", dst) > s0


# ──────────────────────────── front door (api) ────────────────────────────


class TestCompletionAPI:
    def test_openai_shape_streaming_and_usage(self):
        model = _llama()
        engine = ServingEngine(model, page_size=4, max_batch_slots=2)
        api = CompletionAPI(engine, model_name="llama-tiny")
        chunks = []
        resp = api.create_completion(
            [_PROMPTS[0], _PROMPTS[2]], max_tokens=3,
            stream_cb=chunks.append)
        assert resp["object"] == "text_completion"
        assert resp["model"] == "llama-tiny"
        assert len(resp["choices"]) == 2
        for i, ch in enumerate(resp["choices"]):
            assert ch["index"] == i
            assert len(ch["token_ids"]) == 3
            assert ch["finish_reason"] == "length"
        assert resp["usage"]["prompt_tokens"] == (
            _PROMPTS[0].size + _PROMPTS[2].size)
        assert resp["usage"]["completion_tokens"] == 6
        # streamed chunks: 3 tokens + 1 finish per choice, and the
        # terminal chunk's reason agrees with the final response's
        tok_chunks = [c for c in chunks
                      if c["choices"][0]["token_id"] is not None]
        fin_chunks = [c for c in chunks
                      if c["choices"][0]["finish_reason"] is not None]
        assert len(tok_chunks) == 6 and len(fin_chunks) == 2
        assert all(c["choices"][0]["finish_reason"] == "length"
                   for c in fin_chunks)
        assert all(c["object"] == "text_completion.chunk" for c in chunks)
        # streamed ids replay the final choice ids, in order
        ids0 = [c["choices"][0]["token_id"] for c in tok_chunks
                if c["choices"][0]["index"] == 0]
        assert ids0 == resp["choices"][0]["token_ids"]

    def test_stream_chunks_carry_monotone_seq(self):
        """OpenAI-ish chunks expose the engine's per-request sequence
        numbers so a client can verify exactly-once delivery across a
        migration (token chunks: 0-based index; terminal: total)."""
        engine = ServingEngine(_llama(), page_size=4, max_batch_slots=1)
        api = CompletionAPI(engine)
        chunks = []
        api.create_completion(_PROMPTS[2], max_tokens=4,
                              stream_cb=chunks.append)
        seqs = [c["choices"][0]["seq"] for c in chunks
                if c["choices"][0]["token_id"] is not None]
        assert seqs == [0, 1, 2, 3]
        assert chunks[-1]["choices"][0]["seq"] == 4  # terminal: count

    def test_batch_prevalidation_leaves_no_orphans(self):
        """One bad prompt in a batch must reject the WHOLE call before
        anything queues — otherwise its batch-mates would run as orphans
        on the next create_completion and their outputs be discarded."""
        engine = ServingEngine(_llama(), page_size=4, max_batch_slots=2)
        api = CompletionAPI(engine)
        with pytest.raises(ValueError):
            api.create_completion([_PROMPTS[0], np.arange(60)],
                                  max_tokens=10)  # 70 > max_model_len 64
        assert engine.scheduler.queue_depth == 0 and not engine.has_work

    def test_batch_mates_get_distinct_seeds(self):
        """n-best sampling of one prompt: each choice must draw its first
        token from its own stream (seed + index), not n copies of one."""
        engine = ServingEngine(_llama(), page_size=4, max_batch_slots=2)
        api = CompletionAPI(engine)
        seeds = []
        orig = engine.add_request
        engine.add_request = (
            lambda p, **kw: (seeds.append(kw["seed"]), orig(p, **kw))[1])
        api.create_completion([_PROMPTS[2], _PROMPTS[2]], max_tokens=2,
                              seed=7)
        assert seeds == [7, 8]

    def test_router_replicas_distinct_and_individually_drivable(self):
        # the old EnginePool.retrieve() contract, on the Router surface:
        # replicas are distinct engines and each can be driven alone
        router = Router()
        router.add_model("default", _llama(), replicas=2, page_size=4,
                         max_batch_slots=1)
        engines = router.engines()
        assert len(router) == 2
        assert engines[0] is not engines[1]
        rid = engines[1].add_request(_PROMPTS[2], max_new_tokens=2)
        outs = engines[1].run()
        assert outs[rid].n_gen == 2


# ─────────────────────── generation stats satellite ───────────────────────


class TestGenerateStats:
    def test_return_stats_length_and_eos(self):
        model = _llama()
        ids, st = model.generate(paddle.to_tensor(_PROMPTS[1][None, :]),
                                 max_new_tokens=4, temperature=0.0,
                                 return_stats=True)
        assert st == {"n_gen": 4, "stop_reason": "length"}
        assert ids.shape[1] == _PROMPTS[1].size + 4
        eos = int(_dense_gen(model, _PROMPTS[1], 1)[0])
        _, st2 = model.generate(paddle.to_tensor(_PROMPTS[1][None, :]),
                                max_new_tokens=6, temperature=0.0,
                                eos_token_id=eos, return_stats=True)
        assert st2["stop_reason"] == "eos" and st2["n_gen"] < 6


# ─────────────────────────── slow batch sweeps ───────────────────────────


# ───────────────────── one step in flight while planning ─────────────────────


@pytest.fixture
def ring():
    """A private tracer, installed before the engine captures it."""
    from paddle_tpu.serving import tracing

    tr = tracing.RequestTracer(capacity=65536)
    old = tracing.set_tracer(tr)
    try:
        yield tr
    finally:
        tracing.set_tracer(old)


def _counts(tr, engine):
    """The ``step`` counters of every call of ``engine``, oldest first."""
    return [e["counts"] for e in tr.events()
            if e["name"] == "step" and e["req_id"] == engine.engine_id
            and "counts" in e]


def _drive(engine, until=None):
    """step() until the engine drains (or ``until()`` holds)."""
    while engine.has_work and not (until is not None and until()):
        engine.step()


class TestPipelinedStep:
    """Step k+1 is planned, packed and launched while step k is on the
    device; k+1 takes k's samples on the device and each slot is planned
    from ``pos + ahead``. A row whose sequence ended before it lands is
    thrown away. Every stream stays token-identical to dense generate()."""

    @pytest.mark.parametrize("case", ["eos", "cancel", "nan", "known_last",
                                      "final_chunk"])
    def test_streams_match_dense_with_a_step_in_flight(self, ring, case):
        from paddle_tpu import faults

        model = _llama()
        kw = dict(page_size=4, max_batch_slots=2)
        if case == "final_chunk":
            kw["token_budget"] = 4     # the 9-token prompt lands in 3 chunks
        eng = ServingEngine(model, **kw)
        streamed = {}

        def cb(rid, tok, fin, seq):
            if tok is not None:
                streamed.setdefault(rid, []).append(int(tok))

        eos = int(_dense_gen(model, _PROMPTS[0], 3)[2])
        n_new = {"known_last": (1, 2)}.get(case, (8, 6))
        req = [(_PROMPTS[0], n_new[0],
                eos if case == "eos" else None),
               (_PROMPTS[1], n_new[1], None)]
        rids = [eng.add_request(p, max_new_tokens=n, eos_token_id=e,
                                stream_cb=cb) for p, n, e in req]
        if case == "cancel":
            # cancel the first request with its next row in flight
            _drive(eng, until=lambda: len(streamed.get(rids[0], ())) >= 2)
            assert eng.slots[0].ahead_tokens == 1
            at_cancel = list(streamed[rids[0]])
            assert eng.cancel(rids[0])
            _drive(eng)
            assert streamed[rids[0]] == at_cancel
        elif case == "nan":
            # poison the first request's KV while its next row is in
            # flight: the step reading it quarantines the sequence
            _drive(eng, until=lambda: len(streamed.get(rids[0], ())) >= 2)
            with faults.inject("serving.decode_step",
                               call=lambda: eng.pool.poison_seq(rids[0]),
                               times=1) as spec:
                _drive(eng)
            assert spec.fired == 1
        else:
            _drive(eng)
        outs = eng.take_outputs()
        assert outs[rids[0]].finish_reason == {
            "cancel": "cancelled", "nan": "nan", "eos": "stop"}.get(
                case, "length")
        for rid, (p, n, e) in zip(rids, req):
            dense = _dense_gen(model, p, n, eos=e)
            got = np.asarray(outs[rid].token_ids)
            if outs[rid].finish_reason in ("length", "stop"):
                assert got.size == (3 if e is not None else n)
            np.testing.assert_array_equal(got, dense[:got.size])
            assert streamed.get(rid, []) == list(got)
        assert eng.pool.used_pages == 0 and not eng._flight
        c = eng.compile_counts()
        assert c["step"] == c["step_buckets"]
        counts = _counts(ring, eng)
        assert sum(s["landed"] for s in counts) == sum(
            len(streamed.get(r, ())) for r in rids)
        discarded = sum(s["discarded"] for s in counts)
        if case in ("eos", "cancel", "nan"):
            assert discarded >= 1      # the ended sequence's row in flight
        else:
            assert discarded == 0      # no row past a known last token
        assert sum(s["ahead"] for s in counts) >= len(counts) - 2

    def test_final_chunk_sample_feeds_the_next_row_on_the_device(self, ring):
        """With the final chunk in flight the slot's decode row is planned
        at position len(prompt) and takes its token from the chunk's
        sample, which the host has not seen yet."""
        model = _llama()
        eng = ServingEngine(model, page_size=4, max_batch_slots=2)
        rid = eng.add_request(_PROMPTS[1], max_new_tokens=4)
        eng.step()   # the prompt's one chunk lands; its decode row is out
        st = eng.slots[0]
        assert st.pos == _PROMPTS[1].size and len(st.gen) == 1
        assert st.ahead == 1 and st.ahead_tokens == 1
        rows = eng._flight[0].rows
        assert [(r[3][0], r[6]) for r in rows] == [(_PROMPTS[1].size, 0)]
        out = eng.run()[rid]
        np.testing.assert_array_equal(np.asarray(out.token_ids),
                                      _dense_gen(model, _PROMPTS[1], 4))

    @pytest.mark.parametrize("mode", ["plain", "grammar", "drafts"])
    def test_ahead_on_steady_decode_and_landing_first(self, ring, mode):
        """``ahead`` reads 1 on a call that launched its step behind the
        one in flight: every call of a steady decode but the first (a
        fresh bucket) and the last (nothing left to launch); 0 on every
        call while a grammar slot is live or the engine drafts."""
        from paddle_tpu.serving import GrammarFSM, toy_tokenizer

        model = _llama()
        eng = ServingEngine(model, page_size=4, max_batch_slots=2,
                            spec_k=2 if mode == "drafts" else 0)
        rid = eng.add_request(_PROMPTS[0], max_new_tokens=8)
        done_at = []      # the call in which the grammar request retires
        if mode == "grammar":
            fsm = GrammarFSM.compile("[a-z]{4,6}", toy_tokenizer(128))
            eng.add_request(
                _PROMPTS[2], max_new_tokens=8, grammar=fsm,
                stream_cb=lambda r, tok, fin, seq: fin and done_at.append(
                    eng.stats["steps"]))
        _drive(eng)
        outs = eng.take_outputs()
        np.testing.assert_array_equal(np.asarray(outs[rid].token_ids),
                                      _dense_gen(model, _PROMPTS[0], 8))
        ahead = [s["ahead"] for s in _counts(ring, eng)]
        if mode == "plain":
            # the first call's decode step is the engine's first in its
            # bucket: it compiles, so the chunk step lands before it goes
            assert ahead == [0] + [1] * (len(ahead) - 2) + [0]
        elif mode == "drafts":
            assert ahead == [0] * len(ahead)
        else:
            assert ahead[:done_at[0] + 1] == [0] * (done_at[0] + 1)
            assert ahead[-2:] == [1, 0]   # alone again, it runs ahead
        c = eng.compile_counts()
        assert c["step"] == c["step_buckets"]

    def test_one_call_lands_one_step(self, ring):
        """Callers that count tokens per step() see one step's tokens per
        call, as when nothing overlapped: one token per decoding slot."""
        eng = ServingEngine(_llama(), page_size=4, max_batch_slots=2)
        for p in _PROMPTS[:2]:
            eng.add_request(p, max_new_tokens=6)
        landed = []
        while eng.has_work:
            before = eng.stats["generated_tokens"]
            eng.step()
            landed.append(eng.stats["generated_tokens"] - before)
        assert landed == [2] * 6


@pytest.mark.slow
class TestBatchSweeps:
    @pytest.mark.parametrize("slots", [1, 4, 8])
    def test_oversubscribed_sweep_all_complete_and_match(self, slots):
        """2x-oversubscribed mixed workload at each slot count: every
        request completes and matches dense generate token-for-token."""
        model = _llama()
        rng = np.random.RandomState(11 + slots)
        work = [(rng.randint(0, 128, (int(rng.randint(2, 12)),)),
                 int(rng.randint(1, 8))) for _ in range(2 * slots)]
        dense = [_dense_gen(model, p, n) for p, n in work]
        engine = ServingEngine(model, page_size=4, max_batch_slots=slots)
        rids = [engine.add_request(p, max_new_tokens=n) for p, n in work]
        outs = engine.run()
        for rid, want in zip(rids, dense):
            np.testing.assert_array_equal(
                np.asarray(outs[rid].token_ids), want)
        counts = engine.compile_counts()
        assert counts["step"] == counts["step_buckets"]
        assert engine.pool.used_pages == 0
