"""Paged KV-cache pool: fixed page pool + per-sequence block tables.

The serving engine's memory substrate (PAPERS.md: Ragged Paged Attention,
arxiv 2604.15464 — vLLM-style paging on TPU): instead of one dense
``[B, max_len, nkv, hd]`` cache per request, every layer owns a fixed pool
of ``[num_pages, n_kv_heads, page_size, head_dim]`` K and V blocks, and a
sequence is a *list of page ids* (its block table). Admission, retirement,
and fork never move KV bytes — only page ids change hands — so the decode
step's shapes stay fixed while the live batch churns.

Page 0 is the reserved NULL page: block tables are 0-padded and idle batch
slots carry all-zero tables, so their (masked) KV writes land harmlessly
there instead of corrupting a live sequence. The allocator hands out pages
1..num_pages-1.

Sharing is REFCOUNTED and copy-on-write: ``fork`` shares every page of
the source (full and partial tail alike) by bumping refcounts, and the
first divergent append into a shared page copies it lazily
(:meth:`extend`'s write guard) — the sibling's bytes are never mutated.
:class:`PrefixCache` builds on the same refcounts: a per-engine radix
index keyed on token ids maps cached prompt prefixes to page lists, so a
request sharing a system prompt adopts the cached pages at admission and
ragged-prefills only its uncovered suffix (docs/SERVING.md "Prefix
caching"). Cache-resident pages that no live sequence references are
RECLAIMABLE: they never cause an allocation failure — ``_take_page``
evicts LRU cache nodes under pool pressure — and they are excluded from
``used_pages`` (which counts pages live sequences pin).

Allocation is LAZY (a page is taken from the free list only when a token
actually lands in it) but admission is accounted against each sequence's
worst case via ``reserve`` — the scheduler admits a request only if the
pool can cover every live sequence's ``prompt + max_new_tokens`` tail, so
a mid-decode out-of-pages abort is impossible without preemption.

Layout note: the last two axes are ``(page_size, head_dim)`` because the
TPU lowering of the paged kernel (ops/pallas/paged_attention.py) DMAs one
head's page per grid step and needs that block's last two dims to be the
array's own. The kv-head axis is second, so a later multi-chip serving PR
can still shard ``n_kv_heads`` over 'mp' without touching the allocator
or block tables (page ids are replicated host metadata).

Page TIERS (docs/SERVING.md "KV page tiers & quantization"):

- **int8 pages** — ``PagedKVCachePool(dtype="int8")`` stores pages as
  int8 with per-slot f32 absmax scales (``k_scales``/``v_scales``,
  ``[num_pages, n_kv_heads, page_size]``; quantization/observers.py owns
  the scale rule). Writes quantize inside the compiled step; reads
  dequantize in-kernel (ops/pallas/paged_attention.py) — a full-width
  page never exists in HBM. Every allocator semantic treats a scale row
  as part of its page: CoW copies scales with bytes, lazy scrub zeroes
  both, poison lands in the SCALES (int8 cannot hold NaN; q × NaN = NaN
  through dequant), and fork/prefix adoption share scale rows for free
  because scales are page-indexed.
- **host tier** — :meth:`offload_seq` swaps a parked sequence's
  exclusively-owned written pages (bytes + scales, verbatim) into a
  host-RAM :class:`HostPageStore` and returns the HBM pages to the free
  list, ALSO releasing the sequence's unwritten-tail reservation — a
  parked tenant is a real preemption, so ``can_admit``/``used_pages``
  stay honest and admission prefers offload over rejection.
  :meth:`prefetch_seq` re-takes pages and scatters the saved bytes back
  bit-exactly BEFORE the slot's next step (the engine prefetches at
  unpark; the compiled step never blocks on a host→HBM copy).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .. import faults, metrics
from ..quantization.observers import (KV_SCALE_FLOOR, quantize_kv)
from ..tensor import Tensor

faults.declare_point(
    "serving.kv_alloc",
    "PagedKVCachePool._take_page, before a page leaves the free list — "
    "arm ResourceExhausted here to drill pool-exhaustion handling")

__all__ = ["PagedKVCachePool", "PrefixCache", "HostPageStore",
           "page_bytes", "pages_for_hbm_budget", "normalize_kv_dtype"]

_KV_DTYPE_ALIASES = {
    "f32": jnp.float32, "fp32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "f16": jnp.float16, "fp16": jnp.float16, "float16": jnp.float16,
    "int8": jnp.int8,
}


def normalize_kv_dtype(dtype):
    """Resolve a KV-page dtype knob — a string alias (``"bf16"``,
    ``"int8"``, ...) or a jnp/np dtype — to the jnp dtype the pool
    stores. int8 means QUANTIZED pages (per-slot scales ride along)."""
    if isinstance(dtype, str):
        try:
            return _KV_DTYPE_ALIASES[dtype.lower()]
        except KeyError:
            raise ValueError(
                f"unknown kv_dtype {dtype!r}; expected one of "
                f"{sorted(_KV_DTYPE_ALIASES)}") from None
    return dtype


def page_bytes(page_size: int, n_kv_heads: int, head_dim: int,
               num_layers: int, dtype_bytes: int = None,
               kv_dtype=None) -> int:
    """HBM bytes one page costs across ALL layers (K and V). Pass
    ``kv_dtype`` to derive the element width from the pool's ACTUAL page
    dtype (bf16 → 2, int8 → 1 plus the 4-byte f32 scale each slot
    carries); ``dtype_bytes`` is the legacy scalar override (defaults to
    4 = f32) and ignores scale overhead."""
    if kv_dtype is not None:
        if dtype_bytes is not None:
            raise ValueError("pass kv_dtype or dtype_bytes, not both")
        dt = jnp.dtype(normalize_kv_dtype(kv_dtype))
        scale_bytes = 4 if dt == jnp.int8 else 0
        return (2 * num_layers * page_size * n_kv_heads
                * (head_dim * dt.itemsize + scale_bytes))
    if dtype_bytes is None:
        dtype_bytes = 4
    return 2 * num_layers * page_size * n_kv_heads * head_dim * dtype_bytes


def pages_for_hbm_budget(hbm_bytes: int, page_size: int, n_kv_heads: int,
                         head_dim: int, num_layers: int,
                         dtype_bytes: int = None, kv_dtype=None) -> int:
    """Pool sizing math (docs/SERVING.md): pages = HBM budget / page bytes,
    minus nothing — the caller budgets weights/activations separately.
    ``kv_dtype`` sizes against the real page dtype incl. scale overhead
    (the users/chip lever: int8 roughly halves bytes/page)."""
    per = page_bytes(page_size, n_kv_heads, head_dim, num_layers,
                     dtype_bytes=dtype_bytes, kv_dtype=kv_dtype)
    return max(int(hbm_bytes) // per, 0)


class HostPageStore:
    """Host-RAM second page tier: a dict of ``(seq_id, page_index) →``
    per-layer numpy slabs, written by :meth:`PagedKVCachePool.offload_seq`
    and drained by :meth:`prefetch_seq`. Bytes (and int8 scales) are
    stored verbatim — device→host→device round-trips are bit-exact by
    construction (the warm_equals_cold contract of the offload tier).
    Plain host memory, no device handles: survives pool array swaps and
    costs zero HBM."""

    def __init__(self):
        self._pages: Dict[tuple, dict] = {}

    def __len__(self) -> int:
        return len(self._pages)

    def put(self, seq_id, page_index: int, payload: dict) -> None:
        self._pages[(seq_id, int(page_index))] = payload

    def pop(self, seq_id, page_index: int) -> dict:
        return self._pages.pop((seq_id, int(page_index)))

    def seq_pages(self, seq_id) -> List[int]:
        return sorted(pi for (s, pi) in self._pages if s == seq_id)

    def drop_seq(self, seq_id) -> int:
        """Discard a retiring sequence's host copies (no device writes —
        there is nothing to scrub: host bytes never enter a gather)."""
        keys = [k for k in self._pages if k[0] == seq_id]
        for k in keys:
            del self._pages[k]
        return len(keys)


class PagedKVCachePool:
    """Fixed K/V page pool per layer + block-table allocator.

    Device state: ``k_pools``/``v_pools`` — one framework Tensor per layer,
    shape ``[num_pages, n_kv_heads, page_size, head_dim]``. The compiled
    decode step consumes and returns them functionally; the engine swaps
    the fresh arrays back in via :meth:`set_arrays`.

    Host state: free list, per-page refcounts (fork shares full pages
    copy-on-nothing — pages are append-only once full), per-sequence block
    tables and lengths, worst-case reservations, and the high-water mark
    (``peak_used``) the page-reuse tests assert on.
    """

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 n_kv_heads: int, head_dim: int, dtype=jnp.float32,
                 engine_id: str = "", model_id: str = ""):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        # identity labels for the pool gauges: an engine passes its own
        # {engine_id, model_id} so N pools behind a Router stay N series
        # instead of last-writer-wins; a standalone pool reports under the
        # empty-string labels
        self._lbl = {"engine_id": str(engine_id), "model_id": str(model_id)}
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = normalize_kv_dtype(dtype)
        # int8 pages carry per-slot f32 absmax scales (module docstring,
        # "Page TIERS"); every page-granular allocator path below mirrors
        # its byte operation onto the scale arrays
        self.quantized = jnp.dtype(self.dtype) == jnp.int8
        shape = (self.num_pages, self.n_kv_heads, self.page_size,
                 self.head_dim)
        self.k_pools: List[Tensor] = [
            Tensor(jnp.zeros(shape, self.dtype), stop_gradient=True)
            for _ in range(self.num_layers)]
        self.v_pools: List[Tensor] = [
            Tensor(jnp.zeros(shape, self.dtype), stop_gradient=True)
            for _ in range(self.num_layers)]
        if self.quantized:
            sshape = shape[:3]  # [num_pages, n_kv_heads, page_size]
            self.k_scales: Optional[List[Tensor]] = [
                Tensor(jnp.zeros(sshape, jnp.float32), stop_gradient=True)
                for _ in range(self.num_layers)]
            self.v_scales: Optional[List[Tensor]] = [
                Tensor(jnp.zeros(sshape, jnp.float32), stop_gradient=True)
                for _ in range(self.num_layers)]
        else:
            self.k_scales = None
            self.v_scales = None
        # host offload tier: parked sequences' page bytes live here while
        # their HBM pages serve other tenants; _host_idx maps seq_id →
        # set of offloaded page indices (their table entries hold the
        # null-page sentinel 0), _parked_resv journals the tail
        # reservation released while parked
        self.host_store = HostPageStore()
        self._host_idx: Dict[object, set] = {}
        self._parked_resv: Dict[object, int] = {}
        # page 0 reserved: free list covers 1..num_pages-1 (LIFO for reuse
        # locality — a just-freed page is the next handed out)
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._ref = np.zeros(self.num_pages, np.int32)
        # pages freed by a NaN quarantine: zeroed lazily the moment they
        # are re-taken (free() with scrub=True) — masked attention gives
        # padding lanes weight 0, but 0 x NaN = NaN, so a poisoned page
        # must never enter a new block table un-scrubbed. Lazy keeps the
        # quarantine itself O(1): no full-pool rewrite per retirement.
        self._dirty: set = set()
        # refcount-aware deferred scrub (docs/RESILIENCE.md "Quarantine x
        # refcounts"): a quarantined victim's free(scrub=True) must NOT
        # zero a page a sibling fork / the prefix cache still reads —
        # such pages are only MARKED here, and the mark converts to a
        # real scrub when the LAST reference drops (whoever drops it),
        # so a suspect page can never re-enter circulation un-scrubbed.
        self._scrub_pending: set = set()
        # optional per-engine prefix cache; PrefixCache attaches itself
        self.prefix_cache: Optional["PrefixCache"] = None
        self._tables: Dict[object, List[int]] = {}
        self._lens: Dict[object, int] = {}
        self._resv: Dict[object, int] = {}
        self.peak_used = 0
        reg = metrics.get_registry()
        _eng = ("engine_id", "model_id")
        self._m_pages_used = reg.gauge(
            "paddle_tpu_serving_kv_pages_used",
            "KV pages currently allocated out of the pool",
            labels=_eng).labels(**self._lbl)
        self._m_pages_total = reg.gauge(
            "paddle_tpu_serving_kv_pages_total",
            "Usable KV pages in the pool (page 0 reserved excluded)",
            labels=_eng).labels(**self._lbl)
        self._m_page_events = reg.counter(
            "paddle_tpu_serving_kv_page_events_total",
            "Page allocator traffic", labels=("event",) + _eng)
        _tier = reg.gauge(
            "paddle_tpu_serving_kv_page_tier",
            "KV pages currently resident per tier: hbm = pages pinned by "
            "live sequences, host = pages parked in the HostPageStore",
            labels=("tier",) + _eng)
        self._m_tier_hbm = _tier.labels(tier="hbm", **self._lbl)
        self._m_tier_host = _tier.labels(tier="host", **self._lbl)
        self._m_offload = reg.counter(
            "paddle_tpu_serving_kv_offload_pages_total",
            "KV pages swapped HBM → host by offload_seq (parked tenants)",
            labels=_eng).labels(**self._lbl)
        self._m_prefetch = reg.counter(
            "paddle_tpu_serving_kv_prefetch_pages_total",
            "KV pages swapped host → HBM by prefetch_seq (unpark)",
            labels=_eng).labels(**self._lbl)
        self._m_scale_clips = reg.counter(
            "paddle_tpu_serving_kv_dequant_scale_clip_total",
            "Quantized KV slots written at the absmax scale floor "
            "(absmax underflowed KV_SCALE_FLOOR — dynamic range lost)",
            labels=_eng).labels(**self._lbl)
        self._refresh_gauges()

    def _refresh_gauges(self) -> None:
        """Re-set the pool gauges on every allocator event: the totals are
        re-published (not just set once at construction) so a registry
        ``reset()`` mid-life self-heals instead of reporting 0 capacity
        forever. Each pool owns its {engine_id, model_id} series; the
        family-level read aggregates the fleet (docs/OBSERVABILITY.md)."""
        self._m_pages_used.set(self.used_pages)
        self._m_pages_total.set(self.usable_pages)
        self._m_tier_hbm.set(self.used_pages)
        self._m_tier_host.set(len(self.host_store))

    # ---------------------------------------------------------- accounting
    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1

    @property
    def used_pages(self) -> int:
        """Pages pinned by LIVE sequences. Cache-resident pages no
        sequence references are excluded: they are reclaimable on demand
        (evict-then-retry in :meth:`_take_page`), so counting them as
        used would make a warm cache read as pressure it isn't."""
        return (self.usable_pages - len(self._free)
                - self._reclaimable_pages())

    def _reclaimable_pages(self) -> int:
        """Pages held ONLY by the prefix cache — evictable the moment an
        allocation needs them."""
        return (self.prefix_cache.reclaimable_pages()
                if self.prefix_cache is not None else 0)

    def utilization(self) -> float:
        return self.used_pages / max(self.usable_pages, 1)

    def pages_needed(self, n_tokens: int) -> int:
        return max(math.ceil(int(n_tokens) / self.page_size), 1)

    def _unallocated_reserved(self) -> int:
        """Pages promised to live sequences but not yet drawn from the
        free list (their lazy tails)."""
        return sum(max(r - len(self._tables[s]), 0)
                   for s, r in self._resv.items())

    def can_admit(self, max_total_tokens: int,
                  pending_pages: int = 0, cached_pages: int = 0,
                  pending_cached: int = 0) -> bool:
        """True when the pool can cover a new sequence's WORST CASE
        (``max_total_tokens`` = prompt + max_new_tokens) on top of every
        live sequence's outstanding reservation — the no-preemption
        admission guarantee. ``pending_pages`` charges pages promised to
        requests admitted earlier in the same scheduler step, whose
        reservations are not recorded here until their prefill runs.
        ``cached_pages`` discounts pages the prefix cache already holds
        for this request's prompt (they join its table by refcount, not
        by a free-list draw). Matched pages must ALSO leave the
        reclaimable side: the moment the request adopts them their
        refcount pins them, so counting them both as "not needed" and as
        "evictable for someone else" would double-count and overcommit —
        the victim being some LIVE sequence's reserved tail.
        ``pending_cached`` extends the same exclusion to pages matched
        by earlier same-step admissions (conservative when two
        batch-mates match the SAME pages: under-admission just waits a
        step; overcommit kills a tenant)."""
        need = self.pages_needed(max_total_tokens) - int(cached_pages)
        reclaim = max(self._reclaimable_pages() - int(cached_pages)
                      - int(pending_cached), 0)
        avail = len(self._free) + reclaim - self._unallocated_reserved()
        return need + int(pending_pages) <= avail

    # ---------------------------------------------------------- allocation
    def _take_page(self) -> int:
        faults.point("serving.kv_alloc")
        # cache-never-starves-tenants: under pool pressure, evict LRU
        # unreferenced prefix-cache nodes until a page frees — the cache
        # must never turn a coverable allocation into a failure
        while not self._free and self.prefix_cache is not None:
            if not self.prefix_cache.evict_one():
                break
        if not self._free:
            raise RuntimeError(
                "KV page pool exhausted — admission accounting should have "
                "prevented this (reserve() not called?)")
        p = self._free.pop()
        if p in self._dirty:
            # a quarantined page is about to re-enter a block table:
            # scrub ALL dirty pages in one batched update per layer
            # (each .at[].set copies the whole pool, so amortize the
            # copies over every pending page instead of paying them
            # per page)
            pages = jnp.asarray(sorted(self._dirty), jnp.int32)
            for li in range(self.num_layers):
                kp = self.k_pools[li]._value
                vp = self.v_pools[li]._value
                self.k_pools[li] = Tensor(
                    kp.at[pages].set(jnp.zeros((), kp.dtype)),
                    stop_gradient=True)
                self.v_pools[li] = Tensor(
                    vp.at[pages].set(jnp.zeros((), vp.dtype)),
                    stop_gradient=True)
                if self.quantized:
                    # poison lives in the SCALE rows on int8 pools —
                    # scrub them with the page bytes
                    ks = self.k_scales[li]._value
                    vs = self.v_scales[li]._value
                    self.k_scales[li] = Tensor(
                        ks.at[pages].set(jnp.zeros((), ks.dtype)),
                        stop_gradient=True)
                    self.v_scales[li] = Tensor(
                        vs.at[pages].set(jnp.zeros((), vs.dtype)),
                        stop_gradient=True)
            self._dirty.clear()
        self._ref[p] = 1
        self.peak_used = max(self.peak_used, self.used_pages)
        self._m_page_events.labels(event="alloc", **self._lbl).inc()
        self._refresh_gauges()
        return p

    def allocate(self, seq_id, n_tokens: int,
                 max_total_tokens: Optional[int] = None,
                 prefix_pages: Sequence[int] = (),
                 prefix_tokens: int = 0) -> List[int]:
        """Create a sequence holding ``n_tokens`` of KV (the prompt), with
        a worst-case reservation of ``max_total_tokens`` (defaults to
        ``n_tokens``). Returns the block table.

        ``prefix_pages``/``prefix_tokens`` seed the table with SHARED
        pages (a prefix-cache hit): each is adopted by refcount — no
        free-list draw, no KV copy — and the prefix refs are bumped
        BEFORE any fresh page is taken, so a mid-allocate eviction can
        never reclaim the very pages this sequence is adopting. Rollback
        (:meth:`free`) drops shared and fresh pages uniformly."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        if prefix_tokens and int(prefix_tokens) % self.page_size:
            raise ValueError(
                f"prefix_tokens {prefix_tokens} must be page-aligned "
                f"(page_size={self.page_size}) — prefix sharing is "
                f"full-page granular")
        resv = self.pages_needed(max_total_tokens
                                 if max_total_tokens is not None
                                 else n_tokens)
        table: List[int] = []
        for p in prefix_pages:
            self._ref[p] += 1
            table.append(p)
        self._tables[seq_id] = table
        self._lens[seq_id] = int(prefix_tokens)
        self._resv[seq_id] = resv
        if int(n_tokens) > int(prefix_tokens):
            try:
                self.extend(seq_id, n_tokens)
            except Exception:
                # atomic: a mid-allocate failure (real exhaustion or an
                # armed serving.kv_alloc fault) must not leak a half-built
                # sequence — roll back pages already taken and the
                # bookkeeping entries
                self.free(seq_id)
                raise
        # n_tokens == prefix_tokens is the chunked-prefill admission
        # path: the sequence starts as EXACTLY its adopted prefix (zero
        # fresh pages, zero writable-page checks — the next write lands
        # at position prefix_tokens, a page this table doesn't hold yet,
        # so CoW-copying the shared tail page here would only break the
        # sharing the adoption just paid for)
        self.peak_used = max(self.peak_used, self.used_pages)
        return list(self._tables[seq_id])

    def extend(self, seq_id, total_tokens: int) -> None:
        """Grow ``seq_id``'s table to cover ``total_tokens`` of KV, and
        guarantee the LAST slot (the one about to be written) lives in a
        page this sequence owns exclusively — the copy-on-write seam: a
        fork/prefix-share diverging into a shared page copies it here,
        first, so the sibling's (and the cache's) bytes are immutable."""
        self._assert_resident(seq_id, "extend")
        table = self._tables[seq_id]
        need = self.pages_needed(total_tokens)
        while len(table) < need:
            table.append(self._take_page())
        self._lens[seq_id] = max(self._lens[seq_id], int(total_tokens))
        self._ensure_writable(seq_id, int(total_tokens) - 1)

    def extend_write(self, seq_id, start: int, total_tokens: int) -> None:
        """Grow ``seq_id``'s table to cover ``total_tokens`` of KV and
        make EVERY page holding positions ``start .. total_tokens-1``
        exclusively owned — the multi-token variant of :meth:`extend`'s
        one-slot CoW seam. A unified-step prompt chunk scatters a whole
        token range in one compiled program, so any page it touches that
        a fork sibling or the prefix cache still references must be
        copied first (freshly drawn pages are exclusive by construction;
        in practice only the range's FIRST page can be shared — a
        partially written fork tail)."""
        start, total = int(start), int(total_tokens)
        if total <= start:
            return
        self._assert_resident(seq_id, "extend_write")
        table = self._tables[seq_id]
        need = self.pages_needed(total)
        while len(table) < need:
            table.append(self._take_page())
        self._lens[seq_id] = max(self._lens[seq_id], total)
        for pi in range(start // self.page_size,
                        (total - 1) // self.page_size + 1):
            self._ensure_page_writable(seq_id, pi)

    def truncate(self, seq_id, total_tokens: int) -> None:
        """Roll ``seq_id``'s KV length back to ``total_tokens`` — the
        speculative-decoding reject path: draft rows past the accepted
        prefix wrote KV for tokens that were never committed, and
        lowering ``_lens`` is ALL the rollback there is. The pages stay
        in the table (they sit inside the admission-time reservation, so
        nothing else can claim them) and their stale bytes are inert:
        paged attention masks every row at its own position, so KV past
        the sequence length is never gathered, and the next committed
        write at those positions scatters right over it. Refcounts are
        untouched — the rejected range was already made exclusively
        owned by the :meth:`extend_write` that reserved it, and a CoW'd
        page stays correctly owned for the retry."""
        total = int(total_tokens)
        cur = self._lens[seq_id]
        if total < 0 or total > cur:
            raise ValueError(
                f"truncate({seq_id!r}, {total}) outside [0, {cur}] — "
                f"rollback can only shorten a sequence")
        self._lens[seq_id] = total

    def _ensure_writable(self, seq_id, token_pos: int) -> None:
        """Copy-on-write: if the page holding ``token_pos`` is shared
        (refcount > 1 — a fork sibling or the prefix cache also holds
        it), copy its contents into a fresh page and swap the block-table
        entry, leaving the shared original untouched."""
        if token_pos < 0:
            return
        self._ensure_page_writable(seq_id, token_pos // self.page_size)

    def _ensure_page_writable(self, seq_id, pi: int) -> None:
        """CoW one block-table entry by page index (the shared seam of
        :meth:`extend` and :meth:`extend_write`)."""
        table = self._tables[seq_id]
        old = table[pi]
        if self._ref[old] <= 1:
            return
        fresh = self._take_page()
        for li in range(self.num_layers):
            kp = self.k_pools[li]._value
            vp = self.v_pools[li]._value
            self.k_pools[li] = Tensor(kp.at[fresh].set(kp[old]),
                                      stop_gradient=True)
            self.v_pools[li] = Tensor(vp.at[fresh].set(vp[old]),
                                      stop_gradient=True)
            if self.quantized:
                # CoW copies SCALES with pages — a sibling diverging into
                # a shared int8 page must not rescale the original's slots
                ks = self.k_scales[li]._value
                vs = self.v_scales[li]._value
                self.k_scales[li] = Tensor(ks.at[fresh].set(ks[old]),
                                           stop_gradient=True)
                self.v_scales[li] = Tensor(vs.at[fresh].set(vs[old]),
                                           stop_gradient=True)
        table[pi] = fresh
        # the shared original loses OUR reference only (cannot hit zero:
        # ref was > 1); scrub state, if any, stays with the original
        self._ref[old] -= 1
        self._m_page_events.labels(event="cow", **self._lbl).inc()
        self.peak_used = max(self.peak_used, self.used_pages)
        self._refresh_gauges()

    def append_token(self, seq_id) -> None:
        """Make room for one more token (the engine calls this right before
        the decode step writes position ``seq_len``)."""
        self.extend(seq_id, self._lens[seq_id] + 1)

    def _release_ref(self, p: int, scrub: bool = False) -> bool:
        """Drop ONE reference on page ``p`` (the single choreography every
        release path — sequence retirement, cache eviction — goes
        through, so scrub semantics cannot drift between them). Returns
        True when the page actually hit the free list.

        Refcount-aware scrub: a ``scrub=True`` release while siblings
        still hold the page must neither zero it now (a healthy tenant
        is reading those bytes) nor forget it — the page is marked
        scrub-pending, and WHOEVER drops the last reference (even a
        normal ``scrub=False`` retirement, even a cache eviction)
        converts the mark into a real lazy scrub before reuse."""
        self._ref[p] -= 1
        if self._ref[p] > 0:
            if scrub:
                self._scrub_pending.add(p)
            return False
        self._free.append(p)
        if scrub or p in self._scrub_pending:
            self._dirty.add(p)
        self._scrub_pending.discard(p)
        self._m_page_events.labels(event="free", **self._lbl).inc()
        return True

    def free(self, seq_id, scrub: bool = False) -> None:
        """Retire a sequence NOW: drop refcounts, return exclusive pages to
        the free list (immediate reuse — the continuous-batching payoff).
        ``scrub=True`` (NaN quarantine) marks the freed pages dirty so
        :meth:`_take_page` zeroes each one lazily on reuse; pages a fork
        sibling or the prefix cache still references are deferred via
        :meth:`_release_ref` — scrubbed only at refcount zero.

        A sequence retiring with OFFLOADED pages (parked, then cancelled
        or deadline-swept, or exported for migration) drops its host
        copies without any device write: those table entries hold the
        null-page sentinel — their HBM pages were already released at
        offload time — so releasing them again would corrupt page 0's
        refcount. Host bytes never enter a gather, so there is nothing
        to scrub on that tier (docs/RESILIENCE.md)."""
        table = self._tables.pop(seq_id)
        self._lens.pop(seq_id)
        self._resv.pop(seq_id, None)
        self._parked_resv.pop(seq_id, None)
        off = self._host_idx.pop(seq_id, ())
        self.host_store.drop_seq(seq_id)
        for pi, p in enumerate(table):
            if pi in off:
                continue
            self._release_ref(p, scrub=scrub)
        self._refresh_gauges()

    def fork(self, src_id, dst_id, max_total_tokens: Optional[int] = None
             ) -> List[int]:
        """Fork ``src_id`` into ``dst_id`` sharing EVERY page by refcount
        — full pages and the partial tail alike. Nothing is copied at
        fork time: the first divergent append into the shared tail
        triggers copy-on-write (:meth:`extend`'s write guard), so a fork
        that never diverges (parallel scoring, n-best over a shared
        prompt) costs zero KV bytes. The substrate for prefix caching /
        parallel sampling."""
        if dst_id in self._tables:
            raise ValueError(f"sequence {dst_id!r} already allocated")
        self._assert_resident(src_id, "fork")
        src = self._tables[src_id]
        n = self._lens[src_id]
        table: List[int] = []
        for p in src:
            self._ref[p] += 1
            table.append(p)
        self._tables[dst_id] = table
        self._lens[dst_id] = n
        self._resv[dst_id] = self.pages_needed(
            max_total_tokens if max_total_tokens is not None else n)
        self.peak_used = max(self.peak_used, self.used_pages)
        return list(table)

    # ------------------------------------------------------- host tier
    def _assert_resident(self, seq_id, op: str) -> None:
        """Writes, forks, and poison require every page in HBM — an
        offloaded table entry is the null-page sentinel 0, so touching it
        would read/write the reserved page. The engine upholds this by
        excluding parked slots from the step grid and prefetching at
        unpark; this guard turns a policy bug into a loud error instead
        of silent corruption."""
        if self._host_idx.get(seq_id):
            raise RuntimeError(
                f"{op}({seq_id!r}): sequence has "
                f"{len(self._host_idx[seq_id])} offloaded page(s) — "
                f"prefetch_seq() must restore them first")

    def offloaded_pages(self, seq_id=None) -> int:
        """Pages currently parked on the host tier — for one sequence, or
        pool-wide with ``seq_id=None``."""
        if seq_id is not None:
            return len(self._host_idx.get(seq_id, ()))
        return len(self.host_store)

    def spare_pages(self) -> int:
        """Pages the pool could hand out RIGHT NOW without breaking any
        live sequence's reservation: free + cache-reclaimable − promised
        lazy tails. The engine's park/unpark policy reasons in this
        currency (admit the queue head, re-admit a parked tenant)."""
        return (len(self._free) + self._reclaimable_pages()
                - self._unallocated_reserved())

    def can_prefetch(self, seq_id) -> bool:
        """True when :meth:`prefetch_seq` can restore ``seq_id`` AND
        re-assume its worst-case tail reservation without overcommitting
        — unpark is an admission in reverse, held to the same
        no-preemption arithmetic as :meth:`can_admit`."""
        off = self._host_idx.get(seq_id)
        if not off:
            return True
        tail = max(self._parked_resv.get(seq_id, 0)
                   - len(self._tables[seq_id]), 0)
        return len(off) + tail <= self.spare_pages()

    def prefetch_cost(self, seq_id) -> int:
        """Pages :meth:`prefetch_seq` would charge against
        :meth:`spare_pages` — offloaded pages to restore plus the
        journaled tail reservation to re-assume. The engine's anti-thrash
        check subtracts this before unparking so the queue head's next
        admission is never displaced by the tenant it preempted."""
        off = self._host_idx.get(seq_id)
        if not off:
            return 0
        tail = max(self._parked_resv.get(seq_id, 0)
                   - len(self._tables[seq_id]), 0)
        return len(off) + tail

    def offload_seq(self, seq_id) -> int:
        """Swap ``seq_id``'s exclusively-owned written pages to the host
        tier (bytes + int8 scales, verbatim — the round-trip is
        bit-exact) and release BOTH the HBM pages and the sequence's
        unwritten-tail reservation. Shared pages (prefix cache / fork
        siblings hold them) stay resident: other tenants gather them for
        real. Returns pages moved; idempotent on a parked sequence.

        Capacity honesty: freed pages land on the free list, the tail
        reservation is journaled into ``_parked_resv`` and zeroed, so
        ``can_admit`` sees a parked tenant as fully preempted — the
        eviction order "offload before prefix-evict" follows because the
        engine parks victims BEFORE any allocation walks
        :meth:`_take_page`'s cache-eviction loop."""
        table = self._tables[seq_id]
        n = int(self._lens[seq_id])
        off = self._host_idx.setdefault(seq_id, set())
        written = self.pages_needed(n) if n > 0 else 0
        move = [pi for pi in range(min(written, len(table)))
                if pi not in off and self._ref[table[pi]] == 1]
        if seq_id not in self._parked_resv:
            self._parked_resv[seq_id] = self._resv.get(seq_id, 0)
            self._resv[seq_id] = 0
        if move:
            pages = jnp.asarray(np.asarray([table[pi] for pi in move],
                                           np.int32))
            for pi in move:
                payload = {"k": [], "v": []}
                if self.quantized:
                    payload["ks"], payload["vs"] = [], []
                self.host_store.put(seq_id, pi, payload)
            # one gather per layer per array, then split per page — the
            # device→host copy happens HERE (park time, off the step
            # path), never inside a compiled step
            for li in range(self.num_layers):
                kslab = np.asarray(self.k_pools[li]._value[pages])
                vslab = np.asarray(self.v_pools[li]._value[pages])
                for j, pi in enumerate(move):
                    pl = self.host_store._pages[(seq_id, pi)]
                    pl["k"].append(kslab[j])
                    pl["v"].append(vslab[j])
                if self.quantized:
                    ksc = np.asarray(self.k_scales[li]._value[pages])
                    vsc = np.asarray(self.v_scales[li]._value[pages])
                    for j, pi in enumerate(move):
                        pl = self.host_store._pages[(seq_id, pi)]
                        pl["ks"].append(ksc[j])
                        pl["vs"].append(vsc[j])
            for pi in move:
                off.add(pi)
                self._release_ref(table[pi])
                table[pi] = 0
            self._m_offload.inc(len(move))
            self._m_page_events.labels(event="offload", **self._lbl).inc(
                len(move))
        self._refresh_gauges()
        return len(move)

    def prefetch_seq(self, seq_id) -> int:
        """Restore every offloaded page of ``seq_id`` into freshly drawn
        HBM pages (bytes + scales scattered back verbatim → bit-exact)
        and re-assume the journaled tail reservation. All-or-nothing: if
        the pool cannot cover the restore, pages taken so far return to
        the free list and the sequence stays parked. The engine calls
        this at UNPARK, before the slot re-enters the step grid — the
        compiled step itself never waits on a host→HBM copy."""
        off = self._host_idx.get(seq_id)
        if not off:
            # nothing on the host tier; still restore a journaled tail
            # reservation (a park that moved zero pages — all shared)
            if seq_id in self._parked_resv:
                self._resv[seq_id] = max(self._parked_resv.pop(seq_id),
                                         self._resv.get(seq_id, 0))
            return 0
        table = self._tables[seq_id]
        idxs = sorted(off)
        fresh: List[int] = []
        try:
            for _ in idxs:
                fresh.append(self._take_page())
        except Exception:
            for p in fresh:
                self._release_ref(p)
            self._refresh_gauges()
            raise
        pages = jnp.asarray(np.asarray(fresh, np.int32))
        payloads = [self.host_store.pop(seq_id, pi) for pi in idxs]
        for li in range(self.num_layers):
            kp = self.k_pools[li]._value
            vp = self.v_pools[li]._value
            kslab = jnp.asarray(np.stack([p["k"][li] for p in payloads]))
            vslab = jnp.asarray(np.stack([p["v"][li] for p in payloads]))
            self.k_pools[li] = Tensor(kp.at[pages].set(kslab),
                                      stop_gradient=True)
            self.v_pools[li] = Tensor(vp.at[pages].set(vslab),
                                      stop_gradient=True)
            if self.quantized:
                ks = self.k_scales[li]._value
                vs = self.v_scales[li]._value
                kssl = jnp.asarray(np.stack([p["ks"][li]
                                             for p in payloads]))
                vssl = jnp.asarray(np.stack([p["vs"][li]
                                             for p in payloads]))
                self.k_scales[li] = Tensor(ks.at[pages].set(kssl),
                                           stop_gradient=True)
                self.v_scales[li] = Tensor(vs.at[pages].set(vssl),
                                           stop_gradient=True)
        for pi, p in zip(idxs, fresh):
            table[pi] = p
        self._host_idx.pop(seq_id, None)
        if seq_id in self._parked_resv:
            self._resv[seq_id] = max(self._parked_resv.pop(seq_id),
                                     self._resv.get(seq_id, 0))
        self._m_prefetch.inc(len(idxs))
        self._m_page_events.labels(event="prefetch", **self._lbl).inc(
            len(idxs))
        self.peak_used = max(self.peak_used, self.used_pages)
        self._refresh_gauges()
        return len(idxs)

    def record_scale_clips(self, page_ids, offs) -> int:
        """Count this step's written slots whose absmax scale clamped at
        KV_SCALE_FLOOR (all layers, K and V) and move the
        ``kv_dequant_scale_clip_total`` counter. The engine calls this
        with the step's (page, offset) coords right after the program
        returns — a floor-clamped slot quantized with its dynamic range
        collapsed (absmax underflow), the one int8 failure mode absmax
        scaling cannot round away (docs/OBSERVABILITY.md)."""
        if not self.quantized or len(page_ids) == 0:
            return 0
        pages = jnp.asarray(np.asarray(page_ids, np.int32))
        oo = jnp.asarray(np.asarray(offs, np.int32))
        floor = jnp.float32(KV_SCALE_FLOOR)
        n = 0
        for li in range(self.num_layers):
            n += int(jnp.sum(
                self.k_scales[li]._value[pages, :, oo] <= floor))
            n += int(jnp.sum(
                self.v_scales[li]._value[pages, :, oo] <= floor))
        if n:
            self._m_scale_clips.inc(n)
        return n

    def _slot_coords(self, seq_id, n_tokens: int, start: int = 0):
        """(page_ids, offs) device coords of a sequence's KV slots
        ``start .. start+n_tokens-1`` — THE block-table indexing math,
        shared by every pool-rewrite path so it cannot drift between
        them."""
        table = np.asarray(self._tables[seq_id], np.int32)
        idx = np.arange(int(start), int(start) + int(n_tokens))
        return (jnp.asarray(table[idx // self.page_size]),
                jnp.asarray(idx % self.page_size))

    def poison_seq(self, seq_id, value: float = float("nan")) -> int:
        """Chaos helper (tests/test_faults.py, tools/chaos_serve.py):
        overwrite every EXCLUSIVELY-OWNED written KV slot of one sequence
        with ``value`` (default NaN), all layers, K and V. Shared pages
        (refcount > 1 — a fork sibling or the prefix cache holds them)
        are skipped: attention gathers shared bytes for REAL, so
        poisoning them would corrupt healthy tenants — a different drill
        than "this one sequence's KV went bad". Raises if the sequence
        has no exclusive written slots (the drill would silently no-op).
        Returns slots poisoned.

        int8 pools poison the SCALE rows instead of the page bytes: an
        int8 slot cannot hold NaN, but ``q × NaN = NaN`` through the
        in-kernel dequant, so a poisoned scale contaminates attention
        exactly like a poisoned bf16 slot would — and the lazy scrub
        zeroes scale rows with their pages (:meth:`_take_page`)."""
        self._assert_resident(seq_id, "poison_seq")
        n = int(self._lens[seq_id])
        table = self._tables[seq_id]
        idx = np.arange(n)
        excl = self._ref[np.asarray(table, np.int32)[
            idx // self.page_size]] == 1
        idx = idx[excl]
        if idx.size == 0:
            raise ValueError(
                f"poison_seq({seq_id!r}): every written page is shared "
                f"(fork sibling or prefix cache holds a reference) — "
                f"poisoning would corrupt healthy tenants; poison a "
                f"sequence with exclusive pages instead")
        page_ids = jnp.asarray(
            np.asarray(table, np.int32)[idx // self.page_size])
        offs = jnp.asarray(idx % self.page_size)
        if self.quantized:
            for li in range(self.num_layers):
                ks = self.k_scales[li]._value
                vs = self.v_scales[li]._value
                self.k_scales[li] = Tensor(
                    ks.at[page_ids, :, offs].set(
                        jnp.asarray(value, ks.dtype)), stop_gradient=True)
                self.v_scales[li] = Tensor(
                    vs.at[page_ids, :, offs].set(
                        jnp.asarray(value, vs.dtype)), stop_gradient=True)
            return int(idx.size)
        for li in range(self.num_layers):
            kp = self.k_pools[li]._value
            vp = self.v_pools[li]._value
            self.k_pools[li] = Tensor(
                kp.at[page_ids, :, offs].set(jnp.asarray(value, kp.dtype)),
                stop_gradient=True)
            self.v_pools[li] = Tensor(
                vp.at[page_ids, :, offs].set(jnp.asarray(value, vp.dtype)),
                stop_gradient=True)
        return int(idx.size)

    # ------------------------------------------------------------- queries
    def has_seq(self, seq_id) -> bool:
        return seq_id in self._tables

    def seq_len(self, seq_id) -> int:
        return self._lens[seq_id]

    def block_table(self, seq_id) -> List[int]:
        return list(self._tables[seq_id])

    def block_table_array(self, seq_ids: Sequence, width: int) -> np.ndarray:
        """Padded [len(seq_ids), width] int32 block-table batch; ``None``
        entries (idle slots) and table tails pad with the null page 0."""
        out = np.zeros((len(seq_ids), width), np.int32)
        for i, s in enumerate(seq_ids):
            if s is None:
                continue
            t = self._tables[s]
            if len(t) > width:
                raise ValueError(
                    f"sequence {s!r} spans {len(t)} pages > table width "
                    f"{width}")
            out[i, :len(t)] = t
        return out

    # ---------------------------------------------------------- cache hooks
    def attach_prefix_cache(self, cache: "PrefixCache") -> None:
        if self.prefix_cache is not None and self.prefix_cache is not cache:
            raise ValueError("pool already has a prefix cache attached")
        self.prefix_cache = cache

    # ------------------------------------------------------- device arrays
    def set_arrays(self, k_arrays, v_arrays, k_scales=None,
                   v_scales=None) -> None:
        """Swap in the pools a compiled decode step returned (functional
        update — the engine's step owns the only in-flight copy). A
        quantized pool's step also returns the updated scale arrays."""
        self.k_pools = [t if isinstance(t, Tensor)
                        else Tensor(t, stop_gradient=True)
                        for t in k_arrays]
        self.v_pools = [t if isinstance(t, Tensor)
                        else Tensor(t, stop_gradient=True)
                        for t in v_arrays]
        if k_scales is not None:
            self.k_scales = [t if isinstance(t, Tensor)
                             else Tensor(t, stop_gradient=True)
                             for t in k_scales]
            self.v_scales = [t if isinstance(t, Tensor)
                             else Tensor(t, stop_gradient=True)
                             for t in v_scales]

    @property
    def step_stride(self) -> int:
        """Device arrays one layer contributes to the compiled step's
        flat cache operands: (k, v) or (k, v, k_scale, v_scale)."""
        return 4 if self.quantized else 2

    def step_arrays(self, li: int):
        """Layer ``li``'s cache in step-operand order: what the trunk's
        layer ``li`` is handed as its ``cache`` and what
        ``ops/paged_cache.paged_attend`` opens."""
        if self.quantized:
            return (self.k_pools[li], self.v_pools[li],
                    self.k_scales[li], self.v_scales[li])
        return (self.k_pools[li], self.v_pools[li])

    def step_flat(self, caches=None) -> list:
        """Per-layer caches (the pool's own :meth:`step_arrays` by default;
        inside the step program, the updated ones the trunk returned),
        concatenated: the operands the compiled step takes, gives up (they
        are donated) and returns updated, in this order (``k0, v0[, ks0,
        vs0], k1, ...``)."""
        if caches is None:
            caches = map(self.step_arrays, range(self.num_layers))
        return [t for c in caches for t in c]

    def layer_caches(self, flat) -> list:
        """Regroup a :meth:`step_flat`-ordered sequence (arrays, or the
        tracers the step program sees in their place) into one ``cache``
        per layer: the inverse of the concatenation."""
        s = self.step_stride
        return [tuple(flat[s * li: s * (li + 1)])
                for li in range(self.num_layers)]

    def set_step_flat(self, flat) -> None:
        """The way back: accept the compiled step's flat cache outputs
        (:meth:`step_flat`'s order) and swap every array (and scale
        array, when quantized) back in."""
        caches = self.layer_caches(flat)
        self.set_arrays(
            [c[0] for c in caches], [c[1] for c in caches],
            k_scales=[c[2] for c in caches] if self.quantized else None,
            v_scales=[c[3] for c in caches] if self.quantized else None)

    def write_prompt_kv(self, seq_id, layer_kv, start: int = 0) -> None:
        """Prefill's KV write hook: scatter a dense prompt cache into this
        sequence's pages at positions ``start .. start+S-1``. ``layer_kv``
        is a per-layer list of (k, v) arrays ``[S, n_kv_heads, head_dim]``
        (S = true token count; any padded prefill tail must already be
        sliced off). ``start`` > 0 is the prefix-cache suffix scatter:
        matched (shared) pages cover 0..start-1 and are never written —
        match granularity is full pages, so the suffix begins on a page
        this sequence owns. Quantized pools quantize here (per-slot
        absmax, quantization/observers.py) and scatter values + scales —
        the same grid the in-step scatter writes, so prefill-written and
        decode-written slots dequantize identically."""
        self._assert_resident(seq_id, "write_prompt_kv")
        s = int(layer_kv[0][0].shape[0])
        page_ids, offs = self._slot_coords(seq_id, s, start=start)
        for li, (k, v) in enumerate(layer_kv):
            kp = self.k_pools[li]._value
            vp = self.v_pools[li]._value
            if self.quantized:
                kq, ksc = quantize_kv(jnp.asarray(k))
                vq, vsc = quantize_kv(jnp.asarray(v))
                self.k_pools[li] = Tensor(
                    kp.at[page_ids, :, offs].set(kq), stop_gradient=True)
                self.v_pools[li] = Tensor(
                    vp.at[page_ids, :, offs].set(vq), stop_gradient=True)
                ks = self.k_scales[li]._value
                vs = self.v_scales[li]._value
                self.k_scales[li] = Tensor(
                    ks.at[page_ids, :, offs].set(ksc), stop_gradient=True)
                self.v_scales[li] = Tensor(
                    vs.at[page_ids, :, offs].set(vsc), stop_gradient=True)
                continue
            self.k_pools[li] = Tensor(
                kp.at[page_ids, :, offs].set(
                    jnp.asarray(k).astype(kp.dtype)), stop_gradient=True)
            self.v_pools[li] = Tensor(
                vp.at[page_ids, :, offs].set(
                    jnp.asarray(v).astype(vp.dtype)), stop_gradient=True)
        if self.quantized:
            self.record_scale_clips(np.asarray(page_ids),
                                    np.asarray(offs))

    def gather_kv_range(self, page_ids: Sequence[int], n_tokens: int):
        """Read ``n_tokens`` of KV back out through a page list: per-layer
        list of (k, v) arrays ``[n_tokens, n_kv_heads, head_dim]`` — the
        prefix-cache hit path loads these into the suffix prefill's dense
        cache buffers (positions 0..n_tokens-1, already rope'd exactly as
        the original prefill wrote them). Quantized pools return the
        DEQUANTIZED f32 values (toleranced, like quantized attention
        itself) — callers consume values, not codes."""
        table = np.asarray(page_ids, np.int32)
        idx = np.arange(int(n_tokens))
        pages = jnp.asarray(table[idx // self.page_size])
        offs = jnp.asarray(idx % self.page_size)
        out = []
        for li in range(self.num_layers):
            k = self.k_pools[li]._value[pages, :, offs]
            v = self.v_pools[li]._value[pages, :, offs]
            if self.quantized:
                k = (k.astype(jnp.float32)
                     * self.k_scales[li]._value[pages, :, offs][..., None])
                v = (v.astype(jnp.float32)
                     * self.v_scales[li]._value[pages, :, offs][..., None])
            out.append((k, v))
        return out

    def prefix_match_len(self, token_ids) -> int:
        """Read-only probe of the attached prefix cache (0 without one):
        tokens a live admission would adopt instead of prefilling — the
        scheduler charges its prefill budget with only the uncovered
        suffix (docs/SERVING.md "Prefix caching")."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.probe(token_ids)


class _PrefixNode:
    """One radix-tree edge = one FULL page of tokens. The path from the
    root to a node spells a token prefix (page_size tokens per hop); the
    node holds the page id whose KV covers that path's last page — KV at
    any position depends on every token before it (causal attention), so
    a page is reusable exactly when the WHOLE prefix matches, which is
    what keying each hop by its page's token bytes enforces."""

    __slots__ = ("key", "page", "parent", "children", "last_used",
                 "detached")

    def __init__(self, key: bytes, page: int, parent):
        self.key = key
        self.page = page
        self.parent = parent
        self.children: Dict[bytes, "_PrefixNode"] = {}
        self.last_used = 0
        self.detached = False


class PrefixCache:
    """Per-engine radix index over cached prompt prefixes → page lists.

    Built entirely on the pool's refcounts: every resident node holds ONE
    reference on its page, a live sequence that matched the node holds its
    own (via its block table), so a page is reclaimable exactly when the
    cache's reference is the last one. Admission calls :meth:`match` for
    the longest cached prefix (full-page granular, capped one token short
    of the prompt so there is always a suffix to prefill — the sample at
    position s-1 needs its logits computed), adopts the matched pages by
    refcount, ragged-prefills only the uncovered suffix, and
    :meth:`insert`\\ s its own full prompt pages for the next request.

    Eviction is LRU over unreferenced nodes, leaf-first (a pinned
    descendant pins nothing here: a sequence that matched a deep node
    holds refs on every page along the path, so an unpinned node's whole
    subtree is unpinned). The pool drives it from ``_take_page`` under
    pressure — the cache can never turn a coverable allocation into a
    failure — and the engine drives :meth:`evict_nodes` when a NaN
    quarantine makes a just-inserted prefix suspect.

    Telemetry ({engine_id, model_id} from the owning pool):
    ``paddle_tpu_serving_prefix_{hits,misses}_total``,
    ``paddle_tpu_serving_prefill_tokens_saved_total``,
    ``paddle_tpu_serving_prefix_cached_pages`` gauge,
    ``paddle_tpu_serving_prefix_evictions_total``.
    """

    def __init__(self, pool: PagedKVCachePool):
        self.pool = pool
        pool.attach_prefix_cache(self)
        self.page_size = pool.page_size
        self._root = _PrefixNode(b"", 0, None)
        # id-keyed for O(1) removal on eviction (a warm cache evicts on
        # the allocation hot path); _page_arr caches the resident page
        # ids for the vectorized reclaimable count, rebuilt lazily only
        # when the node set changes
        self._nodes: Dict[int, _PrefixNode] = {}
        self._page_arr: Optional[np.ndarray] = None
        self._clock = 0
        reg = metrics.get_registry()
        _eng = ("engine_id", "model_id")
        lbl = pool._lbl
        self._m_hits = reg.counter(
            "paddle_tpu_serving_prefix_hits_total",
            "Admissions that matched a cached prefix and prefilled only "
            "their uncovered suffix", labels=_eng).labels(**lbl)
        self._m_misses = reg.counter(
            "paddle_tpu_serving_prefix_misses_total",
            "Admissions that found no cached prefix (full prefill)",
            labels=_eng).labels(**lbl)
        self._m_saved = reg.counter(
            "paddle_tpu_serving_prefill_tokens_saved_total",
            "Prompt tokens NOT prefilled because a cached prefix covered "
            "them (the prefix-cache capacity win)",
            labels=_eng).labels(**lbl)
        self._m_pages = reg.gauge(
            "paddle_tpu_serving_prefix_cached_pages",
            "KV pages currently resident in the prefix cache (shared "
            "pages pinned by live sequences included)",
            labels=_eng).labels(**lbl)
        self._m_evictions = reg.counter(
            "paddle_tpu_serving_prefix_evictions_total",
            "Cache nodes evicted (LRU under pool pressure, or quarantine "
            "of a suspect prefix)", labels=_eng).labels(**lbl)
        self._m_pages.set(0)

    # -------------------------------------------------------------- queries
    def __len__(self) -> int:
        return len(self._nodes)

    def reclaimable_pages(self) -> int:
        """Resident pages no live sequence references (pool refcount is
        exactly the cache's own) — what eviction can hand back. O(cache)
        per call; bounded by pool size."""
        if not self._nodes:
            return 0
        if self._page_arr is None:
            self._page_arr = np.fromiter(
                (n.page for n in self._nodes.values()), np.int32,
                len(self._nodes))
        return int(np.count_nonzero(self.pool._ref[self._page_arr] == 1))

    def _walk(self, ids: np.ndarray, touch: bool):
        """Longest-prefix walk: full pages only, capped at len(ids)-1
        tokens (at least one token must remain to prefill — its logits
        produce the first sample). Returns the node path."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        max_pages = max(int(ids.size) - 1, 0) // self.page_size
        path: List[_PrefixNode] = []
        cur = self._root
        for i in range(max_pages):
            key = ids[i * self.page_size:(i + 1) * self.page_size].tobytes()
            node = cur.children.get(key)
            if node is None:
                break
            path.append(node)
            cur = node
        if touch and path:
            self._clock += 1
            for n in path:
                n.last_used = self._clock
        return path

    def probe(self, ids) -> int:
        """Read-only match length in tokens (no LRU touch, no counters) —
        the scheduler's budget-honesty probe."""
        return len(self._walk(ids, touch=False)) * self.page_size

    def match(self, ids):
        """Longest cached prefix for ``ids``: (matched_tokens,
        page_ids, nodes). Touches LRU and moves the hit/miss counters;
        the caller adopts the pages by refcount via
        ``pool.allocate(..., prefix_pages=..., prefix_tokens=...)``."""
        path = self._walk(ids, touch=True)
        if not path:
            self._m_misses.inc()
            return 0, [], []
        self._m_hits.inc()
        matched = len(path) * self.page_size
        self._m_saved.inc(matched)
        return matched, [n.page for n in path], path

    # ------------------------------------------------------------ mutation
    def insert(self, ids, n_tokens: int, table: Sequence[int]
               ) -> List[_PrefixNode]:
        """Index every FULL page of ``ids[:n_tokens]`` (a just-prefilled
        prompt), taking one cache reference per NEWLY created node on the
        sequence's own page from ``table``. Pages whose prefix is already
        cached keep the existing node (and its page — the newcomer's
        private copy retires with it). Returns the nodes created here, in
        shallow-to-deep order (the engine journals them for quarantine
        eviction)."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        n_full = min(int(n_tokens), int(ids.size)) // self.page_size
        created: List[_PrefixNode] = []
        cur = self._root
        self._clock += 1
        for i in range(n_full):
            key = ids[i * self.page_size:(i + 1) * self.page_size].tobytes()
            node = cur.children.get(key)
            if node is None:
                node = _PrefixNode(key, int(table[i]), cur)
                cur.children[key] = node
                self.pool._ref[node.page] += 1
                self._nodes[id(node)] = node
                self._page_arr = None
                created.append(node)
            node.last_used = self._clock
            cur = node
        if created:
            self._m_pages.set(len(self._nodes))
            self.pool._refresh_gauges()
        return created

    def _detach(self, node: _PrefixNode, scrub: bool = False) -> bool:
        """Remove one childless node from the index and release the
        cache's page reference. Returns True when the page hit the free
        list (it may stay allocated: a live sequence still holds it)."""
        if node.detached:
            return False
        assert not node.children, "evicting a node with children"
        node.detached = True
        node.parent.children.pop(node.key, None)
        self._nodes.pop(id(node), None)
        self._page_arr = None
        freed = self.pool._release_ref(node.page, scrub=scrub)
        self._m_evictions.inc()
        self._m_pages.set(len(self._nodes))
        return freed

    def evict_one(self) -> bool:
        """LRU eviction step for ``_take_page`` under pool pressure:
        drop the least-recently-used unreferenced LEAF (leaf-first keeps
        the index consistent; an unpinned node's subtree is always
        unpinned, see class docstring). Returns True when a page was
        actually returned to the free list."""
        best: Optional[_PrefixNode] = None
        for n in self._nodes.values():
            if n.children or self.pool._ref[n.page] != 1:
                continue
            if best is None or n.last_used < best.last_used:
                best = n
        if best is None:
            return False
        freed = self._detach(best)
        self.pool._refresh_gauges()
        return freed

    def evict_nodes(self, nodes: Sequence[_PrefixNode]) -> None:
        """Quarantine eviction (engine's NaN path): drop these nodes AND
        their subtrees from the index — prefixes inserted from a
        poisoned request's KV, plus anything built on top of them, must
        never serve another admission. Pages pinned by live sequences
        stay allocated until those retire; the release is scrub-marked
        so a suspect page is zeroed before any reuse."""
        for node in nodes:
            self._evict_subtree(node, scrub=True)
        self.pool._refresh_gauges()

    def clear(self) -> int:
        """Flush the whole index (returns nodes evicted). REQUIRED after
        a weight change (``Router.reload``): cached KV was computed
        under the old weights, so a warm hit would mix stale prefix KV
        with new-weight suffix compute — silently wrong outputs. No
        scrub: stale-but-finite bytes are annihilated by attention masks
        like any retired page's."""
        n = len(self._nodes)
        for child in list(self._root.children.values()):
            self._evict_subtree(child, scrub=False)
        self.pool._refresh_gauges()
        return n

    def _evict_subtree(self, node: _PrefixNode, scrub: bool) -> None:
        if node.detached:
            return
        for child in list(node.children.values()):
            self._evict_subtree(child, scrub)
        self._detach(node, scrub=scrub)
