"""Continuous-batching LLM inference engine over the paged KV cache.

The serving counterpart of ``GenerationMixin.generate`` (one static batch,
dense caches): requests join and retire MID-DECODE. The engine keeps a
fixed grid of ``max_batch_slots`` slots and runs ONE **unified ragged
step** for the whole batch — decode slots (one token each) and
mid-prefill slots (a prompt chunk each) ride the same compiled program.
Each engine step

1. **admits** waiting requests into free slots (scheduler.py) in
   (priority, arrival) order under the pool's worst-case page
   accounting — a radix prefix-cache hit (docs/SERVING.md "Prefix
   caching") adopts the cached prefix pages by refcount at admission, so
   chunked prefill starts AFTER the covered prefix,
2. **plans** the step's token mix under a fixed ``token_budget``: decode
   tokens charged first (decode-first under load), prompt chunks sliced
   to fill the remainder in SLO order (priority tier, earliest deadline,
   arrival), then — with ``spec_k > 0`` — speculative draft rows from
   whatever budget is left (``scheduler.plan_drafts``) — a 10k-token
   prompt admits immediately and trickles in without ever displacing a
   decoding tenant's next token,
3. runs the **unified compiled step**: every query token of the step —
   decode tokens, chunk tokens, and draft tokens alike — is one row of a
   flattened
   ``[T, ...]`` grid (ops/pallas/paged_attention.py "Ragged form"), with
   per-row block tables and absolute positions riding as DATA. ``T`` is
   bucketed (the slot grid when the step fits it, powers of two above),
   so XLA compiles a small fixed set of shapes no matter how prompts
   chunk or the live batch churns (asserted via :meth:`compile_counts`
   and ``paddle_tpu_jit_compiles_total{fn="serving_step"}``),
4. **retires** finished sequences (eos or max tokens), freeing their pages
   immediately for the next admission.

Chunked-prefill progress IS a cache length: a slot mid-prompt holds
``pos`` tokens of KV and nothing else — exactly the state a prefix-cache
hit restores, which is why a mid-prefill request migrates at its chunk
boundary like a decoding one (journal = tokens generated so far, possibly
none; the adoptive engine re-prefills what its own cache doesn't cover).

Idle grid rows carry the null block table (all page 0) and a zero
position; their masked garbage rides along and is discarded on the host.
Per-token streaming goes through each request's ``stream_cb`` with a
monotone per-request sequence number.

Determinism contract (docs/SERVING.md "Seeds and determinism"): every
sampled token is keyed ``fold_in(PRNGKey(req.seed), position)`` — the
final chunk's first-token sample and every decode sample derive from the
SAME per-request stream inside the same compiled step, so a request's
tokens are a pure function of (prompt, seed, temperature), independent of
batch composition, chunk boundaries, and engine history. That purity is
what makes in-flight migration exact: :meth:`export_inflight` journals
each live request's generated tokens, and an adopting engine re-prefills
prompt + journal (chunked like any admission) and continues decoding
token-identically from the journaled position.

Telemetry (docs/OBSERVABILITY.md): every step feeds the always-on
``paddle_tpu.metrics`` registry — TTFT / inter-token-latency / queue-wait
/ step-time histograms, the per-step prefill/decode token mix and chunk
sizes, request lifecycle counters, and page/queue gauges (the latter via
``profiler.record_counter``, which ALSO lands them in the chrome trace
whenever a profiler is recording). Each step is a ``step`` span with its
phases and grid counters on the request-trace ring (``tracing.py``).
``engine.stats`` stays a thin per-step dict view over the same numbers.
"""
from __future__ import annotations

import inspect
import itertools
import time
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults, jit, metrics
from ..autograd.engine import no_grad
from ..ops._apply import apply_op, ensure_tensor
from ..ops.pallas.gated_delta import block_rows, state_block
from ..ops.pallas.paged_attention import query_block_rows, walked_keys
from ..tensor import Tensor
from . import tracing
from .adapters import AdapterStore
from .kv_cache import PagedKVCachePool, PrefixCache
from .scheduler import (BackpressureError, FCFSScheduler, Request,
                        RequestOutput)
from .spec import NGramDrafter

__all__ = ["ServingEngine"]

_MIN_GRID_TOKENS = 16
_engine_counter = itertools.count()
# the grid counters of a call that landed no step (tracing.COUNTERS["step"]
# up to ``kv_held``)
_NO_GRID = (0, 0, 0, 0, 0, 0, 0, 0)
# the state counters of a step that advanced no per-slot state
_NO_STATE = (0, 0, 0, 0)

faults.declare_point(
    "serving.step", "top of ServingEngine.step(), before the deadline "
    "sweep — arm latency here to stall whole iterations")
faults.declare_point(
    "serving.prefill", "admission of one request (cache match + page "
    "adoption + slot parking) — a raise retires that request with "
    "finish_reason=\"error\"; batch-mates proceed")
faults.declare_point(
    "serving.decode_step", "in _plan, after the per-slot KV-room "
    "loop and before the unified compiled step consumes the pools — arm "
    "call= here to corrupt state (e.g. pool.poison_seq), delay_s to trip "
    "the watchdog")
faults.declare_point(
    "serving.compile_step", "building the unified ragged step program — "
    "a transient raise exercises the faults.retry backoff path; each "
    "token-grid bucket still compiles exactly once")


def _cb_accepts_seq(cb) -> bool:
    """True if a stream callback WANTS the 4th positional arg — the
    per-request monotone token sequence number. Signature-probed (the
    MetricsServer health_cb idiom) so the legacy 3-arg
    ``cb(req_id, token, finished)`` contract keeps working unchanged.

    Opting in requires ``*args``, a REQUIRED 4th positional parameter,
    or a parameter named ``seq`` — a legacy callback that merely happens
    to carry a defaulted 4th parameter (``def cb(r, t, f, logger=X)``)
    must NOT suddenly receive an int in it on upgrade."""
    try:
        sig = inspect.signature(cb)
    except (TypeError, ValueError):
        return False
    positional = []
    for p in sig.parameters.values():
        if p.kind == p.VAR_POSITIONAL:
            return True
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            positional.append(p)
    if len(positional) < 4:
        return False
    fourth = positional[3]
    return fourth.default is fourth.empty or fourth.name == "seq"


class _SeqState:
    """One live slot: request + unified-step cursor.

    The slot's WHOLE generation state is ``(ids, pos, gen)``: ``ids`` is
    the admission token stream (prompt + any migration journal), ``pos``
    counts tokens of KV in the pool — chunked-prefill progress IS a
    cache length — and ``gen`` the tokens sampled here (pre-seeded with
    the journal for a migrated request so stream sequence numbers and
    max_new_tokens accounting continue, not restart). While
    ``pos < len(ids)`` the slot is mid-prefill: each step feeds its next
    prompt chunk ``ids[pos:pos+c]``; the FINAL chunk's sample is the
    stream's next token. Once ``pos == len(ids)`` it decodes:
    ``last_token`` feeds back at position ``pos``.

    No PRNG state lives here: sampling keys are derived per token as
    ``fold_in(PRNGKey(req.seed), position)`` inside the compiled step,
    so (ids, gen) is the WHOLE resume state — exactly what
    :meth:`ServingEngine.export_inflight` ships to a sibling engine on
    migration, chunk boundaries included.
    """

    __slots__ = ("req", "ids", "pos", "last_token", "gen", "t_last",
                 "t_admit", "inserted_nodes", "adp_slot", "fsm",
                 "fsm_off", "fsm_state", "parked", "ahead", "ahead_tokens")

    def __init__(self, req: Request, ids: np.ndarray, pos: int):
        self.req = req
        self.ids = np.asarray(ids, np.int32).reshape(-1)
        self.pos = int(pos)          # tokens of KV written so far
        # what the slot's launched, not yet landed rows advance it by: KV
        # positions and sampled tokens. The next plan starts from
        # pos + ahead; pos and gen only ever hold what has landed
        self.ahead = 0
        self.ahead_tokens = 0
        self.last_token = -1         # meaningful once prefill completes
        # generated ids (incl. eos when hit); journal-seeded for a
        # migrated request
        self.gen: List[int] = list(req.resume_tokens or ())
        self.t_last = time.perf_counter()  # last token's landing time (ITL)
        self.t_admit = self.t_last   # chunked-prefill wall-time anchor
        # prefix-cache nodes created FROM this request's prefill KV: if a
        # NaN quarantine makes that KV suspect, these (and their
        # subtrees) are evicted so the poison cannot serve a later match
        self.inserted_nodes = []
        # adapter slot in THIS engine's AdapterStore (0 = base model):
        # resolved from req.adapter_id at admission — names travel,
        # slots are engine-local (docs/SERVING.md "Multi-LoRA adapters")
        self.adp_slot = 0
        # constrained decoding (docs/SERVING.md "Constrained decoding"):
        # the request's GrammarFSM, its interned offset in the engine's
        # grammar table, and the LOCAL DFA state advanced per landed
        # token. (fsm_off + fsm_state) is the absolute table row the
        # slot's sample rows gather their logit mask from; fsm_state
        # alone is what export_inflight journals (engine-independent)
        self.fsm = None
        self.fsm_off = 0
        self.fsm_state = 0
        # host-tier park flag (docs/SERVING.md "KV page tiers"): a
        # parked slot keeps its _SeqState (stream position, grammar
        # state, journal) but contributes ZERO rows to the unified step
        # — its KV pages live in the pool's HostPageStore until unpark.
        # False | "auto" (pressure policy; auto-restored) | "manual"
        # (park_request; sticky until unpark_request)
        self.parked = False

    @property
    def prefilling(self) -> bool:
        return self.pos < self.ids.size


class _Launch:
    """One step on the device: launched, not yet landed. ``rows`` are
    ``(slot, state, tokens, positions, is_chunk, n_draft, src)``, each
    with the ``_SeqState`` it was planned for, so a row whose sequence
    ended before it landed is recognised and thrown away."""

    __slots__ = ("rows", "nxt", "fin", "counts", "state_counts", "t0",
                 "t_prog", "fresh", "clips", "discarded")


class ServingEngine:
    """Continuous-batching engine for any ``GenerationMixin`` model
    (LlamaForCausalLM / GPTForCausalLM): paged KV pool + chunked-prefill
    scheduler + a single unified ragged-paged-attention step (decode
    tokens and prompt chunks in one compiled program).

    ``num_pages=None`` sizes the pool for ``max_batch_slots`` worst-case
    sequences of ``max_model_len`` tokens (+1 null page); pass an explicit
    page count (see docs/SERVING.md for the HBM sizing math) to serve more
    queued requests than fit concurrently — admission simply waits.
    """

    def __init__(self, model, *, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_batch_slots: int = 8,
                 max_model_len: Optional[int] = None,
                 token_budget: int = 1024,
                 prefill_token_budget: Optional[int] = None,
                 min_step_tokens: Optional[int] = None,
                 kv_dtype=jnp.float32, host_offload: bool = False,
                 seed: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 watchdog_stall_s: Optional[float] = 30.0,
                 watchdog_recovery_steps: int = 3,
                 engine_id: Optional[str] = None,
                 model_id: str = "default",
                 prefix_cache: bool = True,
                 spec_k: int = 0, spec_ngram: int = 3,
                 drafter=None,
                 compile_cache_dir: Optional[str] = None,
                 adapter_capacity: int = 4, adapter_rank: int = 4,
                 grammar_states: int = 64):
        if seed is not None:
            # dead since the per-request determinism contract landed:
            # sampling keys derive from fold_in(PRNGKey(req.seed), pos)
            # inside the compiled step, so this arg seeds NOTHING —
            # accepting it silently lets callers believe they pinned
            # reproducibility through a knob that does not exist
            warnings.warn(
                "ServingEngine(seed=...) is deprecated and has no "
                "effect: sampling is keyed per request via "
                "Request.seed (add_request(seed=...)); drop the "
                "constructor argument (docs/SERVING.md \"Seeds and "
                "determinism\")", DeprecationWarning, stacklevel=2)
        self.model = model
        model.eval()
        # identity labels: every per-engine serving series carries
        # {engine_id, model_id} so a Router fronting N engines yields N
        # distinguishable series (docs/OBSERVABILITY.md). The default id is
        # a process-wide counter; a Router assigns stable "model/replica"
        # ids instead.
        self.engine_id = (str(engine_id) if engine_id is not None
                          else str(next(_engine_counter)))
        self.model_id = str(model_id)
        self._lbl = {"engine_id": self.engine_id, "model_id": self.model_id}
        # the fleet-global request tracer (tracing.py): per-request event
        # seqs live THERE, so a migrated request's timeline stays one
        # contiguous stream across engines
        self._trace = tracing.get_tracer()
        self._phase = None          # the open step.* span (tracing.Span)
        # steps launched and not landed, oldest first: at most one between
        # two step() calls (see step)
        self._flight: List[_Launch] = []
        self._no_prev = None        # `prev` with nothing in flight: zeros
        self.trunk = model._decode_trunk()
        n_layers, n_kv, head_dim = model._cache_spec()
        self.n_layers = n_layers
        cfg_max = int(model.config.max_position_embeddings)
        self.max_model_len = min(int(max_model_len or cfg_max), cfg_max)
        self.page_size = int(page_size)
        self.max_batch_slots = int(max_batch_slots)
        # prefill_token_budget survives as the PR 1 spelling of the knob;
        # the budget now bounds the WHOLE unified step's tokens (decode
        # charged first, chunks in the remainder — scheduler.plan_chunks)
        self.token_budget = int(prefill_token_budget
                                if prefill_token_budget is not None
                                else token_budget)
        # operator-pinned step-grid floor (docs/SERVING.md "Unified step
        # & chunked prefill"): with min_step_tokens == token_budget every
        # step — decode-only or mixed — compiles and runs ONE shape, so
        # prompt chunks ride rows the decode grid already paid for and
        # the inter-token latency of decoding tenants is isolation-by-
        # construction. None (default) lets decode-only steps use the
        # cheaper slot-grid shape and mixed steps bucket up.
        self.min_step_tokens = (None if min_step_tokens is None
                                else int(min_step_tokens))
        # speculative decoding (docs/SERVING.md "Speculative decoding"):
        # spec_k > 0 arms a host-side drafter that proposes up to k
        # tokens per decoding slot; the unified step scores them as
        # extra grid rows (data, like chunk rows — zero new compiled
        # programs) and the accept/reject below is an exact-match
        # against the per-position sampled targets, so streams are
        # bit-identical with speculation on or off. A custom `drafter`
        # (anything with propose(ids, k) -> np.ndarray) overrides the
        # built-in NGramDrafter.
        self.spec_k = max(int(spec_k), 0)
        if drafter is not None:
            self.drafter = drafter
            self.spec_k = max(self.spec_k, 1)
        elif self.spec_k > 0:
            self.drafter = NGramDrafter(k=self.spec_k,
                                        max_ngram=int(spec_ngram))
        else:
            self.drafter = None
        # sample-grid width: every slot owns spec_k+1 sample rows (base
        # token + drafts); a fixed per-engine constant so the compiled
        # step's signature never varies with how many drafts a given
        # step actually carries
        self._spec_rows = self.spec_k + 1
        self._compile_cache_dir = (None if compile_cache_dir is None
                                   else str(compile_cache_dir))
        # multi-LoRA store (docs/SERVING.md "Multi-LoRA adapters"):
        # ALWAYS built, even when no adapter is ever registered — its
        # stacked (A, B) arrays ride EVERY compiled step as arguments,
        # so registering a tenant later is a pure value write into
        # already-traced shapes (zero recompiles; compile_counts pins
        # it). Slot 0 is the zero-delta identity every base request
        # indexes.
        self.adapters = AdapterStore.from_model(
            model, rank=adapter_rank, capacity=adapter_capacity,
            dtype=jnp.float32)
        # constrained-decoding mask table (docs/SERVING.md "Constrained
        # decoding"): ONE [grammar_states, vocab] boolean table shared
        # by every interned grammar. Row 0 is the all-True identity that
        # unconstrained sample rows point at — jnp.where against it
        # returns the logits bitwise-unchanged, the grammar-off
        # bit-identity guarantee. Grammars intern as refcounted row
        # segments (first-fit); per-slot states ride the step as
        # offset+local ints. Like the adapter arrays, the table is a
        # step ARGUMENT with a fixed shape: interning is a value write.
        self._vocab_size = int(model.config.vocab_size)
        self._grammar_cap = int(grammar_states)
        if self._grammar_cap < 2:
            raise ValueError("grammar_states must be >= 2 (row 0 is the "
                             f"reserved identity), got {grammar_states}")
        self._grammar_table = np.zeros(
            (self._grammar_cap, self._vocab_size), bool)
        self._grammar_table[0, :] = True
        self._grammar_device = jnp.asarray(self._grammar_table)
        # fsm.key -> [offset, n_states, refcount, fsm]
        self._grammar_segments: Dict[object, list] = {}
        self.pages_per_seq = -(-self.max_model_len // self.page_size)
        if num_pages is None:
            num_pages = self.max_batch_slots * self.pages_per_seq + 1
        # a trunk whose layers are not all paged K/V says what each keeps
        # (kv_cache.py "Layer KINDS"); the state layers hold one row per
        # slot and the spare that padding rows write
        layout = getattr(model, "_cache_layout", None)
        self.pool = PagedKVCachePool(n_layers, num_pages, self.page_size,
                                     n_kv, head_dim, dtype=kv_dtype,
                                     engine_id=self.engine_id,
                                     model_id=self.model_id,
                                     layer_kinds=(None if layout is None
                                                  else layout()),
                                     state_slots=self.max_batch_slots + 1)
        if self.pool.has_state:
            # a sequence's per-slot state follows it only by recompute
            # from position 0; what would share or roll back its cache by
            # pages alone is refused here, not found out mid-stream
            for on, what in ((self.drafter is not None,
                              "speculative decoding (spec_k > 0 or a "
                              "drafter): rejected drafts roll back by "
                              "truncating pages, and the state they "
                              "advanced cannot be rolled back"),
                             (prefix_cache, "prefix_cache=True: an adopted "
                              "prefix brings its pages but not the state "
                              "at its end (no snapshots at page "
                              "boundaries yet)"),
                             (host_offload, "host_offload=True: offload "
                              "moves pages and would leave the state")):
                if on:
                    raise ValueError(
                        f"{type(model).__name__} keeps per-slot recurrent "
                        f"state beside its K/V pages; {what}. Pass "
                        f"spec_k=0, prefix_cache=False, host_offload=False")
        # rows the paged kernel puts in one query block at this pool's
        # shapes: the walk counter (_pack) counts what the kernel walks
        self._query_rows = query_block_rows(
            self.pages_per_seq, n_kv, self.page_size, head_dim,
            jnp.dtype(self.pool.dtype).itemsize,
            int(model.config.num_heads))
        # rows of a run the delta-rule kernel advances a block at a time
        # at this pool's state shapes: the block counter (_pack) counts
        # the rows that take that form
        kinds = [k for k in self.pool.layer_kinds if k != "kv"]
        self._state_block = (state_block(kinds[0].heads, kinds[0].value_dim)
                             if kinds else None)
        # host offload tier (docs/SERVING.md "KV page tiers &
        # quantization"): when armed, admission pressure parks cold
        # lower-urgency slots — their pages swap to the pool's
        # HostPageStore and come back bit-exact at unpark, always BEFORE
        # the slot's next step (the compiled step never blocks on a
        # host→HBM copy; a violation shows up on kv_prefetch_late_total)
        self._host_offload = bool(host_offload)
        # radix prefix cache over the pool (docs/SERVING.md "Prefix
        # caching"): admission longest-prefix-matches cached prompt pages
        # and chunk-prefills only the uncovered suffix. prefix_cache=
        # False opts the whole engine out (every admission prefills from
        # token 0, exactly the pre-cache behavior).
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.pool) if prefix_cache else None)
        self.scheduler = FCFSScheduler(self.max_batch_slots,
                                       self.token_budget,
                                       max_queue=max_queue,
                                       retry_after_cb=self
                                       ._estimate_retry_after)
        # step watchdog (faults.StepWatchdog): trips past the stall
        # threshold, recovers after N healthy steps, drives /healthz via
        # health(). None disables it.
        self.watchdog = (faults.StepWatchdog(
            stall_threshold_s=watchdog_stall_s,
            recovery_steps=watchdog_recovery_steps)
            if watchdog_stall_s is not None else None)
        # EWMA of step wall-time: the drain-rate estimate behind
        # BackpressureError.retry_after_s (seeded at a plausible 50 ms)
        self._avg_step_s = 0.05
        # the ONE shared queue-drain predictor (docs/RESILIENCE.md
        # "Overload & brownout"): backs BOTH the backpressure
        # retry_after_s hint and the overload admission gate, so the
        # hint and the shed decision can never disagree. Imported
        # lazily: overload -> router -> engine would cycle at module
        # import time.
        from .overload import DrainEstimator
        self._estimator = DrainEstimator()
        # OverloadController attached by overload.attach(); None = stock
        # behavior (no admission gate, no brownout actions)
        self._overload = None
        self.slots: List[Optional[_SeqState]] = [None] * self.max_batch_slots
        # THE unified step program: one StaticFunction whose signature
        # cache holds one compiled program per token-grid bucket —
        # decode-only steps, mixed steps, and every chunk geometry reuse
        # the same small set (compile_counts pins it)
        self._step_prog: Optional[jit.StaticFunction] = None
        self._grid_buckets_seen: set = set()
        # NO engine-global RNG: sampling keys derive per slot from
        # fold_in(PRNGKey(req.seed), position) INSIDE the compiled step,
        # so a request's token stream never depends on batch composition
        # or engine history (the `seed` ctor arg survives for API compat
        # but seeds nothing anymore — docs/SERVING.md).
        self._outputs: Dict[object, RequestOutput] = {}
        self.stats: Dict[str, float] = {
            "steps": 0, "generated_tokens": 0, "finished_requests": 0,
            "queue_depth": 0, "running_seqs": 0, "tokens_per_sec": 0.0,
            "page_utilization": 0.0, "peak_pages": 0,
        }
        # typed instruments (docs/OBSERVABILITY.md catalog) — the stats
        # dict above stays a thin per-step view over these. Every series
        # carries {engine_id, model_id}: family-level reads on the
        # registry aggregate across engines, per-engine dashboards filter
        # on the labels.
        reg = metrics.get_registry()
        _eng = ("engine_id", "model_id")
        self._m_ttft = reg.histogram(
            "paddle_tpu_serving_ttft_seconds",
            "Time to first token: request enqueue -> first sampled token",
            labels=_eng).labels(**self._lbl)
        self._m_itl = reg.histogram(
            "paddle_tpu_serving_inter_token_seconds",
            "Inter-token latency: gap between consecutive tokens of one "
            "sequence during decode", labels=_eng).labels(**self._lbl)
        self._m_step = reg.histogram(
            "paddle_tpu_serving_step_seconds",
            "Full engine step: admit + unified ragged step + retire",
            labels=_eng).labels(**self._lbl)
        self._m_prefill = reg.histogram(
            "paddle_tpu_serving_prefill_seconds",
            "One request's whole chunked prefill: admission -> first "
            "sampled token", labels=_eng).labels(**self._lbl)
        self._m_decode = reg.histogram(
            "paddle_tpu_serving_decode_step_seconds",
            "One unified compiled step over all live slots (decode "
            "tokens + prompt chunks)", labels=_eng).labels(**self._lbl)
        self._m_mix = reg.histogram(
            "paddle_tpu_serving_step_mix",
            "Per-step token split of the unified step: tokens of each "
            "kind (decode, prefill chunk, speculative draft) the step "
            "carried", labels=("kind",) + _eng)
        self._m_mix_decode = self._m_mix.labels(kind="decode", **self._lbl)
        self._m_mix_prefill = self._m_mix.labels(kind="prefill",
                                                 **self._lbl)
        self._m_mix_draft = self._m_mix.labels(kind="draft", **self._lbl)
        # speculative-decoding instruments: acceptance is THE health
        # number (accepted/drafted ~ how much free throughput the
        # drafter is buying; near 0 means drafts are wasted grid rows)
        self._m_spec_drafted = reg.counter(
            "paddle_tpu_serving_spec_drafted_tokens_total",
            "Draft tokens proposed by the speculative drafter and scored "
            "as extra unified-step rows", labels=_eng).labels(**self._lbl)
        self._m_spec_accepted = reg.counter(
            "paddle_tpu_serving_spec_accepted_tokens_total",
            "Draft tokens accepted (exact match against the per-position "
            "sampled target); the rest rolled back by KV truncation",
            labels=_eng).labels(**self._lbl)
        self._m_spec_accept = reg.histogram(
            "paddle_tpu_serving_spec_acceptance_ratio",
            "Per-burst acceptance: accepted/drafted for each decode step "
            "that carried draft rows", labels=_eng).labels(**self._lbl)
        self._m_chunk = reg.histogram(
            "paddle_tpu_serving_prefill_chunk_tokens",
            "Tokens per prompt chunk the scheduler sliced under the step "
            "token budget", labels=_eng).labels(**self._lbl)
        self._m_requests = reg.counter(
            "paddle_tpu_serving_requests_total",
            "Requests by lifecycle event",
            labels=("event",) + _eng)
        self._m_tokens = reg.counter(
            "paddle_tpu_serving_generated_tokens_total",
            "Tokens sampled by the engine (prefill first tokens included)",
            labels=_eng).labels(**self._lbl)
        for ev in ("admitted", "rejected", "retired", "preempted"):
            self._m_requests.labels(event=ev, **self._lbl)  # scrapes show 0
        # resilience instruments (docs/RESILIENCE.md): every failure path
        # increments exactly one of these per event, so chaos tests pin
        # telemetry alongside behavior
        self._m_timeouts = reg.counter(
            "paddle_tpu_serving_request_timeouts_total",
            "Admitted requests retired on deadline expiry mid-stream "
            "(finish_reason=\"timeout\"); queued expiry counts "
            "paddle_tpu_serving_expired_total instead",
            labels=_eng).labels(**self._lbl)
        self._m_expired = reg.counter(
            "paddle_tpu_serving_expired_total",
            "QUEUED requests whose deadline lapsed before admission "
            "(finish_reason=\"expired\"): retired with pages never "
            "allocated", labels=_eng).labels(**self._lbl)
        self._m_cancels = reg.counter(
            "paddle_tpu_serving_cancellations_total",
            "Requests retired by cancel() (finish_reason=\"cancelled\")",
            labels=_eng).labels(**self._lbl)
        self._m_nan_quarantines = reg.counter(
            "paddle_tpu_serving_nan_quarantines_total",
            "Sequences quarantined for non-finite decode logits "
            "(finish_reason=\"nan\"); batch-mates are unaffected",
            labels=_eng).labels(**self._lbl)
        self._m_req_errors = reg.counter(
            "paddle_tpu_serving_request_errors_total",
            "Requests retired on an internal failure "
            "(finish_reason=\"error\": admission/alloc/callback faults)",
            labels=_eng).labels(**self._lbl)
        self._m_unavailable = reg.counter(
            "paddle_tpu_serving_unavailable_total",
            "Queued requests retired because no healthy engine could adopt "
            "them (finish_reason=\"unavailable\": the router's "
            "requeue-impossible path)", labels=_eng).labels(**self._lbl)
        self._m_cb_errors = reg.counter(
            "paddle_tpu_serving_callback_errors_total",
            "Exceptions raised by user stream callbacks (isolated: the "
            "engine step survives; the request retires \"error\")",
            labels=_eng).labels(**self._lbl)
        self._m_wd_trips = reg.counter(
            "paddle_tpu_serving_watchdog_trips_total",
            "Watchdog trip episodes (healthy->tripped transitions, not "
            "slow-step count)", labels=_eng).labels(**self._lbl)
        self._m_degraded = reg.gauge(
            "paddle_tpu_serving_degraded",
            "1 while the step watchdog holds this engine degraded "
            "(/healthz returns 503), else 0; refreshed at step end and "
            "on every health() probe", labels=_eng).labels(**self._lbl)
        self._reason_counters = {
            "timeout": self._m_timeouts, "cancelled": self._m_cancels,
            "nan": self._m_nan_quarantines, "error": self._m_req_errors,
            "unavailable": self._m_unavailable,
            "expired": self._m_expired,
        }
        # multi-LoRA + constrained-decoding instruments (ISSUE 16,
        # docs/OBSERVABILITY.md): tenancy split per adapter name, store
        # occupancy, constrained traffic volume, end-of-stream validity
        # (THE constrained-decoding health number: invalid > 0 means a
        # mask or migration bug), spec-draft filtering, and table rows
        self._m_adapter_req = reg.counter(
            "paddle_tpu_serving_adapter_requests_total",
            "Requests admitted under a named LoRA adapter (base/slot-0 "
            "requests are not counted)", labels=("adapter_id",) + _eng)
        self._m_adapter_slots = reg.gauge(
            "paddle_tpu_serving_adapter_slots",
            "Named adapters currently registered in this engine's "
            "AdapterStore (the slot-0 identity is not counted)",
            labels=_eng).labels(**self._lbl)
        self._m_grammar_req = reg.counter(
            "paddle_tpu_serving_grammar_requests_total",
            "Grammar-constrained requests admitted (regex/JSON-schema "
            "FSM attached)", labels=_eng).labels(**self._lbl)
        self._m_grammar_tokens = reg.counter(
            "paddle_tpu_serving_grammar_tokens_total",
            "Tokens landed under an in-step grammar mask (FSM advanced "
            "on the host)", labels=_eng).labels(**self._lbl)
        self._m_grammar_completions = reg.counter(
            "paddle_tpu_serving_grammar_completions_total",
            "Constrained requests retired normally (stop/length) by "
            "whether the finished stream walks its grammar to an "
            "accepting state", labels=("result",) + _eng)
        for r in ("valid", "invalid"):
            self._m_grammar_completions.labels(result=r, **self._lbl)
        self._m_grammar_filtered = reg.counter(
            "paddle_tpu_serving_grammar_draft_filtered_total",
            "Speculative draft tokens dropped before staging because "
            "they would leave the proposer slot's grammar (an unmasked "
            "draft would collapse acceptance)",
            labels=_eng).labels(**self._lbl)
        self._m_grammar_states = reg.gauge(
            "paddle_tpu_serving_grammar_states",
            "Grammar-table rows in use (interned DFA states plus the "
            "row-0 identity) out of the grammar_states capacity",
            labels=_eng).labels(**self._lbl)
        self._m_grammar_states.set(1.0)
        # host-tier SLO guard (docs/OBSERVABILITY.md): pages restored by
        # a BLOCKING prefetch inside _plan — the unpark policy
        # failed to hide the host→HBM copy before the slot's step
        self._m_prefetch_late = reg.counter(
            "paddle_tpu_serving_kv_prefetch_late_total",
            "KV pages prefetched host→HBM inside the step path (late: "
            "the unpark-time prefetch should have restored them first)",
            labels=_eng).labels(**self._lbl)

    # ------------------------------------------------------------ frontend
    def check_request(self, prompt_len: int, max_new_tokens: int) -> None:
        """Raise ValueError if a request of this shape could NEVER be
        served — batch front doors call this for every prompt before
        queueing any, so one bad prompt can't strand its batch-mates."""
        p, m = int(prompt_len), int(max_new_tokens)
        if p > self.max_model_len:
            # 4xx responses must be actionable: name the violated limit
            # AND its configured value in every rejection message
            self._m_requests.labels(event="rejected", **self._lbl).inc()
            raise ValueError(
                f"prompt_len {p} exceeds the context window (limit: "
                f"max_model_len={self.max_model_len}); truncate the prompt "
                f"or construct the engine with a larger max_model_len")
        total = p + m
        if total > self.max_model_len:
            self._m_requests.labels(event="rejected", **self._lbl).inc()
            raise ValueError(
                f"prompt_len {p} + max_new_tokens {m} = {total} exceeds "
                f"the per-request token cap (limit: max_model_len="
                f"{self.max_model_len}); lower max_new_tokens to at most "
                f"{self.max_model_len - p}")
        need = self.pool.pages_needed(total)
        if need > self.pool.usable_pages:
            # even an empty pool could never admit it — rejecting here
            # (not queueing) keeps run() from spinning forever on a head
            # request that can never pass can_admit
            self._m_requests.labels(event="rejected", **self._lbl).inc()
            raise ValueError(
                f"max_total_tokens {total} needs {need} KV pages "
                f"worst-case but the pool has only {self.pool.usable_pages}"
                f" usable pages (limit: num_pages={self.pool.num_pages}, "
                f"page_size={self.pool.page_size}); raise num_pages or "
                f"lower max_new_tokens")

    def _check_features(self, req: Request) -> None:
        """Adapter/grammar feasibility gate, the :meth:`check_request`
        sibling for the ISSUE 16 features: reject at ENQUEUE anything
        this engine could never serve — an adapter it does not hold, a
        grammar compiled against the wrong vocab, or a DFA larger than
        the grammar table — with the limit named in the message."""
        if (req.adapter_id is not None
                and not self.adapters.holds(req.adapter_id)):
            self._m_requests.labels(event="rejected", **self._lbl).inc()
            raise ValueError(
                f"adapter {req.adapter_id!r} is not registered on this "
                f"engine (holding {list(self.adapters.names())}); "
                f"register it first (Router.register_adapter hot-loads "
                f"fleet-wide) or route via select(adapter_id=...)")
        fsm = req.grammar
        if fsm is not None:
            if int(fsm.vocab_size) != self._vocab_size:
                self._m_requests.labels(event="rejected",
                                        **self._lbl).inc()
                raise ValueError(
                    f"grammar was compiled for vocab_size "
                    f"{int(fsm.vocab_size)} but this model's vocab is "
                    f"{self._vocab_size}; recompile the GrammarFSM "
                    f"against this model's tokenizer")
            if fsm.n_states > self._grammar_cap - 1:
                self._m_requests.labels(event="rejected",
                                        **self._lbl).inc()
                raise ValueError(
                    f"grammar needs {fsm.n_states} DFA states but the "
                    f"table holds at most {self._grammar_cap - 1} "
                    f"(limit: grammar_states={self._grammar_cap}); "
                    f"simplify the pattern or raise grammar_states")

    def add_request(self, prompt, max_new_tokens: int = 32,
                    temperature: float = 0.0,
                    eos_token_id: Optional[int] = None, seed: int = 0,
                    stream_cb=None, deadline_s: Optional[float] = None,
                    prefix_cache: bool = True, priority: int = 0,
                    adapter_id: Optional[str] = None, grammar=None):
        """Queue a request; returns its ``req_id``. Generation starts at
        the next :meth:`step` with capacity (continuous batching — no
        barrier on the current batch). ``deadline_s`` bounds the whole
        request from ENQUEUE (queue wait included): past it, the engine
        retires it with ``finish_reason="timeout"``. Raises
        :class:`~.scheduler.BackpressureError` (with a ``retry_after_s``
        hint) when a bounded queue (``max_queue=``) is full.
        ``prefix_cache=False`` opts THIS request out of prefix-cache
        matching and insertion (it prefills from token 0 and shares no
        pages) — the per-request escape hatch next to the engine-level
        ``prefix_cache=`` constructor flag. ``priority`` is the SLO tier
        (lower = more urgent, 0 default): honored at admission order and
        at prompt-chunk scheduling (docs/SERVING.md "Unified step &
        chunked prefill"). ``adapter_id`` names a LoRA adapter this
        engine must already hold (``register_adapter``); ``grammar`` is
        a compiled :class:`~.grammar.GrammarFSM` constraining every
        sampled token (docs/SERVING.md "Constrained decoding")."""
        req = Request(prompt=np.asarray(prompt, np.int32).reshape(-1),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      eos_token_id=eos_token_id, seed=seed,
                      stream_cb=stream_cb, deadline_s=deadline_s,
                      prefix_cache=prefix_cache, priority=priority,
                      adapter_id=adapter_id, grammar=grammar)
        self.check_request(req.prompt.size, req.max_new_tokens)
        self._check_features(req)
        if self._overload is not None:
            # deadline-aware admission (docs/RESILIENCE.md "Overload &
            # brownout"): shed doomed work BEFORE it enters the queue.
            # Only fresh submits are gated — adopt_request (failover of
            # already-accepted work) bypasses on purpose.
            try:
                self._overload.admission_check(self, req)
            except BackpressureError:
                self._m_requests.labels(event="rejected",
                                        **self._lbl).inc()
                raise
        try:
            self.scheduler.add(req)
        except Exception:
            self._m_requests.labels(event="rejected", **self._lbl).inc()
            raise
        self._trace.emit("req.enqueue", req.req_id,
                         arg=float(req.prompt.size), label=self.engine_id)
        return req.req_id

    def cancel(self, req_id) -> bool:
        """Cancel a request wherever it is: pulled from the queue, or
        retired mid-prefill/mid-decode with its KV pages freed THIS
        call. The output (tokens generated so far,
        ``finish_reason="cancelled"``) is delivered through the usual
        :meth:`run` path and the terminal stream callback fires. False
        if the request is unknown or already finished — cancel is
        idempotent, never raises."""
        req = self.scheduler.remove(req_id)
        if req is not None:
            self._finish_queued(req, "cancelled")
            return True
        for i, st in enumerate(self.slots):
            if st is not None and st.req.req_id == req_id:
                # its rows in flight are thrown away when they land
                self._retire_abnormal(st, slot=i, reason="cancelled")
                self._drop_ended()
                return True
        return False

    def health(self) -> Dict[str, object]:
        """Liveness view for ``MetricsServer(health_cb=engine.health)``:
        ``status`` flips to ``"degraded"`` while the watchdog is tripped
        OR a step is live-hung past the stall threshold (stalled_now is
        answerable from the scrape thread mid-step)."""
        degraded = (self.watchdog is not None
                    and self.watchdog.status() != "ok")
        # keep the gauge agreeing with /healthz even MID-step: a live
        # hang is only observable from this (scrape) thread, and the
        # step's own finally can't run until the hang ends
        self._m_degraded.set(1.0 if degraded else 0.0)
        return {
            "status": "degraded" if degraded else "ok",
            "watchdog_trips": (0 if self.watchdog is None
                               else self.watchdog.trips),
            "queue_depth": self.scheduler.queue_depth,
            "running_seqs": sum(1 for s in self.slots if s is not None),
        }

    def _estimate_retry_after(self) -> float:
        """Backpressure hint: admission drains roughly one request per
        step per free slot, so a full queue clears in about
        ``queue_depth x avg_step_time`` — rounded up to a 50 ms floor so
        clients never busy-spin on a hot engine. Delegates to the ONE
        shared :class:`~.overload.DrainEstimator` so this hint and the
        overload admission gate agree by construction."""
        return self._estimator.for_engine(self)

    @property
    def has_work(self) -> bool:
        return bool(self.scheduler.waiting) or any(
            s is not None for s in self.slots)

    def run(self) -> Dict[object, RequestOutput]:
        """Drive :meth:`step` until queue and slots drain; returns every
        request finished since the last :meth:`run` (including ones that
        retired in explicit :meth:`step` calls in between), keyed by
        ``req_id``. Draining — outputs are handed out exactly once, so a
        long-lived server never accumulates them."""
        while self.has_work:
            self.step()
        return self.take_outputs()

    def take_outputs(self) -> Dict[object, RequestOutput]:
        """Drain accumulated terminal outputs WITHOUT stepping (exactly-once
        handout, same contract as :meth:`run`). The router's collection
        path: it steps many engines itself and merges their outputs."""
        out, self._outputs = self._outputs, {}
        return out

    # ------------------------------------------------- router control plane
    def steal_queued(self) -> List[Request]:
        """Pull EVERY waiting (never-admitted) request out of the queue and
        return the live Request objects — the router's drain/failover path.
        No lifecycle counters move: the requests were never admitted here
        and are about to be adopted elsewhere (or retired explicitly via
        :meth:`retire_queued`). In-flight slots are untouched; they finish
        or fall to the cancel/deadline machinery."""
        return self.scheduler.pop_all()

    def export_inflight(self) -> List[Request]:
        """Pop every IN-FLIGHT request (decode slots AND mid-chunked-
        prefill slots) off this engine and return resume journals: each
        Request comes back with ``resume_tokens`` set to the tokens it
        generated here — together with (prompt, seed, temperature,
        deadline, priority) already on the Request, the complete state a
        sibling needs to continue the stream token-identically (chunked
        re-prefill of prompt + journal, then decode from the journaled
        position; emission resumes at stream seq ``len(resume_tokens)``).
        A slot killed BETWEEN prompt chunks journals exactly its tokens
        so far (usually none): its chunk progress was only a cache
        length, which the adoptive engine's prefix cache re-covers — so
        migration at a chunk boundary is the same move as migration
        mid-decode. A CONSTRAINED request additionally journals its DFA
        position in ``resume_fsm_state`` (the engine-independent LOCAL
        state — table offsets differ per engine), so the sibling resumes
        mid-structure without re-walking the grammar. The router's
        migration path for ``mark_down``/step-crash.

        No lifecycle counters move (the requests retire elsewhere), and
        pages are freed best-effort per sequence — a crashed engine's
        pool may refuse, and its memory is being abandoned anyway. A step
        in flight is thrown away: only landed tokens are journaled, and
        the sibling samples the in-flight ones again, identically."""
        self._flight.clear()
        states: List[_SeqState] = []
        for i, st in enumerate(self.slots):
            if st is not None:
                states.append(st)
                self.slots[i] = None
        out: List[Request] = []
        for st in states:
            try:
                if self.pool.has_seq(st.req.req_id):
                    self.pool.free(st.req.req_id)
            except Exception:
                pass  # dead pool: journaling must still succeed
            st.req.resume_tokens = list(st.gen)
            if st.fsm is not None:
                st.req.resume_fsm_state = st.fsm_state
            self._grammar_release(st)
            self._trace.emit("req.export", st.req.req_id,
                             arg=float(len(st.req.resume_tokens)),
                             label=self.engine_id)
            out.append(st.req)
        return out

    def inflight_fsm_states(self) -> Dict[object, Optional[int]]:
        """``{req_id: local grammar FSM state}`` for every live slot
        (None for unconstrained requests) — a read-only snapshot, slots
        untouched. What the router's WAL group commit journals next to
        each progress record so a restarted process can resume a
        constrained stream mid-structure without re-walking the DFA
        (a missing journaled state is recomputed from the token journal
        at adoption, exactly like a migrated request's)."""
        out: Dict[object, Optional[int]] = {}
        for st in self.slots:
            if st is not None:
                out[st.req.req_id] = (int(st.fsm_state)
                                      if st.fsm is not None else None)
        return out

    def adopt_request(self, req: Request) -> None:
        """Enqueue a Request object stolen from ANOTHER engine: req_id,
        arrival time, running deadline, seed, and stream_cb all ride along,
        so queue-wait/TTFT keep measuring from the original enqueue and the
        caller's streaming keeps working. A request journaled by
        :meth:`export_inflight` (``resume_tokens`` set) re-prefills
        prompt + journal at admission (in chunks, like any admission) and
        continues its stream token-identically. Raises exactly like
        :meth:`add_request` (ValueError from :meth:`check_request` or
        :meth:`_check_features` — an adapter this engine doesn't hold is
        a placement error, BackpressureError from a full bounded queue)
        — the router treats a raise as requeue-impossible."""
        self.check_request(req.prompt.size, req.max_new_tokens)
        self._check_features(req)
        try:
            self.scheduler.add(req)
        except Exception:
            self._m_requests.labels(event="rejected", **self._lbl).inc()
            raise
        self._trace.emit("req.adopt", req.req_id,
                         arg=float(len(req.resume_tokens or ())),
                         label=self.engine_id)

    def retire_queued(self, req: Request,
                      reason: str = "unavailable") -> RequestOutput:
        """Terminally retire a request that is NOT queued here anymore
        (stolen via :meth:`steal_queued`) and could not be placed on any
        healthy engine: emits the terminal stream callback and the
        per-reason counter, and delivers the output through this engine's
        normal :meth:`run`/:meth:`take_outputs` path — exactly once, like
        every other retirement."""
        return self._finish_queued(req, reason)

    # ------------------------------------------------ adapters and grammars
    def register_adapter(self, name: str, weights) -> int:
        """Install (or hot-swap) LoRA adapter ``name`` on THIS engine —
        a pure value write into the stacked adapter arrays, so the
        compiled step is untouched (``compile_counts()`` before == after)
        and in-flight work never notices. Fleet-wide hot-load goes
        through ``Router.register_adapter``, which adds the canary."""
        slot = self.adapters.register(name, weights)
        self._m_adapter_slots.set(float(len(self.adapters.names())))
        return slot

    def unregister_adapter(self, name: str) -> None:
        """Zero and free adapter ``name``'s slot. Refuses while any
        admitted OR queued request still points at it — unregistering
        under a live tenant would silently flip its deltas to zero
        mid-stream."""
        if self._adapter_in_use(name):
            raise ValueError(
                f"adapter {name!r} is in use by an admitted or queued "
                f"request; drain it before unregistering")
        self.adapters.unregister(name)
        self._m_adapter_slots.set(float(len(self.adapters.names())))

    def _adapter_in_use(self, name: str) -> bool:
        for st in self.slots:
            if st is not None and st.req.adapter_id == name:
                return True
        return any(r.adapter_id == name for r in self.scheduler.waiting)

    def _grammar_intern(self, fsm) -> int:
        """Refcounted first-fit interning of a compiled DFA into the ONE
        ``[grammar_states, vocab]`` device table the step consumes:
        returns the row offset for this grammar. Same ``fsm.key`` →
        same rows (a popular schema costs its states once, not per
        request). Row 0 is the reserved all-True identity."""
        seg = self._grammar_segments.get(fsm.key)
        if seg is not None:
            seg[2] += 1
            return seg[0]
        n = int(fsm.n_states)
        taken = sorted((s[0], s[1]) for s in self._grammar_segments.values())
        off, ok = 1, False
        for seg_off, seg_n in taken:
            if off + n <= seg_off:
                ok = True
                break
            off = seg_off + seg_n
        if not ok and off + n > self._grammar_cap:
            held = {str(k[0]): s[1] for k, s in
                    self._grammar_segments.items()}
            raise ValueError(
                f"grammar table full: need {n} rows but only "
                f"{self._grammar_cap - off} remain of "
                f"grammar_states={self._grammar_cap} (holding {held}); "
                f"raise grammar_states or drain constrained requests")
        self._grammar_table[off:off + n] = fsm.mask_table
        self._grammar_device = jnp.asarray(self._grammar_table)
        self._grammar_segments[fsm.key] = [off, n, 1, fsm]
        self._m_grammar_states.set(float(1 + sum(
            s[1] for s in self._grammar_segments.values())))
        return off

    def _grammar_release(self, st: "_SeqState") -> None:
        """Drop ``st``'s reference on its interned grammar; at refcount
        zero the rows are zeroed and the segment freed. Idempotent —
        every retirement path calls it unconditionally."""
        fsm, st.fsm = st.fsm, None
        if fsm is None:
            return
        seg = self._grammar_segments.get(fsm.key)
        if seg is None:
            return
        seg[2] -= 1
        if seg[2] <= 0:
            off, n = seg[0], seg[1]
            self._grammar_table[off:off + n] = False
            self._grammar_device = jnp.asarray(self._grammar_table)
            del self._grammar_segments[fsm.key]
        self._m_grammar_states.set(float(1 + sum(
            s[1] for s in self._grammar_segments.values())))

    @property
    def avg_step_s(self) -> float:
        """Step wall-time EWMA — the same drain-rate estimate behind
        ``BackpressureError.retry_after_s``, exposed for the router's
        least-loaded scoring."""
        return self._avg_step_s

    def load_score(self) -> float:
        """Estimated seconds to drain this engine's current commitment:
        outstanding work in STEPS x the step-time EWMA. A slot's charge
        is its remaining prompt in CHUNK steps (ceil(remaining /
        token_budget) — chunked-prefill progress counts: a 10k prompt
        90% prefilled weighs a tenth of a fresh one) plus one decode
        step per remaining token (a 2-token short and a 128-token hog
        must not weigh the same). The queue half rides the scheduler's
        incremental tally (O(1)); the slot scan is bounded by
        ``max_batch_slots``. The router's least-loaded dispatch admits
        onto the minimum-score healthy engine; exact ties (idle fleets)
        round-robin."""
        budget = max(self.scheduler.token_budget, 1)
        steps = self.scheduler.pending_steps
        for st in self.slots:
            if st is None:
                continue
            remaining_prefill = max(int(st.ids.size) - st.pos, 0)
            steps += -(-remaining_prefill // budget)
            steps += max(int(st.req.max_new_tokens) - len(st.gen), 0)
        return steps * self._avg_step_s

    def compile_counts(self) -> Dict[str, int]:
        """Compiled-program tally — the recompilation bound the tests
        assert on: ONE unified step function whose compiled signatures
        are exactly the token-grid buckets seen, so ``step`` must equal
        ``step_buckets`` forever (a drift means something non-bucketed —
        a dtype, a shape — leaked into the program signature) and both
        are bounded by the small fixed bucket set."""
        n = len(self._step_prog._cache) if self._step_prog else 0
        return {"step": n, "step_buckets": len(self._grid_buckets_seen)}

    def step_program_texts(self, compiled: bool = False) -> List[str]:
        """Text of every compiled step bucket — read by chip_smoke.py. The
        lowered (StableHLO) text shows that the Pallas ragged kernel
        (``tpu_custom_call``), not the gather fallback, is what the step
        program holds, and which parameters alias which outputs;
        ``compiled=True`` is the HLO after XLA's passes, where a
        pool-shaped ``copy`` would show (it costs a compile per bucket
        unless JAX's persistent cache holds them)."""
        if self._step_prog is None:
            return []
        return [self._step_prog.program_text(k, compiled=compiled)
                for k in self._step_prog._cache]

    def step_aliased_bytes(self) -> List[Optional[int]]:
        """Per compiled step bucket, the bytes of argument buffers its
        executable updates in place: at least the pool's, or the step
        copies a pool array it was given to consume."""
        if self._step_prog is None:
            return []
        return [self._step_prog.aliased_bytes(k)
                for k in self._step_prog._cache]

    # ---------------------------------------------------------------- step
    def step(self) -> List[RequestOutput]:
        """One engine iteration: admit → one unified ragged step (decode
        tokens + prompt chunks under the token budget) → retire. Returns
        requests that finished during this step.

        A call lands exactly one step, and launches the next one BEFORE it
        lands that one whenever the next plan needs nothing the landing
        produces (:meth:`_lands_first`): the host plans, packs and launches
        step k+1 while step k runs on the device, then waits for and lands
        step k. A row whose token is step k's sample takes it from k's
        output on the device (``prev``/``tok_src`` in :meth:`_make_step`),
        and each slot is planned from ``pos + ahead``: what its rows in
        flight will have written. So between two calls at most one step is
        in flight. With nothing in flight the call launches k, then k+1
        where it may, and lands k; where the engine lands first, the call
        is plan → launch → land of one step, as when nothing overlapped.

        On the trace ring (and, under ``jax.profiler.start_trace``, on
        the host plane of the device trace) the call is one ``step`` span
        tiled by ``step.plan`` → ``step.pack`` → ``step.dispatch`` of the
        step it launches, then ``step.wait`` → ``step.land`` of the step
        it lands; a call that launches and lands nothing is ``step.plan``
        alone. The span's grid counters describe the step it landed."""
        from ..profiler import record_counter

        trace = self._trace
        span = trace.begin("step", self.engine_id)
        self._phase = trace.begin("step.plan", self.engine_id)
        counts = None
        landed: Optional[_Launch] = None
        ahead = discarded = 0
        t0 = time.perf_counter()
        if self.watchdog is not None:
            self.watchdog.begin_step()
        tokens_before = self.stats["generated_tokens"]
        finished: List[RequestOutput] = []
        try:
            try:
                faults.point("serving.step")
                finished.extend(self._sweep_deadlines())
                launched = False
                if not self._flight:
                    self._launch_next(finished)
                    launched = bool(self._flight)
                if self._flight and not self._lands_first():
                    if launched:   # k+1's plan is a phase of its own
                        self._phase = trace.next("step.plan", self._phase)
                    n = len(self._flight)
                    landed = self._launch_next(finished)
                    ahead = int(landed is None and len(self._flight) > n)
                if landed is None and self._flight:
                    landed = self._flight.pop(0)
                    self._land(landed, finished)
                discarded = (0 if landed is None else landed.discarded) \
                    + self._drop_ended()
            finally:
                # the watchdog bracket must close even when the step body
                # raises (an armed fault, an unhandled bug) — otherwise
                # _in_step_since stays set and an IDLE engine reads as
                # live-hung on /healthz forever
                dt = time.perf_counter() - t0
                self._avg_step_s = 0.8 * self._avg_step_s + 0.2 * dt
                if self.watchdog is not None:
                    if self.watchdog.end_step(dt):
                        self._m_wd_trips.inc()
                    self._m_degraded.set(
                        0.0 if self.watchdog.status() == "ok" else 1.0)
            self._m_step.observe(dt)
            self.stats["steps"] += 1
            self.stats["queue_depth"] = self.scheduler.queue_depth
            self.stats["running_seqs"] = sum(
                1 for s in self.slots if s is not None)
            # zero-duration guard: a clock with coarse resolution can
            # report dt == 0 for an idle step — a rate of 0 beats a
            # ZeroDivisionError (or the absurd spike 1e-9 used to produce)
            tokens_this_step = (self.stats["generated_tokens"]
                                - tokens_before)
            self.stats["tokens_per_sec"] = (
                tokens_this_step / dt if dt > 0.0 else 0.0)
            self.stats["page_utilization"] = self.pool.utilization()
            self.stats["peak_pages"] = self.pool.peak_used
            record_counter("serving.queue_depth", self.stats["queue_depth"])
            record_counter("serving.running_seqs",
                           self.stats["running_seqs"])
            record_counter("serving.tokens_per_sec",
                           self.stats["tokens_per_sec"])
            record_counter("serving.page_utilization",
                           self.stats["page_utilization"])
            counts = ((_NO_GRID if landed is None else landed.counts)
                      + (tokens_this_step, ahead, discarded)
                      + (_NO_STATE if landed is None
                         else landed.state_counts))
        finally:
            # a step that raised still closes its spans (without counts):
            # an open one would adopt every later span as its child
            trace.end(self._phase)
            self._phase = None
            trace.end(span, counts)
        # outputs were registered in self._outputs eagerly at retirement
        return finished

    def _lands_first(self) -> bool:
        """Whether the next plan needs what landing the step in flight
        produces, so that step lands before the next one is planned: a
        grammar's mask needs the slot's last token, drafts read the
        landed stream, and parking, unparking (any host-tier engine: a
        caller may park a slot between two calls) and brownout preemption
        move a slot's state off the step path."""
        if self.drafter is not None or self._host_offload:
            return True
        if (self._overload is not None
                and self._overload.preempt_priority_cut() is not None):
            return True
        return any(st is not None and st.fsm is not None
                   for st in self.slots)

    def _drop_ended(self) -> int:
        """Forget the step in flight once none of its rows' sequences is
        live (they ended at an earlier landing, were cancelled or timed
        out): nothing would land from it, and an engine with no live slot
        then holds nothing in flight. Returns the rows thrown away."""
        if not self._flight or any(self.slots[r[0]] is r[1]
                                   for ln in self._flight
                                   for r in ln.rows):
            return 0
        n = sum(r[2].size for ln in self._flight for r in ln.rows)
        self._flight.clear()
        return n

    # ------------------------------------------------- host-tier parking
    def _find_slot(self, req_id):
        for i, st in enumerate(self.slots):
            if st is not None and st.req.req_id == req_id:
                return i, st
        raise KeyError(f"unknown or finished request: {req_id!r}")

    def park_request(self, req_id) -> int:
        """Park a live request: its exclusively-owned KV pages swap to
        the pool's host tier, its unwritten-tail reservation is released,
        and the slot contributes ZERO rows to the unified step until
        :meth:`unpark_request`. The slot itself stays occupied — parking
        frees PAGES, not slots — and the whole stream state (position,
        grammar DFA, journal) survives in place. Returns pages moved;
        idempotent on an already-parked request.

        A park requested through THIS public API is sticky: the per-step
        pressure policy never auto-unparks it (an external controller
        parked it for reasons the engine cannot see); only pressure
        parks (``_park_for_pressure``) auto-restore via
        ``_unpark_ready``."""
        return self._park(req_id, mode="manual")

    def _park(self, req_id, mode: str) -> int:
        if not self._host_offload:
            raise RuntimeError(
                "host_offload is disabled on this engine "
                "(ServingEngine(host_offload=True) to enable the tier)")
        _, st = self._find_slot(req_id)
        if st.parked:
            return 0
        n = self.pool.offload_seq(req_id)
        st.parked = mode
        self._trace.emit("req.park", req_id, arg=float(n))
        return n

    def unpark_request(self, req_id) -> int:
        """Restore a parked request's offloaded pages into HBM (bit-exact
        — bytes and int8 scales scattered back verbatim) and re-assume
        its tail reservation; the slot rejoins the next step's grid.
        Raises if the pool cannot cover the restore — callers gate on
        ``pool.can_prefetch``. Returns pages restored."""
        if not self._host_offload:
            raise RuntimeError(
                "host_offload is disabled on this engine "
                "(ServingEngine(host_offload=True) to enable the tier)")
        _, st = self._find_slot(req_id)
        if not st.parked:
            return 0
        n = self.pool.prefetch_seq(req_id)
        st.parked = False
        self._trace.emit("req.unpark", req_id, arg=float(n))
        return n

    def _park_for_pressure(self) -> None:
        """Offload-before-reject: when the queue head cannot admit for
        PAGES while a decode slot sits free, park the coldest strictly
        lower-priority streams until the head's worst case fits. Runs
        before admission each step; victims keep their slots (their
        pages and tail reservations are what the head needs), so this
        only helps when slots outnumber page capacity — exactly the
        overcommitted sizing the host tier exists for."""
        sched = self.scheduler
        if not sched.waiting:
            return
        if not any(s is None for s in self.slots):
            return  # no free slot: parking frees pages, not slots
        head = sched.waiting[0]
        matched = (self.pool.prefix_match_len(head.admission_ids())
                   if head.prefix_cache else 0)
        cached = matched // self.page_size
        if self.pool.can_admit(head.max_total_tokens, cached_pages=cached):
            return
        cands = [(st.t_last, st.req.req_id, st.req)
                 for st in self.slots
                 if st is not None and not st.parked and not st.prefilling]
        for rid in sched.offload_victims(head, cands):
            self._park(rid, mode="auto")
            if self.pool.can_admit(head.max_total_tokens,
                                   cached_pages=cached):
                return

    def _brownout_enforce(self) -> None:
        """Brownout ladder level >= 3 (``batch-parked``): preempt every
        live batch-tier decode slot — journal its generated tokens onto
        the Request (:meth:`export_inflight`'s move, turned inward),
        free its pages AND its slot, and requeue it behind higher
        tiers. Host-tier parking keeps the slot (it frees pages only),
        which is exactly wrong when slots are the scarce resource under
        overload; the journal costs a chunked re-prefill on restore —
        which the prefix cache largely covers — and buys a whole slot.

        Restoration is ordinary admission: the requeued request carries
        ``resume_tokens``, the ladder's admission hold (level >= 3
        holds the batch tier; see ``FCFSScheduler.admit``) keeps it
        queued until de-escalation, and the resumed stream is
        token-identical (sampling is keyed on (seed, position), never
        on the slot) — the same contract migration already proves.
        A preemption that would overflow the bounded queue is skipped:
        a stream is never dropped to make room for one.

        The victim set widens with the ladder
        (``OverloadController.preempt_priority_cut``): ``batch-parked``
        evicts the batch tier; ``interactive-only`` evicts every
        non-interactive tier."""
        cut = self._overload.preempt_priority_cut()
        if cut is None:
            return
        sched = self.scheduler
        for i, st in enumerate(self.slots):
            if (st is None or st.parked or st.prefilling
                    or st.req.priority < cut):
                continue
            if (sched.max_queue is not None
                    and len(sched.waiting) >= sched.max_queue):
                return
            self.slots[i] = None
            try:
                if self.pool.has_seq(st.req.req_id):
                    self.pool.free(st.req.req_id)
            except Exception:
                pass  # pool fault: the journal must still requeue
            st.req.resume_tokens = list(st.gen)
            if st.fsm is not None:
                st.req.resume_fsm_state = st.fsm_state
            self._grammar_release(st)
            self._trace.emit("req.preempt", st.req.req_id,
                             arg=float(len(st.req.resume_tokens)),
                             label=self.engine_id)
            self._m_requests.labels(event="preempted", **self._lbl).inc()
            sched.add(st.req)

    def _unpark_ready(self) -> None:
        """Restore parked tenants whose pages fit again, highest
        priority / oldest first. Anti-thrash: when the queue still has a
        head, an unpark must leave that head's worst case admittable —
        otherwise the next step would park the same slot right back.
        Manual parks never auto-restore."""
        parked = [(st.req.priority, st.req.arrival_t, st.req.req_id)
                  for st in self.slots
                  if st is not None and st.parked == "auto"]
        if not parked:
            return
        head_need = 0
        if self.scheduler.waiting:
            head = self.scheduler.waiting[0]
            matched = (self.pool.prefix_match_len(head.admission_ids())
                       if head.prefix_cache else 0)
            head_need = max(
                self.pool.pages_needed(head.max_total_tokens)
                - matched // self.page_size, 0)
        for _, _, rid in sorted(parked):
            if not self.pool.can_prefetch(rid):
                continue
            if (head_need and self.pool.spare_pages()
                    - self.pool.prefetch_cost(rid) < head_need):
                continue
            self.unpark_request(rid)

    # -------------------------------------------------- resilience helpers
    def _compile_with_retry(self, point_name: str, make_fn):
        """Build a compiled program under a fault point with a short
        seeded backoff (ONE retry policy for every build site): a
        transient failure costs milliseconds, a persistent one surfaces
        to step()'s per-request isolation. The program still compiles
        exactly once per bucket — only the successful build reaches
        XLA."""
        def build():
            faults.point(point_name)
            return make_fn()

        return faults.retry(build, attempts=3, base_delay_s=0.01,
                            max_delay_s=0.1)

    def _safe_cb(self, req: Request, token, finished, seq: int):
        """Invoke ``req.stream_cb`` isolated: a raising user callback
        cannot abort :meth:`step`. Records the error, disables the
        callback (no further calls for this request), and returns the
        exception (None on success) so the caller can retire the
        request with ``"error"`` carrying the diagnostic.

        ``seq`` is the request's monotone token sequence number (0-based
        generated index; the terminal call passes the total emitted
        count). A callback whose signature takes a 4th positional arg
        receives it — the exactly-once streaming cursor: a migrated
        request's adoptive engine resumes emission at the journaled seq,
        so a client never sees a duplicated or missing chunk. Legacy
        3-arg callbacks are called exactly as before."""
        cb = req.stream_cb
        wants_seq = getattr(req, "_cb_wants_seq", None)
        if wants_seq is None:
            wants_seq = _cb_accepts_seq(cb)
            req._cb_wants_seq = wants_seq  # probe once, rides with req
        try:
            if wants_seq:
                cb(req.req_id, token, finished, seq)
            else:
                cb(req.req_id, token, finished)
            return None
        except Exception as e:
            self._m_cb_errors.inc()
            req.stream_cb = None
            return e

    def _emit_terminal(self, req: Request, gen, reason: str,
                       error=None) -> RequestOutput:
        """Common tail of every abnormal retirement: per-reason counter
        (exactly once per event), lifecycle counter, terminal stream
        callback (isolated), RequestOutput."""
        self._reason_counters[reason].inc()
        self._trace.emit("req.retire", req.req_id, label=reason)
        self._m_requests.labels(event="retired", **self._lbl).inc()
        self.stats["finished_requests"] += 1
        out = RequestOutput(req_id=req.req_id, prompt_token_ids=req.prompt,
                            token_ids=list(gen), finish_reason=reason,
                            error=None if error is None else repr(error))
        # register EAGERLY: if the rest of this step raises (an armed
        # fault, a bug), run() must still deliver every output whose
        # retirement side effects (pages freed, counters, terminal
        # callback) already happened
        self._outputs[out.req_id] = out
        if req.stream_cb is not None:
            self._safe_cb(req, None, reason, len(out.token_ids))
        return out

    def _finish_queued(self, req: Request, reason: str) -> RequestOutput:
        """Retire a request that never ran HERE (timeout/cancel/
        unavailable in queue). A migrated request carries its journal:
        the tokens it generated before its engine died are delivered —
        they were already streamed, so the output must own them too."""
        return self._emit_terminal(req, list(req.resume_tokens or ()),
                                   reason)

    def _fail_admitted_request(self, req: Request,
                               error: Exception) -> RequestOutput:
        """Retire a request whose admission failed partway; any pages its
        allocation grabbed go back to the pool now. A migrated request's
        journaled tokens still deliver — they were already streamed."""
        if self.pool.has_seq(req.req_id):
            self.pool.free(req.req_id)
        return self._emit_terminal(req, list(req.resume_tokens or ()),
                                   "error", error)

    def _retire_abnormal(self, st: _SeqState, slot: int,
                         reason: str, error=None) -> RequestOutput:
        """Retire a LIVE sequence off the normal eos/length path
        (timeout / cancelled / nan / error): pages freed this call, slot
        cleared, tokens generated so far delivered."""
        req = st.req
        if reason == "nan" and st.inserted_nodes and \
                self.prefix_cache is not None:
            # prefix nodes built FROM this request's (now suspect) KV
            # must never serve another admission: evict them and any
            # subtree grown on top; pages pinned by live sequences stay
            # until those retire, and the release is scrub-marked
            self.prefix_cache.evict_nodes(st.inserted_nodes)
        if self.pool.has_seq(req.req_id):
            # scrub=True for NaN: the pool zeroes each freed page lazily
            # on reuse — attention masks give padding lanes weight 0,
            # but IEEE 0 * NaN = NaN, so a poisoned page handed to the
            # next sequence would re-poison it through its masked tail.
            # Normal retires skip it: finite garbage IS annihilated by
            # the 0 weights. Pages a sibling or the cache still
            # references defer (scrub-pending, zeroed at refcount zero).
            self.pool.free(req.req_id, scrub=(reason == "nan"))
        self.slots[slot] = None
        self._grammar_release(st)
        return self._emit_terminal(req, st.gen, reason, error)

    def _sweep_deadlines(self) -> List[RequestOutput]:
        """Retire every over-deadline request; runs at the top of each
        step so an overloaded queue sheds load instead of serving stale
        work. Still-QUEUED requests retire ``finish_reason="expired"``
        — their deadline lapsed while waiting, pages never allocated —
        while admitted (mid-prefill / mid-decode) requests retire
        ``"timeout"`` with the tokens generated so far. The split keeps
        the overload story honest: ``expired`` counts work the fleet
        never touched, ``timeout`` counts work it started but could not
        finish in time. A queued request carrying a journal (migrated
        or brownout-preempted — the fleet DID touch it) therefore
        retires ``"timeout"``, keeping ``expired`` an exact count of
        never-admitted work."""
        finished: List[RequestOutput] = []
        for req in self.scheduler.pop_expired():
            if req.resume_tokens is not None:
                finished.append(self._finish_queued(req, "timeout"))
                continue
            self._trace.emit("req.expire", req.req_id,
                             label=self.engine_id)
            finished.append(self._finish_queued(req, "expired"))
        for i, st in enumerate(self.slots):
            if (st is not None and st.req.deadline is not None
                    and st.req.deadline.expired()):
                finished.append(
                    self._retire_abnormal(st, slot=i, reason="timeout"))
        return finished

    # ----------------------------------------------------------- admission
    def _admit(self, req: Request) -> None:
        """Park a request in a free slot: longest-prefix match against
        the radix cache (full pages, capped at s-1 so the final chunk
        always computes the first sample's logits), adopt matched pages
        by refcount, and set the chunk cursor. The prefill itself runs
        inside the next unified steps, sliced under the token budget —
        admission costs no model compute at all. A migrated request
        (``resume_tokens`` set) admits over prompt + journal: chunked
        re-prefill rebuilds the KV the dead engine held, and the final
        chunk's sample IS the stream's next token (docs/RESILIENCE.md
        "In-flight migration"). Admission also binds ISSUE 16's tenancy
        data: the request's adapter slot index, and its interned grammar
        (offset + DFA state — seeded from ``resume_fsm_state`` for a
        migrated request, else by walking the journal, so constrained
        streams resume mid-structure)."""
        faults.point("serving.prefill")
        ids = req.admission_ids()
        cache = self.prefix_cache if req.prefix_cache else None
        if cache is not None:
            matched, shared_pages, _nodes = cache.match(ids)
        else:
            matched, shared_pages = 0, []
        # matched pages join the table by refcount (no free-list draw,
        # bumped before any fresh page is taken so eviction can't race
        # the adoption); the chunk cursor starts AFTER the covered
        # prefix — chunked-prefill progress and cache hits are the same
        # thing, a cache length
        self.pool.allocate(req.req_id, matched,
                           max_total_tokens=req.max_total_tokens,
                           prefix_pages=shared_pages,
                           prefix_tokens=matched)
        st = _SeqState(req, ids, pos=matched)
        try:
            st.adp_slot = self.adapters.slot(req.adapter_id)
        except KeyError as e:
            raise ValueError(str(e))
        if req.adapter_id is not None:
            self._m_adapter_req.labels(adapter_id=req.adapter_id,
                                       **self._lbl).inc()
        if req.grammar is not None:
            st.fsm_off = self._grammar_intern(req.grammar)
            st.fsm = req.grammar
            if req.resume_fsm_state is not None:
                st.fsm_state = int(req.resume_fsm_state)
            else:
                # fresh admission: the journal (if any) was generated
                # under this same grammar — walk it to the live state
                st.fsm_state = st.fsm.advance(0, req.resume_tokens or ())
            self._m_grammar_req.inc()
        self.slots[self.slots.index(None)] = st
        self._trace.emit("req.admit", req.req_id, arg=float(matched),
                         label=self.engine_id)
        if matched:
            self._trace.emit("req.prefix_hit", req.req_id,
                             arg=float(matched))

    # --------------------------------------------------- unified step
    def _grid_tokens(self, total: int) -> int:
        """Token-grid bucket for one unified step: the slot grid B while
        the step fits it (a decode-only step costs exactly what the old
        decode-only program did, and a small chunk rides padding rows
        that grid already pays for), else the next power of two (floored
        at 16) — with an optional operator-pinned floor
        (``min_step_tokens``) that freezes EVERY step to one shape, the
        strongest inter-token-latency isolation: prompt chunks can never
        change the compiled step's cost (docs/SERVING.md "Unified step &
        chunked prefill")."""
        floor_ = max(self.max_batch_slots, int(self.min_step_tokens or 0))
        if total <= floor_:
            return floor_
        return max(_MIN_GRID_TOKENS, 1 << (int(total) - 1).bit_length())

    def _make_step(self) -> jit.StaticFunction:
        """THE unified ragged step program (tentpole of ISSUE 11): one
        compiled function serving every prefill/decode mix. Inputs ride
        as data, shapes only as the token-grid bucket T:

        - ``tok`` [T, 1] — every query token this step, flattened: one
          row per decode slot, one row per prompt-chunk token,
        - ``tok_pos`` [T] — each row's absolute position,
        - ``tok_bt`` [T, pages_per_seq] — each row's OWNER's block table
          (a chunk repeats its slot's table row per token),
        - ``sample_rows`` [B, S] — grid rows where each slot's samples
          read logits (S = spec_k+1: the slot's last/chunk-final token
          plus its draft rows; column 0 is the pre-speculation
          ``last_row``, unused columns and idle slots point at row 0 and
          are discarded on host),
        - ``sample_pos`` [B, S] — the positions that key each sample,
        - ``tok_adp`` [T] — each row's OWNER's adapter slot in the
          stacked LoRA arrays (0 = reserved zero-delta identity),
        - ``temps``/``seeds`` [B] — per-slot sampling params,
        - ``fsm_state`` [B, S] — each sample's ABSOLUTE grammar-table
          row (0 = reserved all-True identity row; draft columns carry
          host-precomputed hypothetical states),
        - ``tok_src`` [T] — per row, -1 to take ``tok``, or j >= 0 to
          take ``prev[j]``: a decode row whose token the step still in
          flight samples,
        - ``grammar_table`` [grammar_states, V] — the interned DFA
          allow-masks, one device table for every live grammar,
        - ``prev`` [B*S] — the samples of the previous call (its ``nxt``,
          the device array itself: neither transferred nor donated), or
          zeros where nothing is in flight,
        - ``*rest`` — where the pool keeps per-slot state, first
          ``tok_slot`` [T]: each row's OWNER slot (the spare slot B for
          padding rows), which the state layers index their state by; then
          the stacked adapter (A, B) arrays per site, then the pool's
          arrays (paged K/V, and per-slot state where a layer keeps it),
          consumed and returned functionally.

        Adapters and grammars are ALWAYS in the program — disabled is a
        VALUE (slot 0's zero weights add exactly 0.0; row 0's all-True
        mask selects the raw logits bitwise), never a branch, so
        adapter/grammar on/off shares one compiled signature and
        ``compile_counts()`` stays pinned (ISSUE 16).

        The trunk's ``forward_paged`` treats every row as "one token at
        an arbitrary position over an arbitrary page list" — which is
        the whole ragged trick (ops/pallas/paged_attention.py "Ragged
        form"): each layer scatters ALL T rows' KV into the pool first,
        then gathers per-row attention masked at the row's own position,
        so chunk tokens causally see their chunk-mates, decode rows are
        untouched by them, and a DRAFT row at position p+j attends the
        KV its burst-mates scattered this very step — speculation's
        in-step causality for free. Sampling gathers the B*S sample
        rows BEFORE the vocab matmul (the [V] projection runs on B*S
        rows, not T) and derives per-row keys
        fold_in(PRNGKey(seed), sample_pos) — the _sample_key contract,
        traced: a draft row's target at position p+j is EXACTLY the
        token the stream would sample there without speculation, which
        is why acceptance-by-equality preserves bit-identical streams."""
        trunk, model, pool = self.trunk, self.model, self.pool
        site_names = [s for s, _, _ in self.adapters.sites]
        n_adp = 2 * len(site_names)
        # a pool with per-slot state adds one grid after `prev`: tok_slot
        n_own = int(pool.has_state)

        def step_fn(tok, tok_pos, tok_bt, tok_adp, sample_rows, sample_pos,
                    temps, seeds, fsm_state, tok_src, grammar_table, prev,
                    *rest):
            # the pool's operands, regrouped by the pool into one opaque
            # cache per layer: which arrays a layer keeps (int8 pages add
            # their scales, a state layer keeps its state) is the pool's
            # and ops/paged_cache.py's to know, and changes WHICH arrays
            # ride as data, never the program count
            own, rest = rest[:n_own], rest[n_own:]
            adp_flat, caches = rest[:n_adp], pool.layer_caches(rest[n_adp:])
            slots = {"slots": own[0]} if n_own else {}
            with no_grad():
                # a row whose token the step in flight samples reads it
                # from that step's output here, never through the host
                tok = apply_op(
                    lambda t, s, pv: jnp.where(
                        (s >= 0)[:, None], pv[jnp.maximum(s, 0)][:, None], t),
                    [ensure_tensor(tok), ensure_tensor(tok_src),
                     ensure_tensor(prev)], name="tokens_in_flight")
                # per-row adapter gather: every grid row pulls ITS
                # owner's (A, B) stack by index — slot 0 rows pull the
                # zero identity, so the delta below is + 0.0 exactly
                adapters = {}
                for si, site in enumerate(site_names):
                    ga = apply_op(
                        lambda a, ix: a[ix.reshape(-1).astype(jnp.int32)],
                        [ensure_tensor(adp_flat[2 * si]),
                         ensure_tensor(tok_adp)],
                        name="gather_adapter_a")
                    gb = apply_op(
                        lambda b, ix: b[ix.reshape(-1).astype(jnp.int32)],
                        [ensure_tensor(adp_flat[2 * si + 1]),
                         ensure_tensor(tok_adp)],
                        name="gather_adapter_b")
                    adapters[site] = (ga, gb)
                hidden, ncs = trunk.forward_paged(tok, tok_pos, tok_bt,
                                                  caches, adapters=adapters,
                                                  **slots)
                # per-slot sample rows gathered BEFORE the vocab matmul:
                # the grid carries up to token-budget rows but only
                # max_batch_slots * (spec_k+1) of them sample
                last_h = apply_op(
                    lambda h, li: h[li.reshape(-1).astype(jnp.int32)],
                    [ensure_tensor(hidden), ensure_tensor(sample_rows)],
                    name="gather_sample_rows")
                logits = model.logits(last_h)
            last = apply_op(lambda lv: lv[:, -1, :].astype(jnp.float32),
                            [ensure_tensor(logits)], name="last_logits")
            # per-slot finite flag BEFORE sampling: the host quarantines
            # any slot whose logits went NaN/inf (poisoned KV, numeric
            # blowup) without ever trusting its sampled token — and
            # because it rides in the same program, the check costs one
            # fused reduction, not a second compile. Mid-prompt chunks
            # get the same canary: their sample row is real compute even
            # though its sample is discarded.
            fin = apply_op(
                lambda lv: jnp.isfinite(lv).all(axis=-1),
                [last], name="logits_finite")
            # constrained decoding: each sample row gathers its DFA
            # state's allow-mask from the ONE interned grammar table and
            # masks disallowed tokens to -1e30 BEFORE sampling — so
            # greedy, temperature, and draft-target sampling are all
            # constrained by the same op. Row 0 is all-True:
            # where(True, lv, -1e30) IS lv, bitwise — the grammar-off
            # identity that keeps this in the one compiled signature.
            # NaN-quarantine ordering: fin reads the RAW logits above,
            # so a poisoned row still trips the canary even if the mask
            # would have hidden its non-finite lanes.
            masked = apply_op(
                lambda lv, gt, fs: jnp.where(
                    gt[fs.reshape(-1).astype(jnp.int32)], lv,
                    jnp.float32(-1e30)),
                [last, ensure_tensor(grammar_table),
                 ensure_tensor(fsm_state)], name="grammar_mask")

            def batched_sample(lv, tv, sv, pv):
                # per-row key = fold_in(PRNGKey(seed), position) — the
                # _sample_key contract, traced: each request samples
                # from ITS OWN stream, so its tokens are a pure function
                # of (prompt, seed, temperature) no matter which
                # batch-mates ride the grid, how its prompt was chunked,
                # or which engine runs it. seeds and positions are DATA:
                # no recompile, and an idle sample row's (0, 0) key
                # samples masked garbage that the host discards as
                # before. lv is [B*S, V]; temps/seeds broadcast across
                # each slot's S sample rows (one request, one stream),
                # positions arrive per row — a draft row at p+j samples
                # with the SAME key the plain decode at p+j would use.
                S = pv.shape[1]
                tvf = jnp.repeat(tv.astype(jnp.float32), S)
                svf = jnp.repeat(sv, S)
                pvf = pv.reshape(-1)
                greedy = jnp.argmax(lv, axis=-1).astype(jnp.int32)
                t = jnp.maximum(tvf, 1e-6)

                def one_row(seed_i, pos_i, row):
                    key = jax.random.fold_in(jax.random.PRNGKey(seed_i),
                                             pos_i)
                    return jax.random.categorical(key, row)

                sampled = jax.vmap(one_row)(
                    svf, pvf, lv / t[:, None]).astype(jnp.int32)
                return jnp.where(tvf > 0, sampled, greedy)

            nxt = apply_op(batched_sample,
                           [masked, ensure_tensor(temps),
                            ensure_tensor(seeds), ensure_tensor(sample_pos)],
                           name="serve_sample")
            return (nxt, fin, *pool.step_flat(ncs))

        # "the step compiles once per bucket" becomes monitorable:
        # jit_compiles_total{fn="serving_step"} must pin at the
        # bucket-set size. cache_key_extra folds the model architecture
        # and pool geometry into the persistent compile-cache key:
        # config values are baked into the traced program as CONSTANTS,
        # invisible to the shape-only spec key, so two engines whose
        # pools merely have equal shapes must not share an executable.
        step_fn.__name__ = "serving_step"
        cfg = self.model.config
        extra = repr((type(self.model).__name__, sorted(
            (k, v) for k, v in vars(cfg).items()
            if isinstance(v, (bool, int, float, str, type(None)))),
            self.page_size, self.pages_per_seq, self._spec_rows,
            self.adapters.capacity, self.adapters.rank,
            self._grammar_cap, str(jnp.dtype(self.pool.dtype))))
        if n_own:   # which layers keep state, and its shapes
            extra += repr(pool.layer_kinds)
        # the step CONSUMES the pool: its operands (`pool.step_flat()`, the
        # trailing positional arguments, after the ten grids, the grammar
        # table, the previous samples, `tok_slot` where the pool keeps
        # state and the adapter stacks) are given up
        # to the program, which writes this step's rows into the same buffers and hands them back in the same order —
        # `set_step_flat` swaps them in, and what the pool held before the
        # call is deleted. jax pairs a donated input with the first output
        # of equal aval in order, which that order (k0, v0, k1, ...) keeps.
        first_pool = 12 + n_own + n_adp
        return jit.StaticFunction(
            step_fn, observe=[self.model], warmup=False, dy2static=False,
            cache_dir=self._compile_cache_dir, cache_key_extra=extra,
            donate_argnums=range(first_pool,
                                 first_pool + len(pool.step_flat())))

    def _launch_next(self, finished: List[RequestOutput]) -> Optional[_Launch]:
        """Plan, pack and launch the next step behind whatever is in
        flight. Returns the step it landed first, if it did: the oldest in
        flight, where the new step's token-grid bucket never ran here (it
        compiles inside the launch, and no landing should wait behind a
        compile)."""
        t0 = time.perf_counter()
        rows = self._plan(finished)
        if not rows:
            return None
        launch, grids, prev = self._pack(rows, t0)
        landed = None
        if launch.fresh and self._flight:
            landed = self._flight.pop(0)
            self._land(landed, finished)
        self._dispatch(launch, grids, prev)
        return landed

    def _plan(self, finished: List[RequestOutput]) -> list:
        """The ``step.plan`` phase: brownout and host-tier hooks,
        admission, then the next step's rows with their KV room reserved.
        Each slot is planned from ``pos + ahead``, where its rows in flight
        will leave it; a slot whose token in flight is its last
        (``max_new_tokens``) gets no row."""
        if self._overload is not None:
            # brownout level >= 3: preempt batch-tier decode slots
            # (journal + requeue, the migration move turned inward) —
            # BEFORE admission so the freed slots and pages are
            # available to interactive work this very step
            self._brownout_enforce()
        if self._host_offload:
            # page pressure relief BEFORE admission: parking a cold
            # low-priority slot moves its pages (and its worst-case tail
            # reservation) to the host tier, so can_admit sees the
            # reclaimed capacity this very step — offload-before-reject,
            # and before the prefix cache gets evicted for the same pages
            self._park_for_pressure()
        free = sum(1 for s in self.slots if s is None)
        _cap = (None if self._overload is None
                else self._overload.admit_priority_cap())
        for req in self.scheduler.admit(free, self.pool, max_priority=_cap):
            self._m_requests.labels(event="admitted", **self._lbl).inc()
            try:
                # an admission failure (cache/alloc fault, injected
                # drill) fails THIS request, not the engine: batch-mates
                # keep decoding, the queue keeps draining
                self._admit(req)
            except Exception as e:
                finished.append(self._fail_admitted_request(req, e))
        if self._host_offload:
            # restore parked slots whose pages now fit again — AFTER
            # admission so a just-admitted head request is never
            # displaced by the stream it preempted
            self._unpark_ready()
        rows: list = []
        if not any(s is not None for s in self.slots):
            return rows
        decode_idx: List[int] = []
        prefill_info = []
        for i, st in enumerate(self.slots):
            if st is None:
                continue
            if st.parked:
                # parked slot: zero rows this step — its KV lives on the
                # host tier and its block table holds null sentinels
                continue
            if (self._host_offload
                    and self.pool.offloaded_pages(st.req.req_id)):
                # LATE prefetch: an active slot reached the step path
                # with pages still on the host (unpark restored the flag
                # but not the pages, or a caller flipped `parked` by
                # hand). Restore NOW — blocking, which is exactly the
                # stall the unpark-time prefetch exists to avoid — and
                # count it so operators can see the policy miss
                try:
                    n = self.pool.prefetch_seq(st.req.req_id)
                    self._m_prefetch_late.inc(float(n))
                except Exception as e:
                    finished.append(self._retire_abnormal(
                        st, slot=i, reason="error", error=e))
                    continue
            p = st.pos + st.ahead
            if p < st.ids.size:
                prefill_info.append((i, int(st.ids.size) - p, st.req))
            elif len(st.gen) + st.ahead_tokens < st.req.max_new_tokens:
                decode_idx.append(i)
        # brownout hooks (overload.OverloadController): both are pure
        # planning data — chunk sizes and draft gating never touch the
        # compiled step's shape set, so the compile surface is invariant
        # across every ladder level
        _ovl = self._overload
        chunks = self.scheduler.plan_chunks(
            len(decode_idx), prefill_info,
            batch_cap=None if _ovl is None else _ovl.chunk_cap(),
            batch_priority=(2 if _ovl is None
                            else _ovl.config.batch_priority))
        for i, c in chunks:
            self._trace.emit("req.chunk_planned",
                             self.slots[i].req.req_id, arg=float(c))

        # speculative drafts ride the budget's LEFTOVER only — charged
        # strictly after decode tokens and prompt chunks, so speculation
        # can never displace a running stream's next token or slow a
        # prefill (scheduler.plan_drafts splits the remainder in the
        # same SLO order as chunks). Each slot's draft count is further
        # capped so the burst can never overrun max_new_tokens (the base
        # decode emits >= 1, hence remaining-1) or the request's page
        # reservation / context window. A drafting engine lands first
        # (_lands_first), so nothing of these slots is in flight.
        drafts: Dict[int, np.ndarray] = {}
        if (self.drafter is not None and decode_idx
                and not (_ovl is not None and _ovl.drafts_paused)):
            leftover = (self.token_budget - len(decode_idx)
                        - sum(c for _, c in chunks))
            if leftover > 0:
                wants = []
                for i in decode_idx:
                    st = self.slots[i]
                    limit = min(
                        int(st.req.prompt.size) + int(st.req.max_new_tokens),
                        self.max_model_len)
                    cap = min(self.spec_k,
                              int(st.req.max_new_tokens) - len(st.gen) - 1,
                              limit - (st.pos + 1))
                    if cap > 0:
                        wants.append((i, cap, st.req))
                for i, d in self.scheduler.plan_drafts(leftover, wants):
                    st = self.slots[i]
                    # the full stream so far: prompt + gen covers a
                    # migrated request too (gen is journal-seeded), so
                    # drafting is migration-invariant like sampling
                    prop = self.drafter.propose(
                        np.concatenate([st.req.prompt,
                                        np.asarray(st.gen, np.int32)]), d)
                    prop = np.asarray(prop, np.int32).reshape(-1)[:d]
                    if st.fsm is not None and prop.size:
                        # constrained slot: keep only the longest
                        # grammar-valid prefix of the proposal — an
                        # invalid draft could never equal its (masked)
                        # target, so rows past the first violation are
                        # guaranteed-wasted compute, and the hypothetical
                        # FSM states its sample columns need would not
                        # even exist
                        s_, keep = st.fsm_state, 0
                        for t_ in prop:
                            s_ = st.fsm.next_state(s_, int(t_))
                            if s_ < 0:
                                break
                            keep += 1
                        if keep < prop.size:
                            self._m_grammar_filtered.inc(
                                int(prop.size) - keep)
                            prop = prop[:keep]
                    if prop.size:
                        drafts[i] = prop
                        self._trace.emit("req.drafts", st.req.req_id,
                                         arg=float(prop.size))

        # KV room per slot BEFORE the compiled step: decode rows reserve
        # this step's writes via extend()/extend_write() (not
        # append_token — a step aborted after this loop re-reserves the
        # SAME positions on retry instead of drifting _lens one phantom
        # token per aborted step); a slot with draft rows reserves the
        # whole burst range like a chunk does (CoW seam included —
        # rejected drafts roll back by pool.truncate, which relies on
        # this exclusivity); chunk rows reserve their whole range via
        # extend_write. Out of pages (impossible unless injected/
        # buggy): quarantine the victim, keep the rest of the batch —
        # its row simply never joins the grid. A decode row whose token
        # is still in flight reads it on the device: src is its index in
        # the previous step's samples (-1: the host's last_token).
        S = self._spec_rows
        for i in decode_idx:
            st = self.slots[i]
            p = st.pos + st.ahead
            d_toks = drafts.get(i)
            d = 0 if d_toks is None else int(d_toks.size)
            try:
                if d:
                    self.pool.extend_write(st.req.req_id, p, p + 1 + d)
                else:
                    self.pool.extend(st.req.req_id, p + 1)
            except Exception as e:
                finished.append(
                    self._retire_abnormal(st, slot=i, reason="error",
                                          error=e))
                continue
            toks = (np.concatenate([[st.last_token], d_toks]).astype(np.int32)
                    if d else np.asarray([st.last_token], np.int32))
            rows.append((i, st, toks,
                         np.arange(p, p + 1 + d, dtype=np.int32),
                         False, d, i * S if st.ahead_tokens else -1))
        for i, c in chunks:
            st = self.slots[i]
            p = st.pos + st.ahead
            try:
                self.pool.extend_write(st.req.req_id, p, p + c)
            except Exception as e:
                finished.append(
                    self._retire_abnormal(st, slot=i, reason="error",
                                          error=e))
                continue
            rows.append((i, st, st.ids[p:p + c],
                         np.arange(p, p + c, dtype=np.int32), True, 0, -1))
        faults.point("serving.decode_step")
        return rows

    def _pack(self, rows: list, t0: float):
        """The ``step.pack`` phase: the ten numpy grids of ``rows``, and
        the step's record. Returns (launch, grids, prev): ``prev`` is the
        samples of the step in flight, which rows with ``src`` >= 0 read."""
        self._phase = self._trace.next("step.pack", self._phase)
        B = self.max_batch_slots
        total = sum(r[2].size for r in rows)
        T = self._grid_tokens(total)
        launch = _Launch()
        launch.rows, launch.t0, launch.clips = rows, t0, None
        # a bucket this engine never ran compiles (or deserializes from
        # the disk cache) inside the coming program call — remember it
        # now so the wall time lands in the trace's compile bucket
        launch.fresh = T not in self._grid_buckets_seen
        self._grid_buckets_seen.add(T)
        S = self._spec_rows
        tok = np.zeros((T, 1), np.int32)
        tok_pos = np.zeros(T, np.int32)
        tok_bt = np.zeros((T, self.pages_per_seq), np.int32)
        tok_adp = np.zeros(T, np.int32)
        sample_rows = np.zeros((B, S), np.int32)
        sample_pos = np.zeros((B, S), np.int32)
        temps = np.zeros(B, np.float32)
        seeds = np.zeros(B, np.int32)
        # absolute grammar-table rows per sample; idle/unconstrained
        # entries stay 0 = the all-True identity row (mask is a no-op)
        fsm_state = np.zeros((B, S), np.int32)
        tok_src = np.full(T, -1, np.int32)
        # owner slot per row; padding rows write the spare slot's state
        tok_slot = np.full(T, B, np.int32)
        cur = 0
        kv_walked = kv_held = n_decode = n_draft = n_block = 0
        for i, st, toks, poss, is_chunk, d, src in rows:
            c = toks.size
            # the keys the kernel walks: a slot's c consecutive rows form
            # query blocks of up to _query_rows rows, each walking its
            # last row's keys; the keys that exist are the last row's
            first, last = int(poss[0]), int(poss[-1])
            kv_walked += walked_keys(first + 1, c, self._query_rows)
            kv_held += last + 1
            n_block += block_rows(c, self._state_block)
            tok[cur:cur + c, 0] = toks
            tok_pos[cur:cur + c] = poss
            table = self.pool.block_table(st.req.req_id)
            tok_bt[cur:cur + c, :len(table)] = table
            tok_adp[cur:cur + c] = st.adp_slot
            tok_slot[cur:cur + c] = i
            if is_chunk:
                sample_rows[i, 0] = cur + c - 1
                sample_pos[i, 0] = int(poss[-1])
                if st.fsm is not None:
                    # only the FINAL chunk's sample lands, and it is the
                    # stream's next token — mask it at the current (post-
                    # journal) DFA state; mid-prompt chunks' discarded
                    # samples get the same row harmlessly
                    fsm_state[i, 0] = st.fsm_off + st.fsm_state
            else:
                n_decode += 1
                n_draft += d
                tok_src[cur] = src
                # base decode row + its d draft rows are contiguous:
                # sample column j targets position pos+j, i.e. the token
                # FOLLOWING the j-th burst token
                sample_rows[i, :d + 1] = np.arange(cur, cur + d + 1)
                sample_pos[i, :d + 1] = poss
                if st.fsm is not None:
                    # column j masks the token AFTER burst token j, so it
                    # needs the HYPOTHETICAL state once drafts 1..j have
                    # landed — host-walked here; drafts were pre-filtered
                    # to grammar-valid, so the walk stays live. Without
                    # this, unmasked draft targets could never match a
                    # constrained stream and acceptance would collapse.
                    s_ = st.fsm_state
                    fsm_state[i, 0] = st.fsm_off + s_
                    for j in range(1, d + 1):
                        s_ = st.fsm.next_state(s_, int(toks[j]))
                        fsm_state[i, j] = st.fsm_off + s_
            temps[i] = st.req.temperature
            seeds[i] = st.req.seed
            cur += c
        launch.counts = (total, T, n_decode, total - n_decode - n_draft,
                         n_draft, len(rows), kv_walked, kv_held)
        grids = [tok, tok_pos, tok_bt, tok_adp, sample_rows, sample_pos,
                 temps, seeds, fsm_state, tok_src]
        # rows that advance a per-slot state (once a step, not per layer),
        # those of prompt chunks, the slots' runs of rows, and the rows
        # the kernel advances a block at a time
        launch.state_counts = _NO_STATE
        if self.pool.has_state:
            grids.append(tok_slot)
            launch.state_counts = (total, total - n_decode - n_draft,
                                   len(rows), n_block)
        prev = self._flight[-1].nxt if self._flight else None
        return launch, grids, prev

    def _dispatch(self, launch: _Launch, grids: list, prev) -> None:
        """The ``step.dispatch`` phase: one transfer of the grids
        (``step.transfer``), the program call (its ``step.operands``,
        ``step.launch`` and ``step.outputs``), and the pool's arrays
        swapped for the ones the call returns. Nothing here waits for the
        device."""
        trace = self._trace
        self._phase = trace.next("step.dispatch", self._phase)
        if self._step_prog is None:
            launch.fresh = True
            self._step_prog = self._compile_with_retry(
                "serving.compile_step", self._make_step)
        sp = trace.begin("step.transfer", self.engine_id)
        try:
            if prev is None:
                if self._no_prev is None:
                    self._no_prev = Tensor(jax.device_put(np.zeros(
                        self.max_batch_slots * self._spec_rows, np.int32)))
                prev = self._no_prev
            launch.t_prog = time.perf_counter()
            # the grids go up in one transfer call, not one each
            sent = [Tensor(a) for a in jax.device_put(grids)]
        finally:
            trace.end(sp)
        res = self._step_prog(
            *sent[:10], self._grammar_device, prev, *sent[10:],
            *self.adapters.arrays(), *self.pool.step_flat())
        launch.nxt, launch.fin, flat = res[0], res[1], res[2:]
        self.pool.set_step_flat(flat)
        total = launch.counts[0]
        if self.pool.quantized and total:
            # absmax-floor accounting for THIS step's written slots: a
            # clipped scale means a (page, pos, head) row whose KV
            # underflowed the quantizer's dynamic range (kv_cache docs).
            # Asked of the arrays the step returned, before a later step
            # is given them, as a device count that is read when the step
            # lands: nothing here waits for the device
            tok_pos, tok_bt = grids[1][:total], grids[2]
            w_pages = tok_bt[np.arange(total), tok_pos // self.page_size]
            live = w_pages > 0
            if live.any():
                launch.clips = self.pool.scale_clips(
                    w_pages[live], (tok_pos % self.page_size)[live])
        for i, st, toks, poss, is_chunk, d, src in launch.rows:
            st.ahead += toks.size
            st.ahead_tokens += int(not is_chunk
                                   or int(poss[-1]) + 1 == st.ids.size)
        self._flight.append(launch)

    def _land(self, launch: _Launch, finished: List[RequestOutput]) -> None:
        """The ``step.wait`` and ``step.land`` phases of one launched step:
        read its samples back, then acceptance, streaming and retirement.
        A row whose sequence ended since it was planned (eos, NaN
        quarantine, cancel, deadline) is thrown away: its sample is never
        streamed, journaled or counted (``launch.discarded``)."""
        trace = self._trace
        B, S = self.max_batch_slots, self._spec_rows
        self._phase = trace.next("step.wait", self._phase)
        nxt_host = np.asarray(launch.nxt.numpy()).reshape(B, S)
        fin_host = np.asarray(launch.fin.numpy()).reshape(B, S).astype(bool)
        self._phase = trace.next("step.land", self._phase)
        now = time.perf_counter()
        n_decode, n_chunk, n_draft = launch.counts[2:5]
        self._m_decode.observe(now - launch.t0)
        self._m_mix_decode.observe(n_decode)
        self._m_mix_draft.observe(n_draft)
        self._m_mix_prefill.observe(n_chunk)
        if launch.clips is not None:
            self.pool.count_scale_clips(launch.clips)
        for i, st, toks, poss, is_chunk, d, src in launch.rows:
            st.ahead -= toks.size
            st.ahead_tokens -= int(not is_chunk
                                   or int(poss[-1]) + 1 == st.ids.size)
        if launch.fresh:
            # a fresh token-grid bucket compiled inside this program
            # call: charge its wall time to every rider, so the
            # attribution pass can name compile (not prefill) as where
            # a cold request's TTFT went
            for i, st, *_ in launch.rows:
                if self.slots[i] is st:
                    self._trace.emit("req.compile", st.req.req_id,
                                     arg=now - launch.t_prog, t=now)
        discarded = 0
        for i, st, toks, poss, is_chunk, d, src in launch.rows:
            if self.slots[i] is not st or int(poss[0]) != st.pos:
                # the sequence ended after this row was planned (or an
                # earlier row's callback cancelled it reentrantly):
                # touching it again would land a token nobody may see
                discarded += toks.size
                continue
            n_sample = 1 if is_chunk else d + 1
            if not fin_host[i, :n_sample].all():
                # NaN/inf logits on the slot's sample row: quarantine
                # ONLY this sequence — its sampled token is garbage and
                # is never appended (for a chunk, the KV it wrote is as
                # untrustworthy as the sample); pages return to the pool
                # now; batch-mates are untouched because attention
                # gathers strictly via block tables. Mid-prompt chunks
                # get the same canary, so poison never survives to a
                # later chunk.
                if is_chunk:
                    st.pos += toks.size
                    self._m_chunk.observe(toks.size)
                finished.append(
                    self._retire_abnormal(st, slot=i, reason="nan"))
                continue
            if is_chunk:
                c = toks.size
                st.pos += c
                self._m_chunk.observe(c)
                self._trace.emit("req.chunk", st.req.req_id,
                                 arg=float(c), t=now)
                if st.prefilling:
                    continue  # mid-prompt: more chunks to go, no token
                # FINAL chunk: the sample at position len(ids)-1 IS the
                # stream's next token (first generated, or the journal's
                # successor for a migrated request — key position s-1
                # matches the decode the dead engine would have run)
                cache = (self.prefix_cache if st.req.prefix_cache
                         else None)
                if cache is not None:
                    # index this prompt's full pages for the next
                    # admission (prompt only — journal/generated tokens
                    # are per-request noise); the created nodes ride the
                    # slot state so a NaN quarantine can evict exactly
                    # what THIS request contributed
                    st.inserted_nodes = cache.insert(
                        st.req.prompt, int(st.req.prompt.size),
                        self.pool.block_table(st.req.req_id))
                self._m_prefill.observe(now - st.t_admit)
                if not st.req.resume_tokens:
                    # a resumed request's first token landed long ago
                    self._m_ttft.observe(now - st.req.arrival_t)
                out = self._land_token(st, slot=i,
                                       token=int(nxt_host[i, 0]), now=now)
                if out is not None:
                    finished.append(out)
                continue
            # decode burst: sample column j holds the stream's token at
            # position pos+j+1 — the EXACT token a plain decode would
            # sample there (same fold_in key, same logits given the same
            # prefix). Accept the longest prefix of drafts that equals
            # those targets, then land accepted drafts' targets plus the
            # free "bonus" token from the first mismatching (or final)
            # column. Rejected draft rows wrote KV for tokens the stream
            # never took: roll the pool length back BEFORE landing (a
            # landed token may retire the request and free its pages).
            targets = nxt_host[i, :d + 1]
            a = 0
            while a < d and int(toks[a + 1]) == int(targets[a]):
                a += 1
            if d:
                self._m_spec_drafted.inc(d)
                self._m_spec_accepted.inc(a)
                self._m_spec_accept.observe(a / d)
                self._trace.emit("req.spec_accept", st.req.req_id,
                                 arg=float(a), t=now)
                if a < d:
                    self._trace.emit("req.spec_reject", st.req.req_id,
                                     arg=float(d - a), t=now)
                    self.pool.truncate(st.req.req_id, st.pos + a + 1)
            for t in targets[:a + 1]:
                st.pos += 1
                # per-sequence inter-token latency: the streaming SLO —
                # step time plus any step this sequence sat through
                # (accepted drafts land with near-zero gaps: speculation
                # collapses ITL, which is the whole point)
                self._m_itl.observe(now - st.t_last)
                out = self._land_token(st, slot=i, token=int(t), now=now)
                if out is not None:
                    finished.append(out)
                    break
                if self.slots[i] is not st:
                    break  # reentrant cancel inside the stream callback
        launch.discarded = discarded

    def _land_token(self, st: _SeqState, slot: int, token: int,
                    now: float) -> Optional[RequestOutput]:
        """ONE copy of the token-landing choreography, shared by the
        final-chunk first token and every decode token: append to the
        journal, advance the grammar DFA, stream it (isolated,
        reentrant-cancel-aware), and retire on eos/length/grammar-
        complete. Returns the retirement output, if any."""
        st.last_token = token
        st.gen.append(token)
        st.t_last = now
        self._m_tokens.inc()
        self.stats["generated_tokens"] += 1
        self._trace.emit("req.token", st.req.req_id,
                         arg=float(len(st.gen) - 1), t=now)
        if st.fsm is not None and (st.req.eos_token_id is None
                                   or token != st.req.eos_token_id):
            # host mirror of the device mask: the DFA walks every landed
            # non-eos token (the mask guarantees it is allowed, so the
            # walk can't die; eos is terminal and has no DFA edge)
            nxt = st.fsm.next_state(st.fsm_state, token)
            if nxt >= 0:
                st.fsm_state = nxt
            self._m_grammar_tokens.inc()
            self._trace.emit("req.grammar_mask", st.req.req_id,
                             arg=float(st.fsm_state), t=now)
        if st.req.stream_cb is not None:
            cb_err = self._safe_cb(st.req, token, False, len(st.gen) - 1)
            if self.slots[slot] is not st:
                # cancel() ran inside the callback and already retired
                # this sequence — touching it again would double-free
                return None
            if cb_err is not None:
                return self._retire_abnormal(st, slot=slot,
                                             reason="error", error=cb_err)
        return self._maybe_retire(st, slot=slot)

    @staticmethod
    def _sample_key(seed, position):
        """THE determinism contract, in one line: the key that samples
        the token following ``position`` (0-based index of the last
        consumed token) is ``fold_in(PRNGKey(seed), position)`` — a pure
        function of (request seed, stream position). The compiled step
        computes the identical expression per slot (traced, vmapped) for
        final-chunk first tokens and decode tokens alike — threefry is
        deterministic, so every engine derives bit-equal keys and a
        request's sampled stream is independent of batch composition,
        chunk boundaries, engine history, and any migration."""
        return jax.random.fold_in(jax.random.PRNGKey(seed), position)

    # -------------------------------------------------------------- retire
    def _maybe_retire(self, st: _SeqState,
                      slot: int) -> Optional[RequestOutput]:
        req = st.req
        hit_eos = (req.eos_token_id is not None
                   and st.last_token == req.eos_token_id)
        # a constrained request whose DFA can only accept is DONE — the
        # mask admits no further token, so decoding past this point
        # would sample from an all -1e30 row
        done_fsm = st.fsm is not None and st.fsm.is_complete(st.fsm_state)
        if not (hit_eos or done_fsm) and len(st.gen) < req.max_new_tokens:
            return None
        if st.fsm is not None:
            valid = st.fsm.is_accepting(st.fsm_state)
            self._m_grammar_completions.labels(
                result="valid" if valid else "invalid", **self._lbl).inc()
        self._grammar_release(st)
        # retire NOW: pages go back to the pool this very step (has_seq
        # guard: a reentrant cancel from the terminal-token's stream
        # callback may have freed them already)
        if self.pool.has_seq(req.req_id):
            self.pool.free(req.req_id)
        self.slots[slot] = None
        self._m_requests.labels(event="retired", **self._lbl).inc()
        self.stats["finished_requests"] += 1
        out = RequestOutput(req_id=req.req_id,
                            prompt_token_ids=req.prompt,
                            token_ids=list(st.gen),
                            finish_reason=("stop" if hit_eos or done_fsm
                                           else "length"))
        self._trace.emit("req.retire", req.req_id,
                         label=out.finish_reason)
        self._outputs[out.req_id] = out  # eager: survives a later raise
        if req.stream_cb is not None:
            # terminal call: `finished` is the reason string (truthy, so
            # bool-style `if finished:` consumers keep working); isolated
            # like every callback — a raise here only records
            self._safe_cb(req, None, out.finish_reason, len(st.gen))
        return out
