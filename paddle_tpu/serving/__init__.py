"""paddle_tpu.serving — continuous-batching LLM inference engine.

The production decode path the ROADMAP's "millions of users" north star
needs and ``GenerationMixin.generate`` (one static batch, dense caches)
cannot provide: paged KV memory with refcounted copy-on-write sharing
and a radix prefix cache — shared prompt prefixes admit without
re-prefilling (kv_cache.py, docs/SERVING.md "Prefix caching") — FCFS
token-budget admission charging only each request's uncovered suffix
(scheduler.py), a single compiled ragged-paged-attention decode
step over fixed batch slots (engine.py + ops/pallas/paged_attention.py),
an OpenAI-ish front door with streaming (api.py), and a fleet-scale
control plane (router.py): least-loaded dispatch, health-gated
auto-drain/failover with exactly-once requeue, rolling weight reload
from committed checkpoints, and multi-model tenancy. Always-on
telemetry — TTFT / inter-token-latency / queue-wait histograms,
lifecycle counters, page-pool gauges — lands in ``paddle_tpu.metrics``
(docs/OBSERVABILITY.md). The resilience layer (docs/RESILIENCE.md) rides
``paddle_tpu.faults``: per-request deadlines and ``cancel()``, a bounded
queue that rejects with a ``retry_after_s`` hint (BackpressureError),
NaN-logit quarantine that never poisons batch-mates, isolated stream
callbacks, and a step watchdog surfaced through ``/healthz``. Durability
is opt-in (wal.py): ``Router(wal_dir=...)`` journals every admission and
committed token batch to a CRC-framed write-ahead log under ONE
group-commit fsync per step, and ``Router.recover()`` replays it after a
process death — unfinished requests re-admit through the journaled
re-prefill path and streams complete bit-identical with exactly-once
chunk delivery (docs/RESILIENCE.md "Durability").
Multi-tenancy rides the ONE compiled step as data: batched multi-LoRA
adapters (adapters.py — hot-loaded fleet-wide with zero recompiles,
routed by ``(model_id, adapter_id)``) and token-level constrained
decoding (grammar.py — JSON-schema/regex compiled to a DFA whose
allow-masks gate sampling in-step, migration-safe via FSM journals).

Quick start (docs/SERVING.md has the sizing math; examples/serve_llama.py
is runnable):

    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import ServingEngine

    engine = ServingEngine(LlamaForCausalLM(llama_tiny()), page_size=16,
                           max_batch_slots=8)
    engine.add_request(prompt_ids, max_new_tokens=64, eos_token_id=2)
    outputs = engine.run()          # continuous batching until drained
"""
from .adapters import AdapterStore, random_adapter
from .api import CompletionAPI
from .engine import ServingEngine
from .grammar import GrammarFSM, ToyTokenizer, schema_to_regex, toy_tokenizer
from .kv_cache import (HostPageStore, PagedKVCachePool, PrefixCache,
                       SlotState, normalize_kv_dtype, page_bytes,
                       pages_for_hbm_budget)
from .overload import (AdmissionShedError, DrainEstimator, OverloadConfig,
                       OverloadController, RetryBudget)
from .router import EngineHandle, NoHealthyEngineError, Router
from .scheduler import (BackpressureError, FCFSScheduler, Request,
                        RequestOutput)
from .spec import NGramDrafter
from .tracing import (TTFT_BUCKETS, RequestTracer, attribute_ttft,
                      get_tracer, set_tracer, validate_events)
from .wal import RequestWAL, WalRequest, WalState

__all__ = [
    "ServingEngine", "PagedKVCachePool", "PrefixCache", "FCFSScheduler",
    "Request", "RequestOutput", "CompletionAPI",
    "BackpressureError", "Router", "EngineHandle", "NoHealthyEngineError",
    "NGramDrafter", "page_bytes", "pages_for_hbm_budget",
    "HostPageStore", "SlotState", "normalize_kv_dtype",
    "AdapterStore", "random_adapter", "GrammarFSM", "ToyTokenizer",
    "toy_tokenizer", "schema_to_regex",
    "RequestTracer", "TTFT_BUCKETS", "attribute_ttft", "get_tracer",
    "set_tracer", "validate_events",
    "OverloadController", "OverloadConfig", "DrainEstimator",
    "AdmissionShedError", "RetryBudget",
    "RequestWAL", "WalRequest", "WalState",
]
