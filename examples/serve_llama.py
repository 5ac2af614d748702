"""Continuous-batching LLM serving: paged KV cache + router control plane.

Run: JAX_PLATFORMS=cpu python examples/serve_llama.py

Three requests with different prompt lengths and budgets stream through a
2-replica Router fleet — placement is least-loaded (queue depth x
step-time EWMA) with health gating, the third request is admitted
MID-DECODE when capacity frees (the continuous-batching point), and the
page pools' high-water marks stay under what three dense caches would
pin. docs/SERVING.md has the sizing math, scheduler knobs, and the
control-plane state machine.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import CompletionAPI, Router

paddle.seed(0)
model = LlamaForCausalLM(llama_tiny())
# Router: ONE model's weights shared by two engine replicas (jax arrays
# are immutable, sharing is free); submit() places each request on the
# least-loaded healthy engine and run() drives the whole fleet
router = Router()
router.add_model("llama-tiny", model, replicas=2, page_size=16,
                 max_batch_slots=2)

rng = np.random.default_rng(0)
prompts = [rng.integers(0, 512, (n,)) for n in (12, 5, 21)]
for p in prompts:
    router.submit(p, model="llama-tiny", max_new_tokens=16,
                  stream_cb=lambda rid, tok, done:
                  print(f"  req {rid}: {'<done>' if done else tok}"))

outputs = router.run()  # least-loaded dispatch, health-gated, to drain
for rid, out in sorted(outputs.items()):
    print(f"req {rid}: {out.n_gen} tokens, finish={out.finish_reason}")
eng = router.engine("llama-tiny/0")
print(f"fleet: {router.states()}, engine0 peak_pages="
      f"{eng.pool.peak_used}, step_compiles="
      f"{eng.compile_counts()['step']}")

# OpenAI-completions-shaped facade over the same fleet: model= routes
# (unknown ids raise an actionable error naming the served models)
api = CompletionAPI(router, model_name="llama-tiny")
resp = api.create_completion(prompts[0], max_tokens=8, model="llama-tiny")
print(f"{resp['object']}: {resp['choices'][0]['token_ids']} "
      f"({resp['usage']['completion_tokens']} completion tokens)")

# telemetry rode along the whole time (docs/OBSERVABILITY.md): TTFT /
# inter-token percentiles — family-level reads aggregate the fleet, the
# per-engine series carry {engine_id, model_id} — and a one-liner scrape
# endpoint any Prometheus can poll
from paddle_tpu import metrics  # noqa: E402

reg = metrics.get_registry()
ttft = reg.get("paddle_tpu_serving_ttft_seconds")
itl = reg.get("paddle_tpu_serving_inter_token_seconds")
disp = reg.get("paddle_tpu_router_dispatch_total")
print(f"ttft p50={ttft.quantile(0.5)*1e3:.1f}ms "
      f"p99={ttft.quantile(0.99)*1e3:.1f}ms | "
      f"itl p50={itl.quantile(0.5)*1e3:.1f}ms "
      f"({itl.count} gaps observed) | "
      f"router dispatches={int(disp.value)}")
# health_cb wires the ROUTER's aggregate health into /healthz: 503 only
# when some served model has no healthy engine, and ?engine=<id> reports
# a single replica (docs/RESILIENCE.md; tools/chaos_serve.py drills the
# failover/reload paths)
with metrics.MetricsServer(port=0, health_cb=router.health) as srv:
    print(f"scrape endpoint (for real deployments keep it running): "
          f"{srv.url}/metrics  health: {srv.url}/healthz "
          f"-> {router.health()['status']} "
          f"(per-engine: {srv.url}/healthz?engine=llama-tiny/0)")
