"""Profile a training step: RecordEvent scoped annotations + the
Profiler's wait/warmup/active scheduler, exported as a chrome://tracing
JSON (the reference's paddle.profiler surface over the XLA runtime).

Run (CPU):
    JAX_PLATFORMS=cpu python examples/profile_step.py
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.profiler as profiler


def main():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(), nn.Linear(128, 10))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((32, 64)).astype("float32"))
    y = paddle.to_tensor(rng.integers(0, 10, (32,)))

    trace_dir = tempfile.mkdtemp(prefix="pd_prof_")
    sched = profiler.make_scheduler(closed=1, ready=1, record=3, repeat=1)
    with profiler.Profiler(
            scheduler=sched,
            on_trace_ready=profiler.export_chrome_tracing(trace_dir),
            trace_dir=trace_dir) as p:
        for step in range(6):
            with profiler.RecordEvent("train_step"):
                with profiler.RecordEvent("forward"):
                    loss = F.cross_entropy(model(x), y)
                with profiler.RecordEvent("backward"):
                    loss.backward()
                with profiler.RecordEvent("optimizer"):
                    opt.step()
                    opt.clear_grad()
            p.step()

    p.summary(sorted_by=profiler.SortedKeys.CPUTotal)
    traces = [f for f in os.listdir(trace_dir) if f.endswith(".json")]
    assert traces, f"no chrome trace written to {trace_dir}"
    print("chrome trace:", os.path.join(trace_dir, traces[0]))


if __name__ == "__main__":
    main()
