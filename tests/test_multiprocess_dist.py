"""True multi-PROCESS jax.distributed: 2 OS processes, one coordinator.

Reference counterpart: the ``TestDistBase`` subprocess pattern
(``python/paddle/fluid/tests/unittests/test_dist_base.py:926`` — spawn
trainer processes, run a step, compare with single-process). Every other
distributed test in this suite is single-process on a virtual mesh; this
one exercises the real rendezvous path: ``init_parallel_env`` →
``jax.distributed.initialize`` (Gloo CPU collectives) → a cross-process
psum → a DataParallel train step whose updated params must equal the
single-process full-batch run.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # fast lane: -m 'not slow'

_CHILD = r'''
import json, os, sys
import numpy as np

rank = int(os.environ["PADDLE_TRAINER_ID"])
out_path = sys.argv[1]

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu import jit

dist.init_parallel_env()                      # jax.distributed.initialize

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

n_proc = int(os.environ["PADDLE_TRAINERS_NUM"])
if n_proc > 1:
    assert jax.process_count() == n_proc, jax.process_count()
assert len(jax.devices()) == n_proc
mesh = dist.topology.get_mesh()

# -- explicit cross-process collective ---------------------------------
if n_proc > 1:
    ranks_plus1 = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")),
        np.array([float(rank + 1)], np.float32))
    psum = dist.shard_map_fn(
        lambda v: jax.lax.psum(v.value, "dp"),
        in_specs=P("dp"), out_specs=P())
    total = float(np.asarray(psum(paddle.Tensor(ranks_plus1)).numpy())[0])
    assert total == n_proc * (n_proc + 1) / 2, total

# -- DataParallel step: same seed => identical init on every process ----
paddle.seed(0)
model = nn.Linear(4, 2)
opt = paddle.optimizer.SGD(learning_rate=0.1,
                           parameters=model.parameters())
dist.DataParallel(model)                      # replicates params over dp

B = 8                                         # global batch
rng = np.random.default_rng(42)
X = rng.standard_normal((B, 4)).astype(np.float32)
Y = rng.standard_normal((B, 2)).astype(np.float32)
if n_proc > 1:
    shard = B // n_proc
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), X[rank * shard:(rank + 1) * shard])
    y = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), Y[rank * shard:(rank + 1) * shard])
else:
    x, y = X, Y

def train_fn(xb, yb):
    pred = model(xb)
    loss = ((pred - yb) ** 2).mean()
    loss.backward()                            # grad psum inserted by XLA
    opt.step()
    opt.clear_grad()
    return loss

step = jit.StaticFunction(train_fn, observe=[model, opt], warmup=False)
loss = step(paddle.Tensor(x), paddle.Tensor(y))
result = {
    "rank": rank,
    "loss": float(np.asarray(loss.numpy(), dtype="float32")),
    "weight": np.asarray(model.weight.numpy(), dtype="float32").tolist(),
    "bias": np.asarray(model.bias.numpy(), dtype="float32").tolist(),
}
with open(out_path, "w") as f:
    json.dump(result, f)
print(f"rank{rank} done loss={result['loss']:.6f}", flush=True)
'''


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run(env_extra, out_path, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra)
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    return subprocess.Popen(
        [sys.executable, "-u", str(script), str(out_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))


def test_two_process_dp_matches_single_process(tmp_path):
    common = {"PADDLE_TRAINERS_NUM": "2", "MASTER_ADDR": "127.0.0.1",
              "MASTER_PORT": str(_free_port())}
    outs = [tmp_path / f"rank{r}.json" for r in range(2)]
    procs = [
        _run({**common, "PADDLE_TRAINER_ID": str(r)}, outs[r], tmp_path)
        for r in range(2)
    ]
    logs = []
    for p in procs:
        try:
            log, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(log)
    for p, log in zip(procs, logs):
        assert p.returncode == 0, f"trainer failed:\n{log}"

    # single-process full-batch reference
    ref_out = tmp_path / "ref.json"
    ref = _run({"PADDLE_TRAINERS_NUM": "1", "PADDLE_TRAINER_ID": "0"},
               ref_out, tmp_path)
    log, _ = ref.communicate(timeout=420)
    assert ref.returncode == 0, f"reference failed:\n{log}"

    results = [json.load(open(o)) for o in outs]
    reference = json.load(open(ref_out))
    # both ranks converged to identical replicated params
    np.testing.assert_allclose(results[0]["weight"], results[1]["weight"],
                               rtol=1e-6)
    np.testing.assert_allclose(results[0]["bias"], results[1]["bias"],
                               rtol=1e-6)
    # ...and they equal the single-process full-batch update (the grad
    # psum across processes reproduced the full-batch gradient)
    np.testing.assert_allclose(results[0]["weight"], reference["weight"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(results[0]["bias"], reference["bias"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(results[0]["loss"], reference["loss"],
                               rtol=1e-5)
