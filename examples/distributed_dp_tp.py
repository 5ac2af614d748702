"""Hybrid data-parallel × tensor-parallel training on a virtual 8-device
CPU mesh — the same code runs unchanged on a real TPU slice.

Run (no TPU needed):
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/distributed_dp_tp.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
import paddle_tpu as paddle
import paddle_tpu.distributed.fleet as fleet
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import jit
from paddle_tpu.distributed.fleet import (ColumnParallelLinear,
                                          RowParallelLinear)


class MLP(nn.Layer):
    """Column->Row parallel pair: the activation stays sharded over 'mp'
    between the two layers; XLA inserts the reduce from the shardings."""

    def __init__(self, hidden, ffn):
        super().__init__()
        self.up = ColumnParallelLinear(hidden, ffn, gather_output=False)
        self.down = RowParallelLinear(ffn, hidden, input_is_parallel=True)

    def forward(self, x):
        return self.down(F.gelu(self.up(x)))


def main():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2,
                               "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    print(f"mesh: dp={hcg.get_data_parallel_world_size()} "
          f"mp={hcg.get_model_parallel_world_size()}")

    paddle.seed(0)
    H = 64
    model = MLP(H, 4 * H)
    head = nn.Linear(H, 10)
    params = list(model.parameters()) + list(head.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=params)

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((8, H)).astype("float32"))
    y = paddle.to_tensor(rng.integers(0, 10, (8,)))

    def train_fn(x, y):
        loss = F.cross_entropy(head(model(x)), y)
        loss.backward()        # dp grad psum inserted by XLA
        opt.step()
        opt.clear_grad()
        return loss

    step = jit.StaticFunction(train_fn, observe=[model, head, opt],
                              warmup=False)
    first = None
    for i in range(5):
        loss = step(x, y)
        first = first if first is not None else float(loss.numpy())
        print(f"step {i}: loss {float(loss.numpy()):.4f}")
    assert float(loss.numpy()) < first, "loss should decrease"
    print("dp4 x mp2 training OK")


if __name__ == "__main__":
    main()
