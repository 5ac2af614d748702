"""Long-context training with exact ring attention: the sequence axis is
sharded over the mesh's 'sep' axis and k/v blocks stream between
neighbor devices via ppermute, so no device ever holds the full [S, S]
score matrix OR the full sequence — O(C) memory per device. This is
sequence/context parallelism the reference snapshot does not have
(SURVEY §2.3), expressed in ~nothing but shardings.

Run (no TPU needed — 4 virtual CPU devices):
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python examples/ring_attention_long_context.py
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
from paddle_tpu.models import GPTModel, gpt_tiny


def main():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    S, V, H = 256, 512, 64   # each device holds S/4 = 64 positions
    cfg = gpt_tiny(vocab_size=V, hidden_size=H, num_layers=2, num_heads=4,
                   max_position_embeddings=S, sequence_parallel=True)
    trunk = GPTModel(cfg)
    head = nn.Linear(H, V, bias_attr=False)
    params = list(trunk.parameters()) + list(head.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=params)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (2, S))
    labels = np.roll(ids, -1, axis=1)

    def train_fn(ids, labels):
        hidden = trunk(ids)             # ring attention over 'sep'
        logits = head(hidden)
        loss = F.cross_entropy(logits.reshape([-1, V]),
                               labels.reshape([-1]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = jit.StaticFunction(train_fn, observe=[trunk, head, opt],
                              warmup=False)
    first = None
    for i in range(5):
        loss = step(paddle.to_tensor(ids), paddle.to_tensor(labels))
        first = first if first is not None else float(loss.numpy())
        print(f"step {i}: loss {float(loss.numpy()):.4f}")
    assert float(loss.numpy()) < first, "loss should decrease"
    print(f"ring attention over sep=4 OK (S={S}, {S // 4} positions/device)")


if __name__ == "__main__":
    main()
