"""High-level training with hapi Model.fit: datasets, callbacks
(telemetry + checkpoint + early stopping), evaluate and predict — the
reference's paddle.Model workflow.

Run (CPU):
    JAX_PLATFORMS=cpu python examples/hapi_fit.py
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.io import Dataset
from paddle_tpu.metric import Accuracy


class TwoMoons(Dataset):
    """Two noisy half-circles — not linearly separable, but easy for a
    small MLP."""

    def __init__(self, n=256, seed=0):
        rng = np.random.default_rng(seed)
        label = rng.integers(0, 2, n)
        t = rng.uniform(0, np.pi, n)
        x = np.stack([np.cos(t), np.sin(t)], 1)
        x[label == 1] = np.stack([1 - np.cos(t), 0.5 - np.sin(t)],
                                 1)[label == 1]
        self.x = (x + rng.normal(0, 0.08, x.shape)).astype("float32")
        self.y = label.astype("int64")

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def main():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(2, 64), nn.Tanh(), nn.Linear(64, 64),
                        nn.Tanh(), nn.Linear(64, 2))
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=net.parameters()),
        loss=nn.CrossEntropyLoss(),
        metrics=Accuracy())

    ckpt_dir = tempfile.mkdtemp(prefix="pd_hapi_")
    logs_dir = os.path.join(ckpt_dir, "vdl")
    callbacks = [
        paddle.callbacks.VisualDL(log_dir=logs_dir),   # JSONL scalar sink
        paddle.callbacks.ModelCheckpoint(save_dir=ckpt_dir),
        paddle.callbacks.EarlyStopping(monitor="acc", mode="max",
                                       patience=10),
    ]
    model.fit(TwoMoons(), TwoMoons(seed=1), batch_size=32, epochs=3,
              callbacks=callbacks, verbose=1)

    eval_out = model.evaluate(TwoMoons(seed=2), batch_size=32, verbose=0)
    print("eval:", {k: float(np.ravel(v)[0]) for k, v in eval_out.items()})
    assert eval_out["acc"] > 0.7, "should beat chance comfortably"

    preds = model.predict(TwoMoons(seed=3), batch_size=32)
    print("predict batches:", len(preds[0]))
    print("hapi fit/evaluate/predict OK; checkpoints in", ckpt_dir)


if __name__ == "__main__":
    main()
