"""Post-training quantization: calibrate an eval model with observers,
convert to int8-simulated deployment form, and compare against fp32 —
the reference's paddle.quantization PTQ flow.

Run (CPU):
    JAX_PLATFORMS=cpu python examples/ptq_quantize.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.quantization import PTQ, AbsmaxObserver, QuantConfig


def main():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 10))
    model.eval()

    rng = np.random.default_rng(0)
    calib = [paddle.to_tensor(rng.standard_normal((16, 32), "float32"))
             for _ in range(4)]
    x = paddle.to_tensor(rng.standard_normal((8, 32), "float32"))
    fp32_out = np.asarray(model(x).numpy())

    ptq = PTQ(QuantConfig(activation=AbsmaxObserver(),
                          weight=AbsmaxObserver()))
    qmodel = ptq.quantize(model)
    for batch in calib:          # observers record activation ranges
        qmodel(batch)
    deploy = ptq.convert(qmodel)  # freeze scales into plain layers

    int8_out = np.asarray(deploy(x).numpy())
    err = np.abs(int8_out - fp32_out).max()
    print(f"max |int8 - fp32| logit error: {err:.4f}")
    assert err < 0.2, "int8 simulation should stay close on a small net"
    print("PTQ flow OK")


if __name__ == "__main__":
    main()
