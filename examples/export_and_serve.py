"""Export a trained model with jit.save and serve it through the
inference Predictor (StableHLO program + weights on disk), asserting
logits parity with the eager model — the reference's
save_inference_model -> AnalysisPredictor flow.

Run (CPU):
    JAX_PLATFORMS=cpu python examples/export_and_serve.py
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import inference, jit


class Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.conv = nn.Conv2D(1, 8, 3, padding=1)
        self.fc = nn.Linear(8 * 8 * 8, 10)

    def forward(self, x):
        h = paddle.nn.functional.relu(self.conv(x))
        # flatten (not reshape-with-shape[0]) keeps the batch dim symbolic
        # under a dynamic-batch InputSpec export
        return self.fc(paddle.flatten(h, start_axis=1))


def main():
    paddle.seed(0)
    model = Net()
    model.eval()
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((2, 1, 8, 8), "float32"))
    eager_logits = model(x).numpy()

    outdir = tempfile.mkdtemp(prefix="pd_serve_")
    path = os.path.join(outdir, "net")
    jit.save(model, path, input_spec=[
        paddle.static.InputSpec([None, 1, 8, 8], "float32")])
    print("exported:", sorted(os.listdir(outdir)))

    config = inference.Config(path + ".pdmodel", path + ".pdiparams")
    predictor = inference.create_predictor(config)
    in_name = predictor.get_input_names()[0]
    predictor.get_input_handle(in_name).copy_from_cpu(np.asarray(x.numpy()))
    predictor.run()
    out = predictor.get_output_handle(
        predictor.get_output_names()[0]).copy_to_cpu()

    np.testing.assert_allclose(out, eager_logits, rtol=1e-4, atol=1e-4)
    print("predictor logits match eager — serving path OK")


if __name__ == "__main__":
    main()
