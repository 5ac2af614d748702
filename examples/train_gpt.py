"""Train a small GPT on synthetic data — eager loop, then the same step
compiled with jit.to_static, then checkpoint save/resume.

Run (CPU):
    JAX_PLATFORMS=cpu python examples/train_gpt.py
On a TPU host, drop the env overrides.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
import paddle_tpu as paddle
from paddle_tpu import amp, jit
from paddle_tpu.models import GPTConfig, GPTForCausalLM


def main():
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

    rng = np.random.default_rng(0)
    B, S = 4, 64
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (B, S)))
    labels = paddle.to_tensor(np.roll(np.asarray(ids.numpy()), -1, axis=1))

    def train_fn(ids, labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            _, loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # a few eager steps
    for i in range(3):
        loss = train_fn(ids, labels)
        print(f"eager step {i}: loss {float(loss.numpy()):.4f}")

    # the SAME function compiled: one donated-buffer XLA program
    step = jit.StaticFunction(train_fn, observe=[model, opt], warmup=False)
    first = None
    for i in range(5):
        loss = step(ids, labels)
        first = first if first is not None else float(loss.numpy())
        print(f"compiled step {i}: loss {float(loss.numpy()):.4f}")
    assert float(loss.numpy()) < first, "loss should decrease"

    # checkpoint round trip
    import tempfile
    path = os.path.join(tempfile.mkdtemp(prefix="pd_gpt_"),
                        "gpt_example.pdparams")
    paddle.save({"model": model.state_dict(), "opt": opt.state_dict()},
                path)
    state = paddle.load(path)
    model.set_state_dict(state["model"])
    opt.set_state_dict(state["opt"])
    print("checkpoint round trip OK")


if __name__ == "__main__":
    main()
