"""Model zoo: GPT forward/backward/training, TP mesh, amp, jit-compiled."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn.functional as F
from paddle_tpu import amp, jit
from paddle_tpu.distributed import fleet
from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt_tiny


@pytest.fixture(autouse=True)
def _reset_mesh():
    yield
    dist.set_mesh(None)


def _batch(cfg, B=4, S=32, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (B, S))
    labels = np.roll(ids, -1, axis=1)
    return paddle.to_tensor(ids), paddle.to_tensor(labels)


class TestGPTSingleDevice:
    def test_forward_shapes(self):
        paddle.seed(0)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        ids, labels = _batch(cfg)
        logits = model(ids)
        assert logits.shape == [4, 32, cfg.vocab_size]
        logits, loss = model(ids, labels=labels)
        assert loss.size == 1
        # random init => loss ~ ln(V)
        assert abs(float(loss.numpy()) - np.log(cfg.vocab_size)) < 1.0

    def test_weight_tying(self):
        paddle.seed(0)
        model = GPTForCausalLM(gpt_tiny())
        emb_w = model.gpt.embeddings.weight
        n_emb = sum(1 for _, p in model.named_parameters() if p is emb_w)
        assert n_emb == 1
        ids, labels = _batch(model.config)
        _, loss = model(ids, labels=labels)
        loss.backward()
        assert emb_w.grad is not None  # grads from both embedding and head

    def test_training_reduces_loss(self):
        paddle.seed(1)
        cfg = gpt_tiny(num_layers=1, vocab_size=128)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                     parameters=model.parameters())

        @jit.to_static
        def step(ids, labels):
            _, loss = model(ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids, labels = _batch(cfg, B=8, S=16, seed=2)
        losses = [float(step(ids, labels).numpy()) for _ in range(8)]
        assert losses[-1] < losses[0] * 0.9
        assert len(step._cache) == 1

    def test_train_step_operations_carry_their_scopes(self):
        """The compiled train step's operations say which part of the
        model they belong to (``jax.named_scope``): what a device trace's
        operation names are split by (PERF.md section 3)."""
        import re

        paddle.seed(1)
        cfg = gpt_tiny(num_layers=1, vocab_size=128, fused_loss=True,
                       hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                     parameters=model.parameters())

        @jit.to_static
        def step(ids, labels):
            _, loss = model(ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids, labels = _batch(cfg, B=2, S=16, seed=2)
        for _ in range(2):
            step(ids, labels)
        text = step._lowered().as_text(debug_info=True)
        scopes = {part for loc in re.findall(r'loc\("([^"]+)"', text)
                  for part in loc.split("/")}
        assert {"embed", "attn", "mlp", "head_loss",
                "adamw_update"} <= scopes


class TestGPTTensorParallel:
    def test_tp_matches_single_device(self):
        cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        ids, labels = _batch(cfg, seed=3)

        dist.set_mesh(None)
        paddle.seed(11)
        ref_model = GPTForCausalLM(cfg)
        _, ref_loss = ref_model(ids, labels=labels)

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
        fleet.fleet._is_initialized = False
        fleet.init(strategy=strategy)
        paddle.seed(11)
        tp_model = GPTForCausalLM(cfg)
        # same init (paddle.seed resets the PRNG key; layer creation order equal)
        _, tp_loss = tp_model(ids, labels=labels)
        np.testing.assert_allclose(float(tp_loss.numpy()), float(ref_loss.numpy()),
                                   rtol=2e-4, atol=2e-4)

    def test_tp_training_step(self):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
        fleet.fleet._is_initialized = False
        fleet.init(strategy=strategy)
        paddle.seed(4)
        cfg = gpt_tiny(num_layers=1, vocab_size=256)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                     parameters=model.parameters())

        @jit.to_static
        def step(ids, labels):
            _, loss = model(ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids, labels = _batch(cfg, B=8, S=16, seed=5)
        losses = [float(step(ids, labels).numpy()) for _ in range(6)]
        assert losses[-1] < losses[0]
        # embedding stays vocab-sharded through compiled updates
        assert not model.gpt.embeddings.weight.value.sharding.is_fully_replicated


class TestGPTAmp:
    def test_bf16_o2_training(self):
        paddle.seed(6)
        cfg = gpt_tiny(num_layers=1, vocab_size=128)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                     parameters=model.parameters())
        model, opt = amp.decorate(model, opt, level="O2")

        @jit.to_static
        def step(ids, labels):
            with amp.auto_cast(level="O2"):
                _, loss = model(ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids, labels = _batch(cfg, B=8, S=16, seed=7)
        losses = [float(np.asarray(step(ids, labels).numpy(), dtype="float32"))
                  for _ in range(8)]
        assert losses[-1] < losses[0]
