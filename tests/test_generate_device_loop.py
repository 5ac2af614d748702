"""Device-side generation loop (models/generation.py _make_device_loop):
the whole decode runs as ONE compiled lax.while_loop program. Greedy
outputs must match the host-driven loop token for token, including the
all-rows-EOS early exit.

Reference ecosystem parity: PaddleNLP GenerationMixin.generate; the
device loop is the TPU-native formulation (a host loop pays a
device<->host round trip per token).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)


def _models():
    return [
        ("gpt", lambda: GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            max_position_embeddings=96, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0))),
        ("llama", lambda: LlamaForCausalLM(LlamaConfig(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            num_key_value_heads=2, max_position_embeddings=96))),
    ]


@pytest.mark.parametrize("name,ctor", _models(), ids=lambda m: m if
                         isinstance(m, str) else "")
def test_device_loop_matches_host_loop(name, ctor):
    paddle.seed(0)
    m = ctor()
    ids = paddle.to_tensor(
        np.random.default_rng(3).integers(0, 128, (2, 8)))
    host = m.generate(ids, max_new_tokens=12, temperature=0.0,
                      device_loop=False)
    dev = m.generate(ids, max_new_tokens=12, temperature=0.0,
                     device_loop=True)
    np.testing.assert_array_equal(np.asarray(host.numpy()),
                                  np.asarray(dev.numpy()))


def test_device_loop_eos_early_exit():
    """B=1 so the first EOS satisfies the all-rows condition: both loops
    must stop at the same (shortened) length with identical tokens."""
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=96, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    ids = paddle.to_tensor(
        np.random.default_rng(5).integers(0, 128, (1, 8)))
    full = np.asarray(m.generate(ids, max_new_tokens=10, temperature=0.0,
                                 device_loop=False).numpy())
    eos = int(full[0, 8 + 3])  # the 4th generated token
    host = np.asarray(m.generate(ids, max_new_tokens=10, temperature=0.0,
                                 eos_token_id=eos,
                                 device_loop=False).numpy())
    dev = np.asarray(m.generate(ids, max_new_tokens=10, temperature=0.0,
                                eos_token_id=eos,
                                device_loop=True).numpy())
    assert host.shape[1] < full.shape[1], "early exit did not trigger"
    np.testing.assert_array_equal(host, dev)


def test_per_row_eos_freeze():
    """A row that emits EOS is frozen (pads with EOS) while other rows
    keep generating — HF/PaddleNLP semantics, identical in both loops."""
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=96, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    ids = paddle.to_tensor(
        np.random.default_rng(3).integers(0, 128, (2, 8)))
    full = np.asarray(m.generate(ids, max_new_tokens=10, temperature=0.0,
                                 device_loop=False).numpy())
    gen = full[:, 8:]
    # pick an eos only ONE row emits (and not at the same step as the other)
    eos = None
    for tok in gen[0]:
        if tok not in gen[1]:
            eos = int(tok)
            break
    assert eos is not None, "degenerate sample: rows identical"
    host = np.asarray(m.generate(ids, max_new_tokens=10, temperature=0.0,
                                 eos_token_id=eos,
                                 device_loop=False).numpy())
    dev = np.asarray(m.generate(ids, max_new_tokens=10, temperature=0.0,
                                eos_token_id=eos,
                                device_loop=True).numpy())
    np.testing.assert_array_equal(host, dev)
    # after row 0's first eos, every row-0 token must be eos
    row0 = host[0, 8:]
    first = int(np.argmax(row0 == eos))
    assert (row0[first:] == eos).all()
    # row 1 is unaffected up to the shared stopping point
    np.testing.assert_array_equal(host[1], full[1, :host.shape[1]])


def test_device_loop_sampled_is_plausible():
    """Sampled (temperature>0) device-loop generation returns in-vocab
    tokens of the right shape (exact RNG parity with the host loop is not
    required — key split order differs by construction)."""
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=96, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    ids = paddle.to_tensor(
        np.random.default_rng(7).integers(0, 128, (2, 8)))
    out = np.asarray(m.generate(ids, max_new_tokens=6, temperature=0.8,
                                top_k=16, device_loop=True).numpy())
    assert out.shape == (2, 14)
    assert out.min() >= 0 and out.max() < 128
