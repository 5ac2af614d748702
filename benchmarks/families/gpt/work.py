"""The work count of the dense GPT decoder (harness/work.py says what a work
count is): every layer attends over every key with ``hidden_size`` wide
heads in all, bf16 K/V (2 bytes), one tied head."""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from ...harness.work import Kernel, prompt_pairs


def matmul_params(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(parameters in the decoder blocks' matrices, in the tied head)."""
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    per_layer = 3 * h * h + h * h + 2 * h * f
    return (per_layer * int(cfg["num_hidden_layers"]),
            h * int(cfg["vocab_size"]))


def attention_flops(cfg: Dict[str, Any], context: float) -> float:
    """QK^T and PV for ONE query token over ``context`` keys, all layers."""
    return 4.0 * context * int(cfg["hidden_size"]) * \
        int(cfg["num_hidden_layers"])


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward (backward = 2 x forward), causal attention at
    its mean context (seq_len + 1) / 2; recomputation is not counted."""
    blocks, head = matmul_params(cfg)
    fwd = 2.0 * (blocks + head) + attention_flops(cfg, (seq_len + 1) / 2.0)
    return 3.0 * fwd


def flash_train_work(cfg: Dict[str, Any], batch: int,
                     seq_len: int) -> Tuple[float, float]:
    """(flops, HBM bytes) of causal attention forward and backward for one
    step: forward 2 matmuls, backward 5 (scores again, dV, dP, dQ, dK) over
    the causal half; q, k, v, o read or written once forward (4 tensors),
    q, k, v, o, do, dq, dk, dv once backward (8), bf16."""
    h, n = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    pairs = batch * seq_len * (seq_len + 1) / 2.0
    flops = (2 + 5) * 2.0 * pairs * h * n
    byts = (4 + 8) * batch * seq_len * h * 2.0 * n
    return flops, byts


def kv_bytes_per_token(cfg: Dict[str, Any]) -> float:
    """K and V of one token in every layer, bf16."""
    return 2.0 * int(cfg["hidden_size"]) * 2.0 * \
        int(cfg["num_hidden_layers"])


def serve_token_flops(cfg: Dict[str, Any], context: int,
                      sampled: bool) -> float:
    """Forward of one served token at its own context; the head only where
    a token is sampled from the row."""
    blocks, head = matmul_params(cfg)
    return 2.0 * blocks + (2.0 * head if sampled else 0.0) + \
        attention_flops(cfg, context)


def paged_attention_work(cfg: Dict[str, Any],
                         prefills: Iterable[int],
                         decode_contexts: Iterable[int],
                         cached: Iterable[int] = ()) -> Tuple[float, float]:
    """(flops, K/V bytes) attention needs for prompts of the given numbers
    of new rows (each key read once, the causal pairs) and for decode tokens
    at the given contexts (every cached key read once per token).
    ``cached``, where given, holds beside each prompt the keys its session
    already has in the cache: the new rows meet those too, and read them."""
    h, n = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    kv = kv_bytes_per_token(cfg)
    prefills = list(prefills)
    cached = list(cached) or [0] * len(prefills)
    flops = byts = 0.0
    for p, c in zip(prefills, cached, strict=True):
        flops += 4.0 * prompt_pairs(p, c) * h * n
        byts += (p + c) * kv
    for c in decode_contexts:
        flops += 4.0 * c * h * n
        byts += c * kv
    return flops, byts



# ---- what the readers ask of a family (benchmarks/families/__init__.py) ----
def prompt_flops(cfg: Dict[str, Any], new: int, cached: int = 0) -> float:
    """Forward of a prompt's ``new`` rows behind ``cached`` keys that its
    session already has in the cache: the blocks for each new row, attention
    over the causal pairs, the head once (one token is sampled)."""
    blocks, head = matmul_params(cfg)
    h_layers = int(cfg["hidden_size"]) * int(cfg["num_hidden_layers"])
    return 2.0 * blocks * new + 2.0 * head + \
        4.0 * prompt_pairs(new, cached) * h_layers


def decode_flops(cfg: Dict[str, Any], context: int) -> float:
    """Forward of one decoded token at its own context, sampled."""
    return serve_token_flops(cfg, context, sampled=True)


def _paged_window(cfg, rec):
    return paged_attention_work(cfg, rec["prefill_lens"],
                                rec["decode_contexts"],
                                rec["prefill_cached"]) + (1,)


def _flash_steps(cfg, rec):
    return flash_train_work(cfg, rec["batch"], rec["seq_len"]) + \
        (rec["steps"],)


KERNELS = {
    # ops/pallas/paged_attention.py, ``name="paged_attention"``
    "paged_attention": Kernel(("mosaic:paged_attention",), "serve",
                              _paged_window),
    # every Mosaic kernel of the train step: it has no other. The program's
    # three flash ``pallas_call``s show by the name of the traced function.
    "flash_attention": Kernel(("mosaic:",), "train", _flash_steps),
}
