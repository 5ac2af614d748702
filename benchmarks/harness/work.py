"""Operations and bytes that the ALGORITHM needs, from the requests' and
batches' own lengths — never from a kernel's grid or an engine's chunking —
so that a share of a roofline or of a peak reads the same work whatever
implements it. Dense GPT decoder; bf16 K/V (2 bytes)."""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple


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


def prompt_pairs(new: int, cached: int = 0) -> float:
    """Query-key pairs of ``new`` causal prompt rows that follow ``cached``
    keys already in the cache (the diagonal included)."""
    return new * cached + new * (new + 1) / 2.0


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


def roofline_seconds(flops: float, byts: float, peaks: Dict[str, float]) -> float:
    return max(flops / peaks["bf16_flops"], byts / peaks["hbm_bytes_per_s"])
