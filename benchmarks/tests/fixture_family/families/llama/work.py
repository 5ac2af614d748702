"""The work count of the second family: grouped kv heads, so a layer's keys
and values are ``num_key_value_heads * head_dim`` wide where its queries are
``num_attention_heads * head_dim``; three matrices in the gated MLP; a head
that is not the embedding. bf16 K/V (2 bytes)."""
from __future__ import annotations

from typing import Any, Dict, Tuple

from ...harness.work import Kernel, prompt_pairs


def _dims(cfg) -> Tuple[int, int, int, int]:
    hd = int(cfg["head_dim"])
    return (int(cfg["hidden_size"]), int(cfg["num_attention_heads"]) * hd,
            int(cfg["num_key_value_heads"]) * hd,
            int(cfg["num_hidden_layers"]))


def matmul_params(cfg: Dict[str, Any]) -> Tuple[int, int]:
    h, q, kv, n = _dims(cfg)
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * int(
        cfg["intermediate_size"])
    return per_layer * n, h * int(cfg["vocab_size"])


def prompt_flops(cfg, new: int, cached: int = 0) -> float:
    blocks, head = matmul_params(cfg)
    _, q, _, n = _dims(cfg)
    return 2.0 * blocks * new + 2.0 * head + \
        4.0 * prompt_pairs(new, cached) * q * n


def decode_flops(cfg, context: int) -> float:
    return prompt_flops(cfg, 1, context - 1)


def _paged_window(cfg, rec):
    _, q, kv, n = _dims(cfg)
    kv_bytes = 2.0 * kv * 2.0 * n           # K and V of one token, bf16
    flops = byts = 0.0
    for p, c in zip(rec["prefill_lens"], rec["prefill_cached"], strict=True):
        flops += 4.0 * prompt_pairs(p, c) * q * n
        byts += (p + c) * kv_bytes
    for c in rec["decode_contexts"]:
        flops += 4.0 * c * q * n
        byts += c * kv_bytes
    return flops, byts, 1


KERNELS = {"paged_attention": Kernel(("mosaic:paged_attention",), "serve",
                                     _paged_window)}
