"""The work count of Olmo-Hybrid (harness/work.py says what a work count
is): gated delta-rule layers whose work per token does not grow with the
context, and full attention layers that read every cached key; SwiGLU MLPs;
an untied head. bf16 K/V (2 bytes) in the full layers only; the delta rule's
state float32."""
from __future__ import annotations

from typing import Any, Dict, Tuple

from ...harness.work import Kernel, prompt_pairs
from .weights import FULL, layer_types


def _dims(cfg):
    h = int(cfg["hidden_size"])
    types = layer_types(cfg)
    n_full = sum(1 for t in types if t == FULL)
    return h, n_full, len(types) - n_full


def matmul_params(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(parameters in the layers' matrices, in the head)."""
    h, n_full, n_lin = _dims(cfg)
    f = int(cfg["intermediate_size"])
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd = h // nh
    H = int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    mlp = 3 * h * f
    full = 2 * h * nh * hd + 2 * h * nkv * hd + mlp
    linear = h * (2 * H * dk + 2 * H * dv + 2 * H) + H * dv * h + mlp
    return full * n_full + linear * n_lin, h * int(cfg["vocab_size"])


def recurrence_flops(cfg: Dict[str, Any]) -> float:
    """The delta rule for one token in every linear layer: S k, the update
    (alpha S, the outer product, the sum) and S q, about 6 d_k d_v a head."""
    _, _, n_lin = _dims(cfg)
    return 6.0 * int(cfg["linear_key_head_dim"]) * \
        int(cfg["linear_value_head_dim"]) * \
        int(cfg["linear_num_value_heads"]) * n_lin


def _attn_width(cfg) -> int:
    """Query heads x head size of a full layer, times the full layers."""
    h, n_full, _ = _dims(cfg)
    return h * n_full


def prompt_flops(cfg: Dict[str, Any], new: int, cached: int = 0) -> float:
    """A prompt's ``new`` rows behind ``cached`` keys: the layers' matrices
    and the recurrence for each row, attention over the causal pairs in the
    full layers, the head once."""
    blocks, head = matmul_params(cfg)
    return (2.0 * blocks + recurrence_flops(cfg)) * new + 2.0 * head + \
        4.0 * prompt_pairs(new, cached) * _attn_width(cfg)


def decode_flops(cfg: Dict[str, Any], context: int) -> float:
    return prompt_flops(cfg, 1, context - 1)


def kv_bytes_per_token(cfg: Dict[str, Any]) -> float:
    """K and V of one token in the full layers only, bf16."""
    _, n_full, _ = _dims(cfg)
    nh = int(cfg["num_attention_heads"])
    hd = int(cfg["hidden_size"]) // nh
    return 2.0 * int(cfg["num_key_value_heads"]) * hd * 2.0 * n_full


def _paged_window(cfg, rec):
    kv, width = kv_bytes_per_token(cfg), _attn_width(cfg)
    flops = byts = 0.0
    for p, c in zip(rec["prefill_lens"], rec["prefill_cached"], strict=True):
        flops += 4.0 * prompt_pairs(p, c) * width
        byts += (p + c) * kv
    for c in rec["decode_contexts"]:
        flops += 4.0 * c * width
        byts += c * kv
    return flops, byts, 1


def state_bytes(cfg: Dict[str, Any]) -> float:
    """One read and one write of a sequence's float32 state, every linear
    layer."""
    _, _, n_lin = _dims(cfg)
    return 2.0 * 4 * int(cfg["linear_num_value_heads"]) * \
        int(cfg["linear_key_head_dim"]) * \
        int(cfg["linear_value_head_dim"]) * n_lin


def row_bytes(cfg: Dict[str, Any]) -> float:
    """A token's float32 q, k, v and o and its decay and write strength,
    every linear layer."""
    _, _, n_lin = _dims(cfg)
    H = int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    return 4.0 * H * (2 * dk + 2 * dv + 2) * n_lin


def _delta_window(cfg, rec):
    """The state read and written once per decode token and once per
    prompt (a prompt's rows carry it between them), and every row's own."""
    rows = sum(rec["prefill_lens"]) + len(rec["decode_contexts"])
    visits = len(rec["prefill_lens"]) + len(rec["decode_contexts"])
    return (recurrence_flops(cfg) * rows,
            state_bytes(cfg) * visits + row_bytes(cfg) * rows, 1)


KERNELS = {
    # ops/pallas/paged_attention.py, ``name="paged_attention"``: the full
    # layers' K/V
    "paged_attention": Kernel(("mosaic:paged_attention",), "serve",
                              _paged_window),
    # ops/pallas/gated_delta.py, ``name="gated_delta"``
    "gated_delta": Kernel(("mosaic:gated_delta",), "serve", _delta_window),
}
