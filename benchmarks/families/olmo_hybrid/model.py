"""What the Olmo-Hybrid family needs from the program: the model built from
a configuration file's keys, in bfloat16, with the benchmark's seeded
weights loaded into it one layer at a time, and what its pool has to hold."""
from __future__ import annotations

from typing import Any, Dict

from . import weights as W

# reference leaf -> attribute path under the program's decoder layer
_MLP = {"norm_attn": "post_attention_layernorm.weight",
        "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
        "w_down": "mlp.down_proj.weight",
        "norm_mlp": "post_feedforward_layernorm.weight"}
_LEAVES = {
    W.FULL: {"wq": "mixer.q_proj.weight", "wk": "mixer.k_proj.weight",
             "wv": "mixer.v_proj.weight", "wo": "mixer.o_proj.weight",
             "q_norm": "mixer.q_norm.weight",
             "k_norm": "mixer.k_norm.weight", **_MLP},
    W.LINEAR: {"wq": "mixer.q_proj.weight", "wk": "mixer.k_proj.weight",
               "wv": "mixer.v_proj.weight", "wa": "mixer.a_proj.weight",
               "wb": "mixer.b_proj.weight", "wg": "mixer.g_proj.weight",
               "wo": "mixer.o_proj.weight", "conv_q": "mixer.q_conv",
               "conv_k": "mixer.k_conv", "conv_v": "mixer.v_conv",
               "A_log": "mixer.A_log", "dt_bias": "mixer.dt_bias",
               "o_norm": "mixer.o_norm.weight", **_MLP},
}
_TOP = {"embed": "model.embed_tokens.weight", "norm_f": "model.norm.weight",
        "head": "lm_head.weight"}


def olmo_config(cfg: Dict[str, Any], param_dtype: str = "bfloat16"):
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig

    if cfg["rope_parameters"].get("rope_theta") is not None:
        raise ValueError("this family applies no rotary positions")
    if cfg["tie_word_embeddings"] or cfg["attention_bias"]:
        raise ValueError("untied head, no attention bias")
    return OlmoHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        layer_types=W.layer_types(cfg),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], param_dtype=param_dtype)


def load_weights(model, cfg: Dict[str, Any], seed: int) -> None:
    """Copy the seeded leaves into the program's parameters, one layer made
    and dropped at a time."""
    named = dict(model.named_parameters())

    def put(leaves, paths, prefix):
        for ref, path in paths.items():
            p, v = named.pop(prefix + path), leaves[ref]
            if tuple(v.shape) != tuple(p.shape):
                raise ValueError(f"{prefix}{path}: seeded {v.shape} vs "
                                 f"program {p.shape}")
            p._set_value(v.astype(p._value.dtype))

    put(W.make_top(cfg, seed), _TOP, "")
    for i, kind in enumerate(W.layer_types(cfg)):
        put(W.make_layer(cfg, seed, i), _LEAVES[kind], f"model.layers.{i}.")
    if named:
        raise ValueError(f"program parameters with no reference leaf: "
                         f"{sorted(named)}")


def serve_model(cfg: Dict[str, Any], seed: int):
    """The program's model as it is served (bfloat16 parameters), the
    seeded weights in it."""
    from paddle_tpu.models import OlmoHybridForCausalLM

    model = OlmoHybridForCausalLM(olmo_config(cfg))
    load_weights(model, cfg, seed)
    return model


def pool_args(model, serving: Dict[str, Any]) -> Dict[str, Any]:
    """Pages for the full layers' K/V alone, as many as ``kv_pool_bytes``
    buys (the model's ``_cache_spec`` counts those layers); the engine adds
    the linear layers' per-slot state for ``max_batch_slots`` and a spare."""
    from ...harness.serve_loop import paged_kv_pool_args

    if serving["prefix_cache"]:
        raise ValueError("a trunk with per-slot state serves without a "
                         "prefix cache")
    return paged_kv_pool_args(model, serving)
