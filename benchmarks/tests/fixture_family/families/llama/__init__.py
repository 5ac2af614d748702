"""A second family, at a test's size only: the program's second trunk
(``paddle_tpu/models/llama.py``: rotary positions, RMSNorm, gated MLP,
grouped kv heads) bound to the harness, with a reference and a work count of
its own. ``benchmarks/tests/test_second_family.py`` copies this directory
into a copy of the benchmark, and nothing that was there changes."""
from typing import Any, Dict

from . import work  # noqa: F401
from .reference import logits_at, make_weights  # noqa: F401

CONTROLS = ("fp8",)

# reference leaf -> attribute path under the program's decoder layer
_LAYER_LEAVES = {
    "norm1": "input_layernorm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight",
    "norm2": "post_attention_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
}
_TOP_LEAVES = {"embed": "llama.embed_tokens.weight",
               "norm_f": "llama.norm.weight", "head": "lm_head.weight"}


def serve_model(cfg: Dict[str, Any], seed: int):
    from paddle_tpu import amp
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("hidden_size != num_attention_heads * head_dim")
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=False))
    model = amp.decorate(model, level="O2", dtype="bfloat16")
    weights = make_weights(cfg, seed)
    named = dict(model.named_parameters())
    leaves = {ref: (named.pop(prog), weights[ref])
              for ref, prog in _TOP_LEAVES.items()}
    for i in range(int(cfg["num_hidden_layers"])):
        for ref, prog in _LAYER_LEAVES.items():
            leaves[f"L{i}.{ref}"] = (named.pop(f"llama.layers.{i}.{prog}"),
                                     weights["layers"][ref][i])
    if named:
        raise ValueError(f"program parameters with no reference leaf: "
                         f"{sorted(named)}")
    for name, (p, v) in leaves.items():
        if tuple(v.shape) != tuple(p.shape):
            raise ValueError(f"{name}: seeded {v.shape} vs program {p.shape}")
        p._set_value(v.astype(p._value.dtype))
    return model


def pool_args(model, serving: Dict[str, Any]) -> Dict[str, Any]:
    from ...harness.serve_loop import paged_kv_pool_args

    return paged_kv_pool_args(model, serving)    # kv heads, not query heads
