"""What the GPT family needs from the program: the model built from a
configuration file's keys, with the benchmark's seeded weights in it, and
what its pool has to hold."""
from __future__ import annotations

from typing import Any, Dict, Iterator, Tuple

from . import weights as W

# reference leaf -> attribute path under the program's decoder layer
_LAYER_LEAVES = {
    "ln1_g": "ln1.weight", "ln1_b": "ln1.bias",
    "w_qkv": "attn.qkv_proj.weight", "b_qkv": "attn.qkv_proj.bias",
    "w_o": "attn.out_proj.weight", "b_o": "attn.out_proj.bias",
    "ln2_g": "ln2.weight", "ln2_b": "ln2.bias",
    "w_fc1": "mlp.fc1.weight", "b_fc1": "mlp.fc1.bias",
    "w_fc2": "mlp.fc2.weight", "b_fc2": "mlp.fc2.bias",
}
_TOP_LEAVES = {"wte": "gpt.embeddings.weight",
               "wpe": "gpt.position_embeddings.weight",
               "lnf_g": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}


def gpt_config(cfg: Dict[str, Any]):
    from paddle_tpu.models.gpt import GPTConfig

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("hidden_size != num_attention_heads * head_dim")
    return GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        tie_word_embeddings=True, fused_loss=True)


def leaf_parameters(model) -> Iterator[Tuple[str, Any]]:
    """(reference leaf name, program Parameter) for every parameter."""
    named = dict(model.named_parameters())
    for ref, prog in _TOP_LEAVES.items():
        yield ref, named.pop(prog)
    n_layers = int(model.config.num_layers)
    for i in range(n_layers):
        for ref, prog in _LAYER_LEAVES.items():
            yield f"L{i}.{ref}", named.pop(f"gpt.layers.{i}.{prog}")
    if named:
        raise ValueError(f"program parameters with no reference leaf: "
                         f"{sorted(named)}")


def load_weights(model, weights: Dict[str, Any]) -> None:
    """Copy the seeded leaves into the program's parameters (same dtype as
    the parameter holds: bfloat16 after ``amp.decorate``)."""
    for name, p in leaf_parameters(model):
        if name.startswith("L"):
            layer, leaf = name[1:].split(".", 1)
            v = weights["layers"][leaf][int(layer)]
        else:
            v = weights[name]
        if tuple(v.shape) != tuple(p.shape):
            raise ValueError(f"{name}: seeded {v.shape} vs program {p.shape}")
        p._set_value(v.astype(p._value.dtype))


def serve_model(cfg: Dict[str, Any], seed: int):
    """The program's model as it is served (``amp`` O2 bfloat16), the seeded
    weights in it."""
    from paddle_tpu import amp
    from paddle_tpu.models import GPTForCausalLM

    model = GPTForCausalLM(gpt_config(cfg))
    model = amp.decorate(model, level="O2", dtype="bfloat16")
    load_weights(model, W.make_weights(cfg, seed))
    return model


def pool_args(model, serving: Dict[str, Any]) -> Dict[str, Any]:
    """What ``Router.add_model`` needs to size this model's pool: keys and
    values of every layer in bfloat16 pages, as many as ``kv_pool_bytes``
    buys."""
    from ...harness.serve_loop import paged_kv_pool_args

    return paged_kv_pool_args(model, serving)
