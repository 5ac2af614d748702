"""Olmo-Hybrid weights from ``--seed``, made one layer at a time on the
device, in the type they are served in (bfloat16).

A layer's leaves depend on the seed and the layer's index alone, so the
program's loader and the reference each make a layer when they need it and
drop it after: the whole model in float32 (16 layers, 16.4 GB) would not fit
one chip. Distribution: N(0, 0.02) matrices and embedding, the output and
down projections scaled by 1/sqrt(2 L), RMSNorm gains 1, the convolutions
U(-1/2, 1/2) (PyTorch's default for a depthwise kernel of 4), and the decays
as FLA initialises them: A ~ U(0, 16) stored as its log, dt log-uniform in
[0.001, 0.1] stored through the inverse of softplus.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...harness.traffic import seed_key

STD = 0.02
LINEAR, FULL = "linear_attention", "full_attention"


def layer_types(cfg: Dict[str, Any]):
    return list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def shapes(cfg: Dict[str, Any], kind: str) -> Tuple:
    """((leaf, shape, init), ...) of one layer of ``kind``; init is
    ("normal", std), ("ones",), ("uniform", bound), ("a_log",), ("dt",)."""
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    proj = STD / math.sqrt(2 * int(cfg["num_hidden_layers"]))
    mlp = (("norm_attn", (h,), ("ones",)),
           ("w_gate", (h, f), ("normal", STD)),
           ("w_up", (h, f), ("normal", STD)),
           ("w_down", (f, h), ("normal", proj)),
           ("norm_mlp", (h,), ("ones",)))
    if kind == FULL:
        nh, nkv = int(cfg["num_attention_heads"]), int(
            cfg["num_key_value_heads"])
        hd = h // nh
        return (("wq", (h, nh * hd), ("normal", STD)),
                ("wk", (h, nkv * hd), ("normal", STD)),
                ("wv", (h, nkv * hd), ("normal", STD)),
                ("wo", (nh * hd, h), ("normal", proj)),
                ("q_norm", (nh * hd,), ("ones",)),
                ("k_norm", (nkv * hd,), ("ones",))) + mlp
    H = int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    K = int(cfg["linear_conv_kernel_dim"])
    conv = ("uniform", 1.0 / math.sqrt(K))
    return (("wq", (h, H * dk), ("normal", STD)),
            ("wk", (h, H * dk), ("normal", STD)),
            ("wv", (h, H * dv), ("normal", STD)),
            ("wa", (h, H), ("normal", STD)),
            ("wb", (h, H), ("normal", STD)),
            ("wg", (h, H * dv), ("normal", STD)),
            ("wo", (H * dv, h), ("normal", proj)),
            ("conv_q", (H * dk, K), conv), ("conv_k", (H * dk, K), conv),
            ("conv_v", (H * dv, K), conv),
            ("A_log", (H,), ("a_log",)), ("dt_bias", (H,), ("dt",)),
            ("o_norm", (dv,), ("ones",))) + mlp


def top_shapes(cfg: Dict[str, Any]) -> Tuple:
    h, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    return (("embed", (v, h), ("normal", STD)),
            ("norm_f", (h,), ("ones",)),
            ("head", (h, v), ("normal", STD)))


def _leaf(key, shape, init):
    kind = init[0]
    if kind == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif kind == "normal":
        x = jax.random.normal(key, shape, jnp.float32) * jnp.float32(init[1])
    elif kind == "uniform":
        x = jax.random.uniform(key, shape, jnp.float32, -init[1], init[1])
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.0, 16.0))
    else:   # dt log-uniform in [1e-3, 0.1], then softplus^-1
        lo, hi = math.log(1e-3), math.log(0.1)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), 1e-4)
        x = dt + jnp.log(-jnp.expm1(-dt))
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    return {name: _leaf(jax.random.fold_in(key, i), shape, init)
            for i, (name, shape, init) in enumerate(spec)}


def make_layer(cfg: Dict[str, Any], seed: int, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s leaves, bfloat16."""
    kind = layer_types(cfg)[layer]
    key = jax.random.fold_in(seed_key(seed), 1000 + layer)
    return _make(key, shapes(cfg, kind))


def make_top(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The embedding, the final norm and the head, bfloat16."""
    return _make(jax.random.fold_in(seed_key(seed), 1), top_shapes(cfg))


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """What the reference is handed: the seed, from which it makes each
    layer when it reaches it (module docstring)."""
    return {"seed": int(seed)}
