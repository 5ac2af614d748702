"""GPT weights from ``--seed``: one jitted call, on the device, in the type
they are served and trained in (bfloat16).

The layout is the reference's (reference.py, beside this file); model.py copies the
leaves into the program's parameters by name. Distribution: the GPT-2/GPT-3
initialisation, N(0, 0.02) for matrices and embeddings with the two
residual projections scaled by 1/sqrt(2 L), LayerNorm gains 1 and shifts 0,
and N(0, 0.02) biases, so that every bias path carries a number.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ...harness.traffic import seed_key

STD = 0.02


def shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    h, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    n, v = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    p = int(cfg["max_position_embeddings"])
    proj = STD / math.sqrt(2 * n)
    layer = {  # name: (shape without the layer axis, std; None: constant)
        "ln1_g": ((h,), None), "ln1_b": ((h,), None),
        "w_qkv": ((h, 3 * h), STD), "b_qkv": ((3 * h,), STD),
        "w_o": ((h, h), proj), "b_o": ((h,), STD),
        "ln2_g": ((h,), None), "ln2_b": ((h,), None),
        "w_fc1": ((h, f), STD), "b_fc1": ((f,), STD),
        "w_fc2": ((f, h), proj), "b_fc2": ((h,), STD),
    }
    top = {"wte": ((v, h), STD), "wpe": ((p, h), STD),
           "lnf_g": ((h,), None), "lnf_b": ((h,), None)}
    return {"layers": layer, "top": top, "n_layers": n}


def _leaf(key, shape, std, name):
    if std is None:
        fill = 1.0 if name.endswith("_g") else 0.0
        return jnp.full(shape, fill, jnp.bfloat16)
    x = jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)
    return x.astype(jnp.bfloat16)


def _layer_key(key, leaf_index: int, layer):
    return jax.random.fold_in(jax.random.fold_in(key, 100 + leaf_index),
                              layer)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    layer, top, n = spec
    out: Dict[str, Any] = {"layers": {}}
    for i, (name, (shape, std)) in enumerate(top):
        out[name] = _leaf(jax.random.fold_in(key, i), shape, std, name)
    for i, (name, (shape, std)) in enumerate(layer):
        # one key per layer, so that a layer can be made again on its own
        out["layers"][name] = jax.vmap(
            lambda l: _leaf(_layer_key(key, i, l), shape, std, name))(
                jnp.arange(n, dtype=jnp.int32))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make_layer(key, layer_spec, layer):
    return {name: _leaf(_layer_key(key, i, layer), shape, std, name)
            for i, (name, (shape, std)) in enumerate(layer_spec)}


def _spec(cfg):
    sh = shapes(cfg)
    return (tuple(sorted(sh["layers"].items())),
            tuple(sorted(sh["top"].items())), sh["n_layers"])


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """{"wte", "wpe", "lnf_g", "lnf_b", "layers": {leaf: [L, ...]}}, bf16."""
    return _make(seed_key(seed), _spec(cfg))


def make_layer(cfg: Dict[str, Any], seed: int, layer: int) -> Dict[str, Any]:
    """Layer ``layer`` of ``make_weights`` alone (the same numbers)."""
    return _make_layer(seed_key(seed), _spec(cfg)[0],
                       jnp.asarray(layer, jnp.int32))


def make_top(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The leaves outside the layers (the same numbers as make_weights)."""
    out = _make(seed_key(seed), ((), _spec(cfg)[1], 0))
    del out["layers"]
    return out


def layer_slice(weights: Dict[str, Any], i: int) -> Dict[str, Any]:
    return {k: v[i] for k, v in weights["layers"].items()}
