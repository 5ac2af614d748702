"""The plain reference of the second family, written from the equations in
``jax.numpy`` float32 (Touvron et al., arXiv:2302.13971 section 2.1 and
arXiv:2307.09288 section 2.2): pre-RMSNorm blocks, rotary positions on
interleaved pairs (Su et al., arXiv:2104.09864), SwiGLU, grouped-query
attention (query head h reads kv head h // groups), an untied head. No
kernel, no cache, nothing imported from the program; its own weights from
the seed. ``precision``: ``f32`` (float32 operands at ``HIGHEST``) or
``fp8`` (operands rounded to float8 e4m3, one scale per tensor)."""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ...harness.traffic import seed_key

F32 = jnp.float32
STD = 0.02


def _shapes(cfg):
    h, f, v = (int(cfg[k]) for k in ("hidden_size", "intermediate_size",
                                     "vocab_size"))
    hd = int(cfg["head_dim"])
    nq, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    proj = STD / math.sqrt(2 * int(cfg["num_hidden_layers"]))
    layer = {"norm1": ((h,), None), "wq": ((h, nq * hd), STD),
             "wk": ((h, nkv * hd), STD), "wv": ((h, nkv * hd), STD),
             "wo": ((nq * hd, h), proj), "norm2": ((h,), None),
             "w_gate": ((h, f), STD), "w_up": ((h, f), STD),
             "w_down": ((f, h), proj)}
    top = {"embed": ((v, h), STD), "norm_f": ((h,), None),
           "head": ((h, v), STD)}
    return tuple(sorted(layer.items())), tuple(sorted(top.items()))


def _leaf(key, shape, std):
    x = jax.random.normal(key, shape, F32)
    # a norm's gain is 1 and a draw, so that every gain carries a number
    x = 1.0 + 0.05 * x if std is None else x * std
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, shapes, n_layers):
    layer, top = shapes
    out: Dict[str, Any] = {"layers": {}}
    for i, (name, (shape, std)) in enumerate(top):
        out[name] = _leaf(jax.random.fold_in(key, i), shape, std)
    for i, (name, (shape, std)) in enumerate(layer):
        keys = jax.random.split(jax.random.fold_in(key, 100 + i), n_layers)
        out["layers"][name] = jax.vmap(
            lambda k: _leaf(k, shape, std))(keys)
    return out


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """{"embed", "norm_f", "head", "layers": {leaf: [L, ...]}}, bfloat16."""
    return _make(seed_key(seed), _shapes(cfg), int(cfg["num_hidden_layers"]))


def _mm(eq, a, b, prec):
    if prec == "fp8":
        def q(x):
            s = jnp.max(jnp.abs(x)) / 448.0    # e4m3's largest finite
            s = jnp.where(s == 0, 1.0, s)
            return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
        a, b = q(a), q(b)
    elif prec != "f32":
        raise ValueError(f"precision {prec!r} not in ('f32', 'fp8')")
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def _rope(x, theta):
    """Rotate the pairs (x[2i], x[2i+1]) of [B, S, heads, D] by the angle
    position * theta**(-2i / D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _block(p, x, cfg, prec):
    hd, nkv = cfg["head_dim"], cfg["num_key_value_heads"]
    groups = cfg["num_attention_heads"] // nkv
    b, s, _ = x.shape
    y = _rms_norm(x, p["norm1"], cfg["rms_norm_eps"])
    q = _mm("bsk,kn->bsn", y, p["wq"], prec).reshape(b, s, nkv * groups, hd)
    k = _mm("bsk,kn->bsn", y, p["wk"], prec).reshape(b, s, nkv, hd)
    v = _mm("bsk,kn->bsn", y, p["wv"], prec).reshape(b, s, nkv, hd)
    q = _rope(q, cfg["rope_theta"]).reshape(b, s, nkv, groups, hd)
    k = _rope(k, cfg["rope_theta"])
    att = _mm("bqjgd,bkjd->bjgqk", q, k, prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    ctx = _mm("bjgqk,bkjd->bqjgd", att, v, prec).reshape(b, s, -1)
    x = x + _mm("bsk,kn->bsn", ctx, p["wo"], prec)
    y = _rms_norm(x, p["norm2"], cfg["rms_norm_eps"])
    gate = _mm("bsk,kn->bsn", y, p["w_gate"], prec)
    up = _mm("bsk,kn->bsn", y, p["w_up"], prec)
    return x + _mm("bsk,kn->bsn", jax.nn.silu(gate) * up, p["w_down"], prec)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits(weights, ids, rows, cfg, prec):
    cfg = dict(cfg)
    w = jax.tree.map(lambda a: a.astype(F32), weights)
    x = w["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = _block({k: v[i] for k, v in w["layers"].items()}, x, cfg, prec)
    x = _rms_norm(x[rows[:, 0], rows[:, 1]], w["norm_f"], cfg["rms_norm_eps"])
    return _mm("rk,kn->rn", x, w["head"], prec)


_KEYS = ("head_dim", "num_attention_heads", "num_key_value_heads",
         "num_hidden_layers", "rope_theta", "rms_norm_eps")


def logits_at(weights, ids, rows, cfg, prec: str = "f32"):
    """float32 logits [len(rows), V] at the (row, position) pairs ``rows``
    of a full causal forward over ``ids`` [B, S]."""
    static = tuple((k, cfg[k]) for k in _KEYS)
    return _logits(weights, jnp.asarray(ids, jnp.int32),
                   jnp.asarray(rows, jnp.int32), static, prec)
