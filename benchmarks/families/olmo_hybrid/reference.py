"""The plain reference: Olmo-Hybrid written from its equations in
``jax.numpy`` float32 at ``Precision.HIGHEST``. No kernel, no cache, no
chunking, and nothing imported from the program. Its gated delta rule is a
``lax.scan`` over positions of the equation as arXiv:2412.06464 writes it,
``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``, with
``S`` ``[d_v, d_k]`` and ``o_t = S_t q_t``.

It runs ONE LAYER AT A TIME: a layer's weights are made from the seed
(weights.py), applied to every sequence of the call, and dropped, so that
16 layers whose float32 weights alone are 16.4 GB fit a chip.

``precision`` selects how every matrix product of the layers and the head is
computed: ``f32`` (float32 at HIGHEST, the reference) or ``fp8`` (operands
rounded to float8 e4m3 with one scale per tensor, float32 accumulation: the
control, the nearest precision below the configuration's bfloat16). The
recurrence, the convolution and the norms stay float32 in both.

What the configuration leaves open is read as the file's ``assumed`` says:
no rotary positions in the full layers; post-norm as in Olmo 2; QK-norm
over the whole q and k projections; the output gate and per-head gated
RMSNorm of FLA's GatedDeltaNet.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import weights as W

F32 = jnp.float32
PRECISIONS = ("f32", "fp8")


def _q_fp8(x):
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def contract(eq: str, a, b, prec: str):
    if prec == "fp8":
        a, b = _q_fp8(a), _q_fp8(b)
    elif prec != "f32":
        raise ValueError(f"precision {prec!r} not in {PRECISIONS}")
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def dense(x, w, prec):
    return contract("bsk,kn->bsn", x, w, prec)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def mlp_and_norms(p, x, h, eps, prec):
    """``x + RMSNorm(h)``, then the SwiGLU MLP behind its own RMSNorm."""
    x = x + rms_norm(h, p["norm_attn"], eps)
    y = dense(jax.nn.silu(dense(x, p["w_gate"], prec))
              * dense(x, p["w_up"], prec), p["w_down"], prec)
    return x + rms_norm(y, p["norm_mlp"], eps)


def full_layer(p, x, n_heads: int, n_kv: int, eps: float, prec: str):
    """Causal softmax attention with QK-norm and no rotation."""
    b, s, h = x.shape
    hd = h // n_heads
    q = rms_norm(dense(x, p["wq"], prec), p["q_norm"], eps)
    k = rms_norm(dense(x, p["wk"], prec), p["k_norm"], eps)
    v = dense(x, p["wv"], prec)
    q = q.reshape(b, s, n_heads, hd)
    rep = n_heads // n_kv
    k = jnp.repeat(k.reshape(b, s, n_kv, hd), rep, axis=2)
    v = jnp.repeat(v.reshape(b, s, n_kv, hd), rep, axis=2)
    att = contract("bqhd,bkhd->bhqk", q, k, prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    ctx = contract("bhqk,bkhd->bqhd", att, v, prec).reshape(b, s, h)
    return mlp_and_norms(p, x, dense(ctx, p["wo"], prec), eps, prec)


def causal_conv(x, w):
    """Depthwise causal convolution over positions: ``y[t] = sum_i w[:, i]
    x[t - K + 1 + i]``, zeros before position 0. ``x`` [B, S, C]."""
    K = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * w[:, i] for i in range(K))


def delta_rule(q, k, v, alpha, beta):
    """``q``/``k`` [B, S, H, d_k], ``v`` [B, S, H, d_v], ``alpha``/``beta``
    [B, S, H]; the state starts at zero. Returns o [B, S, H, d_v]."""
    b, _, H, dk = q.shape
    dv = v.shape[-1]
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)

    def step(S, xs):
        qt, kt, vt, at, bt = xs                   # [B, H, .]
        Sk = mm("bhvk,bhk->bhv", S, kt)
        S = at[..., None, None] * (
            S - bt[..., None, None] * mm("bhv,bhk->bhvk", Sk, kt)) \
            + bt[..., None, None] * mm("bhv,bhk->bhvk", vt, kt)
        return S, mm("bhvk,bhk->bhv", S, qt)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, H, dv, dk), F32), xs)
    return jnp.moveaxis(o, 0, 1)


def linear_layer(p, x, n_heads: int, dk: int, dv: int, neg_eigval: bool,
                 eps: float, prec: str):
    """The gated delta-rule layer (module docstring)."""
    b, s, _ = x.shape
    H = n_heads

    def branch(w, conv, d):
        y = jax.nn.silu(causal_conv(dense(x, w, prec), conv))
        return y.reshape(b, s, H, d)

    q = l2_normalize(branch(p["wq"], p["conv_q"], dk)) / math.sqrt(dk)
    k = l2_normalize(branch(p["wk"], p["conv_k"], dk))
    v = branch(p["wv"], p["conv_v"], dv)
    beta = jax.nn.sigmoid(dense(x, p["wb"], prec)) * (2.0 if neg_eigval
                                                      else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(dense(x, p["wa"], prec)
                                               + p["dt_bias"])
    o = delta_rule(q, k, v, jnp.exp(g), beta)
    gate = jax.nn.silu(dense(x, p["wg"], prec)).reshape(b, s, H, dv)
    y = (rms_norm(o, p["o_norm"], eps) * gate).reshape(b, s, H * dv)
    return mlp_and_norms(p, x, dense(y, p["wo"], prec), eps, prec)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _full_jit(p, x, n_heads, n_kv, eps, prec):
    return full_layer(_f32(p), x, n_heads, n_kv, eps, prec)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _linear_jit(p, x, n_heads, dk, dv, neg_eigval, eps, prec):
    return linear_layer(_f32(p), x, n_heads, dk, dv, neg_eigval, eps, prec)


@jax.jit
def _embed_jit(embed, ids):
    return embed.astype(F32)[ids]


@functools.partial(jax.jit, static_argnums=(4, 5))
def _rows_logits_jit(norm_f, head, x, rows, eps, prec):
    y = rms_norm(x[rows[:, 0], rows[:, 1]], norm_f.astype(F32), eps)
    return contract("rk,kn->rn", y, head.astype(F32), prec)


def logits_at(weights, ids, rows, cfg: Dict[str, Any], prec: str = "f32"):
    """float32 logits [len(rows), V] at the (row, position) pairs ``rows``
    of a causal forward over ``ids`` [B, S], layer after layer."""
    seed, eps = weights["seed"], float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        top = W.make_top(cfg, seed)
        x = _embed_jit(top["embed"], jnp.asarray(ids, jnp.int32))
        for i, kind in enumerate(W.layer_types(cfg)):
            p = W.make_layer(cfg, seed, i)
            if kind == W.FULL:
                x = _full_jit(p, x, int(cfg["num_attention_heads"]),
                              int(cfg["num_key_value_heads"]), eps, prec)
            else:
                x = _linear_jit(p, x, int(cfg["linear_num_value_heads"]),
                                int(cfg["linear_key_head_dim"]),
                                int(cfg["linear_value_head_dim"]),
                                bool(cfg["linear_allow_neg_eigval"]), eps,
                                prec)
            del p
        return _rows_logits_jit(top["norm_f"], top["head"], x,
                                jnp.asarray(rows, jnp.int32), eps, prec)
