"""The plain reference: GPT-3 (arXiv:2005.14165 section 2.1 — the GPT-2
decoder: learned positions, pre-LayerNorm blocks, erf GELU as the program's
configuration builds it, output head tied to the token embedding) written
from the equations in ``jax.numpy`` float32. No kernel, no cache, no
batching tricks, and nothing imported from the program.

It runs layer by layer through small jitted functions, so that a 1.3 B
model's float32 forward, backward and AdamW state fit one chip after the
program's own state is freed, and so that one compile serves every layer.

``precision`` selects how every contraction is computed:

- ``f32``: float32 operands at ``Precision.HIGHEST`` — the reference;
- ``bf16``: operands rounded to bfloat16, float32 accumulation — what the
  configuration states; used only as a witness;
- ``int8``: operands rounded to 8-bit integers with one scale per row of
  the contraction (straight-through gradient);
- ``fp8``: operands rounded to float8 e4m3 with one scale per tensor (the
  usual fp8 recipe; straight-through gradient).

The last two are the controls: the nearest precisions below bfloat16.

Parameters are STORED as the configuration states: bfloat16 with no float32
master copy, except LayerNorm gains and shifts, which stay float32. The
AdamW arithmetic is float32 and the result is rounded to the stored type.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

F32 = jnp.float32
PRECISIONS = ("f32", "bf16", "int8", "fp8")


# ------------------------------------------------------------ contractions
def _q8(x, axis):
    """Symmetric 8-bit rounding with one scale per row along ``axis``;
    the gradient passes straight through."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return x + jax.lax.stop_gradient(jnp.round(x / s) * s - x)


def _q_fp8(x):
    """Rounding to float8 e4m3 (largest finite 448) with one scale per
    tensor; the gradient passes straight through."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def contract(eq: str, a, b, a_axis: int, b_axis: int, prec: str):
    """``einsum(eq, a, b)`` with float32 accumulation; ``a_axis``/``b_axis``
    are the contracted axes (the rows that int8 scales)."""
    if prec == "int8":
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    elif prec == "fp8":
        a, b = _q_fp8(a), _q_fp8(b)
    elif prec == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    elif prec != "f32":
        raise ValueError(f"precision {prec!r} not in {PRECISIONS}")
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def linear(x, w, b, prec):
    return contract("...k,kn->...n", x, w, -1, 0, prec) + b


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


# ------------------------------------------------------------------ model
def block(p, x, n_heads: int, eps: float, prec: str):
    """One pre-LN decoder block on [B, S, H] float32, causal."""
    p = _f32(p)
    bsz, s, h = x.shape
    hd = h // n_heads
    y = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = linear(y, p["w_qkv"], p["b_qkv"], prec)
    q, k, v = (t.reshape(bsz, s, n_heads, hd)
               for t in jnp.split(qkv, 3, axis=-1))
    att = contract("bqhd,bkhd->bhqk", q, k, -1, -1, prec) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    ctx = contract("bhqk,bkhd->bqhd", att, v, -1, 1, prec)
    x = x + linear(ctx.reshape(bsz, s, h), p["w_o"], p["b_o"], prec)
    y = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
    y = gelu(linear(y, p["w_fc1"], p["b_fc1"], prec))
    return x + linear(y, p["w_fc2"], p["b_fc2"], prec)


def embed(wte, wpe, ids):
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
    return wte.astype(F32)[ids] + wpe.astype(F32)[pos][None]


def head_logits(wte, lnf_g, lnf_b, x, eps, prec):
    y = layer_norm(x, lnf_g.astype(F32), lnf_b.astype(F32), eps)
    return contract("...k,nk->...n", y, wte.astype(F32), -1, -1, prec)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block_jit(p, x, n_heads, eps, prec):
    return block(p, x, n_heads, eps, prec)


_embed_jit = jax.jit(embed)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _rows_logits_jit(wte, lnf_g, lnf_b, x, rows, eps, prec):
    return head_logits(wte, lnf_g, lnf_b, x[rows[:, 0], rows[:, 1]], eps,
                       prec)


def hidden_states(weights, ids, cfg, prec: str = "f32"):
    """Final residual stream [B, S, H] (before ln_f) for int32 ``ids``."""
    n_heads = int(cfg["num_attention_heads"])
    eps = float(cfg["layer_norm_epsilon"])
    x = _embed_jit(weights["wte"], weights["wpe"], ids)
    for i in range(int(cfg["num_hidden_layers"])):
        x = _block_jit(W.layer_slice(weights, i), x, n_heads, eps, prec)
    return x


def logits_at(weights, ids, rows, cfg, prec: str = "f32"):
    """float32 logits [len(rows), V] at the (row, position) pairs ``rows``
    of a full causal forward over ``ids`` [B, S]."""
    x = hidden_states(weights, jnp.asarray(ids, jnp.int32), cfg, prec)
    return _rows_logits_jit(weights["wte"], weights["lnf_g"],
                            weights["lnf_b"], x,
                            jnp.asarray(rows, jnp.int32),
                            float(cfg["layer_norm_epsilon"]), prec)


# --------------------------------------------------------------- training
def _ce_sum(wte, lnf_g, lnf_b, x, labels, eps, prec):
    lg = head_logits(wte, lnf_g, lnf_b, x, eps, prec)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _head_grads_jit(wte, lnf_g, lnf_b, x, labels, eps, prec):
    return jax.value_and_grad(_ce_sum, argnums=(0, 1, 2, 3))(
        wte, lnf_g, lnf_b, x, labels, eps, prec)


def _adamw(p, g, m, v, t, hp):
    """AdamW (Loshchilov & Hutter; Adam's bias-corrected step size form) in
    float32 on a bfloat16-stored parameter; returns (p bf16, m, v)."""
    lr, b1, b2 = hp["learning_rate"], hp["beta1"], hp["beta2"]
    p32 = p.astype(F32) * (1.0 - lr * hp["weight_decay"])
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    p32 = p32 - lr_t * m / (jnp.sqrt(v) + hp["epsilon"])
    return p32.astype(p.dtype), m, v


def _norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))),
                        tree)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9), donate_argnums=(0, 1, 2))
def _block_bwd_update_jit(p, m, v, x, dy, t, n_heads, eps, prec, hp):
    _, vjp = jax.vjp(lambda p_, x_: block(p_, x_, n_heads, eps, prec),
                     _f32(p), x)
    dp, dx = vjp(dy)
    hp = dict(hp)
    new = {k: _adamw(p[k], dp[k], m[k], v[k], t, hp) for k in p}
    return (dx, {k: n[0] for k, n in new.items()},
            {k: n[1] for k, n in new.items()},
            {k: n[2] for k, n in new.items()}, _norms(dp))


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2))
def _leaf_update_jit(p, m, v, g, t, hp):
    return _adamw(p, g, m, v, t, dict(hp)) + (_norms(g),)


@jax.jit
def _embed_grads_jit(ids, dx):
    pos_g = jnp.sum(dx, axis=0)
    return ids.reshape(-1), dx.reshape(-1, dx.shape[-1]), pos_g


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))


def _stored(leaves: Dict[str, Any]) -> Dict[str, Any]:
    """Leaves in the type the configuration stores them in: LayerNorm
    parameters float32, the rest bfloat16 as seeded."""
    return {k: v.astype(F32) if k.startswith("ln") else v
            for k, v in leaves.items()}


class TrainReference:
    """Follows the program's first steps: ``step(ids, labels)`` returns the
    mean loss and updates the state; ``grad_norms`` holds the per-leaf norm
    of the first step's gradient, ``change_norms()`` the per-leaf norm of
    the parameters' change since the start."""

    def __init__(self, cfg: Dict[str, Any], hp: Dict[str, float], seed: int,
                 precision: str = "f32", fault: Optional[str] = None,
                 head_rows: int = 1024):
        if fault not in (None, "half_batch"):
            raise ValueError(f"unknown fault {fault!r}")
        self.cfg, self.seed, self.prec, self.fault = cfg, seed, precision, fault
        self.hp = tuple(sorted((k, float(v)) for k, v in hp.items()))
        self.n_layers = int(cfg["num_hidden_layers"])
        self.head_rows = head_rows
        w = W.make_weights(cfg, seed)
        self.layers = [_stored(W.layer_slice(w, i))
                       for i in range(self.n_layers)]
        self.top = _stored({k: w[k] for k in ("wte", "wpe", "lnf_g", "lnf_b")})
        del w
        zeros = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jnp.zeros(a.shape, F32), tree)
        self.m = [zeros(layer) for layer in self.layers]
        self.v = [zeros(layer) for layer in self.layers]
        self.top_m, self.top_v = zeros(self.top), zeros(self.top)
        self.t = 0
        self.grad_norms: Dict[str, float] = {}
        self.probe_grad: Optional[np.ndarray] = None   # first d loss / d wpe

    def step(self, ids, labels) -> float:
        ids = jnp.asarray(np.asarray(ids), jnp.int32)
        labels = jnp.asarray(np.asarray(labels), jnp.int32)
        if self.fault == "half_batch":   # half of the rows, mean over them
            half = ids.shape[0] // 2
            ids, labels = ids[:half], labels[:half]
        cfg = self.cfg
        n_heads = int(cfg["num_attention_heads"])
        eps = float(cfg["layer_norm_epsilon"])
        self.t += 1
        t = jnp.asarray(self.t, F32)
        xs = [_embed_jit(self.top["wte"], self.top["wpe"], ids)]
        for i in range(self.n_layers):
            xs.append(_block_jit(self.layers[i], xs[-1], n_heads, eps,
                                 self.prec))
        n_tok = ids.size
        x = xs.pop().reshape(n_tok, -1)
        flat_labels = labels.reshape(-1)
        loss = 0.0
        g_wte = g_g = g_b = None
        dxs = []
        for lo in range(0, n_tok, self.head_rows):
            sl = slice(lo, lo + self.head_rows)
            part, (dw, dg, db, dx) = _head_grads_jit(
                self.top["wte"], self.top["lnf_g"], self.top["lnf_b"],
                x[sl], flat_labels[sl], eps, self.prec)
            loss = loss + part
            g_wte = dw if g_wte is None else g_wte + dw
            g_g = dg if g_g is None else g_g + dg
            g_b = db if g_b is None else g_b + db
            dxs.append(dx)
        scale = 1.0 / n_tok
        dy = (jnp.concatenate(dxs) * scale).reshape(ids.shape + (-1,))
        del dxs, x
        first = self.t == 1
        for i in reversed(range(self.n_layers)):
            dy, self.layers[i], self.m[i], self.v[i], norms = \
                _block_bwd_update_jit(self.layers[i], self.m[i], self.v[i],
                                      xs.pop(), dy, t, n_heads, eps,
                                      self.prec, self.hp)
            if first:
                for k, n in norms.items():
                    self.grad_norms[f"L{i}.{k}"] = n
        flat_ids, flat_dx, g_wpe_rows = _embed_grads_jit(ids, dy)
        g_wte = (g_wte * scale).at[flat_ids].add(flat_dx)
        g_wpe = jnp.zeros(self.top["wpe"].shape, F32).at[
            :g_wpe_rows.shape[0]].add(g_wpe_rows)
        grads = {"wte": g_wte, "wpe": g_wpe, "lnf_g": g_g * scale,
                 "lnf_b": g_b * scale}
        if first:
            self.probe_grad = np.asarray(g_wpe)
        for k, g in grads.items():
            self.top[k], self.top_m[k], self.top_v[k], n = _leaf_update_jit(
                self.top[k], self.top_m[k], self.top_v[k], g, t, self.hp)
            if first:
                self.grad_norms[k] = n
        if first:
            self.grad_norms = {k: float(n)
                               for k, n in self.grad_norms.items()}
        return float(loss) * scale

    def change_norms(self) -> Dict[str, float]:
        """Per-leaf ‖p_now − p_start‖, the start regenerated from the seed."""
        top0 = W.make_top(self.cfg, self.seed)
        out = {k: float(_diff_norm(self.top[k], top0[k])) for k in self.top}
        for i in range(self.n_layers):
            layer0 = W.make_layer(self.cfg, self.seed, i)
            for k, a in self.layers[i].items():
                out[f"L{i}.{k}"] = float(_diff_norm(a, layer0[k]))
        return out
