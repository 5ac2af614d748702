"""Fused linear + softmax-cross-entropy over vocab chunks.

Reference parity: the fused softmax-CE family
(paddle/phi/kernels/gpu/cross_entropy_kernel.cu fuses softmax+CE;
fused_softmax_mask ops) — but the TPU pain point is upstream of the softmax:
the LM head materializes logits [B·S, V] (V≈50K ⇒ 0.8GB bf16 forward and a
multi-GB fp32 softmax/grad footprint in backward), which is what capped the
round-2 bench at B=8–16 per chip (round-2 notes: B≥24 OOMs).

TPU-native redesign: never materialize [N, V]. The vocab dim is scanned in
chunks with an online logsumexp (the flash-attention trick applied to the
vocab softmax):

  forward:  lax.scan over W chunks [C, H] → chunk logits [N, C] live only in
            registers/VMEM-scale working set; carry (m, l, label_logit).
  backward: second scan recomputes chunk logits, forms p−onehot per chunk,
            accumulates dh += (p−onehot)·W_c and emits dW per chunk.

Peak extra memory drops from O(N·V) to O(N·C); FLOPs are identical to the
dense path (the same matmuls, chunked). Pure XLA (scan of MXU matmuls) — a
Pallas kernel adds nothing here because each chunk is already one large
matmul XLA schedules well; the win is the algorithmic memory bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["fused_linear_cross_entropy"]

DEFAULT_CHUNK = 8192


def _pick_chunk(v: int, chunk: int) -> int:
    """Chunk size actually used for a (possibly padded) vocab of v rows."""
    return min(chunk, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(hidden, weight, labels, chunk: int = DEFAULT_CHUNK,
                               ignore_index: int = -100):
    """mean CE of softmax(hidden @ weightᵀ) vs labels, without [N, V].

    hidden: [N, H] (any float dtype; math in f32), weight: [V, H],
    labels: [N] int. Returns scalar mean loss over non-ignored labels.
    """
    loss, _ = _fwd(hidden, weight, labels, chunk, ignore_index)
    return loss


def _chunks(weight, chunk):
    """Split W [V, H] into [n, C, H]; V not divisible by C gets zero-row
    padding (the scan masks the padded tail, so the O(N·C) memory bound
    holds for EVERY vocab size — silently falling back to C=V would
    re-materialize exactly the [N, V] block this module exists to avoid)."""
    v, h = weight.shape
    c = _pick_chunk(v, chunk)
    pad = (-v) % c
    if pad:
        weight = jnp.pad(weight, ((0, pad), (0, 0)))
    return weight.reshape((v + pad) // c, c, h), c, v


def _fwd(hidden, weight, labels, chunk, ignore_index):
    n, h = hidden.shape
    wch, c, v = _chunks(weight, chunk)
    hid32 = hidden.astype(jnp.float32)
    valid = labels != ignore_index
    lab = jnp.where(valid, labels, 0).astype(jnp.int32)

    def body(carry, xs):
        m, l, lab_logit = carry
        w_c, base = xs
        logits = hid32 @ w_c.astype(jnp.float32).T  # [N, C]
        col_ok = base + jnp.arange(c, dtype=jnp.int32) < v
        logits = jnp.where(col_ok[None, :], logits, -jnp.inf)
        m_cur = jnp.max(logits, axis=1)
        m_new = jnp.maximum(m, m_cur)
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1)
        # label logit if it falls in this chunk
        idx = lab - base
        in_chunk = (idx >= 0) & (idx < c)
        picked = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, c - 1)[:, None], axis=1)[:, 0]
        lab_logit = jnp.where(in_chunk, picked, lab_logit)
        return (m_new, l, lab_logit), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    bases = jnp.arange(wch.shape[0], dtype=jnp.int32) * c
    (m, l, lab_logit), _ = jax.lax.scan(body, init, (wch, bases))
    lse = m + jnp.log(l)
    per_tok = jnp.where(valid, lse - lab_logit, 0.0)
    denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    loss = jnp.sum(per_tok) / denom
    return loss, (hidden, weight, lab, valid, lse, denom)


def _bwd(chunk, ignore_index, res, g):
    hidden, weight, lab, valid, lse, denom = res
    n, h = hidden.shape
    wch, c, v = _chunks(weight, chunk)
    hid32 = hidden.astype(jnp.float32)
    scale = (g / denom) * valid.astype(jnp.float32)  # [N]

    def body(dh, xs):
        w_c, base = xs
        w32 = w_c.astype(jnp.float32)
        logits = hid32 @ w32.T                        # [N, C]
        col_ok = base + jnp.arange(c, dtype=jnp.int32) < v
        p = jnp.where(col_ok[None, :],
                      jnp.exp(logits - lse[:, None]), 0.0)  # softmax chunk
        idx = lab - base
        in_chunk = (idx >= 0) & (idx < c)
        onehot = (jnp.arange(c, dtype=jnp.int32)[None, :]
                  == jnp.clip(idx, 0, c - 1)[:, None]) \
            & in_chunk[:, None]
        d = (p - onehot.astype(jnp.float32)) * scale[:, None]  # [N, C]
        dh = dh + d @ w32
        dw_c = d.T @ hid32                            # [C, H]
        return dh, dw_c.astype(weight.dtype)

    bases = jnp.arange(wch.shape[0], dtype=jnp.int32) * c
    dh, dwch = jax.lax.scan(body, jnp.zeros((n, h), jnp.float32),
                            (wch, bases))
    dw = dwch.reshape(-1, h)[:v]  # drop the zero-padded tail rows
    return (dh.astype(hidden.dtype), dw, None)


fused_linear_cross_entropy.defvjp(_fwd, _bwd)
