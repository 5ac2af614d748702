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

Peak extra memory drops from O(N·V) to O(N·C); the matmuls are the dense
path's, chunked, over a vocab padded by at most 127 rows a chunk (the
backward recomputes the chunk logits, one product more than a dense head
that kept them). Pure XLA (scan of MXU matmuls) — a Pallas kernel adds
nothing here because each chunk is already one large matmul XLA schedules
well; the win is the algorithmic memory bound.

Precision: every product multiplies in the operands' own dtype (the
``jnp.result_type`` of hidden and weight) and accumulates in float32; the
softmax arithmetic (max, exp, sum, lse, p − onehot, the dh accumulator) is
float32. bf16 operands (``amp`` O2) therefore take one MXU pass a product,
and lose nothing in the forward: a bf16 × bf16 product is exact in a float32
accumulator. The backward rounds p − onehot (values in [−1, 1]; the loss's
scale g / count multiplies the products' float32 results, so no rounding or
float16 underflow depends on it) to the operands' dtype in front of its two
products, as the backward of any bf16 head does. float32 operands multiply
as float32 at the package's matmul precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["fused_linear_cross_entropy"]

DEFAULT_CHUNK = 8192


def _pick_chunk(v: int, chunk: int) -> int:
    """Rows a chunk for a vocab of v rows: v itself where it fits one chunk,
    else ceil(v / n) for the fewest n chunks of at most ``chunk`` rows,
    rounded up to a multiple of 128 (the MXU's tile) where that still fits."""
    if v <= chunk:
        return v
    n = -(-v // chunk)
    c = -(-v // n)
    c128 = -(-c // 128) * 128
    return c128 if c128 <= chunk else c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(hidden, weight, labels, chunk: int = DEFAULT_CHUNK,
                               ignore_index: int = -100):
    """mean CE of softmax(hidden @ weightᵀ) vs labels, without [N, V].

    hidden: [N, H], weight: [V, H] (any float dtype: the products multiply
    in the promoted dtype of the two, float64 as float32, and accumulate in
    f32; softmax math in f32), labels: [N] int. Returns scalar mean loss
    over non-ignored labels.
    """
    loss, _ = _fwd(hidden, weight, labels, chunk, ignore_index)
    return loss


def _chunks(weight, chunk):
    """Split W [V, H] into n equal chunks [n, C, H], C ≤ chunk; V not
    divisible by C gets zero-row padding, under 128 rows a chunk (the scan
    masks the padded tail, so the O(N·C) memory bound holds for EVERY vocab
    size — silently falling back to C=V would re-materialize exactly the
    [N, V] block this module exists to avoid)."""
    v, h = weight.shape
    c = _pick_chunk(v, chunk)
    pad = (-v) % c
    if pad:
        weight = jnp.pad(weight, ((0, pad), (0, 0)))
    return weight.reshape((v + pad) // c, c, h), c, v


def _operand_dtype(hidden, weight):
    """The dtype the products multiply in: the promoted dtype of the two
    operands as they arrive, and float32 for anything wider."""
    dt = jnp.result_type(hidden.dtype, weight.dtype)
    return dt if jnp.finfo(dt).bits <= 32 else jnp.dtype(jnp.float32)


def _dot(a, b, contract):
    """a · b over ``contract`` = (a's axis, b's axis), accumulated in f32."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd(hidden, weight, labels, chunk, ignore_index):
    n, h = hidden.shape
    wch, c, v = _chunks(weight, chunk)
    dt = _operand_dtype(hidden, weight)
    hid = hidden.astype(dt)
    valid = labels != ignore_index
    lab = jnp.where(valid, labels, 0).astype(jnp.int32)

    def body(carry, xs):
        m, l, lab_logit = carry
        w_c, base = xs
        logits = _dot(hid, w_c.astype(dt), (1, 1))   # [N, C] f32
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
    dt = _operand_dtype(hidden, weight)
    hid = hidden.astype(dt)
    scale = g / denom  # applied to the products' f32 results, not to d

    def body(dh, xs):
        w_c, base = xs
        w_c = w_c.astype(dt)
        logits = _dot(hid, w_c, (1, 1))               # [N, C] f32
        col_ok = base + jnp.arange(c, dtype=jnp.int32) < v
        p = jnp.where(col_ok[None, :],
                      jnp.exp(logits - lse[:, None]), 0.0)  # softmax chunk
        idx = lab - base
        in_chunk = (idx >= 0) & (idx < c)
        onehot = (jnp.arange(c, dtype=jnp.int32)[None, :]
                  == jnp.clip(idx, 0, c - 1)[:, None]) \
            & in_chunk[:, None]
        d = jnp.where(valid[:, None], p - onehot.astype(jnp.float32),
                      0.0).astype(dt)                 # [N, C] in [-1, 1]
        dh = dh + _dot(d, w_c, (1, 0))
        dw_c = _dot(d, hid, (0, 0)) * scale           # [C, H]
        return dh, dw_c.astype(weight.dtype)

    bases = jnp.arange(wch.shape[0], dtype=jnp.int32) * c
    dh, dwch = jax.lax.scan(body, jnp.zeros((n, h), jnp.float32),
                            (wch, bases))
    dw = dwch.reshape(-1, h)[:v]  # drop the zero-padded tail rows
    return ((dh * scale).astype(hidden.dtype), dw, None)


fused_linear_cross_entropy.defvjp(_fwd, _bwd)
