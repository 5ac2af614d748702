"""Ring attention: exact attention over sequence-sharded q/k/v.

Beyond-reference capability (SURVEY.md §2.3: the reference snapshot has NO
sequence/context parallelism — long-sequence support stops at fused/flash
attention kernels; SURVEY §7 step 6 requires it for the TPU build's
long-context north star).

Design (Ring Attention, Liu et al. 2023, re-derived for ICI): q/k/v
[B, S, H, D] with S sharded over the mesh's ``sep`` axis. Each device
keeps its q block resident and streams every k/v block through the ring
with ``ppermute`` (one neighbor hop per step — bandwidth-optimal on a
torus), folding each block into a running flash-style log-sum-exp
softmax. On TPU each block runs through the Pallas flash kernel, so the
forward is truly O(S/P) per device (nothing [C, C]-shaped ever
materializes); the einsum fallback (CPU / tiny shards) and the backward
recompute hold one transient [C, C] score block per step. The P-step
loop overlaps each block's compute with the next block's transfer under
XLA's async collective-permute. Backward differentiates through the
scan+ppermute (ppermute transposes to the reverse rotation; the flash
path's custom bwd recomputes via the einsum VJP), so grads are exact.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops._apply import apply_op, ensure_tensor
from ..tensor import Tensor
from . import topology

__all__ = ["ring_attention"]


def _use_flash_blocks(C: int, D: int) -> bool:
    """Per-block flash needs the pallas backend and blocks big enough to
    tile; tiny shards keep the einsum path."""
    from ..ops.pallas import flash_attention as fa

    import os

    if os.environ.get("PADDLE_TPU_RING_FLASH", "1") != "1":
        return False
    if not (fa._interpret() or jax.default_backend() == "tpu"):
        return False
    return C >= 128 and D in (64, 128)


def _ring_scan(q, k, v, axis: str, block_update):
    """Shared ring-scan driver (inside shard_map, manual over ``axis``):
    stream every k/v block around the ring with ppermute, folding each
    into the (acc, m, l) online-softmax carry via ``block_update(src,
    k_blk, v_blk, acc, m, l) -> (acc, m, l)``; out = acc / l. Both the
    flash-block and einsum paths ride this one driver so carry init, the
    ppermute pattern, and the final normalization cannot diverge."""
    r = jax.lax.axis_index(axis)
    Pn = jax.lax.axis_size(axis)
    B, C, H, D = q.shape
    perm = [(j, (j + 1) % Pn) for j in range(Pn)]

    def step(carry, i):
        k_blk, v_blk, acc, m, l = carry
        src = (r - i) % Pn  # ring: after i hops we hold rank (r-i)'s block
        acc, m, l = block_update(src, k_blk, v_blk, acc, m, l)
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return (k_blk, v_blk, acc, m, l), None

    vary = lambda x: jax.lax.pcast(x, (axis,), to="varying")
    acc0 = vary(jnp.zeros((B, H, C, D), jnp.float32))
    m0 = vary(jnp.full((B, H, C), -jnp.inf, jnp.float32))
    l0 = vary(jnp.zeros((B, H, C), jnp.float32))
    (k_f, v_f, acc, m, l), _ = jax.lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(Pn))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # [B, C, H, D]


def _ring_flash_fwd_local(q, k, v, axis: str, causal: bool, scale: float):
    """Flash-block ring FORWARD: each k/v block runs through the Pallas
    flash kernel — nothing [C, C]-shaped ever materializes; the kernel's
    LSE residual drives the exact cross-block merge (flash-decoding
    identity: out = Σ_i o_i · exp(lse_i − LSE_total), carried as
    (acc, m, l) with acc accumulating o_i · exp(lse_i − m))."""
    from ..ops.pallas import flash_attention as fa

    r = jax.lax.axis_index(axis)
    B, C, H, D = q.shape
    q_bh = jnp.swapaxes(q, 1, 2).reshape(B * H, C, D)

    def blk_flash(k_blk, v_blk, is_diag):
        """(o [B,H,C,D] f32 normalized-within-block, lse [B,H,C])."""
        k_bh = jnp.swapaxes(k_blk, 1, 2).reshape(B * H, C, D)
        v_bh = jnp.swapaxes(v_blk, 1, 2).reshape(B * H, C, D)

        def run(diag_causal):
            o, lse = fa._flash_fwd_bhsd(q_bh, k_bh, v_bh,
                                        causal=diag_causal, scale=scale,
                                        vma=frozenset({axis}))
            return (o.reshape(B, H, C, D).astype(jnp.float32),
                    lse.reshape(B, H, C))

        if not causal:
            return run(False)
        # diagonal block: causal within; off-diagonal past: full
        return jax.lax.cond(is_diag, lambda: run(True), lambda: run(False))

    def block_update(src, k_blk, v_blk, acc, m, l):
        o_i, lse_i = blk_flash(k_blk, v_blk, src == r)
        if causal:
            # future blocks contribute nothing: -inf their lse
            lse_i = jnp.where(src > r, -jnp.inf, lse_i)
        m_new = jnp.maximum(m, lse_i)
        # guard -inf − -inf (nothing accumulated yet): exp(nan) → where
        safe = lambda x: jnp.where(jnp.isfinite(m_new), x - m_new, -jnp.inf)
        alpha = jnp.exp(safe(m))
        w_i = jnp.exp(safe(lse_i))
        acc = acc * alpha[..., None] + o_i * w_i[..., None]
        return acc, m_new, l * alpha + w_i

    return _ring_scan(q, k, v, axis, block_update)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash_local(q, k, v, axis: str, causal: bool, scale: float):
    """Flash-block ring attention: forward streams blocks through the
    Pallas kernel (O(C) memory); BACKWARD recomputes via the einsum
    formulation's VJP (the [C, C] score block appears transiently in bwd
    only — the pallas_call has no jax AD rule, and grads through the
    merge weights' lse would need kernel support)."""
    return _ring_flash_fwd_local(q, k, v, axis, causal, scale)


def _ring_flash_fwd_rule(q, k, v, axis, causal, scale):
    return _ring_flash_fwd_local(q, k, v, axis, causal, scale), (q, k, v)


def _ring_flash_bwd_rule(axis, causal, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b, c: _ring_einsum_local(a, b, c, axis, causal, scale),
        q, k, v)
    return vjp(g)


_ring_flash_local.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def _ring_attn_local(q, k, v, axis: str, causal: bool, scale: float):
    """Per-device body (inside shard_map, manual over ``axis``):
    q/k/v [B, C, H, D] local chunks of the S dim. Flash-block path on
    TPU (C >= 128); einsum online-softmax elsewhere."""
    B, C, H, D = q.shape
    if _use_flash_blocks(C, D):
        return _ring_flash_local(q, k, v, axis, causal, scale)
    return _ring_einsum_local(q, k, v, axis, causal, scale)


def _ring_einsum_local(q, k, v, axis: str, causal: bool, scale: float):
    """Einsum ring body: inline online-softmax with the [C, C] score
    block per step (CPU/no-pallas/tiny shards, and the bwd recompute)."""
    r = jax.lax.axis_index(axis)
    B, C, H, D = q.shape
    qh = jnp.swapaxes(q, 1, 2)  # [B, H, C, D]
    q_pos = r * C + jnp.arange(C)  # global positions of local queries

    def block_update(src, k_blk, v_blk, acc, m, l):
        kh = jnp.swapaxes(k_blk, 1, 2)  # [B, H, C, D]
        vh = jnp.swapaxes(v_blk, 1, 2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
        if causal:
            k_pos = src * C + jnp.arange(C)
            mask = q_pos[:, None] >= k_pos[None, :]  # [C, C]
            scores = jnp.where(mask[None, None], scores,
                               jnp.asarray(-1e9, scores.dtype))
        blk_max = jnp.max(scores, axis=-1)  # [B, H, C]
        m_new = jnp.maximum(m, blk_max)
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vh)
        return acc, m_new, l * correction + jnp.sum(p, axis=-1)

    return _ring_scan(q, k, v, axis, block_update)


def ring_attention(query, key, value, causal: bool = False,
                   scale: Optional[float] = None, axis: str = "sep",
                   mesh=None):
    """Exact attention with q/k/v [B, S, H, D] sequence-sharded over the
    mesh's ``axis``; returns the output with the same sharding. Falls back
    to one-device flash/dense attention when the axis is absent or size 1."""
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    mesh = mesh or topology.get_mesh()
    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] <= 1:
        from ..nn import functional as F

        # sdpa scales by 1/sqrt(D) internally; fold a custom scale into q so
        # the fallback matches the ring path exactly
        default = 1.0 / math.sqrt(q.shape[-1])
        if abs(scale - default) > 1e-12:
            q = q * (scale / default)
        return F.scaled_dot_product_attention(q, k, v, is_causal=causal)
    if q.shape[1] % mesh.shape[axis]:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by "
            f"{axis} degree {mesh.shape[axis]}")

    def fn(qv, kv, vv):
        spec = P(None, axis, None, None)
        mapped = jax.shard_map(
            lambda a, b, c: _ring_attn_local(a, b, c, axis, causal, scale),
            mesh=mesh, axis_names={axis},
            in_specs=(spec, spec, spec), out_specs=spec)
        return mapped(qv, kv, vv)

    return apply_op(fn, [q, k, v], name="ring_attention")
