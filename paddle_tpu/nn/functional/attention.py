"""Attention functionals.

reference parity: FlashAttention integration
(paddle/phi/kernels/gpu/flash_attn_kernel.cu:213, dynload/flashattn.h) and
nn.functional.scaled_dot_product_attention. On TPU the fused kernel is a
Pallas flash-attention (paddle_tpu/ops/pallas/flash_attention.py) used when
running on TPU hardware; elsewhere (CPU tests) the reference jnp einsum path
runs — same math, XLA-fused.
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from ...autograd.engine import apply_op
from ...ops._apply import ensure_tensor

__all__ = ["scaled_dot_product_attention", "flash_attention", "flash_attn_unpadded"]


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale, key=None):
    """[B, S, H, D] paddle flash-attn layout."""
    qh = jnp.swapaxes(q, 1, 2)  # B H S D
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((qlen, klen), bool), klen - qlen)
        logits = jnp.where(cm, logits, jnp.asarray(-1e30, logits.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)  # B S H D


# FLAGS_use_pallas_flash_attention (framework/flags.py) — lets users route
# attention off the Pallas kernel for debugging/numerics comparison
pallas_flash_enabled = True

# Measured dispatch threshold (v5e, r4, tools/bench_flash.py with chained
# data-dependent timing): the Pallas kernel wins fwd+bwd at EVERY swept
# length — S=512: 1.93 vs 1.99ms, S=1024: 1.73 vs 5.07ms, S=2048: 3.71 vs
# 11.11ms, S=4096: 6.09 vs 32.57ms (naive attention is HBM-bound on the
# [S,S] score tensor; flash never materializes it). Below 512 the [S,S]
# block is small enough that XLA's fusion ties and dispatch overhead
# dominates.
# Env override lets a bench A/B the threshold without code edits.
pallas_flash_min_seq = int(os.environ.get("PADDLE_TPU_FLASH_MIN_SEQ", 512))


def _use_pallas(q_value, seq_len: int) -> bool:
    if not pallas_flash_enabled or seq_len < pallas_flash_min_seq:
        return False
    if isinstance(q_value, jax.core.Tracer):
        # inside a jit trace there is no concrete device; the trace
        # compiles for the default backend (this is the hot path —
        # every StaticFunction train step traces through here).
        # Caveat: a jit targeting a NON-default backend on a TPU host
        # will still stage the TPU kernel; route off via
        # incubate.set_config({"kernel": {"enable": False}}) there.
        return jax.default_backend() == "tpu"
    return all(d.platform == "tpu" for d in q_value.devices())


def _per_shard(fn, mesh, q_shape, has_seed: bool, has_kpad: bool):
    """Mosaic kernels cannot be partitioned by GSPMD ("wrap the call in a
    shard_map"): under a mesh the flash kernel runs per shard inside a
    shard_map that is manual over EVERY mesh axis — batch split over 'dp',
    heads over 'mp' (the Megatron layout a column-parallel QKV already
    produces), replicated over the rest. Each shard offsets the dropout
    seed, so shards do not repeat one keep-mask."""
    from jax.sharding import PartitionSpec as P

    def axis(name, dim):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    b_ax, h_ax = axis("dp", q_shape[0]), axis("mp", q_shape[2])
    qkv = P(b_ax, None, h_ax, None)
    in_specs = [qkv, qkv, qkv]
    if has_seed:
        in_specs.append(P())
    if has_kpad:
        in_specs.append(P(b_ax, None))

    def local(q, k, v, *extra):
        if has_seed:
            shard = jnp.float32(0)
            for ax in (b_ax, h_ax):
                if ax is not None:
                    shard = shard * mesh.shape[ax] + jax.lax.axis_index(ax)
            # seeds ride as f32-exact ints below 2^24
            extra = ((extra[0] + shard * 65537.0) % float(1 << 24),
                     *extra[1:])
        return fn(q, k, v, *extra)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=qkv, check_vma=False)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p: float = 0.0, is_causal: bool = False,
                                 training: bool = True, name=None):
    """Inputs [batch, seq, num_heads, head_dim] (paddle flash-attn layout)."""
    query, key, value = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    drop = dropout_p if training else 0.0
    rng_key = None
    if drop > 0.0:
        from ...generator import default_generator

        rng_key = default_generator.next_key()

    seq_len = int(query.shape[1]) if len(query.shape) >= 2 else 0

    def _as_key_padding(mask, batch):
        """ONLY the unambiguous [B, 1, 1, Sk] BOOL form (True = attend)
        → [B, Sk] keep array; anything else returns None and stays on
        the XLA path. 2D/3D bool masks are NOT accepted: under XLA's
        trailing-dim broadcast a [Sq, Sk] or [B/H-aligned, Sk] mask means
        per-query/per-head masking, which is not key padding — routing
        them to the kernel would silently change semantics per device.
        The batch dim must match exactly (a broadcast [1,1,1,Sk] with
        B>1 would under-fill the kernel's [B·H] grid)."""
        if mask is None or mask.dtype != jnp.bool_:
            return None
        shp = tuple(int(x) for x in mask.shape)
        if (len(shp) == 4 and shp[0] == batch and shp[1] == 1
                and shp[2] == 1 and shp[3] == klen):
            # klen must match exactly: a stale-length mask would be
            # silently truncated/mis-padded by the kernel but fail
            # loudly on the XLA broadcast — keep both paths failing the
            # same way
            return mask.reshape(shp[0], shp[3])
        return None

    mask_val = ensure_tensor(attn_mask)._value if attn_mask is not None \
        else None
    klen = int(key.shape[1]) if len(key.shape) >= 2 else 0
    kpad = _as_key_padding(mask_val, int(query.shape[0]))
    if ((attn_mask is None or kpad is not None)
            and _use_pallas(query._value, seq_len)):
        from ...ops.pallas import flash_attention as fa

        # dropout runs INSIDE the kernel (counter-based hash mask — no
        # [S,S] mask materialization; the naive path's u32 bernoulli draw
        # is 512MB/layer at B8 S1024 H16). The seed is derived from the
        # framework RNG key as DATA — under StaticFunction tracing the key
        # is traced state, so a host int would be a TracerArrayConversion
        # error (and a retrace per step even if it weren't).
        from ...tensor import Tensor

        ins = [query, key, value]
        has_seed = drop > 0.0
        if has_seed:
            seed_val = jax.random.randint(
                rng_key, (), 0, 1 << 24).astype(jnp.float32)
            ins.append(Tensor(seed_val, stop_gradient=True))
        has_kpad = kpad is not None
        if has_kpad:
            ins.append(Tensor(kpad, stop_gradient=True))

        def fn(q, k, v, *extra, _p=drop, _hs=has_seed, _hk=has_kpad):
            seed = extra[0] if _hs else 0
            kp = extra[-1] if _hk else None
            return fa.flash_attention_bshd(
                q, k, v, causal=is_causal, dropout_p=_p,
                dropout_seed=seed, key_padding_mask=kp)

        from ...distributed import topology

        mesh = topology.get_mesh()
        if mesh is not None and mesh.size > 1:
            fn = _per_shard(fn, mesh, query.shape, has_seed, has_kpad)
        return apply_op(fn, ins, name="flash_attention")

    ins = [query, key, value]
    has_mask = attn_mask is not None
    if has_mask:
        ins.append(ensure_tensor(attn_mask))

    def fn(q, k, v, *m):
        mask = m[0] if has_mask else None
        return _sdpa_ref(q, k, v, mask, drop, is_causal, None, rng_key)

    return apply_op(fn, ins, name="scaled_dot_product_attention")


def flash_attention(query, key, value, dropout: float = 0.0, causal: bool = False,
                    return_softmax: bool = False, fixed_seed_offset=None,
                    rng_name: str = "", training: bool = True, name=None):
    """reference: paddle.nn.functional.flash_attention.flash_attention
    (phi flash_attn kernel). Returns (out, softmax_lse placeholder)."""
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal, training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale: float = None,
                        dropout: float = 0.0, causal: bool = False,
                        return_softmax: bool = False, training: bool = True, name=None):
    """Varlen flash attention (reference: flash_attn_unpadded). Implemented by
    segment-masked dense attention: tokens are packed [total, H, D] and
    cu_seqlens delimit sequences."""
    query, key, value = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    cu_q = ensure_tensor(cu_seqlens_q)

    def fn(q, k, v, cu):
        total, h, d = q.shape
        seg = jnp.cumsum(
            jnp.zeros((total,), jnp.int32).at[cu[1:-1]].add(1)
        )  # segment id per token
        s = scale if scale is not None else 1.0 / math.sqrt(d)
        logits = jnp.einsum("qhd,khd->hqk", q, k) * s
        same = seg[:, None] == seg[None, :]
        if causal:
            same = same & (jnp.arange(total)[:, None] >= jnp.arange(total)[None, :])
        logits = jnp.where(same[None], logits, -1e30)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    from ...tensor import Tensor

    out = apply_op(fn, [query, key, value, Tensor(cu_q._value, stop_gradient=True)],
                   name="flash_attn_unpadded")
    return out, None
