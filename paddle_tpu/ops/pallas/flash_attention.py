"""Flash attention (forward AND backward) as Pallas TPU kernels.

TPU-native replacement for the reference's flash-attn integration
(paddle/phi/kernels/gpu/flash_attn_kernel.cu:213 — fwd+bwd both registered):
online-softmax attention tiled over VMEM blocks so the [S, S] score matrix
never materializes in HBM, in either direction.

Layout: paddle flash-attn layout [batch, seq, heads, head_dim] at the API
boundary; internally [batch*heads, seq, head_dim]. TPU grids run
sequentially over the innermost dim, so VMEM scratch accumulators carry
across that dim (the standard TPU flash pattern):

- forward: grid (bh, nq, nk) — k innermost; carries (acc, running max m,
  running sum l); emits O and the logsumexp LSE = m + log l (the residual
  that makes a flash backward possible).
- dq kernel: grid (bh, nq, nk) — k innermost; recomputes p from (q, k, LSE)
  per block and accumulates dq = scale * Σ_j ds·k.
- dkv kernel: grid (bh, nk, nq) — q innermost; accumulates
  dv = Σ_i pᵀ·do and dk = scale * Σ_i dsᵀ·q.

where ds = p ∘ (do·vᵀ − Δ) and Δ = rowsum(do ∘ o) is precomputed in XLA
(elementwise — no [S,S]). LSE/Δ ride in [*, bq, 128]-lane-replicated blocks,
the layout jax's own TPU kernels use for row statistics.

Set PADDLE_TPU_PALLAS_INTERPRET=1 to run the kernels in pallas interpret
mode (CPU) — used by the test suite to exercise the real kernel code paths
without a TPU.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured blocks (v5e, r4 sweeps): every config beats XLA, but the two
# r4 sweeps disagree on the best S=1024 blocks — quick sweep: (1024,1024)
# 1.17ms; full sweep: (1024,512) 1.73ms with (1024,1024) at 2.20ms — i.e.
# the spread between large-block configs is within run-to-run noise.
# (1024, 1024) is the default pending a higher-rep tie-break
# (tools/bench_flash.py --s 1024 --reps N); _pick_block clamps to S below
# 1024, landing on the measured-best (512, 512) at S=512.
DEFAULT_BLOCK_Q = int(os.environ.get("PADDLE_TPU_FLASH_BQ", 1024))
DEFAULT_BLOCK_K = int(os.environ.get("PADDLE_TPU_FLASH_BK", 1024))
NEG_INF = -1e30
LANES = 128


def _interpret() -> bool:
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1"


def _compiler_params():
    """Mosaic dimension semantics: batch×head and the q-block axis are
    parallel (no cross-iteration carries), the innermost axis is 'arbitrary'
    (the online-softmax / accumulator carry rides it). Without this Mosaic
    assumes every grid dim may carry state and serializes the whole grid."""
    if _interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _i32(x):
    # index maps must stay int32: under jax_enable_x64 a python-int literal
    # traces as i64, which Mosaic refuses to legalize
    return jnp.asarray(x, jnp.int32)


def _pick_block(seq: int, block: int) -> int:
    if seq < block:
        return min(block, max(128, 1 << (seq - 1).bit_length()))
    return block


def _keep_mask(seed, bh, qi, ki, block_q: int, block_k: int,
               drop_p: float):
    """Deterministic dropout keep-mask for score block (bh, qi, ki).

    Counter-based hash (xorshift-multiply rounds) on the GLOBAL element
    coordinates in plain i32 jnp ops: the same (seed, batch-head, row,
    col) always yields the same bit, so the dq and dkv kernels reproduce
    the forward's mask exactly — regardless of their different grid
    orders or block shapes — with no PRNG-state plumbing, and it runs
    under interpret mode (pltpu.prng_seed has no CPU lowering).

    ``seed`` is a DATA value (f32 scalar holding an int < 2^24, exact in
    f32): under StaticFunction tracing the framework RNG key is traced
    state, so the seed cannot be a static python int."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    seed_i = seed.astype(jnp.int32) if hasattr(seed, "astype") \
        else jnp.int32(seed)
    x = (rows * jnp.int32(-1640531527)          # 0x9E3779B9
         ^ cols * jnp.int32(-2048144789)        # 0x85EBCA6B
         ^ (seed_i + bh * jnp.int32(668265263)))  # 0x27D4EB2F
    x = x ^ (x >> 15)
    x = x * jnp.int32(-2045495917)              # 0x85EBCA77^... odd const
    x = x ^ (x >> 13)
    x = x * jnp.int32(-1028477387)              # 0xC2B2AE35
    x = x ^ (x >> 16)
    u = (x & jnp.int32(0xFFFFFF)).astype(jnp.float32) / 16777216.0
    return u >= jnp.float32(drop_p)


# ───────────────────────────── forward ─────────────────────────────


def _attn_kernel(q_ref, k_ref, v_ref, seed_ref, kp_ref, o_ref, lse_ref,
                 acc_ref, m_ref, l_ref, *,
                 causal: bool, scale: float, block_q: int, block_k: int,
                 seq_q: int, seq_k: int, drop_p: float = 0.0,
                 has_kpad: bool = False):
    bh = pl.program_id(0)  # read at kernel top: program_id inside a
    qi = pl.program_id(1)  # pl.when body escapes the interpret context
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    neg_inf = jnp.float32(NEG_INF)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref[...])
        m_ref[...] = jnp.full_like(m_ref[...], neg_inf)
        l_ref[...] = jnp.zeros_like(l_ref[...])

    # causal: skip k-blocks entirely above the diagonal (the grid is
    # rectangular, so roughly half the blocks are dead weight otherwise)
    needed = (qi * block_q + (block_q - 1) + (seq_k - seq_q)
              >= ki * block_k) if causal else (ki >= 0)

    @pl.when(needed)
    def _body():
        # bf16 inputs + fp32 accumulation: the MXU's native mode. Casting
        # inputs up to f32 first would fall off the fast path entirely.
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * jnp.float32(scale)

        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_k  # padded keys
        if has_kpad:
            # caller-supplied per-key padding mask (f32 0/1, [1, bk])
            mask = mask & (kp_ref[0] > 0.5)[None, :]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = mask & (q_pos + (seq_k - seq_q) >= k_pos)
        s = jnp.where(mask, s, neg_inf)

        m_prev = m_ref[...]  # [bq, 128] replicated
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)  # [bq, 128]
        p = jnp.exp(s - m_new[:, :1])  # [bq, bk]
        # a row with ZERO valid keys in every block so far has m_new still
        # at neg_inf, so exp(s - m_new) = exp(0) = 1 for masked positions —
        # zero them so such rows emit 0 (l clamps to 1e-30 in _finish),
        # consistent with the backward kernels' p=0 reconstruction
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        v = v_ref[0]  # [bk, d]
        if drop_p > 0.0:
            # after-softmax dropout: l (the softmax denominator) uses the
            # UNmasked p, so mask∘(p/l) == (mask∘p)/l — apply to the pv
            # accumulation only
            keep = _keep_mask(seed_ref[0, 0], bh, qi, ki,
                              block_q, block_k, drop_p)
            p = jnp.where(keep, p, 0.0) / jnp.float32(1.0 - drop_p)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)  # [bq, d]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l_fin = jnp.maximum(l_ref[...], jnp.float32(1e-30))
        o_ref[0] = (acc_ref[...] / l_fin[:, :1]).astype(o_ref.dtype)
        # logsumexp residual for the flash backward
        lse_ref[0] = m_ref[...] + jnp.log(l_fin)


def _scalar_spec():
    """(1,1) scalar block: SMEM on the real TPU backend, plain VMEM-ish
    block under interpret (SMEM has no interpret support)."""
    if not _interpret():
        return pl.BlockSpec((1, 1), lambda *_: (_i32(0), _i32(0)),
                            memory_space=pltpu.SMEM)
    return pl.BlockSpec((1, 1), lambda *_: (_i32(0), _i32(0)))


def _flash_fwd_bhsd(q, k, v, causal: bool, scale: float,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    drop_p: float = 0.0, drop_seed=0, kpad=None,
                    kpad_heads: int = 1, vma=None):
    """q,k,v: [BH, S, D] → (out [BH, Sq, D], lse [BH, Sq] f32).
    ``vma``: varying-mesh-axes metadata for the out_shapes — required when
    the kernel runs inside a shard_map manual region (the vma checker
    rejects ShapeDtypeStructs without it).
    ``kpad``: optional per-key keep mask [B, Sk] f32 0/1 (key padding);
    ``kpad_heads`` is H, so block b of the [B·H] grid reads row b // H —
    no H-fold mask copy is ever materialized."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    nq = qp.shape[1] // bq
    nk = kp.shape[1] // bk

    grid = (bh, nq, nk)
    seed2 = jnp.full((1, 1), drop_seed, jnp.float32)
    has_kpad = kpad is not None
    if has_kpad:
        kp2 = jnp.pad(kpad, ((0, 0), (0, pad_k))) if pad_k else kpad
        _h = kpad_heads
        kp_spec = pl.BlockSpec((1, bk), lambda b, i, j: (b // _i32(_h), j))
    else:
        kp2 = jnp.ones((1, bk), jnp.float32)
        kp_spec = pl.BlockSpec((1, bk), lambda b, i, j: (_i32(0), _i32(0)))
    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, seq_q=sq, seq_k=sk,
                          drop_p=drop_p, has_kpad=has_kpad),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _i32(0))),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _i32(0))),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _i32(0))),
            _scalar_spec(),
            kp_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _i32(0))),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, _i32(0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, qp.shape[1], d), q.dtype,
                                 **({"vma": vma} if vma else {})),
            jax.ShapeDtypeStruct((bh, qp.shape[1], LANES), jnp.float32,
                                 **({"vma": vma} if vma else {})),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_fwd",
        **_compiler_params(),
    )(qp, kp, vp, seed2, kp2)
    return out[:, :sq], lse[:, :sq, 0]


# ───────────────────────────── backward ─────────────────────────────


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, seed_ref,
               kp_ref, dq_ref, dq_acc, *, causal: bool, scale: float,
               block_q: int, block_k: int, seq_q: int, seq_k: int,
               drop_p: float = 0.0, has_kpad: bool = False):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc[...])

    needed = (qi * block_q + (block_q - 1) + (seq_k - seq_q)
              >= ki * block_k) if causal else (ki >= 0)

    @pl.when(needed)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]   # [bq, 1]
        dlt = dlt_ref[0][:, :1]   # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * jnp.float32(scale)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_k
        if has_kpad:
            mask = mask & (kp_ref[0] > 0.5)[None, :]
        if causal:
            mask = mask & (q_pos + (seq_k - seq_q) >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk] f32

        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)  # [bq, bk]
        if drop_p > 0.0:
            # dL/dp routes only through kept positions (same mask as fwd);
            # note Δ = rowsum(do∘o) already equals rowsum(p∘dp_eff)
            keep = _keep_mask(seed_ref[0, 0], bh, qi, ki,
                              block_q, block_k, drop_p)
            dp = jnp.where(keep, dp, 0.0) / jnp.float32(1.0 - drop_p)
        ds = (p * (dp - dlt)).astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * jnp.float32(scale)).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dlt_ref, seed_ref,
                kp_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                scale: float, block_q: int, block_k: int, seq_q: int,
                seq_k: int, drop_p: float = 0.0, has_kpad: bool = False):
    bh = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc[...])
        dv_acc[...] = jnp.zeros_like(dv_acc[...])

    needed = (qi * block_q + (block_q - 1) + (seq_k - seq_q)
              >= kj * block_k) if causal else (qi >= 0)

    @pl.when(needed)
    def _body():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        dlt = dlt_ref[0][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * jnp.float32(scale)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # padded q rows must not contribute to dk/dv sums
        mask = (k_pos < seq_k) & (q_pos < seq_q)
        if has_kpad:
            mask = mask & (kp_ref[0] > 0.5)[None, :]
        if causal:
            mask = mask & (q_pos + (seq_k - seq_q) >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk] f32
        if drop_p > 0.0:
            # same (seed, b, row, col) hash as the fwd — the dkv grid
            # iterates (b, kj, qi) but the mask depends only on global
            # coordinates, so order is irrelevant
            keep = _keep_mask(seed_ref[0, 0], bh, qi, kj,
                              block_q, block_k, drop_p)
            inv = jnp.float32(1.0 - drop_p)
            p_eff = jnp.where(keep, p, 0.0) / inv
        else:
            keep, inv, p_eff = None, None, p
        pl_ = p_eff.astype(do.dtype)

        # dv += p_effᵀ · do : contract the bq dim (dropout: out = p_eff·v)
        dv_acc[...] += jax.lax.dot_general(
            pl_, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)  # [bq, bk]
        if drop_p > 0.0:
            dp = jnp.where(keep, dp, 0.0) / inv
        ds = (p * (dp - dlt)).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * jnp.float32(scale)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_bhsd(q, k, v, o, lse, do, causal: bool, scale: float,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    drop_p: float = 0.0, drop_seed=0, kpad=None,
                    kpad_heads: int = 1):
    """All [BH, S, D] (lse [BH, Sq]) → (dq, dk, dv)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk

    # Δ = rowsum(do ∘ o): pure elementwise+reduce, XLA fuses it — no [S,S]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def padq(x):
        return jnp.pad(x, ((0, 0), (0, pad_q), (0, 0))) if pad_q else x

    def padk(x):
        return jnp.pad(x, ((0, 0), (0, pad_k), (0, 0))) if pad_k else x

    qp, dop = padq(q), padq(do)
    kp, vp = padk(k), padk(v)
    # row statistics ride lane-replicated [BH, Sqp, 128] blocks
    lse_b = jnp.broadcast_to(
        (jnp.pad(lse, ((0, 0), (0, pad_q))) if pad_q else lse)[..., None],
        (bh, sq + pad_q, LANES))
    dlt_b = jnp.broadcast_to(
        (jnp.pad(delta, ((0, 0), (0, pad_q))) if pad_q else delta)[..., None],
        (bh, sq + pad_q, LANES))

    nq = qp.shape[1] // bq
    nk = kp.shape[1] // bk
    has_kpad = kpad is not None
    kw = dict(causal=causal, scale=scale, block_q=bq, block_k=bk,
              seq_q=sq, seq_k=sk, drop_p=drop_p, has_kpad=has_kpad)
    seed2 = jnp.full((1, 1), drop_seed, jnp.float32)
    if has_kpad:
        kp2 = jnp.pad(kpad, ((0, 0), (0, pad_k))) if pad_k else kpad
        _h = kpad_heads
        kp_spec_q = pl.BlockSpec((1, bk),
                                 lambda b, i, j: (b // _i32(_h), j))
        kp_spec_k = pl.BlockSpec((1, bk),
                                 lambda b, j, i: (b // _i32(_h), j))
    else:
        kp2 = jnp.ones((1, bk), jnp.float32)
        kp_spec_q = pl.BlockSpec((1, bk),
                                 lambda b, i, j: (_i32(0), _i32(0)))
        kp_spec_k = pl.BlockSpec((1, bk),
                                 lambda b, j, i: (_i32(0), _i32(0)))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _i32(0))),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _i32(0))),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _i32(0))),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _i32(0))),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, _i32(0))),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, _i32(0))),
            _scalar_spec(),
            kp_spec_q,
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _i32(0))),
        out_shape=jax.ShapeDtypeStruct((bh, qp.shape[1], d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_attention_bwd_dq",
        **_compiler_params(),
    )(qp, kp, vp, dop, lse_b, dlt_b, seed2, kp2)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _i32(0))),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _i32(0))),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, _i32(0))),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, _i32(0))),
            pl.BlockSpec((1, bq, LANES), lambda b, j, i: (b, i, _i32(0))),
            pl.BlockSpec((1, bq, LANES), lambda b, j, i: (b, i, _i32(0))),
            _scalar_spec(),
            kp_spec_k,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _i32(0))),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _i32(0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kp.shape[1], d), k.dtype),
            jax.ShapeDtypeStruct((bh, kp.shape[1], d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_attention_bwd_dkv",
        **_compiler_params(),
    )(kp, vp, qp, dop, lse_b, dlt_b, seed2, kp2)

    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


# ───────────────────────────── public op ─────────────────────────────


def _ref_attention_bshd(q, k, v, causal: bool, scale: float):
    """Pure-XLA reference (same math), used off-TPU."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        sq_, sk_ = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq_, sk_), bool), sk_ - sq_)
        logits = jnp.where(cm, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _to_bh(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _from_bh(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention(q, k, v, drop_seed, causal: bool, scale: float,
                     block_q: int, block_k: int, drop_p: float = 0.0):
    # drop_seed is an f32 scalar OPERAND (position 3): under StaticFunction
    # tracing the framework RNG is traced state, so the seed cannot be a
    # static python int without retracing per step
    o, _ = _fwd(q, k, v, drop_seed, causal, scale, block_q, block_k, drop_p)
    return o


def _fwd(q, k, v, drop_seed, causal, scale, block_q, block_k, drop_p=0.0):
    b, sq, h, d = q.shape
    of, lse = _flash_fwd_bhsd(_to_bh(q), _to_bh(k), _to_bh(v), causal, scale,
                              block_q=block_q, block_k=block_k,
                              drop_p=drop_p, drop_seed=drop_seed)
    o = _from_bh(of, b, h)
    return o, (q, k, v, drop_seed, o, lse)


def _bwd(causal, scale, block_q, block_k, drop_p, res, g):
    q, k, v, drop_seed, o, lse = res
    b, sq, h, d = q.shape
    dq, dk, dv = _flash_bwd_bhsd(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(o), lse, _to_bh(g),
        causal, scale, block_q=block_q, block_k=block_k,
        drop_p=drop_p, drop_seed=drop_seed)
    return (_from_bh(dq, b, h), _from_bh(dk, b, h), _from_bh(dv, b, h),
            jnp.zeros_like(drop_seed))


_flash_attention.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention_kpad(q, k, v, drop_seed, kpad, causal: bool,
                          scale: float, block_q: int, block_k: int,
                          drop_p: float = 0.0):
    """Key-padding variant: ``kpad`` [B, Sk] f32 0/1 rides as an operand
    (separate custom_vjp so the unmasked hot path's signature stays
    untouched). The kernels index row b // H — do NOT H-fold the mask;
    a [B*H, Sk] array would be silently mis-read (rows 0..B-1 only)."""
    o, _ = _fwd_kpad(q, k, v, drop_seed, kpad, causal, scale, block_q,
                     block_k, drop_p)
    return o


def _fwd_kpad(q, k, v, drop_seed, kpad, causal, scale, block_q, block_k,
              drop_p=0.0):
    b, sq, h, d = q.shape
    of, lse = _flash_fwd_bhsd(_to_bh(q), _to_bh(k), _to_bh(v), causal, scale,
                              block_q=block_q, block_k=block_k,
                              drop_p=drop_p, drop_seed=drop_seed, kpad=kpad,
                              kpad_heads=h)
    o = _from_bh(of, b, h)
    return o, (q, k, v, drop_seed, kpad, o, lse)


def _bwd_kpad(causal, scale, block_q, block_k, drop_p, res, g):
    q, k, v, drop_seed, kpad, o, lse = res
    b, sq, h, d = q.shape
    dq, dk, dv = _flash_bwd_bhsd(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(o), lse, _to_bh(g),
        causal, scale, block_q=block_q, block_k=block_k,
        drop_p=drop_p, drop_seed=drop_seed, kpad=kpad, kpad_heads=h)
    return (_from_bh(dq, b, h), _from_bh(dk, b, h), _from_bh(dv, b, h),
            jnp.zeros_like(drop_seed), jnp.zeros_like(kpad))


_flash_attention_kpad.defvjp(_fwd_kpad, _bwd_kpad)


def flash_attention_bshd(q, k, v, causal: bool = False, scale: float = None,
                         block_q: int = None, block_k: int = None,
                         dropout_p: float = 0.0, dropout_seed: int = 0,
                         key_padding_mask=None):
    """Flash attention, paddle layout [B, S, H, D]. Fwd and bwd are both
    Pallas flash kernels (no [S,S] materialization in either direction).
    Block sizes default to the measured-best ladder (PADDLE_TPU_FLASH_BQ/BK
    env overrides; explicit args win — the sweep harness uses them).

    ``dropout_p``: after-softmax attention dropout INSIDE the kernel (the
    reference's flash_attn dropout — flash_attn_kernel.cu takes a
    dropout rate). The keep-mask is a counter-based hash of the global
    (seed, batch-head, row, col), so fwd and both bwd kernels reproduce
    it exactly without materializing an [S, S] mask. ``dropout_seed`` is
    DATA (int or traced scalar < 2^24; exact in the f32 it rides in), so
    a fresh per-step seed costs no retrace."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    seed_f = jnp.asarray(dropout_seed, jnp.float32)
    if key_padding_mask is not None:
        # [B, Sk] bool/0-1 keep mask — the kernels index row b // H, no
        # H-fold copy is materialized (nor saved in the vjp residuals)
        kpad = key_padding_mask.astype(jnp.float32)
        return _flash_attention_kpad(q, k, v, seed_f, kpad, causal, scale,
                                     block_q or DEFAULT_BLOCK_Q,
                                     block_k or DEFAULT_BLOCK_K,
                                     float(dropout_p))
    return _flash_attention(q, k, v, seed_f, causal, scale,
                            block_q or DEFAULT_BLOCK_Q,
                            block_k or DEFAULT_BLOCK_K,
                            float(dropout_p))
