"""Fused multi-tensor AdamW as a Pallas TPU kernel.

Reference parity: paddle/phi/kernels/gpu/fused_adam_kernel.cu (multi-tensor
Adam: one launch updates every parameter chunk) — the reference motivation is
amortizing per-tensor kernel-launch overhead.

TPU framing: inside a jitted train step there are no per-tensor launches to
amortize (XLA already fuses the elementwise updates), so the only possible
win is scheduling: one Pallas kernel streams w/m/v/g through VMEM in a
single pass with explicit double-buffering instead of whatever fusion
grouping XLA picks across 100+ parameter tensors. Whether that wins is an
empirical question — tools/bench_adamw.py measures it on chip, and the
optimizer only routes through this kernel if it measured faster
(the VERDICT r2 #6 contract: keep it only with a measured win).

Layout: the caller flattens all params into ONE fp32 vector per state
(w, m, v, grad) — the multi-tensor part — padded to a multiple of the
(8, 128) f32 tile and viewed [rows, 1024].

RETIRED from the hot path (r4, measured on v5e at 355M params with chained
data-dependent timing): XLA 14.9ms (667 GB/s, ~81% of HBM peak) vs this
kernel 42.9ms (232 GB/s). The update is purely memory-bound and XLA's
fusion already streams it near roofline. The r4 run was later found to
have timed a crippled 16x1024 blocking (alignment bug in the harness),
and the intended 256x1024 design point turns out not to compile on v5e
at all (exceeds scoped VMEM, r5) — the honest A/B runs at the largest
compilable blocking via ``block_rows`` (tools/bench_adamw.py sweeps it).
Kept as reference code and for the A/B harness; optimizers use the XLA
path.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adamw_flat"]

LANE = 1024          # flat view: [rows, 1024] f32
# 128x1024 f32 = 0.5MB per operand block: 4 block inputs + 3 block
# outputs (the lr/bc scalars live in SMEM) double-buffered ~= 7MB,
# inside v5e's 16MB scoped VMEM. The original 256-row design point never
# compiled on real v5e — 16.79M > 16M scoped-vmem limit, measured r5 —
# so 256 exists only as a sweep point on hardware with more headroom.
BLOCK_ROWS = 128


def _interpret() -> bool:
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1"


def _adamw_kernel(w_ref, m_ref, v_ref, g_ref, lr_ref, bc1_ref, bc2_ref,
                  wo_ref, mo_ref, vo_ref, *, beta1, beta2, eps,
                  weight_decay):
    # bias corrections bc{1,2} = 1 - beta^t arrive precomputed: Mosaic has
    # no lowering for math.powf (measured on-chip failure, r4), and a
    # scalar pow belongs on the XLA side anyway.
    w = w_ref[...]
    m = m_ref[...]
    v = v_ref[...]
    g = g_ref[...]
    lr = lr_ref[0, 0]
    bc1 = bc1_ref[0, 0]
    bc2 = bc2_ref[0, 0]
    b1 = jnp.float32(beta1)
    b2 = jnp.float32(beta2)
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + jnp.float32(eps))
    wo_ref[...] = w - lr * (update + jnp.float32(weight_decay) * w)
    mo_ref[...] = m_new
    vo_ref[...] = v_new


def fused_adamw_flat(w, m, v, g, lr, step, *, block_rows=None,
                     beta1=0.9, beta2=0.999,
                     eps=1e-8, weight_decay=0.01):
    """One AdamW step over flat fp32 vectors. Returns (w', m', v').

    w/m/v/g: [N] f32 (N padded to 8·1024 by the caller or here);
    lr: scalar f32; step: scalar f32 (1-based).
    """
    n = w.shape[0]
    pad = (-n) % (8 * LANE)
    if pad:
        w, m, v, g = (jnp.pad(x, (0, pad)) for x in (w, m, v, g))
    rows = w.shape[0] // LANE
    shape2 = (rows, LANE)
    w2, m2, v2, g2 = (x.reshape(shape2) for x in (w, m, v, g))
    br = min(block_rows or BLOCK_ROWS, rows)
    while rows % br:
        br //= 2
    br = max(br, 1)
    grid = (rows // br,)

    lr2 = jnp.full((1, 1), lr, jnp.float32)
    t_f = jnp.asarray(step, jnp.float32)
    bc1 = jnp.full((1, 1), 1.0 - jnp.float32(beta1) ** t_f, jnp.float32)
    bc2 = jnp.full((1, 1), 1.0 - jnp.float32(beta2) ** t_f, jnp.float32)

    # index maps must return int32 built INSIDE the lambda: under
    # jax_enable_x64 a python-int literal traces as i64 (Mosaic refuses to
    # legalize it), and a precomputed array would be a captured constant
    def _z():
        return jnp.asarray(0, jnp.int32)

    blk = pl.BlockSpec((br, LANE), lambda i: (i, _z()))
    scal = pl.BlockSpec((1, 1), lambda i: (_z(), _z()),
                        memory_space=pltpu.SMEM) \
        if not _interpret() \
        else pl.BlockSpec((1, 1), lambda i: (_z(), _z()))
    wo, mo, vo = pl.pallas_call(
        functools.partial(_adamw_kernel, beta1=beta1, beta2=beta2, eps=eps,
                          weight_decay=weight_decay),
        grid=grid,
        in_specs=[blk, blk, blk, blk, scal, scal, scal],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct(shape2, jnp.float32)] * 3,
        interpret=_interpret(),
    )(w2, m2, v2, g2, lr2, bc1, bc2)
    out = (wo.reshape(-1), mo.reshape(-1), vo.reshape(-1))
    if pad:
        out = tuple(x[:n] for x in out)
    return out


def xla_adamw_flat(w, m, v, g, lr, step, *, beta1=0.9, beta2=0.999,
                   eps=1e-8, weight_decay=0.01):
    """The same update as plain XLA ops — the A/B baseline."""
    b1, b2 = jnp.float32(beta1), jnp.float32(beta2)
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    bc1 = 1.0 - jnp.power(b1, jnp.float32(step))
    bc2 = 1.0 - jnp.power(b2, jnp.float32(step))
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + jnp.float32(eps))
    w_new = w - lr * (update + jnp.float32(weight_decay) * w)
    return w_new, m_new, v_new
