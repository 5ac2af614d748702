"""The gated delta rule over a ragged step's rows (Pallas TPU) + pure-jnp
fallback: each slot's state is read once, carried through that slot's rows
and written back where it lies.

The recurrence (Yang et al., "Gated Delta Networks", arXiv:2412.06464), for
one head and one row ``t``, with ``S`` the ``[d_v, d_k]`` state::

    S_t = alpha_t * S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

which is ``S_t = alpha S + beta (v - alpha S k) k^T``. The state is held
TRANSPOSED, ``[d_k, d_v]``, so that ``S k`` and ``S q`` are sums over the
sublane axis and the update is ``k (beta (v - alpha u))^T``: every operation
is elementwise on the VPU in float32, and no product goes through the MXU.

Layout (serving/kv_cache.py owns it): ``state`` ``[slots, H / G, d_k, G *
d_v]`` float32, ``G`` heads side by side on the lane axis
(:func:`state_head_group`: 2 at ``d_v`` 192, so a tile row is 384 lanes, 3
whole vregs, where one head's 192 would pad to 256). Row ``t`` of the step
belongs to slot ``slots[t]``; a slot's rows are consecutive and in position
order (the engine packs them so), and a row at position 0 starts from a
zero state, so a slot is never trusted across sequences. The engine's
padding rows name the spare slot at position 0.

Kernel shape: a grid over the step's rows, one row a grid step, every head.
The state's block index is the row's slot (scalar prefetch), so the
pipeline copies a slot's state in when its first row comes and writes it
back after its last: a decode row reads and writes its state once, a prompt
chunk of 200 rows does too, with the state resident in VMEM between rows.
The state operand is aliased to the state output (``input_output_aliases``)
and the serving step donates it, so the update happens in place.

The pure-jnp fallback (``gated_delta_ref``) is the same math as a scan over
rows; tier-1 CPU tests drive the engine through it, and
PADDLE_TPU_PALLAS_INTERPRET=1 runs the kernel on the CPU.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta", "gated_delta_ref", "state_head_group",
           "heads_view", "grouped_view"]

LANES = 128
# two buffers each of the state's input and output blocks (2.2 MB a block at
# Olmo-Hybrid's 30 heads of 96 x 192), the rows' small blocks beside them
VMEM_LIMIT_BYTES = 32 << 20


def _i32(x):
    # index maps answer in i32: the package runs with x64 on, and Mosaic
    # refuses an i64 block index
    return jnp.asarray(x, jnp.int32)


def _interpret() -> bool:
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1"


def state_head_group(heads: int, value_dim: int) -> int:
    """Heads laid side by side on a state tile's lane axis: the fewest that
    fill whole 128-lane rows (2 at ``d_v`` 192), else all of them."""
    for g in range(1, heads + 1):
        if heads % g == 0 and (g * value_dim) % LANES == 0:
            return g
    return heads


def heads_view(state, value_dim: int):
    """``[S, H/G, d_k, G*d_v]`` -> ``[S, H, d_k, d_v]``."""
    s, hg, dk, n = state.shape
    g = n // value_dim
    return jnp.transpose(state.reshape(s, hg, dk, g, value_dim),
                         (0, 1, 3, 2, 4)).reshape(s, hg * g, dk, value_dim)


def grouped_view(state, group: int):
    """``[S, H, d_k, d_v]`` -> ``[S, H/G, d_k, G*d_v]``."""
    s, h, dk, dv = state.shape
    return jnp.transpose(state.reshape(s, h // group, group, dk, dv),
                         (0, 1, 3, 2, 4)).reshape(s, h // group, dk,
                                                  group * dv)


# ───────────────────────── pure-jnp fallback ─────────────────────────


def gated_delta_ref(state, q, k, v, alpha, beta, slots, positions):
    """``q``/``k`` ``[T, H, d_k]``, ``v`` ``[T, H, d_v]``, ``alpha``/``beta``
    ``[T, H]``, all float32; ``slots``/``positions`` ``[T]``. Returns (o
    ``[T, H, d_v]`` float32, the updated state), row after row."""
    dv = v.shape[-1]
    group = state.shape[-1] // dv
    heads = heads_view(state, dv)

    def row(st, xs):
        qt, kt, vt, at, bt, slot, pos = xs
        s = jnp.where(pos == 0, 0.0, st[slot])             # [H, d_k, d_v]
        a = at[:, None]
        u = jnp.sum(s * kt[:, :, None], axis=1)             # S k  [H, d_v]
        s = a[:, :, None] * s + kt[:, :, None] * \
            (bt[:, None] * (vt - a * u))[:, None, :]
        return st.at[slot].set(s), jnp.sum(s * qt[:, :, None], axis=1)

    heads, o = jax.lax.scan(row, heads, (q, k, v, alpha, beta,
                                         slots.astype(jnp.int32),
                                         positions.astype(jnp.int32)))
    return o, grouped_view(heads, group)


# ───────────────────────── Pallas TPU kernel ─────────────────────────


def _kernel(slot_ref, pos_ref, qT_ref, kT_ref, v_ref, a_ref, b_ref, s_in,
            o_ref, s_out, *, value_dim: int, group: int):
    t = pl.program_id(0)
    first = jnp.logical_or(
        t == 0, slot_ref[t] != slot_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first)
    def _carry_in():      # the slot's state, copied in for its first row
        s_out[...] = s_in[...]

    @pl.when(pos_ref[t] == 0)
    def _fresh():         # a sequence starts: nothing of the slot carries
        s_out[...] = jnp.zeros(s_out.shape, s_out.dtype)

    _, hg, dk, n = s_out.shape
    qT, kT = qT_ref[0], kT_ref[0]                           # [d_k, H]
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, n), 1)

    def spread(m, p):
        # head p*G+j's column on its d_v lanes of the tile
        out = jnp.broadcast_to(m[:, p * group:p * group + 1], (dk, n))
        for j in range(1, group):
            h = p * group + j
            out = jnp.where(lane >= j * value_dim,
                            jnp.broadcast_to(m[:, h:h + 1], (dk, n)), out)
        return out

    for p in range(hg):
        kk, qq = spread(kT, p), spread(qT, p)
        a, b = a_ref[0, p:p + 1, :], b_ref[0, p:p + 1, :]   # [1, G*d_v]
        st = s_out[0, p]                                    # [d_k, G*d_v]
        u = jnp.sum(st * kk, axis=0, keepdims=True)         # S k
        st = a * st + kk * (b * (v_ref[0, p:p + 1, :] - a * u))
        s_out[0, p] = st
        o_ref[0, p:p + 1, :] = jnp.sum(st * qq, axis=0, keepdims=True)


# one trace and one lowering a shape: a step's layers call the kernel with
# the same shapes
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_delta_call(state, q, k, v, alpha, beta, slots, positions, *,
                      interpret: bool):
    T, H, dk = q.shape
    dv = v.shape[-1]
    _, hg, _, n = state.shape
    group = n // dv
    qT, kT = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)   # [T, d_k, H]

    def lanes(x):   # [T, H, d_v] -> a tile row's lanes, G heads a row
        return x.reshape(T, hg, n)

    def per_lane(x):   # [T, H] -> each head's value on its d_v lanes
        return lanes(jnp.broadcast_to(x[:, :, None], (T, H, dv)))

    row = lambda t, sl, ps: (t, _i32(0), _i32(0))   # noqa: E731
    rows = pl.BlockSpec((1, hg, n), row)
    st = pl.BlockSpec((1, hg, dk, n),
                      lambda t, sl, ps: (sl[t], _i32(0), _i32(0), _i32(0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # slots, positions
        grid=(T,),
        in_specs=[pl.BlockSpec((1, dk, H), row), pl.BlockSpec((1, dk, H), row),
                  rows, rows, rows, st],
        out_specs=[rows, st],
    )
    o, state = pl.pallas_call(
        functools.partial(_kernel, value_dim=dv, group=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, hg, n), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 7 (after the two scalar-prefetch operands) is the state
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="gated_delta",
    )(slots.astype(jnp.int32), positions.astype(jnp.int32), qT, kT,
      lanes(v), per_lane(alpha), per_lane(beta), state)
    return o.reshape(T, H, dv), state


def gated_delta(state, q, k, v, alpha, beta, slots, positions,
                use_kernel: bool = None):
    """The gated delta rule over a step's rows (module docstring).
    ``use_kernel=None`` picks the Pallas kernel on TPU backends (or under
    PADDLE_TPU_PALLAS_INTERPRET=1) and the jnp scan elsewhere; both compute
    the same float32 math. Returns (o ``[T, H, d_v]`` float32, state)."""
    args = [a.astype(jnp.float32) for a in (q, k, v, alpha, beta)]
    if use_kernel is None:
        use_kernel = _interpret() or jax.default_backend() == "tpu"
    if use_kernel:
        return _gated_delta_call(state, *args, slots, positions,
                                 interpret=_interpret())
    return gated_delta_ref(state, *args, slots, positions)
