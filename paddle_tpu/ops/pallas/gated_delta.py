"""The gated delta rule over a ragged step's rows (Pallas TPU) + pure-jnp
fallback: each slot's state is read once, carried through that slot's rows
and written back where it lies.

The recurrence (Yang et al., "Gated Delta Networks", arXiv:2412.06464), for
one head and one row ``t``, with ``S`` the ``[d_v, d_k]`` state and ``g_t``
the row's log decay (``alpha_t = exp(g_t)``)::

    S_t = alpha_t * S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

which is ``S_t = alpha S + beta (v - alpha S k) k^T``. The state is held
TRANSPOSED, ``[d_k, d_v]``, so that ``S k`` and ``S q`` are sums over the
sublane axis and the update is ``k (beta (v - alpha u))^T``.

Layout (serving/kv_cache.py owns it): ``state`` ``[slots, H / G, d_k, G *
d_v]`` float32, ``G`` heads side by side on the lane axis
(:func:`state_head_group`: 2 at ``d_v`` 192, so a tile row is 384 lanes, 3
whole vregs, where one head's 192 would pad to 256). Row ``t`` of the step
belongs to slot ``slots[t]``; a slot's rows are consecutive and in position
order (the engine packs them so), and a row at position 0 starts from a
zero state, so a slot is never trusted across sequences. The LAST slot is
the spare that the engine's padding rows name: nothing reads its state or
their output, so its rows advance nothing and output zeros.

Runs and units. A run is a slot's consecutive rows at consecutive
positions (a prompt chunk, a decode row). A run of at least ``C`` rows
(:func:`state_block`: 64 at Olmo-Hybrid-7B's ``G`` 2) advances in the
CHUNKWISE form, a block of ``C`` rows at a time on the MXU; a shorter run
advances row by row on the VPU, every operation elementwise in float32.
Which path a row takes follows from the rows alone (:func:`block_rows`
says how many rows of a run take the block form; the serving engine counts
them with it). The wrapper turns ``slots`` and ``positions`` into a list of
units, each one grid step: a row of a short run, or a block window of a
long run (``C`` consecutive rows of it: the run's first window holds its
``n mod C`` leading rows, or ``C``, and the rest follow whole, so a window
never leaves its run). The grid's length is the number of units, known at
run time; the spare's rows make none.

The block form (arXiv:2412.06464 §3.3, the UT transform of arXiv:2406.06484
§3; FLA's ``chunk_gated_delta_rule``), per head, with ``S0`` the state
before the block, ``G`` the in-block cumulative log decay and ``D[i, j] =
exp(G_i - G_j)`` for ``j <= i``::

    A = strictly_lower(diag(beta) (D * K K^T)),   T = (I + A)^-1
    U = T diag(beta) V,   W = T diag(beta exp(G)) K,   Delta = U - W S0
    O = diag(exp(G)) Q S0 + (D * Q K^T) Delta
    S = exp(G_C) S0 + K^T diag(exp(G_C - G)) Delta

The decay stays in log space (``exp`` of a difference that is never
positive): Olmo's ``g`` reaches about -1.6 a row, and a product of 64
``alpha`` underflows float32. ``T`` is found by block forward substitution,
``T <- T - T A_L T`` over diagonal blocks of 1, 2, 4 ... rows, each step a
product of bounded matrices, stable for ``beta`` up to 2 over correlated
keys where a series in ``A`` would not be. The ``G`` heads of a state tile
are stacked on the sublane axis (``G C`` rows: 128 at ``G`` 2), so every
product is one MXU tile wide, and each stacked row keeps only its own
head's lanes. Rows of a window past its run's share take ``beta = 0`` and
``g = 0``: the identity. Every product runs at ``Precision.HIGHEST`` on
float32 operands.

Kernel shape: one grid step a unit. The state's block is the unit's slot
(scalar prefetch), so the pipeline copies a slot's state in when its first
unit comes and writes it back after its last, in place: the state operand
is aliased to the state output (``input_output_aliases``) and the serving
step donates it. A row unit reads its row's q, k, v, decay and write
strength through the pipeline; a block unit copies its window of rows in
by hand (the next block's copies start while this unit computes) and
copies its outputs out the same way.

The pure-jnp fallback (``gated_delta_ref``) is the same math as a scan over
rows; tier-1 CPU tests drive the engine through it, and
PADDLE_TPU_PALLAS_INTERPRET=1 runs the kernel on the CPU.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta", "gated_delta_ref", "state_head_group",
           "state_block", "block_rows", "heads_view", "grouped_view"]

LANES = 128
SUBLANES = 8
# two buffers each of the state's input and output blocks (2.2 MB a block at
# Olmo-Hybrid's 30 heads of 96 x 192), two of a block window's rows in and
# its outputs out (about 10 MB), the rows' small blocks beside them
VMEM_LIMIT_BYTES = 64 << 20
HIGHEST = jax.lax.Precision.HIGHEST
# state tiles a block unit computes per loop trip, so that one tile's MXU
# products overlap the next's
TILE_UNROLL = 3


def _i32(x):
    # index maps answer in i32: the package runs with x64 on, and Mosaic
    # refuses an i64 block index
    return jnp.asarray(x, jnp.int32)


def _interpret() -> bool:
    return os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def state_head_group(heads: int, value_dim: int) -> int:
    """Heads laid side by side on a state tile's lane axis: the fewest that
    fill whole 128-lane rows (2 at ``d_v`` 192), else all of them."""
    for g in range(1, heads + 1):
        if heads % g == 0 and (g * value_dim) % LANES == 0:
            return g
    return heads


def state_block(heads: int, value_dim: int):
    """Rows of a block of the chunkwise form: what stacks a state tile's
    ``G`` heads to at most one 128-row MXU tile, a power of two (64 at
    ``G`` 2). ``None`` where the
    tile is not whole 128-lane rows: a block's rows are copied by hand, and
    the chip copies whole lane tiles only, so every run goes row by row."""
    g = state_head_group(heads, value_dim)
    if (g * value_dim) % LANES:
        return None
    return max(SUBLANES, 1 << ((LANES // g).bit_length() - 1))


def block_rows(run_rows: int, block) -> int:
    """Rows of one run (a slot's ``run_rows`` consecutive rows at
    consecutive positions) that advance in the block form: all of them
    where the run is at least one block of ``block`` rows long
    (:func:`state_block`), else none."""
    return run_rows if block is not None and run_rows >= block else 0


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


def gated_delta_ref(state, q, k, v, g, beta, slots, positions):
    """``q``/``k`` ``[T, H, d_k]``, ``v`` ``[T, H, d_v]``, ``g`` (the log
    decay) / ``beta`` ``[T, H]``, all float32; ``slots``/``positions``
    ``[T]``. Returns (o ``[T, H, d_v]`` float32, the updated state), row
    after row; rows of the last slot (the spare) output zeros and advance
    nothing."""
    dv = v.shape[-1]
    group = state.shape[-1] // dv
    spare = state.shape[0] - 1
    heads = heads_view(state, dv)

    def row(st, xs):
        qt, kt, vt, gt, bt, slot, pos = xs
        s = jnp.where(pos == 0, 0.0, st[slot])             # [H, d_k, d_v]
        a = jnp.exp(gt)[:, None]
        u = jnp.sum(s * kt[:, :, None], axis=1)             # S k  [H, d_v]
        s = a[:, :, None] * s + kt[:, :, None] * \
            (bt[:, None] * (vt - a * u))[:, None, :]
        live = slot != spare
        o = jnp.sum(s * qt[:, :, None], axis=1)
        return (st.at[slot].set(jnp.where(live, s, st[slot])),
                jnp.where(live, o, 0.0))

    heads, o = jax.lax.scan(row, heads, (q, k, v, g, beta,
                                         slots.astype(jnp.int32),
                                         positions.astype(jnp.int32)))
    return o, grouped_view(heads, group)


# ───────────────────────── Pallas TPU kernel ─────────────────────────


def _units(slots, positions, spare: int, block):
    """The grid's units (module docstring): each unit's first row and the
    rows of it that are its own (0 for a row unit), in row order; whether
    each row advances in a block; and the number of units (at least 1: a
    step of padding alone runs one row unit on the spare)."""
    T = slots.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    live = slots != spare
    new = jnp.concatenate([jnp.ones((1,), bool),
                           (slots[1:] != slots[:-1])
                           | (positions[1:] != positions[:-1] + 1)])
    last = jnp.concatenate([new[1:], jnp.ones((1,), bool)])
    start = jax.lax.cummax(jnp.where(new, t, 0))
    end = jax.lax.cummin(jnp.where(last, t + 1, T), reverse=True)
    off = t - start
    if block is None:
        in_block = jnp.zeros((T,), bool)
        head, own = live, jnp.zeros((T,), jnp.int32)
    else:
        n = end - start
        in_block = live & (n >= block)
        lead = (n - 1) % block + 1          # rows of the run's first window
        head = live & (~in_block | (off == 0)
                       | ((off >= lead) & ((off - lead) % block == 0)))
        own = jnp.where(in_block, jnp.where(off == 0, lead, block), 0)
    idx = jnp.cumsum(head.astype(jnp.int32)) - 1
    dest = jnp.where(head, idx, T)
    unit_row = jnp.zeros((T,), jnp.int32).at[dest].set(t, mode="drop")
    # one entry past the last unit, so that "is the next unit a block"
    # reads inside the array
    unit_own = jnp.zeros((T + 1,), jnp.int32).at[dest].set(
        own.astype(jnp.int32), mode="drop")
    return unit_row, unit_own, in_block, live, jnp.maximum(idx[-1] + 1, 1)


def _mm(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _row_step(t, qT_ref, kT_ref, v_ref, a_ref, b_ref, o_ref, s_out, *,
              value_dim: int, group: int):
    """One row, every head pair, elementwise on the VPU."""
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


def _shr(x, d: int):
    # x // d for x >= 0 and d a power of two, in i32
    return jax.lax.shift_right_logical(x, jnp.int32(d.bit_length() - 1))


def _unit_lower_inverse(A, block: int):
    """``(I + A)^-1`` for ``A`` ``[R, R]`` strictly lower inside each of
    its diagonal blocks of ``block`` rows (zero elsewhere), by block forward
    substitution. The diagonal blocks of 8 rows, one vreg's rows each, are
    solved on the VPU, a column at a time; then two diagonal blocks of ``s``
    rows merge into one of ``2 s`` as ``T - T A_L T``, ``A_L`` the part of
    ``A`` below the diagonal inside each ``2 s`` block, and since only the
    lower half of a ``2 s`` block's rows changes the products run over
    those rows alone. Every step multiplies bounded matrices: stable where
    a series in ``A`` is not (``beta`` near 2 over correlated keys)."""
    R = A.shape[0]
    f32 = jnp.float32
    nb = R // SUBLANES
    below = jax.lax.broadcasted_iota(jnp.int32, (nb, SUBLANES, R), 1)
    T = (jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
         == jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)).astype(f32)
    T = T.reshape(nb, SUBLANES, R)
    for j in range(SUBLANES - 1):
        # rows below row j of each 8-row block lose A[r, j] times row j
        coef = jnp.stack([jnp.broadcast_to(
            A[m * SUBLANES:(m + 1) * SUBLANES,
              m * SUBLANES + j:m * SUBLANES + j + 1], (SUBLANES, R))
            for m in range(nb)])
        T = T - jnp.where(below > j, coef, 0.0) * T[:, j:j + 1, :]
    T = T.reshape(R, R)
    s = SUBLANES
    while s < block:
        starts = range(s, R, 2 * s)              # each 2s block's lower half
        lo = lambda M: jnp.concatenate(          # noqa: E731
            [M[b:b + s] for b in starts], axis=0)
        r2 = jax.lax.broadcasted_iota(jnp.int32, (R // 2, R), 0)
        c2 = jax.lax.broadcasted_iota(jnp.int32, (R // 2, R), 1)
        upper = (_shr(c2, 2 * s) == _shr(r2, s)) & ((_shr(c2, s) & 1) == 0)
        X = _mm(jnp.where(upper, lo(A), 0.0), T)              # [R/2, R]

        def spread(M):     # lower-half rows back in place, zeros above
            return jnp.concatenate(
                [p for b in range(0, R // 2, s)
                 for p in (jnp.zeros((s, R), f32), M[b:b + s])], axis=0)

        T = T - spread(_mm(lo(T), spread(X)))
        s *= 2
    return T


def _block_step(own, buf, qbuf, kbuf, vbuf, gbuf, bbuf, obuf, cols, s_out,
                *, value_dim: int, group: int, block: int):
    """One block window of a run in the chunkwise form (module docstring):
    the window's first ``own`` rows are its own, the rest the identity."""
    _, hg, dk, n = s_out.shape
    C, R = block, group * block
    nt = n // LANES
    f32 = jnp.float32
    valid = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < own
    g = jnp.where(valid, gbuf[buf], 0.0)
    beta = jnp.where(valid, bbuf[buf], 0.0)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(f32)
    G = _mm(tri, g)
    G_end = G[C - 1:C, :]
    gam, to_end = jnp.exp(G), jnp.exp(G_end - G)
    end_decay = jnp.exp(G_end)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    lane_head = sum(((lane >= j * value_dim).astype(jnp.int32)
                     for j in range(1, group)), jnp.zeros_like(lane))
    # each tile's columns, its G heads' rows stacked: lanes 0-3 of cols[p]
    # hold G, beta, exp(G) and exp(G_end - G); row 0 from lane 128 on holds
    # exp(G_end) on each head's lanes
    for p in range(hg):
        col = []
        for j in range(group):
            h = p * group + j
            col.append(jnp.concatenate(
                [G[:, h:h + 1], beta[:, h:h + 1], gam[:, h:h + 1],
                 to_end[:, h:h + 1]], axis=1))
        cols[p, :, 0:4] = jnp.concatenate(col, axis=0)
        lane_end = jnp.zeros((1, n), f32)
        for j in range(group):
            h = p * group + j
            lane_end = jnp.where(lane_head == j, end_decay[:, h:h + 1],
                                 lane_end)
        cols[p, 0:1, LANES:LANES + n] = lane_end

    rr = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    cc = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    same = _shr(rr, C) == _shr(cc, C)
    strict = same & (cc < rr)
    causal = same & (cc <= rr)
    # head j's lanes lie in lane tiles [t0, t1): its products run there
    spans = [(j * value_dim // LANES, -(-(j + 1) * value_dim // LANES))
             for j in range(group)]
    own_lanes = [lane_head[:, t0 * LANES:t1 * LANES] == j
                 for j, (t0, t1) in enumerate(spans)]

    def rows_of(j, x):            # x [C, w] as head j's rows of R, else 0
        return jnp.concatenate(
            [x if i == j else jnp.zeros(x.shape, f32) for i in range(group)],
            axis=0)

    def tile(p, carry):
        c = cols[p]
        G_c, b_c, gam_c, end_c = (c[:, i:i + 1] for i in range(4))
        lane_end = c[0:1, LANES:LANES + n]
        Gb = jnp.broadcast_to(G_c, (R, R))
        dec = jnp.where(causal, jnp.exp(jnp.where(causal, Gb - Gb.T, 0.0)),
                        0.0)
        K = kbuf[buf, pl.ds(p * group, group)].reshape(R, -1)
        Q = qbuf[buf, pl.ds(p * group, group)].reshape(R, -1)
        KQ = _mm(jnp.concatenate([K, Q], axis=0), K, ((1,), (1,)))
        KK, QK = KQ[:R], KQ[R:]
        T = _unit_lower_inverse(jnp.where(strict, b_c * dec * KK, 0.0), C)
        W = _mm(T, (b_c * gam_c) * K)                       # [R, DKP]
        S0 = s_out[0, p]                                    # [d_k, n]
        decQK = dec * QK
        deltas, outs = [], []
        for j, (t0, t1) in enumerate(spans):
            r = slice(j * C, (j + 1) * C)
            V = jnp.concatenate([vbuf[buf, p, t] for t in range(t0, t1)],
                                axis=1)                     # [C, w]
            U = _mm(T[r], rows_of(j, b_c[r] * V))
            QW = jnp.concatenate([Q[r], W[r]], axis=0)[:, :dk]
            QWS = _mm(QW, S0[:, t0 * LANES:t1 * LANES])     # [2C, w]
            delta = jnp.where(own_lanes[j], U - QWS[C:], 0.0)
            outs.append(gam_c[r] * QWS[:C]
                        + _mm(decQK[r], rows_of(j, delta)))
            deltas.append(jnp.concatenate(
                [jnp.zeros((C, t0 * LANES), f32)] * (t0 > 0) + [delta]
                + [jnp.zeros((C, (nt - t1) * LANES), f32)] * (t1 < nt),
                axis=1))                                    # [C, n]
        for t in range(nt):
            piece = None
            for j, (t0, t1) in enumerate(spans):
                if t0 <= t < t1:
                    o = outs[j][:, (t - t0) * LANES:(t - t0 + 1) * LANES]
                    piece = o if piece is None else jnp.where(
                        lane_head[:, t * LANES:(t + 1) * LANES] == j, o,
                        piece)
            obuf[buf, p, t] = piece
        D = jnp.concatenate(deltas, axis=0)                 # [R, n]
        s_out[0, p] = lane_end * S0 + _mm(K[:, :dk], end_c * D,
                                          ((0,), (0,)))
        return carry

    unroll = math.gcd(hg, TILE_UNROLL)

    def tiles(k, carry):
        for u in range(unroll):
            tile(k * unroll + u, carry)
        return carry

    jax.lax.fori_loop(_i32(0), _i32(hg // unroll), tiles, 0)


def _kernel(cnt_ref, ur_ref, own_ref, sl_ref, ps_ref, qT_ref, kT_ref, v_ref,
            a_ref, b_ref, s_in, *rest, value_dim: int, group: int, block):
    if block is None:
        o_ref, s_out = rest
    else:
        (qh, kh, vp, gp, bp, o_ref, s_out, ob, qbuf, kbuf, vbuf, gbuf, bbuf,
         obuf, cols, isem, osem, pend) = rest
    i = pl.program_id(0)
    row = ur_ref[i]
    first = jnp.logical_or(
        i == 0, sl_ref[row] != sl_ref[ur_ref[jnp.maximum(i - 1, 0)]])

    @pl.when(first)
    def _carry_in():      # the slot's state, copied in for its first unit
        s_out[...] = s_in[...]

    @pl.when(ps_ref[row] == 0)
    def _fresh():         # a sequence starts: nothing of the slot carries
        s_out[...] = jnp.zeros(s_out.shape, s_out.dtype)

    row_step = functools.partial(
        _row_step, row, qT_ref, kT_ref, v_ref, a_ref, b_ref, o_ref, s_out,
        value_dim=value_dim, group=group)
    if block is None:
        row_step()
        return

    C = block
    last = i == cnt_ref[0] - 1

    def copies_in(u, buf):
        ws, buf = ur_ref[u], _i32(buf)
        pairs = ((qh, qbuf), (kh, kbuf), (vp, vbuf), (gp, gbuf), (bp, bbuf))
        return [pltpu.make_async_copy(
            src.at[(slice(None),) * (src.ndim - 2) + (pl.ds(ws, C),)],
            dst.at[buf], isem.at[buf, _i32(j)])
            for j, (src, dst) in enumerate(pairs)]

    def copy_out(buf):
        buf = _i32(buf)
        return pltpu.make_async_copy(
            obuf.at[buf], ob.at[:, :, pl.ds(ur_ref[i], C)], osem.at[buf])

    @pl.when(i == 0)
    def _start():
        pend[0] = 0
        pend[1] = 0

        @pl.when(own_ref[0] > 0)
        def _own_rows():
            for c in copies_in(0, 0):
                c.start()

    @pl.when(jnp.logical_and(jnp.logical_not(last), own_ref[i + 1] > 0))
    def _next_rows():     # the next block's window, while this unit runs
        for c in copies_in(i + 1, jax.lax.rem(i + 1, _i32(2))):
            c.start()

    @pl.when(own_ref[i] == 0)
    def _row():
        row_step()

    @pl.when(own_ref[i] > 0)
    def _block():
        buf = jax.lax.rem(i, _i32(2))
        for c in copies_in(i, buf):
            c.wait()

        @pl.when(pend[buf] == 1)
        def _free():      # the output buffer's last copy out has landed
            copy_out(buf).wait()
            pend[buf] = 0

        _block_step(
            own_ref[i], buf, qbuf, kbuf, vbuf, gbuf, bbuf, obuf,
            cols, s_out, value_dim=value_dim, group=group, block=C)

        @pl.when(pend[1 - buf] == 1)
        def _in_order():  # a window may overlap the last: it lands after
            copy_out(1 - buf).wait()
            pend[1 - buf] = 0

        copy_out(buf).start()
        pend[buf] = 1

    @pl.when(last)
    def _drain():
        for buf in (0, 1):
            @pl.when(pend[buf] == 1)
            def _wait():
                copy_out(buf).wait()


# one trace and one lowering a shape: a step's layers call the kernel with
# the same shapes
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_delta_call(state, q, k, v, g, beta, slots, positions, *,
                      interpret: bool):
    T, H, dk = q.shape
    dv = v.shape[-1]
    n_slots, hg, _, n = state.shape
    group = n // dv
    block = state_block(H, dv)
    if block is not None and T < block:
        block = None                    # no run can fill a block
    slots = slots.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    unit_row, unit_own, in_block, live, count = _units(
        slots, positions, n_slots - 1, block)
    qT, kT = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)   # [T, d_k, H]

    def lanes(x):   # [T, H, d_v] -> a tile row's lanes, G heads a row
        return x.reshape(T, hg, n)

    def per_lane(x):   # [T, H] -> each head's value on its d_v lanes
        return lanes(jnp.broadcast_to(x[:, :, None], (T, H, dv)))

    idx = lambda i, cnt, ur, own, sl, ps: (ur[i], _i32(0), _i32(0))  # noqa
    rows = pl.BlockSpec((1, hg, n), idx)
    st = pl.BlockSpec((1, hg, dk, n),
                      lambda i, cnt, ur, own, sl, ps: (sl[ur[i]], _i32(0),
                                                       _i32(0), _i32(0)))
    in_specs = [pl.BlockSpec((1, dk, H), idx), pl.BlockSpec((1, dk, H), idx),
                rows, rows, rows, st]
    operands = [qT, kT, lanes(v), per_lane(jnp.exp(g)), per_lane(beta),
                state]
    out_specs = [rows, st]
    out_shape = [jax.ShapeDtypeStruct((T, hg, n), jnp.float32),
                 jax.ShapeDtypeStruct(state.shape, state.dtype)]
    scratch = []
    if block is not None:
        dkp, hp = _round_up(dk, LANES), _round_up(H, LANES)
        nt = n // LANES
        # a block window is copied by hand from HBM: the chip copies rows at
        # any offset of an array whose rows are one lane tile
        hbm = pl.BlockSpec(memory_space=pltpu.HBM)

        def head_major(x):    # [T, H, d_k] -> [H, T, d_k to whole lanes]
            return jnp.pad(jnp.swapaxes(x, 0, 1),
                           ((0, 0), (0, 0), (0, dkp - dk)))

        def tile_major(x):    # [T, H/G, n] -> [H/G, n / 128, T, 128]
            return jnp.transpose(x.reshape(T, hg, nt, LANES), (1, 2, 0, 3))

        def heads_on_lanes(x):    # [T, H] -> [T, H to whole lanes]
            return jnp.pad(x, ((0, 0), (0, hp - H)))

        in_specs += [hbm] * 5
        operands += [head_major(q), head_major(k), tile_major(lanes(v)),
                     heads_on_lanes(g), heads_on_lanes(beta)]
        out_specs.append(hbm)
        out_shape.append(jax.ShapeDtypeStruct((hg, nt, T, LANES),
                                              jnp.float32))
        f32 = jnp.float32
        scratch = [pltpu.VMEM((2, H, block, dkp), f32),
                   pltpu.VMEM((2, H, block, dkp), f32),
                   pltpu.VMEM((2, hg, nt, block, LANES), f32),
                   pltpu.VMEM((2, block, hp), f32),
                   pltpu.VMEM((2, block, hp), f32),
                   pltpu.VMEM((2, hg, nt, block, LANES), f32),
                   pltpu.VMEM((hg, group * block, LANES + n), f32),
                   pltpu.SemaphoreType.DMA((2, 5)),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SMEM((2,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,   # unit count, units' rows and own rows,
        grid=(count,),           # slots, positions
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        functools.partial(_kernel, value_dim=dv, group=group, block=block),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # operand 10 (after the five scalar-prefetch operands) is the state
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="gated_delta",
    )(count.reshape(1), unit_row, unit_own, slots, positions, *operands)
    o, state = outs[0], outs[1]
    if block is not None:
        o_block = jnp.transpose(outs[2], (2, 0, 1, 3)).reshape(T, hg, n)
        o = jnp.where(in_block[:, None, None], o_block, o)
    o = jnp.where(live[:, None, None], o, 0.0)
    return o.reshape(T, H, dv), state


def gated_delta(state, q, k, v, g, beta, slots, positions,
                use_kernel: bool = None):
    """The gated delta rule over a step's rows (module docstring); ``g`` is
    the log decay. ``use_kernel=None`` picks the Pallas kernel on TPU
    backends (or under PADDLE_TPU_PALLAS_INTERPRET=1) and the jnp scan
    elsewhere; both compute the same float32 math. Returns (o ``[T, H,
    d_v]`` float32, state)."""
    args = [a.astype(jnp.float32) for a in (q, k, v, g, beta)]
    if use_kernel is None:
        use_kernel = _interpret() or jax.default_backend() == "tpu"
    if use_kernel:
        return _gated_delta_call(state, *args, slots, positions,
                                 interpret=_interpret())
    return gated_delta_ref(state, *args, slots, positions)
