"""Pallas flash-attention fwd+bwd vs the XLA reference, in interpret mode.

Reference parity: phi flash_attn fwd+bwd kernels
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu:213,302 and
flash_attn_grad_kernel). The Pallas kernels run in interpret mode on CPU so
the real kernel code paths (block indexing, masks, lse math) are tested
without a TPU; VERDICT.md weak #3 required the bwd to stop materializing
[S,S] — asserted here on the compiled jaxpr.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 128), (256, 256), (100, 100)])
def test_forward_matches_reference(causal, sq, sk):
    b, h, d = 2, 2, 64
    q = _rand((b, sq, h, d), 0)
    k = _rand((b, sk, h, d), 1)
    v = _rand((b, sk, h, d), 2)
    scale = 1.0 / np.sqrt(d)
    out = fa._flash_attention(q, k, v, jnp.float32(0), causal, scale, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
    ref = fa._ref_attention_bshd(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq", [128, 256, 100])
def test_backward_matches_reference(causal, sq):
    b, h, d = 2, 2, 64
    q = _rand((b, sq, h, d), 3)
    k = _rand((b, sq, h, d), 4)
    v = _rand((b, sq, h, d), 5)
    scale = 1.0 / np.sqrt(d)

    def loss_flash(q, k, v):
        return jnp.sum(fa._flash_attention(q, k, v, jnp.float32(0), causal, scale, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(fa._ref_attention_bshd(q, k, v, causal, scale) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal}, sq={sq})")


def test_cross_attention_backward():
    b, h, d, sq, sk = 1, 2, 64, 128, 256
    q = _rand((b, sq, h, d), 6)
    k = _rand((b, sk, h, d), 7)
    v = _rand((b, sk, h, d), 8)
    scale = 1.0 / np.sqrt(d)
    g_flash = jax.grad(
        lambda q, k, v: jnp.sum(fa._flash_attention(q, k, v, jnp.float32(0), True, scale, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(fa._ref_attention_bshd(q, k, v, True, scale)),
        argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4)


def test_the_three_kernels_carry_their_names():
    """A device trace tells the forward, dQ and dK/dV kernels apart by the
    ``name=`` of their ``pallas_call`` (PERF.md section 3)."""
    q, k, v = (_rand((1, 256, 1, 64), i) for i in (3, 4, 5))
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q, k, v: jnp.sum(
            fa._flash_attention(q, k, v, jnp.float32(0), True, 0.125,
                                fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K))),
    )(q, k, v)

    def names(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from names(sub)

    assert sorted(names(jaxpr.jaxpr)) == [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
        "flash_attention_fwd"]


def test_backward_jaxpr_has_no_SxS_intermediate():
    """The grad jaxpr must contain no [S,S]-shaped dense intermediates
    outside the pallas kernels (VERDICT weak #3: bwd used to re-run
    full-softmax XLA math materializing [S,S] per head)."""
    b, h, d, s = 1, 1, 64, 512
    q = _rand((b, s, h, d), 9)
    k = _rand((b, s, h, d), 10)
    v = _rand((b, s, h, d), 11)

    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q, k, v: jnp.sum(
            fa._flash_attention(q, k, v, jnp.float32(0), True, 0.125, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K))),
    )(q, k, v)
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue  # kernel-internal blocks are VMEM-tiled by construction
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (len(shape) >= 2 and shape[-1] == s
                        and shape[-2] == s), (
                f"[S,S] intermediate {shape} from {eqn.primitive.name}")


def test_fused_adamw_kernel_matches_xla():
    """ops/pallas/fused_adamw.py — interpret-mode numerics (the on-chip A/B
    decides whether the optimizer routes through it; tools/bench_adamw.py)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_adamw import (fused_adamw_flat,
                                                   xla_adamw_flat)

    rng = np.random.default_rng(0)
    n = 10000  # not tile-aligned: exercises the pad path
    w = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32) * 1e-3
    got = fused_adamw_flat(w, m, v, g, jnp.float32(1e-3), jnp.float32(5.0),
                           weight_decay=0.01)
    want = xla_adamw_flat(w, m, v, g, jnp.float32(1e-3), jnp.float32(5.0),
                          weight_decay=0.01)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128), (256, 256)])
def test_flash_block_config_matrix(bq, bk):
    """Every block config the on-chip sweep (tools/bench_flash.py) exercises
    must already be numerically right in interpret mode."""
    q = _rand((1, 256, 2, 32), 5)
    k = _rand((1, 256, 2, 32), 6)
    v = _rand((1, 256, 2, 32), 7)
    scale = 1.0 / np.sqrt(32)
    out = fa._flash_attention(q, k, v, jnp.float32(0), True, scale, bq, bk)
    ref = fa._ref_attention_bshd(q, k, v, True, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    # backward too: the sweep times fwd+bwd
    g = jax.grad(lambda q, k, v: jnp.sum(
        fa._flash_attention(q, k, v, jnp.float32(0), True, scale, bq, bk)
        .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
    for arr in g:
        assert np.all(np.isfinite(np.asarray(arr, np.float32)))


@pytest.mark.parametrize("d", [64, 128])
def test_causality_no_future_leak(d):
    """Perturbing a FUTURE key/value must not change earlier outputs.

    Pinned after r4's llama-on-TPU loss anomaly: llama is the only zoo
    model with head_dim=128, so the D=128 kernel path needs its own
    causality evidence, not just D=64's."""
    b, s, h = 1, 256, 2
    q = _rand((b, s, h, d), 10)
    k = _rand((b, s, h, d), 11)
    v = _rand((b, s, h, d), 12)
    out = fa.flash_attention_bshd(q, k, v, causal=True)
    k2 = k.at[:, -1].add(100.0)
    v2 = v.at[:, -1].add(100.0)
    out2 = fa.flash_attention_bshd(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :-1]),
                               np.asarray(out2[:, :-1]), atol=1e-6)
    # and the final row DOES see its own (non-future) key: sanity that the
    # probe can detect a change at all
    assert float(jnp.max(jnp.abs(out2[:, -1] - out[:, -1]))) > 1e-3


def test_dropout_zero_matches_no_dropout():
    b, s, h, d = 1, 256, 2, 64
    q, k, v = _rand((b, s, h, d), 20), _rand((b, s, h, d), 21), _rand((b, s, h, d), 22)
    base = fa.flash_attention_bshd(q, k, v, causal=True)
    zero = fa.flash_attention_bshd(q, k, v, causal=True, dropout_p=0.0,
                                   dropout_seed=123)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(zero))


def test_dropout_statistics_and_determinism():
    """In-kernel dropout: deterministic given a seed, different across
    seeds, and ~E[out] preserved (inverted-dropout scaling)."""
    b, s, h, d = 1, 256, 2, 64
    q, k, v = _rand((b, s, h, d), 23), _rand((b, s, h, d), 24), _rand((b, s, h, d), 25)
    a1 = np.asarray(fa.flash_attention_bshd(q, k, v, dropout_p=0.3,
                                            dropout_seed=7))
    a2 = np.asarray(fa.flash_attention_bshd(q, k, v, dropout_p=0.3,
                                            dropout_seed=7))
    a3 = np.asarray(fa.flash_attention_bshd(q, k, v, dropout_p=0.3,
                                            dropout_seed=8))
    np.testing.assert_array_equal(a1, a2)
    assert np.abs(a1 - a3).max() > 1e-4, "seed has no effect"
    ref = np.asarray(fa.flash_attention_bshd(q, k, v))
    # inverted dropout preserves the mean output magnitude (loose bound:
    # attention rows are convex combos, dropping 30% adds variance)
    assert np.abs(a1.mean() - ref.mean()) < 0.1


def test_dropout_backward_consistent_with_forward():
    """The bwd kernels must reproduce the fwd's hash mask exactly: check
    d/dq, d/dk AND d/dv against finite differences of the kernel's own
    (deterministic) forward. dv exercises the p_eff·do path; dq/dk
    exercise the subtler ds = p·(dp_eff − Δ) path (mask applied to dp but
    not p, Δ = rowsum(do∘o) = rowsum(p∘dp_eff))."""
    b, s, h, d = 1, 128, 1, 64
    q = _rand((b, s, h, d), 26)
    k = _rand((b, s, h, d), 27)
    v = _rand((b, s, h, d), 28)

    def f(qq, kk, vv):
        return jnp.sum(fa.flash_attention_bshd(
            qq, kk, vv, causal=True, dropout_p=0.4, dropout_seed=99)
            .astype(jnp.float32) * 1.7)

    grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    eps = 1e-2
    rng = np.random.default_rng(0)
    for argn, (name, arr) in enumerate([("dq", q), ("dk", k), ("dv", v)]):
        g = grads[argn]
        for _ in range(4):
            i = tuple(rng.integers(0, dim) for dim in arr.shape)
            args_p = [q, k, v]
            args_m = [q, k, v]
            args_p[argn] = arr.at[i].add(eps)
            args_m[argn] = arr.at[i].add(-eps)
            fd = (f(*args_p) - f(*args_m)) / (2 * eps)
            assert abs(float(g[i]) - float(fd)) < 5e-2, (
                f"{name} mismatch at {i}: analytic {float(g[i])} "
                f"vs fd {float(fd)}")


def test_dropout_mask_block_layout_invariant():
    """The hash mask depends on global coordinates only: different block
    configs must produce the SAME dropped positions."""
    b, s, h, d = 1, 256, 1, 64
    q, k, v = _rand((b, s, h, d), 29), _rand((b, s, h, d), 30), _rand((b, s, h, d), 31)
    seed = jnp.float32(42)
    a = np.asarray(fa._flash_attention(q, k, v, seed, False, 0.125,
                                       128, 128, 0.25))
    bb = np.asarray(fa._flash_attention(q, k, v, seed, False, 0.125,
                                        256, 128, 0.25))
    np.testing.assert_allclose(a, bb, atol=2e-5, rtol=2e-5)


def test_key_padding_mask_matches_reference():
    """Per-key padding inside the kernel (reference: flash_attn's padded
    batches) must equal dense attention with -inf on masked keys — fwd
    and all grads, causal and not."""
    b, s, h, d = 2, 256, 2, 64
    q = _rand((b, s, h, d), 40)
    k = _rand((b, s, h, d), 41)
    v = _rand((b, s, h, d), 42)
    scale = 1.0 / np.sqrt(d)
    lengths = np.array([s - 37, s - 120])
    keep = (np.arange(s)[None, :] < lengths[:, None])
    kpad = jnp.asarray(keep, jnp.bool_)

    for causal in (False, True):
        def f_flash(q, k, v):
            return fa.flash_attention_bshd(q, k, v, causal=causal,
                                           key_padding_mask=kpad)

        def f_ref(q, k, v):
            qh = jnp.swapaxes(q, 1, 2)
            kh = jnp.swapaxes(k, 1, 2)
            vh = jnp.swapaxes(v, 1, 2)
            logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
            m = jnp.asarray(keep)[:, None, None, :]
            if causal:
                cm = jnp.tril(jnp.ones((s, s), bool))
                m = m & cm[None, None]
            logits = jnp.where(m, logits, fa.NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1)
            return jnp.swapaxes(
                jnp.einsum("bhqk,bhkd->bhqd", probs, vh), 1, 2)

        out = np.asarray(f_flash(q, k, v))
        ref = np.asarray(f_ref(q, k, v))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

        gf = jax.grad(lambda *a: jnp.sum(f_flash(*a) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(f_ref(*a) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, bb, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(bb), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name} mismatch (causal={causal})")


def test_bert_padding_mask_routes_to_flash(monkeypatch):
    """BERT's [B, S] padding mask must reach the flash kernel as bool
    [B,1,1,S] key padding (bert.py to_bool + transformer bool
    pass-through + attention _as_key_padding) and match the XLA path."""
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, BertModel
    from paddle_tpu.nn.functional import attention as A

    cfg = BertConfig(vocab_size=128, hidden_size=64, num_layers=2,
                     num_heads=2, intermediate_size=128,
                     max_position_embeddings=128,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(0)
    m = BertModel(cfg)
    m.eval()
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 128, (2, 64)))
    am = paddle.to_tensor((np.arange(64)[None, :]
                           < np.array([50, 30])[:, None]).astype("int64"))

    monkeypatch.setattr(A, "pallas_flash_enabled", False)
    ref, _ = m(ids, attention_mask=am)
    monkeypatch.setattr(A, "pallas_flash_enabled", True)
    monkeypatch.setattr(A, "_use_pallas", lambda qv, s: True)
    out, _ = m(ids, attention_mask=am)
    np.testing.assert_allclose(np.asarray(out.numpy()),
                               np.asarray(ref.numpy()),
                               atol=5e-5, rtol=5e-5)


def test_fully_masked_rows_emit_zero():
    """A query row with ZERO valid keys (all-padded batch row) must emit
    zeros, not a uniform average over masked values (ADVICE r4: running
    max stuck at neg_inf made p=exp(0)=1 for every masked position), and
    its gradients must be zero — consistent with the backward kernels'
    p=0 reconstruction."""
    b, s, h, d = 2, 128, 2, 64
    q = _rand((b, s, h, d), 50)
    k = _rand((b, s, h, d), 51)
    v = _rand((b, s, h, d), 52)
    # batch row 1: every key padded out
    keep = np.ones((b, s), bool)
    keep[1, :] = False
    kpad = jnp.asarray(keep)

    for causal in (False, True):
        out, vjp = jax.vjp(
            lambda q, k, v: fa.flash_attention_bshd(
                q, k, v, causal=causal, key_padding_mask=kpad), q, k, v)
        o = np.asarray(out)
        assert np.all(np.isfinite(o))
        np.testing.assert_allclose(o[1], 0.0, atol=1e-6)
        # valid rows keep matching the dense reference
        ref = np.asarray(fa._ref_attention_bshd(
            q[:1], k[:1], v[:1], causal, 1.0 / np.sqrt(d)))
        np.testing.assert_allclose(o[:1], ref, atol=5e-5, rtol=5e-5)
        dq, dk, dv = vjp(jnp.ones_like(out))
        for g in (dq, dk, dv):
            ga = np.asarray(g)
            assert np.all(np.isfinite(ga))
            np.testing.assert_allclose(ga[1], 0.0, atol=1e-6)


def test_kernel_runs_per_shard_under_a_mesh():
    """Mosaic kernels cannot be partitioned by GSPMD, so under a mesh
    nn.functional.attention runs the kernel inside a shard_map (batch over
    'dp', heads over 'mp'). Forward and gradients through that wrapper
    match the unsharded kernel, and the output stays sharded."""
    from paddle_tpu.distributed import topology
    from paddle_tpu.nn.functional import attention as A

    mesh = topology.create_mesh({"dp": 4, "mp": 2})
    b, s, h, d = 4, 128, 2, 64
    q, k, v = (_rand((b, s, h, d), i) for i in (20, 21, 22))

    def attn(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True)

    sharded = A._per_shard(attn, mesh, q.shape, has_seed=False,
                           has_kpad=False)
    out = jax.jit(sharded)(q, k, v)
    assert tuple(out.sharding.spec)[:3] == ("dp", None, "mp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(attn(q, k, v)),
                               atol=2e-5, rtol=2e-5)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    g = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(attn), argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)
