"""The Pallas kernels of the main path, compiled for a DESCRIBED TPU v5e at
real widths (GPT-3 1.3B / the Llama trunk's GQA). Nothing runs and no chip
is needed: the installed TPU compiler lowers for a ``v5e:2x2`` topology
description, so what Mosaic would refuse on the chip is refused here — the
block-shape and SMEM faults that interpret mode cannot see.

All of it lives in this ONE file, and the topology is described inside a
module-scoped fixture: only one process may hold the TPU library, so it must
load in the one xdist worker that runs this file, after collection.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without one — keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel_calls(text):
    """The compiled program's Mosaic kernels, by instruction name."""
    return [line.split(" = ")[0].strip() for line in text.split("\n")
            if "tpu_custom_call" in line]


def _assert_one_paged_kernel(text):
    """One call is ONE kernel, under the name the trace reduction keys on
    (benchmarks/layer_metrics/paged_attention_roofline.py)."""
    calls = _kernel_calls(text)
    assert len(calls) == 1 and "paged_attention" in calls[0], calls


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("B,S,H,D", [(4, 1024, 16, 128), (8, 1024, 16, 64)])
def test_flash_attention_compiles_for_v5e(one_chip, B, S, H, D, grad):
    x = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    text = _compiled_text(fn, x, x, x)
    # forward alone is one kernel; the backward adds the dq and dkv kernels
    assert text.count("tpu_custom_call") >= (3 if grad else 1)
    # each under its own name, which is how a device trace tells them apart
    names = ["flash_attention_fwd"] + (
        ["flash_attention_bwd_dq", "flash_attention_bwd_dkv"] if grad else [])
    calls = _kernel_calls(text)
    assert all(any(n in c for c in calls) for n in names), calls


def _paged_shapes(one_chip, T, nh, nkv, hd, page, pages, quantized):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    num_pages = 1024
    pool = s((num_pages, nkv, page, hd),
             jnp.int8 if quantized else jnp.bfloat16)
    shapes = [s((T, nh, hd), jnp.bfloat16), pool, pool,
              s((T, pages), jnp.int32), s((T,), jnp.int32)]
    if quantized:
        scale = s((num_pages, nkv, page), jnp.float32)
        shapes += [scale, scale]
    return shapes


def _paged_fn(entry, quantized):
    if quantized:
        return lambda q, k, v, bt, sl, ks, vs: entry(
            q, k, v, bt, sl, use_kernel=True, k_scale=ks, v_scale=vs)
    return lambda q, k, v, bt, sl: entry(q, k, v, bt, sl, use_kernel=True)


# nh16/nkv16/hd128/page16 is GPT-3 1.3B; T spans the engine's token-grid
# buckets up to the default token_budget (64 is the benchmark cell's widest:
# its token_budget); pages = max_model_len 2048 / 16
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("T", [8, 64, 256, 1024])
def test_ragged_paged_attention_compiles_for_v5e(one_chip, T, quantized):
    text = _compiled_text(
        _paged_fn(pa.ragged_paged_attention, quantized),
        *_paged_shapes(one_chip, T, 16, 16, 128, 16, 128, quantized))
    _assert_one_paged_kernel(text)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, quantized):
    text = _compiled_text(
        _paged_fn(pa.paged_attention, quantized),
        *_paged_shapes(one_chip, 8, 16, 16, 128, 16, 128, quantized))
    _assert_one_paged_kernel(text)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_attention_gqa_compiles_for_v5e(one_chip, quantized):
    """The Llama trunk's GQA: 32 query heads over 8 kv heads."""
    text = _compiled_text(
        _paged_fn(pa.ragged_paged_attention, quantized),
        *_paged_shapes(one_chip, 256, 32, 8, 128, 16, 128, quantized))
    _assert_one_paged_kernel(text)


def test_table_not_a_multiple_of_the_page_block_compiles(one_chip):
    """max_model_len 1600 / page 16 = 100 pages against blocks of 8: the
    last block overhangs the table and is padded with the null page."""
    assert pa._pages_per_block(100, 16, 16, 128, 2) == 8
    text = _compiled_text(
        _paged_fn(pa.ragged_paged_attention, False),
        *_paged_shapes(one_chip, 64, 16, 16, 128, 16, 100, False))
    _assert_one_paged_kernel(text)


def test_smem_limit_is_where_the_compiler_puts_it(one_chip):
    """The scalar-prefetched block table lives whole in SMEM. Just under
    SMEM_PREFETCH_LIMIT_BYTES compiles; just over is OUR error, with the
    numbers in it, not a compiler crash."""
    pages = 128
    fits = pa.SMEM_PREFETCH_LIMIT_BYTES // (4 * (pages + 1))
    text = _compiled_text(
        _paged_fn(pa.ragged_paged_attention, False),
        *_paged_shapes(one_chip, fits, 16, 16, 128, 16, pages, False))
    _assert_one_paged_kernel(text)
    with pytest.raises(ValueError, match=rf"{fits + 1} rows, {pages} pages"):
        _compiled_text(
            _paged_fn(pa.ragged_paged_attention, False),
            *_paged_shapes(one_chip, fits + 1, 16, 16, 128, 16, pages,
                           False))
