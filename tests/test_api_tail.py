"""Top-level API tail: ops/extras, framework core_api, summary, and the
full-namespace parity gate against the reference's paddle.__all__."""
import re

import numpy as np
import pytest

import paddle_tpu as paddle


@pytest.mark.needs_reference
def test_reference_top_level_parity():
    """Every name in the reference's paddle.__all__ must resolve here."""
    src = open("/root/reference/python/paddle/__init__.py").read()
    block = re.search(r"__all__ = \[(.*?)\]", src, re.S).group(1)
    ref_all = re.findall(r"'([^']+)'", block)
    assert len(ref_all) > 250  # sanity: we parsed the real list
    missing = [n for n in ref_all if not hasattr(paddle, n)]
    assert missing == [], f"top-level names missing: {missing}"


# ------------------------------------------------------------- extras ops


def test_logit_inverts_sigmoid():
    x = paddle.to_tensor(np.array([0.1, 0.5, 0.9], np.float32))
    y = paddle.logit(x)
    np.testing.assert_allclose(1 / (1 + np.exp(-np.asarray(y.numpy()))),
                               np.asarray(x.numpy()), rtol=1e-5)
    # eps clamps out-of-range inputs instead of producing inf
    z = paddle.logit(paddle.to_tensor(np.array([0.0, 1.0], np.float32)),
                     eps=1e-6)
    assert np.all(np.isfinite(np.asarray(z.numpy())))


def test_heaviside_nan_to_num_sgn():
    x = paddle.to_tensor(np.array([-1.0, 0.0, 2.0], np.float32))
    h = paddle.heaviside(x, paddle.to_tensor(np.array([0.5], np.float32)))
    np.testing.assert_array_equal(np.asarray(h.numpy()), [0.0, 0.5, 1.0])

    bad = paddle.to_tensor(np.array([np.nan, np.inf, -np.inf], np.float32))
    fixed = paddle.nan_to_num(bad, nan=1.0, posinf=2.0, neginf=-2.0)
    np.testing.assert_array_equal(np.asarray(fixed.numpy()), [1.0, 2.0, -2.0])

    c = paddle.sgn(paddle.to_tensor(np.array([3 + 4j, 0j], np.complex64)))
    np.testing.assert_allclose(np.asarray(c.numpy()), [0.6 + 0.8j, 0j],
                               rtol=1e-6)


def test_gcd_lcm_deg_rad():
    a = paddle.to_tensor(np.array([12, 20], np.int64))
    b = paddle.to_tensor(np.array([18, 8], np.int64))
    np.testing.assert_array_equal(np.asarray(paddle.gcd(a, b).numpy()), [6, 4])
    np.testing.assert_array_equal(np.asarray(paddle.lcm(a, b).numpy()),
                                  [36, 40])
    d = paddle.rad2deg(paddle.to_tensor(np.array([np.pi], np.float32)))
    np.testing.assert_allclose(np.asarray(d.numpy()), [180.0], rtol=1e-5)
    r = paddle.deg2rad(paddle.to_tensor(np.array([180.0], np.float32)))
    np.testing.assert_allclose(np.asarray(r.numpy()), [np.pi], rtol=1e-5)


def test_multiplex_and_index_add_and_take():
    i1 = np.array([[1, 2], [3, 4]], np.float32)
    i2 = np.array([[5, 6], [7, 8]], np.float32)
    idx = paddle.to_tensor(np.array([1, 0], np.int32))
    out = paddle.multiplex([paddle.to_tensor(i1), paddle.to_tensor(i2)], idx)
    np.testing.assert_array_equal(np.asarray(out.numpy()),
                                  [[5, 6], [3, 4]])

    x = paddle.to_tensor(np.zeros((3, 2), np.float32))
    added = paddle.index_add(x, paddle.to_tensor(np.array([0, 2])), 0,
                             paddle.to_tensor(np.ones((2, 2), np.float32)))
    np.testing.assert_array_equal(np.asarray(added.numpy()),
                                  [[1, 1], [0, 0], [1, 1]])

    t = paddle.to_tensor(np.arange(6).reshape(2, 3))
    taken = paddle.take(t, paddle.to_tensor(np.array([0, 7, -1])),
                        mode="clip")
    np.testing.assert_array_equal(np.asarray(taken.numpy()), [0, 5, 0])


def test_trapezoid_matches_numpy():
    y = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    t = paddle.trapezoid(paddle.to_tensor(y), dx=0.5)
    np.testing.assert_allclose(float(t.numpy()),
                               np.trapezoid(y, dx=0.5), rtol=1e-6)
    ct = paddle.cumulative_trapezoid(paddle.to_tensor(y), dx=0.5)
    np.testing.assert_allclose(np.asarray(ct.numpy()),
                               [0.75, 2.0, 3.75], rtol=1e-6)


def test_renorm_vander_polar():
    x = paddle.to_tensor(np.array([[3.0, 4.0], [0.3, 0.4]], np.float32))
    rn = paddle.renorm(x, p=2.0, axis=0, max_norm=1.0)
    norms = np.linalg.norm(np.asarray(rn.numpy()), axis=1)
    assert norms[0] == pytest.approx(1.0, rel=1e-5)
    assert norms[1] == pytest.approx(0.5, rel=1e-5)  # already under the cap

    v = paddle.vander(paddle.to_tensor(np.array([1.0, 2.0, 3.0], np.float32)),
                      n=3)
    np.testing.assert_allclose(np.asarray(v.numpy()),
                               np.vander([1.0, 2.0, 3.0], 3), rtol=1e-6)

    p = paddle.polar(paddle.to_tensor(np.array([1.0], np.float32)),
                     paddle.to_tensor(np.array([np.pi / 2], np.float32)))
    np.testing.assert_allclose(np.asarray(p.numpy()), [1j], atol=1e-6)


def test_add_n_scatter_nd_broadcast_tensors():
    ts = [paddle.to_tensor(np.full((2, 2), i, np.float32)) for i in range(3)]
    np.testing.assert_array_equal(np.asarray(paddle.add_n(ts).numpy()),
                                  np.full((2, 2), 3.0))

    out = paddle.scatter_nd(paddle.to_tensor(np.array([[1], [1]], np.int64)),
                            paddle.to_tensor(np.array([2.0, 3.0], np.float32)),
                            [4])
    np.testing.assert_array_equal(np.asarray(out.numpy()), [0, 5, 0, 0])

    a, b = paddle.broadcast_tensors([
        paddle.to_tensor(np.ones((1, 3), np.float32)),
        paddle.to_tensor(np.ones((2, 1), np.float32))])
    assert tuple(a.shape) == (2, 3) and tuple(b.shape) == (2, 3)
    assert paddle.broadcast_shape([1, 3], [2, 1]) == [2, 3]


def test_inplace_variants_rebind():
    x = paddle.to_tensor(np.zeros((2, 3), np.float32))
    y = paddle.reshape_(x, [3, 2])
    assert y is x and tuple(x.shape) == (3, 2)
    paddle.unsqueeze_(x, 0)
    assert tuple(x.shape) == (1, 3, 2)
    paddle.squeeze_(x, 0)
    assert tuple(x.shape) == (3, 2)
    t = paddle.to_tensor(np.array([0.0], np.float32))
    paddle.tanh_(t)
    np.testing.assert_array_equal(np.asarray(t.numpy()), [0.0])
    paddle.increment(t, 2.5)
    np.testing.assert_allclose(np.asarray(t.numpy()), [2.5])


def test_predicates_and_shape_helpers():
    x = paddle.to_tensor(np.zeros((2, 3), np.float32))
    assert paddle.is_tensor(x) and not paddle.is_tensor(5)
    assert paddle.is_floating_point(x)
    assert not paddle.is_integer(x)
    assert not paddle.is_complex(x)
    assert int(paddle.rank(x).numpy()) == 2
    np.testing.assert_array_equal(np.asarray(paddle.shape(x).numpy()), [2, 3])
    assert paddle.tolist(x) == [[0, 0, 0], [0, 0, 0]]


# ---------------------------------------------------------------- core_api


def test_iinfo_finfo_dtype():
    assert paddle.iinfo(paddle.int32).max == 2 ** 31 - 1
    assert paddle.iinfo("int8").min == -128
    assert paddle.finfo(paddle.float32).eps == pytest.approx(2 ** -23)
    assert paddle.finfo("bfloat16").max > 3e38
    assert paddle.dtype("float32") == paddle.float32


def test_default_dtype_get_set():
    assert paddle.get_default_dtype() == "float32"
    paddle.set_default_dtype("float64")
    try:
        assert paddle.get_default_dtype() == "float64"
    finally:
        paddle.set_default_dtype("float32")
    with pytest.raises(TypeError):
        paddle.set_default_dtype("int32")


def test_places():
    assert paddle.CPUPlace() == paddle.CPUPlace()
    assert paddle.CUDAPlace(0).get_device_id() == 0
    assert paddle.CUDAPlace(0) != paddle.CUDAPlace(1)
    assert "tpu" in repr(paddle.CUDAPlace(0))


def test_create_parameter_and_lazyguard():
    with paddle.LazyGuard():
        w = paddle.create_parameter([4, 5], "float32")
    assert tuple(w.shape) == (4, 5) and not w.stop_gradient
    b = paddle.create_parameter([5], "float32", is_bias=True)
    np.testing.assert_array_equal(np.asarray(b._value), np.zeros(5))


def test_batch_reader():
    def reader():
        yield from range(7)

    batches = list(paddle.batch(reader, 3)())
    assert batches == [[0, 1, 2], [3, 4, 5], [6]]
    batches = list(paddle.batch(reader, 3, drop_last=True)())
    assert batches == [[0, 1, 2], [3, 4, 5]]


def test_cuda_rng_state_aliases():
    st = paddle.get_cuda_rng_state()
    paddle.set_cuda_rng_state(st)


def test_check_shape():
    paddle.check_shape([2, 3, -1])
    with pytest.raises(ValueError):
        paddle.check_shape([2, -5])
    with pytest.raises(TypeError):
        paddle.check_shape("nope")


def test_summary_counts(capsys):
    from paddle_tpu import nn

    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    info = paddle.summary(net, (4, 8))
    assert info["total_params"] == 8 * 16 + 16 + 16 * 2 + 2
    out = capsys.readouterr().out
    assert "Total params" in out and "Linear" in out

def test_vsplit_indices_semantics():
    import numpy as np
    import paddle_tpu as paddle
    x = paddle.to_tensor(np.arange(40).reshape(10, 4))
    parts = paddle.vsplit(x, [2, 5])
    assert [tuple(t.shape) for t in parts] == [(2, 4), (3, 4), (5, 4)]
    np.testing.assert_array_equal(np.asarray(parts[1].numpy()),
                                  np.arange(40).reshape(10, 4)[2:5])
    halves = paddle.vsplit(x, 2)
    assert [tuple(t.shape) for t in halves] == [(5, 4), (5, 4)]


@pytest.mark.needs_reference
def test_distributed_namespace_parity():
    import paddle_tpu.distributed as dist

    src = open("/root/reference/python/paddle/distributed/__init__.py").read()
    block = re.search(r"__all__ = \[(.*?)\]", src, re.S).group(1)
    names = re.findall(r'["\']([^"\']+)["\']', block)
    assert len(names) > 30
    missing = [n for n in names if not hasattr(dist, n)]
    assert missing == [], missing


@pytest.mark.needs_reference
def test_tensor_method_parity():
    from paddle_tpu.tensor import Tensor

    src = open("/root/reference/python/paddle/tensor/__init__.py").read()
    block = re.search(r"tensor_method_func = \[(.*?)\]", src, re.S).group(1)
    meths = re.findall(r"'([^']+)'", block)
    assert len(meths) > 200
    missing = [n for n in meths if not hasattr(Tensor, n)]
    assert missing == [], missing


def test_inplace_method_variants():
    x = paddle.to_tensor(np.array([4.0], np.float32))
    x.sqrt_()
    np.testing.assert_allclose(np.asarray(x.numpy()), [2.0])
    x.exp_()
    np.testing.assert_allclose(np.asarray(x.numpy()), [np.exp(2.0)],
                               rtol=1e-6)
    y = paddle.to_tensor(np.array([1.5, -0.5], np.float32))
    y.clip_(0.0, 1.0)
    np.testing.assert_array_equal(np.asarray(y.numpy()), [1.0, 0.0])
    z = paddle.to_tensor(np.array([[1.0, 2.0]], np.float32))
    z.flatten_()
    assert tuple(z.shape) == (2,)
    w = paddle.to_tensor(np.array([7.0], np.float32))
    w.subtract_(paddle.to_tensor(np.array([2.0], np.float32)))
    np.testing.assert_array_equal(np.asarray(w.numpy()), [5.0])


def test_distributed_misc_functions():
    import paddle_tpu.distributed as dist

    assert dist.is_available() is True
    assert dist.get_backend().startswith("xla:")
    assert dist.ParallelMode.DATA_PARALLEL == 0
    with pytest.raises(ValueError):
        dist.ProbabilityEntry(1.5)
    e = dist.CountFilterEntry(3)
    assert "count_filter" in e._to_attr()
    objs = [None]
    dist.broadcast_object_list(objs)  # single-process: no-op
    out = []
    dist.scatter_object_list(out, [["a"], ["b"]])
    assert out == [["a"]]


def test_queue_and_inmemory_dataset(tmp_path):
    import paddle_tpu.distributed as dist

    f = tmp_path / "data.txt"
    f.write_text("1,2\n3,4\n5,6\n")
    ds = dist.InMemoryDataset()
    ds.set_filelist([str(f)])
    ds.set_parse_fn(lambda line: [int(v) for v in line.split(",")])
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 3
    ds.local_shuffle()
    assert sorted(list(ds)) == [[1, 2], [3, 4], [5, 6]]
    ds.release_memory()
    assert ds.get_memory_data_size() == 0
