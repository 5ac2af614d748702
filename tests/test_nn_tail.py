"""nn/nn.functional API tail + subnamespace parity gates.

The gates mirror test_api_tail's top-level gate: every name in the
reference's nn/functional/metric/io/vision __all__ must resolve here.
"""
import re

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn

t = paddle.to_tensor


def _ref_all(path):
    src = open(path).read()
    block = re.search(r"__all__ = \[(.*?)\]", src, re.S).group(1)
    return re.findall(r"'([^']+)'", block)


@pytest.mark.needs_reference
@pytest.mark.parametrize("ref_path,mod", [
    ("/root/reference/python/paddle/nn/__init__.py", nn),
    ("/root/reference/python/paddle/nn/functional/__init__.py", F),
    ("/root/reference/python/paddle/optimizer/__init__.py", paddle.optimizer),
    ("/root/reference/python/paddle/metric/__init__.py", paddle.metric),
    ("/root/reference/python/paddle/io/__init__.py", paddle.io),
    ("/root/reference/python/paddle/vision/__init__.py", paddle.vision),
], ids=["nn", "functional", "optimizer", "metric", "io", "vision"])
def test_subnamespace_parity(ref_path, mod):
    missing = [n for n in _ref_all(ref_path) if not hasattr(mod, n)]
    assert missing == [], f"missing from {mod.__name__}: {missing}"


# ---------------------------------------------------------- functional


def test_pairwise_distance_and_elu_inplace():
    d = F.pairwise_distance(t(np.array([[0.0, 3.0]], np.float32)),
                            t(np.array([[4.0, 0.0]], np.float32)))
    np.testing.assert_allclose(float(np.asarray(d.numpy())[0]), 5.0,
                               rtol=1e-5)
    x = t(np.array([-1.0, 1.0], np.float32))
    y = F.elu_(x)
    assert y is x
    np.testing.assert_allclose(np.asarray(x.numpy()),
                               [np.exp(-1) - 1, 1.0], rtol=1e-5)


def test_diag_embed_and_sequence_mask():
    de = F.diag_embed(t(np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)))
    assert tuple(de.shape) == (2, 2, 2)
    np.testing.assert_array_equal(np.asarray(de.numpy())[0],
                                  [[1, 0], [0, 2]])
    off = F.diag_embed(t(np.array([1.0, 2.0], np.float32)), offset=1)
    assert tuple(off.shape) == (3, 3)
    assert np.asarray(off.numpy())[0, 1] == 1.0

    m = F.sequence_mask(t(np.array([2, 4], np.int64)), maxlen=5)
    np.testing.assert_array_equal(np.asarray(m.numpy()),
                                  [[1, 1, 0, 0, 0], [1, 1, 1, 1, 0]])
    m2 = F.sequence_mask(t(np.array([1, 3], np.int64)))  # maxlen inferred
    assert tuple(m2.shape) == (2, 3)


def test_grid_sample_identity_and_shift():
    img = t(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    theta = t(np.array([[[1.0, 0, 0], [0, 1.0, 0]]], np.float32))
    grid = F.affine_grid(theta, [1, 1, 4, 4])
    out = F.grid_sample(img, grid)
    np.testing.assert_allclose(np.asarray(out.numpy()),
                               np.asarray(img.numpy()), atol=1e-4)
    # half-pixel x-shift: interior becomes the average of neighbors
    theta2 = t(np.array([[[1.0, 0, 2.0 / 3.0], [0, 1.0, 0]]], np.float32))
    grid2 = F.affine_grid(theta2, [1, 1, 4, 4])
    out2 = np.asarray(F.grid_sample(img, grid2).numpy())
    np.testing.assert_allclose(out2[0, 0, 0, 0], 1.0, atol=1e-4)
    # zeros padding beyond the right edge
    assert out2[0, 0, 0, -1] < np.asarray(img.numpy())[0, 0, 0, -1]


def test_temporal_shift_moves_channels():
    N, T, C = 1, 3, 4
    x = np.zeros((N * T, C, 1, 1), np.float32)
    for ti in range(T):
        x[ti, :, 0, 0] = ti + 1
    out = np.asarray(F.temporal_shift(t(x), seg_num=T,
                                      shift_ratio=0.25).numpy())
    # channel 0 shifted backward (takes value from t+1); last t zero
    np.testing.assert_array_equal(out[:, 0, 0, 0], [2, 3, 0])
    # channel 1 shifted forward; first t zero
    np.testing.assert_array_equal(out[:, 1, 0, 0], [0, 1, 2])
    # remaining channels unshifted
    np.testing.assert_array_equal(out[:, 2, 0, 0], [1, 2, 3])


def test_rnnt_loss_matches_numpy_dp():
    rng = np.random.default_rng(0)
    B, T, U, V = 2, 4, 3, 5
    logits = rng.standard_normal((B, T, U + 1, V)).astype(np.float32)
    labels = rng.integers(1, V, (B, U))
    tl = np.full((B,), T, np.int64)
    ul = np.full((B,), U, np.int64)

    def ref_one(a, lab):
        lp = a - np.log(np.exp(a).sum(-1, keepdims=True))
        alpha = np.full((T, U + 1), -np.inf)
        alpha[0, 0] = 0.0
        for ti in range(T):
            for u in range(U + 1):
                if ti == 0 and u == 0:
                    continue
                c = []
                if ti > 0:
                    c.append(alpha[ti - 1, u] + lp[ti - 1, u, 0])
                if u > 0:
                    c.append(alpha[ti, u - 1] + lp[ti, u - 1, lab[u - 1]])
                alpha[ti, u] = np.logaddexp.reduce(c)
        return -(alpha[T - 1, U] + lp[T - 1, U, 0])

    want = np.mean([ref_one(logits[b], labels[b]) for b in range(B)])
    got = float(F.rnnt_loss(t(logits), t(labels), t(tl), t(ul)).numpy())
    np.testing.assert_allclose(got, want, rtol=1e-4)

    # grads flow (transducer training path)
    lg = t(logits)
    lg.stop_gradient = False
    loss = F.rnnt_loss(lg, t(labels), t(tl), t(ul))
    loss.backward()
    assert lg.grad is not None
    assert np.isfinite(np.asarray(lg.grad.numpy())).all()


def test_sparse_attention_matches_dense_on_full_pattern():
    rng = np.random.default_rng(1)
    B, H, S, D = 1, 2, 4, 8
    q = rng.standard_normal((B, H, S, D)).astype(np.float32)
    k = rng.standard_normal((B, H, S, D)).astype(np.float32)
    v = rng.standard_normal((B, H, S, D)).astype(np.float32)
    # full (dense) CSR pattern
    offset = np.tile(np.arange(0, S * S + 1, S), (B, H, 1)).astype(np.int32)
    cols = np.tile(np.tile(np.arange(S), S), (B, H, 1)).astype(np.int32)
    out = np.asarray(F.sparse_attention(t(q), t(k), t(v), t(offset),
                                        t(cols)).numpy())
    scores = q @ k.transpose(0, 1, 3, 2) / np.sqrt(D)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(out, p @ v, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------- layers


def test_softmax2d_sums_channels():
    x = t(np.random.default_rng(2).standard_normal((2, 3, 4, 4)
                                                   ).astype(np.float32))
    out = np.asarray(nn.Softmax2D()(x).numpy())
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-5)
    with pytest.raises(ValueError):
        nn.Softmax2D()(t(np.zeros((2, 3), np.float32)))


def test_hsigmoid_layer_trains():
    paddle.seed(0)
    layer = nn.HSigmoidLoss(feature_size=8, num_classes=6)
    opt = paddle.optimizer.SGD(learning_rate=0.5,
                               parameters=layer.parameters())
    rng = np.random.default_rng(3)
    x = t(rng.standard_normal((16, 8)).astype(np.float32))
    y = t(rng.integers(0, 6, (16, 1)))
    losses = []
    for _ in range(20):
        loss = layer(x, y).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0] * 0.7, losses[::5]


def test_multi_margin_and_rnnt_layers():
    mm = nn.MultiMarginLoss()
    loss = mm(t(np.array([[0.1, 0.9], [0.8, 0.2]], np.float32)),
              t(np.array([1, 0])))
    assert float(loss.numpy()) >= 0
    rl = nn.RNNTLoss()
    logits = np.random.default_rng(4).standard_normal(
        (1, 3, 2, 4)).astype(np.float32)
    out = rl(t(logits), t(np.array([[1]], np.int64)),
             t(np.array([3], np.int64)), t(np.array([1], np.int64)))
    assert np.isfinite(float(out.numpy()))


def test_beam_search_decode_greedy_consistency():
    # deterministic cell: next-token logits depend only on current token,
    # transition i -> i+1 strongly preferred; 0 is start, 4 is end
    V = 6

    def cell(inputs, states):
        import jax.numpy as jnp

        tok = np.asarray(inputs.numpy()).astype(np.int64)
        logits = np.full((tok.shape[0], V), -5.0, np.float32)
        for r, tk in enumerate(tok):
            logits[r, min(tk + 1, V - 1)] = 5.0
        return paddle.to_tensor(logits), states

    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=4,
                               beam_size=2)
    ids, probs = nn.dynamic_decode(dec, inits={"h": np.zeros((1, 1))},
                                   max_step_num=10, batch_size=1)
    best = np.asarray(ids.numpy())[0, 0]
    end = np.nonzero(best == 4)[0][0]
    np.testing.assert_array_equal(best[:end + 1], [1, 2, 3, 4])  # the chain
    assert np.all(best[end:] == 4)  # finished beams pad with end_token
    assert tuple(np.asarray(probs.numpy()).shape) == (1, 2)


def test_metric_accuracy_function():
    logits = t(np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]], np.float32))
    labels = t(np.array([[1], [0], [0]]))
    acc = paddle.metric.accuracy(logits, labels, k=1)
    np.testing.assert_allclose(float(acc.numpy()), 2.0 / 3.0, rtol=1e-6)
    acc2 = paddle.metric.accuracy(logits, labels, k=2)
    np.testing.assert_allclose(float(acc2.numpy()), 1.0, rtol=1e-6)


def test_io_get_worker_info_main_process():
    assert paddle.io.get_worker_info() is None
    info = paddle.io.WorkerInfo(1, 4)
    assert "id=1" in repr(info)


def test_vision_image_backend():
    assert paddle.vision.get_image_backend() == "pil"
    paddle.vision.set_image_backend("cv2")
    assert paddle.vision.get_image_backend() == "cv2"
    paddle.vision.set_image_backend("pil")
    with pytest.raises(ValueError):
        paddle.vision.set_image_backend("magick")
    import tempfile, os

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "a.npy")
        np.save(path, np.ones((2, 2)))
        arr = paddle.vision.image_load(path)
        np.testing.assert_array_equal(arr, np.ones((2, 2)))


def _record_worker_id(sample):
    return sample


class _IdDataset:
    def __len__(self):
        return 8

    def __getitem__(self, i):
        import paddle_tpu

        info = paddle_tpu.io.get_worker_info()
        assert info is not None
        return np.array([info.id], np.int64)


def test_worker_ids_reset_per_epoch():
    from paddle_tpu.io import DataLoader

    loader = DataLoader(_IdDataset(), batch_size=4, num_workers=2,
                        worker_mode="process", use_shared_memory=False)
    for _ in range(2):  # second epoch spawns a FRESH pool
        ids = np.concatenate([np.asarray(b.numpy()).ravel()
                              for b in loader])
        assert set(ids) <= {0, 1}, ids  # never 2/3 from the global counter


def test_llama_sequence_parallel_smoke():
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 8}
    fleet.fleet._is_initialized = False
    fleet.init(is_collective=True, strategy=strategy)
    try:
        paddle.seed(9)
        model = LlamaForCausalLM(llama_tiny(sequence_parallel=True,
                                            max_position_embeddings=64))
        ids = t(np.random.default_rng(9).integers(0, 512, (2, 64)))
        labels = t(np.roll(np.asarray(ids.numpy()), -1, 1))
        _, loss = model(ids, labels=labels)
        dist.set_mesh(None)
        fleet.fleet._is_initialized = False
        paddle.seed(9)
        dense = LlamaForCausalLM(llama_tiny(max_position_embeddings=64))
        _, dense_loss = dense(ids, labels=labels)
        np.testing.assert_allclose(float(loss.numpy()),
                                   float(dense_loss.numpy()), rtol=2e-4)
    finally:
        dist.set_mesh(None)
        fleet.fleet._is_initialized = False


def test_rnnt_fastemit_refuses_loudly():
    with pytest.raises(NotImplementedError, match="fastemit"):
        F.rnnt_loss(t(np.zeros((1, 2, 2, 3), np.float32)),
                    t(np.array([[1]], np.int64)),
                    t(np.array([2])), t(np.array([1])),
                    fastemit_lambda=0.1)


def test_buffered_reader_propagates_errors():
    from paddle_tpu import reader

    def bad():
        yield 1
        raise IOError("disk gone")

    with pytest.raises(RuntimeError, match="disk gone"):
        list(reader.buffered(bad, 4)())
