"""Final namespace tail: vision ops/transforms/datasets, audio backends,
geometric samplers, device streams, saved_tensors_hooks — plus the
all-namespace parity gate."""
import os
import re
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle

t = paddle.to_tensor
R = "/root/reference/python/paddle"


def _ref_all(path):
    src = open(path).read()
    m = re.search(r"__all__ = \[(.*?)\]", src, re.S)
    return re.findall(r'["\']([^"\']+)["\']', m.group(1)) if m else []


@pytest.mark.needs_reference
@pytest.mark.parametrize("ref,mod_path", [
    (f"{R}/vision/transforms/__init__.py", "vision.transforms"),
    (f"{R}/vision/datasets/__init__.py", "vision.datasets"),
    (f"{R}/vision/models/__init__.py", "vision.models"),
    (f"{R}/vision/ops.py", "vision.ops"),
    (f"{R}/audio/__init__.py", "audio"),
    (f"{R}/text/__init__.py", "text"),
    (f"{R}/geometric/__init__.py", "geometric"),
    (f"{R}/profiler/__init__.py", "profiler"),
    (f"{R}/quantization/__init__.py", "quantization"),
    (f"{R}/autograd/__init__.py", "autograd"),
    (f"{R}/device/__init__.py", "device"),
    (f"{R}/distribution/__init__.py", "distribution"),
    (f"{R}/sparse/__init__.py", "sparse"),
    # r5 session 3: this namespace was the one facade the gate missed —
    # VisualDL/WandbCallback/ReduceLROnPlateau were absent until added
    (f"{R}/callbacks.py", "callbacks"),
])
def test_namespace_parity(ref, mod_path):
    mod = paddle
    for part in mod_path.split("."):
        mod = getattr(mod, part)
    missing = [n for n in _ref_all(ref) if not hasattr(mod, n)]
    assert missing == [], f"{mod_path} missing {missing}"


# ------------------------------------------------------------- vision ops


def test_prior_box_shapes_and_range():
    feat = t(np.zeros((1, 8, 4, 4), np.float32))
    img = t(np.zeros((1, 3, 32, 32), np.float32))
    boxes, var = paddle.vision.ops.prior_box(
        feat, img, min_sizes=[8.0], aspect_ratios=[1.0, 2.0], clip=True)
    assert tuple(boxes.shape)[:2] == (4, 4)
    b = np.asarray(boxes.numpy())
    assert b.min() >= 0.0 and b.max() <= 1.0
    assert tuple(var.shape) == tuple(boxes.shape)


def test_matrix_nms_suppresses_overlaps():
    bboxes = t(np.array([[[0, 0, 10, 10], [0.5, 0.5, 10.5, 10.5],
                          [20, 20, 30, 30]]], np.float32))
    scores = t(np.array([[[0.9, 0.85, 0.8]]], np.float32))
    out, idx, num = paddle.vision.ops.matrix_nms(
        bboxes, scores, score_threshold=0.1, post_threshold=0.5,
        nms_top_k=10, keep_top_k=10, background_label=-1,
        return_index=True)
    o = np.asarray(out.numpy())
    # best box and the far box survive; the heavy overlap decays below 0.5
    assert int(np.asarray(num.numpy())[0]) == 2
    assert {0.9, 0.8} <= set(np.round(o[:, 1], 4)) or o[:, 1].max() <= 0.9


def test_psroi_pool_shapes():
    C = 2 * 2 * 3  # out_c=3 for 2x2 bins
    x = t(np.random.default_rng(0).standard_normal((1, C, 8, 8)
                                                   ).astype(np.float32))
    boxes = t(np.array([[0, 0, 8, 8]], np.float32))
    out = paddle.vision.ops.psroi_pool(x, boxes, t(np.array([1])), 2)
    assert tuple(out.shape) == (1, 3, 2, 2)
    layer = paddle.vision.ops.PSRoIPool(2)
    out2 = layer(x, boxes, t(np.array([1])))
    np.testing.assert_allclose(np.asarray(out.numpy()),
                               np.asarray(out2.numpy()))


def test_distribute_fpn_proposals_partitions():
    rois = np.array([[0, 0, 10, 10],      # small → low level
                     [0, 0, 200, 200]], np.float32)  # big → high level
    multi, restore = paddle.vision.ops.distribute_fpn_proposals(
        t(rois), min_level=2, max_level=5, refer_level=4, refer_scale=224)
    sizes = [int(np.asarray(m.numpy()).shape[0]) for m in multi]
    assert sum(sizes) == 2 and len(multi) == 4
    ri = np.asarray(restore.numpy()).ravel()
    assert sorted(ri.tolist()) == [0, 1]


def test_generate_proposals_runs():
    rng = np.random.default_rng(1)
    H = W = 4
    A = 3
    scores = t(rng.uniform(0, 1, (1, A, H, W)).astype(np.float32))
    deltas = t(rng.standard_normal((1, 4 * A, H, W)).astype(np.float32) * 0.1)
    img_size = t(np.array([[32.0, 32.0]], np.float32))
    anchors = t(np.tile(np.array([[0, 0, 8, 8], [0, 0, 16, 16],
                                  [4, 4, 12, 12]], np.float32),
                        (1, 1)))
    var = t(np.full((A, 4), 0.1, np.float32))
    rois, rscores, num = paddle.vision.ops.generate_proposals(
        scores, deltas, img_size, anchors, var, pre_nms_top_n=20,
        post_nms_top_n=5, return_rois_num=True)
    n = int(np.asarray(num.numpy())[0])
    assert 0 < n <= 5
    r = np.asarray(rois.numpy())
    assert r.shape == (n, 4)
    assert (r[:, 2] >= r[:, 0]).all() and (r[:, 3] >= r[:, 1]).all()


def test_yolo_loss_finite_and_grads():
    rng = np.random.default_rng(2)
    na, cls, H = 3, 4, 4
    x = t(rng.standard_normal((2, na * (5 + cls), H, H)).astype(np.float32))
    x.stop_gradient = False
    gt = np.zeros((2, 5, 4), np.float32)
    gt[:, 0] = [0.5, 0.5, 0.3, 0.4]
    labels = np.zeros((2, 5), np.int64)
    loss = paddle.vision.ops.yolo_loss(
        x, t(gt), t(labels), anchors=[10, 13, 16, 30, 33, 23],
        anchor_mask=[0, 1, 2], class_num=cls, ignore_thresh=0.7,
        downsample_ratio=8)
    lv = np.asarray(loss.numpy())
    assert lv.shape == (2,) and np.isfinite(lv).all() and (lv > 0).all()
    loss.sum().backward()
    assert x.grad is not None
    assert np.isfinite(np.asarray(x.grad.numpy())).all()


def test_read_file_round_trip(tmp_path):
    pth = tmp_path / "blob.bin"
    pth.write_bytes(bytes(range(10)))
    data = paddle.vision.ops.read_file(str(pth))
    np.testing.assert_array_equal(np.asarray(data.numpy()),
                                  np.arange(10, dtype=np.uint8))


# ------------------------------------------------------------- audio


def test_audio_wav_round_trip(tmp_path):
    sig = np.sin(np.linspace(0, 50, 4000)).astype(np.float32)[None]
    path = str(tmp_path / "tone.wav")
    paddle.audio.save(path, t(sig), 8000)
    meta = paddle.audio.info(path)
    assert (meta.sample_rate, meta.num_channels,
            meta.bits_per_sample) == (8000, 1, 16)
    loaded, sr = paddle.audio.load(path)
    assert sr == 8000
    np.testing.assert_allclose(np.asarray(loaded.numpy()), sig, atol=1e-3)
    part, _ = paddle.audio.load(path, frame_offset=100, num_frames=50)
    assert tuple(part.shape) == (1, 50)
    assert paddle.audio.backends.get_current_backend() == "wave"
    with pytest.raises(RuntimeError):
        paddle.audio.datasets.ESC50()


# ------------------------------------------------------------- geometric


def test_weighted_sample_neighbors_prefers_heavy_edges():
    # node 1 has neighbors {0 (w=100), 2 (w=0.001)}
    row = t(np.array([0, 2], np.int64))
    colptr = t(np.array([0, 0, 2, 2], np.int64))
    w = t(np.array([100.0, 0.001]))
    hits = 0
    for _ in range(10):
        nb, cnt = paddle.geometric.weighted_sample_neighbors(
            row, colptr, w, t(np.array([1], np.int64)), sample_size=1)
        hits += int(np.asarray(nb.numpy())[0] == 0)
    assert hits >= 8  # overwhelmingly the heavy edge


def test_reindex_heter_graph():
    src, dst, nodes = paddle.geometric.reindex_heter_graph(
        t(np.array([5, 9], np.int64)),
        [t(np.array([7, 5], np.int64)), t(np.array([9, 11], np.int64))],
        [t(np.array([1, 1], np.int64)), t(np.array([2, 0], np.int64))])
    assert np.asarray(nodes.numpy()).tolist() == [5, 9, 7, 11]
    assert np.asarray(src[0].numpy()).tolist() == [2, 0]
    assert np.asarray(dst[1].numpy()).tolist() == [0, 0]


# ------------------------------------------------------------- transforms


def test_affine_perspective_erase_functional():
    T = paddle.vision.transforms
    img = np.arange(64, dtype=np.uint8).reshape(8, 8, 1)
    np.testing.assert_array_equal(
        T.affine(img, 0.0, (0, 0), 1.0, (0.0, 0.0)), img)
    pts = [(0, 0), (7, 0), (7, 7), (0, 7)]
    np.testing.assert_array_equal(T.perspective(img, pts, pts), img)
    shifted = T.affine(img, 0.0, (2, 0), 1.0, (0.0, 0.0))
    np.testing.assert_array_equal(shifted[:, 2:, 0], img[:, :-2, 0])
    er = T.erase(img.copy(), 1, 1, 3, 3, 0)
    assert er[1:4, 1:4].sum() == 0
    te = T.erase(t(np.ones((1, 4, 4), np.float32)), 0, 0, 2, 2, 0.0)
    assert float(np.asarray(te.numpy()).sum()) == 12.0
    for cls in (T.RandomAffine(15, translate=(0.2, 0.2)),
                T.RandomPerspective(prob=1.0), T.RandomErasing(prob=1.0)):
        assert cls(img).shape == img.shape


# ------------------------------------------------------------ datasets


def test_dataset_folder_and_image_folder(tmp_path):
    for cls_name, fill in (("a", 1), ("b", 2)):
        os.makedirs(tmp_path / cls_name)
        for i in range(3):
            np.save(str(tmp_path / cls_name / f"{i}.npy"),
                    np.full((2, 2), fill, np.float32))
    ds = paddle.vision.datasets.DatasetFolder(str(tmp_path))
    assert len(ds) == 6 and ds.classes == ["a", "b"]
    sample, label = ds[5]
    assert label == 1 and sample[0, 0] == 2.0
    flat = paddle.vision.datasets.ImageFolder(str(tmp_path))
    assert len(flat) == 6 and flat[0][0].shape == (2, 2)
    with pytest.raises(RuntimeError, match="zero-egress"):
        paddle.vision.datasets.Flowers()


def test_shufflenet_swish_forward():
    paddle.seed(0)
    net = paddle.vision.models.shufflenet_v2_swish(num_classes=10)
    x = t(np.random.default_rng(0).standard_normal((1, 3, 32, 32)
                                                   ).astype(np.float32))
    out = net(x)
    assert tuple(out.shape) == (1, 10)


# ------------------------------------------------------------- device/hooks


def test_device_stream_event_api():
    d = paddle.device
    s1, s2 = d.Stream(), d.Stream()
    with d.stream_guard(s2):
        assert d.current_stream() is s2
    assert d.current_stream() is s1 or d.current_stream() is not s2
    e = d.Event()
    e.record()
    assert e.query() is True
    assert d.get_cudnn_version() is None
    assert not d.is_compiled_with_rocm()
    assert "cpu" in d.get_all_device_type()
    with pytest.raises(RuntimeError):
        d.IPUPlace()


def test_saved_tensors_hooks_pack_unpack():
    events = []

    def pack(v):
        events.append("pack")
        return np.asarray(v)

    def unpack(p):
        events.append("unpack")
        import jax.numpy as jnp

        return jnp.asarray(p)

    x = t(np.array([3.0], np.float32))
    x.stop_gradient = False
    with paddle.autograd.saved_tensors_hooks(pack, unpack):
        y = (x * x).sum()
    y.backward()
    np.testing.assert_allclose(np.asarray(x.grad.numpy()), [6.0])
    assert "pack" in events and "unpack" in events
    # outside the context, hooks do not fire
    events.clear()
    z = (x * x).sum()
    z.backward()
    assert events == []


def test_gloo_trio_two_process(tmp_path):
    import socket
    import subprocess
    import sys
    import textwrap

    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    worker = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
        import os
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import paddle_tpu.distributed as dist
        rank = int(sys.argv[1])
        dist.gloo_init_parallel_env(rank, 2, "127.0.0.1:{port}")
        for _ in range(2):
            dist.gloo_barrier()
        dist.gloo_release()
        print(f"GLOO{{rank}}OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", worker, str(r)],
                              stdout=subprocess.PIPE, text=True, env=env)
             for r in range(2)]
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=120)
        assert p.returncode == 0 and f"GLOO{r}OK" in out, out


def test_matrix_nms_compensation():
    # C's only suppressor B is itself crushed by A, so C must survive
    # (the Matrix-NMS compensation term — a plain soft-NMS would kill C)
    A = [0, 0, 10, 10]
    B = [1, 0, 11, 10]
    C = [9.2, 0, 19.2, 10]
    bb = t(np.array([[A, B, C]], np.float32))
    sc = t(np.array([[[0.9, 0.85, 0.8]]], np.float32))
    out, num = paddle.vision.ops.matrix_nms(
        bb, sc, score_threshold=0.1, post_threshold=0.3, nms_top_k=10,
        keep_top_k=10, background_label=-1)
    kept = np.round(np.asarray(out.numpy())[:, 1], 3)
    assert 0.9 in kept          # A untouched
    assert kept.min() > 0.5     # C compensated, not crushed to ~0.12


def test_distribute_fpn_proposals_per_image_counts():
    rois = np.array([[0, 0, 10, 10], [0, 0, 200, 200],   # image 0
                     [0, 0, 12, 12]], np.float32)         # image 1
    multi, restore, nums = paddle.vision.ops.distribute_fpn_proposals(
        t(rois), min_level=2, max_level=5, refer_level=4, refer_scale=224,
        rois_num=t(np.array([2, 1], np.int64)))
    # every level reports a per-image vector of length 2
    for n in nums:
        assert tuple(n.shape) == (2,)
    total = np.stack([np.asarray(n.numpy()) for n in nums]).sum(axis=0)
    np.testing.assert_array_equal(total, [2, 1])


def test_prior_box_min_max_order():
    feat = t(np.zeros((1, 8, 1, 1), np.float32))
    img = t(np.zeros((1, 3, 32, 32), np.float32))
    kw = dict(min_sizes=[8.0], max_sizes=[16.0], aspect_ratios=[1.0, 2.0])
    b_false, _ = paddle.vision.ops.prior_box(feat, img, **kw)
    b_true, _ = paddle.vision.ops.prior_box(
        feat, img, min_max_aspect_ratios_order=True, **kw)
    bf = np.asarray(b_false.numpy())[0, 0]
    bt = np.asarray(b_true.numpy())[0, 0]
    assert bf.shape[0] == bt.shape[0] == 3  # min, ratio, max
    np.testing.assert_allclose(bf[0], bt[0])       # min box first in both
    np.testing.assert_allclose(bf[-1], bt[1])      # max box moves to slot 1
    np.testing.assert_allclose(bf[1], bt[-1])      # ratio box moves last
