"""chip_smoke.py's phase functions at a tiny size on the CPU (Pallas kernels
in interpret mode), and the refusals of everything that runs on the chip:
``chip_smoke.main()`` and ``bench.py`` exit non-zero on a platform that is
not ``tpu``, and on a TPU with the interpreter switched on.
"""
import importlib.util
import os
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _load(name, path):
    """Load a root/tools script by path. The scripts put the repo root and
    tools/ on sys.path themselves; that is undone here (module-level
    inserts leak into every later test)."""
    before = list(sys.path)
    try:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path[:] = before


@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke_under_test", os.path.join(REPO, "chip_smoke.py"))


@pytest.fixture()
def timing(monkeypatch):
    """tools/_bench_timing.py, importable by name for the scripts' own
    ``from _bench_timing import ...`` while the test runs."""
    monkeypatch.syspath_prepend(TOOLS)
    monkeypatch.delitem(sys.modules, "_bench_timing", raising=False)
    import _bench_timing

    yield _bench_timing
    sys.modules.pop("_bench_timing", None)


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _tiny_cfg():
    from paddle_tpu.models.gpt import gpt_tiny

    return gpt_tiny(vocab_size=512, hidden_size=256, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    fused_loss=True)


# ------------------------------------------------------- phases, tiny


def test_train_then_serve_phases_tiny(smoke, interpret, monkeypatch):
    """The one-chip path of main(): train, then serve the same weights. On
    the CPU the lowered text cannot show a TPU custom call, so the proof
    that the Pallas kernel served every step is that the gather fallback
    is gone."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    def gone(*a, **k):
        raise AssertionError("the engine fell back to ref_paged_attention")

    monkeypatch.setattr(pa, "ref_paged_attention", gone)
    dev = jax.devices()[0]
    model = smoke.train_phase(_tiny_cfg(), batch=2, seq=64, steps=4, dev=dev)
    smoke.serve_phase(model, kv_pool_bytes=8 << 20, page_size=16,
                      max_model_len=256, max_batch_slots=2, token_budget=32,
                      prompt_lens=(8, 40, 100, 17), new_tokens=(6, 8, 5, 7),
                      dev=dev)


def test_paged_kernel_phase_tiny(smoke, interpret):
    smoke.paged_kernel_phase(4, 2, 128, page_size=16, pages_per_seq=8,
                             dev=jax.devices()[0])


def test_paged_kernel_phase_catches_a_wrong_kernel(smoke, interpret,
                                                   monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention as pa

    real = pa.ref_paged_attention
    monkeypatch.setattr(pa, "ref_paged_attention",
                        lambda *a, **k: real(*a, **k) * 1.1)
    with pytest.raises(AssertionError, match="differs from ref_paged"):
        smoke.paged_kernel_phase(4, 2, 128, page_size=16, pages_per_seq=8,
                                 dev=jax.devices()[0])


def test_gated_delta_phase_tiny(smoke, interpret):
    """4 heads of d_v 32: a 32-row block, so a 70-row chunk advances in
    blocks beside the decode rows and the padding."""
    smoke.gated_delta_phase(4, 16, 32, slots=6, chunk=70,
                            dev=jax.devices()[0])


def test_gated_delta_phase_catches_a_wrong_kernel(smoke, interpret,
                                                  monkeypatch):
    from paddle_tpu.ops.pallas import gated_delta as gd

    real = gd.gated_delta_ref
    monkeypatch.setattr(gd, "gated_delta_ref",
                        lambda *a, **k: tuple(x * 1.001 for x in
                                              real(*a, **k)))
    with pytest.raises(AssertionError, match="differs from gated_delta_ref"):
        smoke.gated_delta_phase(4, 16, 32, slots=6, chunk=70,
                                dev=jax.devices()[0])


def test_sharded_train_phase_tiny(smoke):
    """The --chips 4 path on the virtual CPU devices (dp4 x mp2 uses all
    eight): losses track the one-device run, parameters are spread."""
    from paddle_tpu.models.gpt import gpt_tiny

    cfg = gpt_tiny(vocab_size=512, hidden_size=256, num_layers=2,
                   num_heads=4, max_position_embeddings=256, fused_loss=True)
    n = len(jax.devices())
    smoke.sharded_train_phase(cfg, batch=n // 2 * 2, seq=64, steps=4,
                              dp=n // 2, mp=2)


def test_dense_agreement_rejects_a_wrong_token(smoke):
    """The serve phase's judge: a stream that is not the dense argmax (to
    tolerance) fails."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.serving.scheduler import RequestOutput

    paddle.seed(0)
    model = GPTForCausalLM(_tiny_cfg())
    model.eval()
    prompt = np.arange(1, 9)
    good = np.asarray(model.generate(paddle.to_tensor(prompt[None, :]),
                                     max_new_tokens=4, temperature=0.0)
                      .numpy())[0, prompt.size:]
    ok = RequestOutput("a", prompt, [int(t) for t in good], "length")
    n, exact, worst = smoke._dense_agreement(model, [ok], smoke.DENSE_RTOL)
    assert (n, exact) == (4, 4) and worst == 0.0
    bad = RequestOutput("b", prompt, [int(good[0]), (int(good[1]) + 7) % 512,
                                      int(good[2]), int(good[3])], "length")
    with pytest.raises(AssertionError, match="below the dense argmax"):
        smoke._dense_agreement(model, [bad], smoke.DENSE_RTOL)


# ------------------------------------------------------------ refusals


def test_chip_smoke_main_exits_nonzero_off_tpu(smoke, timing, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""  # no result line, nothing at all


def test_bench_exits_nonzero_off_tpu(timing, monkeypatch, capsys):
    bench = _load("bench_under_test", os.path.join(REPO, "bench.py"))
    monkeypatch.delenv("BENCH_SMALL", raising=False)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--model", "gpt13"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""  # no result line


def _fake_tpu(monkeypatch):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    return dev


def test_require_tpu_accepts_a_tpu(timing, monkeypatch):
    dev = _fake_tpu(monkeypatch)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert timing.require_tpu() is dev


def test_require_tpu_refuses_the_interpreter_on_a_tpu(timing, monkeypatch,
                                                      interpret):
    _fake_tpu(monkeypatch)
    with pytest.raises(SystemExit) as e:
        timing.require_tpu()
    assert e.value.code == 2


# ------------------------------------------------------- compile cache


@pytest.fixture()
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_set_means_nothing_set_in_code(
        timing, monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert timing.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_env_unset_means_fixed_path_in_checkout(
        timing, monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert timing.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert timing.enable_compile_cache() == want  # never a moving name


# ------------------------------------------------------------ MFU peaks


def test_mfu_peak_table_is_keyed_by_device_kind(timing):
    bench = _load("bench_under_test2", os.path.join(REPO, "bench.py"))
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert bench._mfu(98.5, v5e) == 0.5
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    assert bench._mfu(1.0, cpu) is None  # a CPU number is no device metric
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    with pytest.raises(KeyError, match="TPU v9"):
        bench._mfu(1.0, unknown)
