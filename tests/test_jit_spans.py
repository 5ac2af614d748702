"""The spans where a compiled step is built and launched, and where set-up
creates parameters, on the one trace ring (``paddle_tpu.profiler.ring``):
a ``StaticFunction`` call is ``step.operands`` → ``step.launch`` →
``step.outputs``; a signature's first call nests ``step.build`` (counts:
``fresh``) tiled by ``step.trace`` / ``step.lower`` / ``step.compile``, or
``step.load`` where this package's persistent cache holds the program; the
serving engine's ``step.dispatch`` holds ``step.transfer`` and the call's
three; ``Layer.create_parameter`` records one ``param.init``. Tracing off
records none of them."""
from contextlib import contextmanager

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, nn
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.profiler import ring
from paddle_tpu.serving import ServingEngine, tracing

CALL = ["step.operands", "step.launch", "step.outputs"]
DISPATCH = ["step.transfer"] + CALL


@contextmanager
def _fresh(**kw):
    tracer = ring.RequestTracer(**kw)
    old = ring.set_tracer(tracer)
    try:
        yield tracer
    finally:
        ring.set_tracer(old)


@pytest.fixture(autouse=True)
def _isolated_cache_layers():
    jit.clear_compile_cache(memory=True)
    yield
    jit.clear_compile_cache(memory=True)


def _spans(tracer):
    return [e for e in tracer.events() if "span" in e]


def _children(spans, parent):
    return [e for e in spans if e["parent"] == parent["span"]]


def _tiles(parent, kids):
    """``kids`` lie in ``parent``'s interval, in ring order, disjoint."""
    prev = parent["t"] - parent["arg"]
    for k in kids:
        k0, k1 = k["t"] - k["arg"], k["t"]
        assert prev <= k0 + 1e-9 and k1 <= parent["t"] + 1e-9, k["name"]
        prev = k1


def _double_plus_one(x):
    return x * 2.0 + 1.0


def _sf(cache_dir=None):
    return jit.StaticFunction(_double_plus_one, warmup=False,
                              dy2static=False, cache_dir=cache_dir)


def _x(n=4):
    return paddle.to_tensor(np.arange(n, dtype=np.float32))


def test_the_ring_is_one_whichever_module_names_it():
    assert tracing.get_tracer is ring.get_tracer
    assert tracing.RequestTracer is ring.RequestTracer
    with _fresh(capacity=64) as tr:
        assert tracing.get_tracer() is tr


def test_a_call_outside_any_span_records_its_three_spans_at_the_top():
    f = _sf()
    f(_x())                                  # builds
    with _fresh(capacity=256) as tr:
        out = f(_x())
        np.testing.assert_allclose(out.numpy(), np.arange(4) * 2.0 + 1.0)
        spans = _spans(tr)
    assert [e["name"] for e in spans] == CALL
    assert all(e["parent"] == 0 and e["req_id"] == "_double_plus_one"
               for e in spans)
    assert tr._open is None


@pytest.mark.parametrize("cache", ["none", "disk"])
def test_a_first_call_builds_under_its_operands_and_a_second_does_not(
        tmp_path, cache):
    """The first call's ``step.operands`` holds ``step.build``, tiled by
    trace, lower and compile (``fresh`` 1); with a persistent cache dir the
    miss reads the disk first (``step.load``, then on). The second call of
    the signature builds nothing."""
    f = _sf(cache_dir=str(tmp_path) if cache == "disk" else None)
    with _fresh(capacity=256) as tr:
        f(_x())
        first = _spans(tr)
        f(_x())
        second = _spans(tr)[len(first):]
    ops = [e for e in first if e["name"] == "step.operands"]
    assert len(ops) == 1 and ops[0]["parent"] == 0
    build, = _children(first, ops[0])
    assert build["name"] == "step.build"
    assert build["counts"] == {"fresh": 1}
    parts = _children(first, build)
    want = ["step.trace", "step.lower", "step.compile"]
    if cache == "disk":
        want = ["step.load"] + want
    assert [e["name"] for e in parts] == want
    _tiles(build, parts)
    _tiles(ops[0], [build])
    assert [e["name"] for e in second] == CALL


def test_a_persistent_cache_hit_loads_and_a_memory_hit_is_the_build_alone(
        tmp_path):
    _sf(cache_dir=str(tmp_path))(_x())       # stores the executable
    with _fresh(capacity=256) as tr:
        _sf(cache_dir=str(tmp_path))(_x())   # memory: another's build
        memory = _spans(tr)
        jit.clear_compile_cache(memory=True)
        _sf(cache_dir=str(tmp_path))(_x())   # disk
        disk = _spans(tr)[len(memory):]
    for spans, parts in ((memory, []), (disk, ["step.load"])):
        build, = [e for e in spans if e["name"] == "step.build"]
        assert build["counts"] == {"fresh": 0}
        assert [e["name"] for e in _children(spans, build)] == parts


def test_param_init_once_per_parameter():
    with _fresh(capacity=256) as tr:
        layer = nn.Linear(4, 3)
        norm = nn.LayerNorm(3)
        spans = _spans(tr)
    params = layer.parameters() + norm.parameters()
    assert [e["name"] for e in spans] == ["param.init"] * len(params) == \
        ["param.init"] * 4
    assert [e["seq"] for e in spans] == [0, 1, 2, 3]
    assert all(e["req_id"] == "param" and e["parent"] == 0 and e["arg"] >= 0
               for e in spans)


def _tiny_model():
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=1,
        num_key_value_heads=1, max_position_embeddings=64))


def test_every_dispatch_is_tiled_by_transfer_operands_launch_outputs():
    with _fresh(capacity=8192) as tr:
        eng = ServingEngine(_tiny_model(), page_size=4, num_pages=64,
                            max_batch_slots=2, max_model_len=64,
                            token_budget=8)
        rng = np.random.RandomState(1)
        eng.add_request(rng.randint(1, 32, (5,)), max_new_tokens=5)
        eng.add_request(rng.randint(1, 32, (11,)), max_new_tokens=3)
        eng.run()
        spans = _spans(tr)
    dispatches = [e for e in spans if e["name"] == "step.dispatch"]
    assert len(dispatches) >= 5
    built = 0
    for d in dispatches:
        kids = _children(spans, d)
        assert [k["name"] for k in kids] == DISPATCH
        assert kids[0]["req_id"] == eng.engine_id
        assert {k["req_id"] for k in kids[1:]} == {"serving_step"}
        _tiles(d, kids)
        builds = _children(spans, kids[1])
        built += len(builds)
        assert all(b["name"] == "step.build" for b in builds)
    assert built == eng.compile_counts()["step"]   # one per bucket


def test_tracing_off_records_nothing(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    old = ring.set_tracer(None)              # built anew from the env
    try:
        tr = ring.get_tracer()
        assert not tr.enabled
        f = _sf()
        f(_x())
        f(_x())
        nn.Linear(2, 2)
        eng = ServingEngine(_tiny_model(), page_size=4, num_pages=32,
                            max_batch_slots=2, max_model_len=32)
        eng.add_request(np.arange(1, 6), max_new_tokens=3)
        eng.run()
        assert tr.events() == [] and tr._open is None
    finally:
        ring.set_tracer(old)
