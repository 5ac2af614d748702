"""The readers of the spans where the program builds and launches a compiled
step (``layer_metrics/program_parts.py``): each on a synthetic ring, ``None``
where the ring holds none of its spans (as the parent commit's does) and, for
set-up's, where the ring has wrapped; then against the real serve entry at a
tiny size."""
import time
import types

import pytest

from benchmarks.harness import program_spans as ps, runner, spec
from benchmarks.layer_metrics import program_parts as pp
from benchmarks.tests import tiny
from paddle_tpu.serving import tracing

DISPATCH = {"dispatch_part_ms.transfer": "dispatch_transfer_ms",
            "dispatch_part_ms.operands": "dispatch_operands_ms",
            "dispatch_part_ms.launch": "dispatch_launch_ms",
            "dispatch_part_ms.outputs": "dispatch_outputs_ms"}
SETUP = {"setup_part_s.param_init": "setup_param_init_s",
         "setup_part_s.trace": "setup_trace_s",
         "setup_part_s.lower": "setup_lower_s",
         "setup_part_s.compile": "setup_compile_s"}
NEW = {**DISPATCH, **SETUP}
COUNTS = (8, 8, 8, 0, 0, 8, 8000, 8000, 8, 1, 0)


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def wait(self, ms):
        self.t += ms * 1e-3


def _dispatch(tr, clock, parts, own_ms):
    """One ``step.dispatch`` whose children last ``parts`` ms (transfer,
    operands, launch, outputs; None: no children, as the parent's), and
    ``own_ms`` of its own."""
    ph = tr.next("step.dispatch", tr.begin("step.pack", "m/0"))
    clock.wait(own_ms)
    if parts is not None:
        sp = tr.begin("step.transfer", "m/0")
        clock.wait(parts[0])
        tr.end(sp)
        sp = tr.begin("step.operands", "serving_step")
        for name, took in zip(("step.launch", "step.outputs", None),
                              parts[1:]):
            clock.wait(took)
            if name is not None:
                sp = tr.next(name, sp)
        tr.end(sp)
    return ph


def _routed_step(tr, clock, parts, own_ms=0.5, launches=1):
    """One sweep with one step: plan, pack, ``launches`` dispatches, wait
    and land."""
    sweep = tr.begin("sweep", "router")
    step = tr.begin("step", "m/0")
    ph = tr.begin("step.plan", "m/0")
    clock.wait(0.2)
    tr.end(ph)
    for _ in range(launches):
        ph = _dispatch(tr, clock, parts, own_ms)
        tr.end(ph)
    ph = tr.begin("step.wait", "m/0")
    clock.wait(5.0)
    ph = tr.next("step.land", ph)
    clock.wait(0.4)
    tr.end(ph)
    tr.end(step, COUNTS)
    tr.end(sweep)


def _build(tr, clock, parts, fresh=1):
    """One ``step.build`` of ``fn`` tiled by ``parts`` ((name, ms) pairs)."""
    build = tr.begin("step.build", "fn")
    sp = None
    for name, took in parts:
        sp = tr.begin(name, "fn") if sp is None else tr.next(name, sp)
        clock.wait(took)
    tr.end(sp)
    tr.end(build, (fresh,))


def _param(tr, clock, ms):
    sp = tr.begin("param.init", "param")
    clock.wait(ms)
    tr.end(sp)


@pytest.fixture
def ring():
    clock = _Clock()
    tr = tracing.RequestTracer(capacity=512, clock=clock)
    old = tracing.set_tracer(tr)
    try:
        yield tr, clock
    finally:
        tracing.set_tracer(old)


def _run(n_steps, kind="serve"):
    return types.SimpleNamespace(record={"kind": kind,
                                         "engine_steps": n_steps})


def _read(name, run):
    return spec.load_reader("layer_metrics", name).read(run)


def _setup(tr, clock):
    """Set-up as a run has it: three parameters, a fresh build, a build the
    package's cache held, one that another function's build held."""
    for ms in (100.0, 200.0, 300.0):
        _param(tr, clock, ms)
    _build(tr, clock, [("step.trace", 2000.0), ("step.lower", 700.0),
                       ("step.compile", 3000.0)])
    _build(tr, clock, [("step.load", 50.0)], fresh=0)
    _build(tr, clock, [], fresh=0)


def test_each_dispatch_part_on_a_synthetic_ring(ring):
    tr, clock = ring
    _routed_step(tr, clock, (9, 9, 9, 9))                   # before the window
    _routed_step(tr, clock, (2.0, 0.6, 1.0, 0.2))
    tr.emit("req.token", "r1", arg=0.0)                     # points between
    _routed_step(tr, clock, (2.4, 0.8, 1.2, 0.2), launches=2)
    _routed_step(tr, clock, (1.6, 0.4, 0.8, 0.2), own_ms=0.3)
    run = _run(3)
    got = {name: _read(name, run) for name in DISPATCH}
    assert got == pytest.approx({
        "dispatch_part_ms.transfer": (2.0 + 4.8 + 1.6) / 3,
        "dispatch_part_ms.operands": (0.6 + 1.6 + 0.4) / 3,
        "dispatch_part_ms.launch": (1.0 + 2.4 + 0.8) / 3,
        "dispatch_part_ms.outputs": (0.2 + 0.4 + 0.2) / 3}, rel=1e-9)
    # with one launch a step, the four and the dispatch's own time are the
    # dispatch that step_phase_ms.dispatch reads
    single = _run(1)
    parts = sum(_read(name, single) for name in DISPATCH)
    assert parts + 0.3 == pytest.approx(
        _read("step_phase_ms.dispatch", single), rel=1e-9)


def test_each_setup_part_on_a_synthetic_ring(ring):
    tr, clock = ring
    _setup(tr, clock)
    _routed_step(tr, clock, (2.0, 0.6, 1.0, 0.2))   # the window's, not set-up's
    got = {name: _read(name, _run(1)) for name in SETUP}
    assert got == pytest.approx({
        "setup_part_s.param_init": 0.6, "setup_part_s.trace": 2.0,
        "setup_part_s.lower": 0.7, "setup_part_s.compile": 3.05}, rel=1e-9)
    # a training run reads them too
    assert _read("setup_part_s.trace", _run(5, kind="train")) \
        == pytest.approx(2.0)
    builds = [e for e in tr.events() if e["name"] == "step.build"]
    assert [b["counts"] for b in builds] == [{"fresh": 1}, {"fresh": 0},
                                             {"fresh": 0}]


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_without_spans_and_does_not_raise(ring, name):
    """What the parent commit's ring looks like: the step and its phases,
    no child of ``step.dispatch``, no set-up span."""
    tr, clock = ring
    tr.emit("req.token", "r1", arg=0.0)
    _routed_step(tr, clock, None)
    assert _read(name, _run(1)) is None
    assert _read(name, _run(5, kind="train")) is None
    assert _read(name, _run(0)) is None


@pytest.mark.parametrize("flushed", [False, True])
@pytest.mark.parametrize("name", sorted(SETUP))
def test_setup_reader_finds_nothing_on_a_wrapped_ring(name, flushed):
    """A ring that overwrote its oldest events has lost set-up's spans in
    part: it says so by its drop count, or, once a flight dump flushed that,
    by a set-up span's key whose seqs no longer start at 0."""
    clock = _Clock()
    tr = tracing.RequestTracer(capacity=16, clock=clock)
    old = tracing.set_tracer(tr)
    try:
        _setup(tr, clock)
        for _ in range(12):
            _param(tr, clock, 1.0)
        assert tr.dropped
        if flushed:
            tr.flush_metrics()
            assert tr.dropped == 0
        assert _read(name, _run(1)) is None
    finally:
        tracing.set_tracer(old)


def test_every_new_metric_is_in_benchmark_json():
    cells = {w: {m["name"]: m for m in spec.load_cell(w).per_layer}
             for w in ("gpt3-1.3b.serve-decode", "gpt3-1.3b.train")}
    for name, fn in NEW.items():
        assert spec.load_reader("layer_metrics", name).read \
            is getattr(pp, fn)
        m = cells["gpt3-1.3b.serve-decode"][name]
        assert m["source"] == "program_span"
    for name in DISPATCH:
        m = cells["gpt3-1.3b.serve-decode"][name]
        assert (m["layer"], m["moves"]) == ("host loop", "serve_tokens_per_s")
        assert name not in cells["gpt3-1.3b.train"]
    for name in SETUP:
        for cell in cells.values():
            assert (cell[name]["layer"], cell[name]["moves"]) \
                == ("set-up", "setup_s")


def test_the_real_serve_entry_records_every_part():
    """The real serve entry, tiny, on the CPU: every window step's dispatch
    has its four parts, which add up to no more than the dispatches, and
    set-up's parts are there and within set-up's time."""
    old = tracing.set_tracer(tracing.RequestTracer(capacity=65536))
    try:
        cell = tiny.cell("serve-tiny")
        import jax

        t_start = time.perf_counter()
        ctx = runner.Ctx(cell, 2 ** 31 + 7, jax.devices()[:1], t_start)
        entry = spec.load_entry(cell.entry).build(ctx)
        entry.setup()
        setup_s = time.perf_counter() - t_start
        record = entry.window(1.0)
        entry.release()
        run = types.SimpleNamespace(record=record)
        w = ps.window_steps(run)
        got = {name: _read(name, run) for name in NEW}
        events = tracing.get_tracer().events()
    finally:
        tracing.set_tracer(old)
    assert w is not None and record["compiled_in_window"] == 0
    assert all(v is not None and v > 0 for v in got.values()), got
    ids = {s["span"] for s in w.steps}
    dispatches = [e for e in events if e["name"] == "step.dispatch"
                  and e.get("parent") in ids]
    for d in dispatches:
        kids = [e for e in events if e.get("parent") == d["span"]]
        assert [k["name"] for k in kids] == list(pp.DISPATCH_PARTS)
        assert all(d["t"] - d["arg"] <= k["t"] - k["arg"] <= k["t"] <= d["t"]
                   for k in kids)
    mean_dispatch_ms = 1e3 * sum(d["arg"] for d in dispatches) / len(w.steps)
    assert sum(got[n] for n in DISPATCH) <= mean_dispatch_ms
    assert sum(got[n] for n in SETUP) <= setup_s
