"""The readers of the program's own spans and counters
(``harness/program_spans.py``): each on a synthetic ring, ``None`` where the
ring holds no span, and against the real serve entry at a tiny size, where the
window's steps have to be the ring's last ``engine_steps`` ``step`` spans."""
import time
import types

import pytest

from benchmarks.harness import program_spans as ps, runner, spec
from benchmarks.tests import tiny
from paddle_tpu.serving import tracing

NEW = {"step_host_ms": "step_host_ms",
       "step_phase_ms.plan": "phase_plan_ms",
       "step_phase_ms.pack": "phase_pack_ms",
       "step_phase_ms.dispatch": "phase_dispatch_ms",
       "step_phase_ms.wait": "phase_wait_ms",
       "step_phase_ms.land": "phase_land_ms",
       "step_phase_ms.sweep": "phase_sweep_ms",
       "step_ms.decode_only": "step_decode_only_ms",
       "step_ms.chunk": "step_chunk_ms",
       "grid_fill_pct": "grid_fill_pct",
       "kv_walk_amplification": "kv_walk_amplification"}


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _routed_step(tr, clock, ms, counts, own_ms=1.0):
    """One sweep with one step whose phases last ``ms`` (plan, pack,
    dispatch, wait, land), the router's own ``own_ms`` after it."""
    sweep = tr.begin("sweep", "router")
    step = tr.begin("step", "m/0")
    ph = tr.begin("step.plan", "m/0")
    for name, took in zip(ps.PHASES[1:] + (None,), ms):
        clock.t += took * 1e-3
        if name is None or (name == "step.pack" and counts[0] == 0):
            break
        ph = tr.next(name, ph)
    tr.end(ph)
    tr.emit("step.tokens", "m/0", arg=float(counts[-1]))
    tr.end(step, counts)
    clock.t += own_ms * 1e-3
    tr.end(sweep)


@pytest.fixture
def ring():
    clock = _Clock()
    tr = tracing.RequestTracer(capacity=256, clock=clock)
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


#            rows bucket decode chunk draft seqs walked held landed
DECODE = (8, 8, 8, 0, 0, 8, 8000, 8000, 8)
CHUNK = (40, 64, 7, 33, 0, 8, 40000, 8000, 8)


def test_each_reader_on_a_synthetic_ring(ring):
    tr, clock = ring
    _routed_step(tr, clock, (9, 9, 9, 9, 9), DECODE)       # before the window
    _routed_step(tr, clock, (2, 1, 6, 100, 3), DECODE)     # 112 + 1
    tr.emit("req.token", "r1", arg=0.0)                    # points between
    _routed_step(tr, clock, (4, 3, 8, 400, 5), CHUNK, 3)   # 420 + 3
    _routed_step(tr, clock, (2, 1, 6, 120, 3), DECODE)     # 132 + 1
    run = _run(3)
    got = {name: _read(name, run) for name in NEW}
    want = {
        "step_host_ms": ((113 + 423 + 133) - (100 + 400 + 120)) / 3,
        "step_phase_ms.plan": 8 / 3, "step_phase_ms.pack": 5 / 3,
        "step_phase_ms.dispatch": 20 / 3, "step_phase_ms.wait": 620 / 3,
        "step_phase_ms.land": 11 / 3, "step_phase_ms.sweep": 5 / 3,
        "step_ms.decode_only": (112 + 132) / 2, "step_ms.chunk": 420.0,
        "grid_fill_pct": 100.0 * 56 / 80,
        "kv_walk_amplification": 56000 / 24000}
    assert got == pytest.approx(want, rel=1e-9)
    # the phases and the router's own tile the sweep
    assert sum(v for k, v in got.items() if k.startswith("step_phase_ms")) \
        == pytest.approx((113 + 423 + 133) / 3)


def test_a_step_that_ran_no_rows_is_its_plan_alone(ring):
    tr, clock = ring
    _routed_step(tr, clock, (2, 1, 6, 100, 3), DECODE)
    _routed_step(tr, clock, (1,), (0,) * 9, 0.5)
    run = _run(2)
    assert _read("step_phase_ms.plan", run) == pytest.approx(1.5)
    assert _read("step_phase_ms.wait", run) == pytest.approx(50.0)
    assert _read("step_ms.decode_only", run) == pytest.approx(112.0)
    assert _read("step_ms.chunk", run) is None   # none in the window
    assert _read("grid_fill_pct", run) == pytest.approx(100.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_without_spans_and_does_not_raise(ring, name):
    """What the parent commit's ring looks like: point events only."""
    tr, _ = ring
    tr.emit("step.tokens", "m/0", arg=8.0)
    tr.emit("req.token", "r1", arg=0.0)
    assert _read(name, _run(1)) is None
    assert _read(name, _run(5, kind="train")) is None
    assert _read(name, _run(0)) is None


def test_fewer_step_spans_than_the_window_had_steps_reads_nothing(ring):
    tr, clock = ring
    _routed_step(tr, clock, (2, 1, 6, 100, 3), DECODE)
    assert ps.find(tr.events(), 2) is None      # a wrapped ring
    eng_only = tr.begin("step", "m/0")          # stepped with no router
    tr.end(eng_only, DECODE)
    assert ps.find(tr.events(), 1) is None


def test_every_new_metric_is_in_benchmark_json_for_the_serve_cell():
    cell = spec.load_cell("gpt3-1.3b.serve-decode")
    listed = {m["name"]: m for m in cell.per_layer}
    assert set(NEW) <= set(listed)
    for name, fn in NEW.items():
        assert spec.load_reader("layer_metrics", name).read is getattr(ps, fn)
    assert not set(NEW) & {m["name"] for m in
                           spec.load_cell("gpt3-1.3b.train").per_layer}


def test_the_windows_steps_are_the_rings_last_engine_steps_spans():
    """The real serve entry, tiny, on the CPU: after ``release`` the ring's
    last ``engine_steps`` ``step`` spans are the window's — they lie inside
    it, tile their sweeps, and with the router's own give the wall time a
    step that ``step_ms.serve`` reads."""
    old = tracing.set_tracer(tracing.RequestTracer(capacity=65536))
    try:
        cell = tiny.cell("serve-tiny")
        import jax

        ctx = runner.Ctx(cell, 2 ** 31 + 5, jax.devices()[:1],
                         time.perf_counter())
        entry = spec.load_entry(cell.entry).build(ctx)
        entry.setup()
        t0 = time.perf_counter()
        record = entry.window(1.0)
        t1 = time.perf_counter()
        entry.release()
        run = types.SimpleNamespace(record=record)
        w = ps.window_steps(run)
    finally:
        tracing.set_tracer(old)
    assert w is not None and len(w.steps) == record["engine_steps"] > 3
    assert all(t0 <= s["t"] - s["arg"] and s["t"] <= t1 for s in w.sweeps)
    assert w.counter("landed") > 0
    phases = sum(ps._phase(p)(run) for p in ps.PHASES)
    mean_sweep = 1e3 * w.sweep_s() / len(w.steps)
    assert phases + ps.phase_sweep_ms(run) == pytest.approx(mean_sweep,
                                                            abs=0.5)
    assert ps.step_host_ms(run) + ps.phase_wait_ms(run) \
        == pytest.approx(mean_sweep, rel=1e-9)
    kinds = [(ps.step_decode_only_ms(run), False),
             (ps.step_chunk_ms(run), True)]
    n = {c: sum(1 for s in w.steps
                if (s["counts"]["chunk_rows"] > 0) == c) for _, c in kinds}
    weighted = sum(v * n[c] for v, c in kinds if v is not None) / len(w.steps)
    assert weighted + ps.phase_sweep_ms(run) == pytest.approx(mean_sweep,
                                                              rel=1e-6)
    # the sweeps are the window less the harness's own submit and sampling
    assert mean_sweep <= 1e3 * record["wall_s"] / record["engine_steps"]
    assert 0 < ps.grid_fill_pct(run) <= 100
    assert ps.kv_walk_amplification(run) >= 1.0
