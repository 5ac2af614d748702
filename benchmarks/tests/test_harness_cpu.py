"""The harness end to end at a tiny size on the CPU: both loops, without the
look for a chip; the faults a cell can have, planted under the timed path;
the control (the reference in int8 in the program's place). Each broken run
has to come out ``correct: false``.

Limits for the tiny size were set as PERF.md sets the cells': from sound
runs on a dozen seeds and from the control and the faults below (readings in
the comments of each limit's test).
"""
import time

import numpy as np
import pytest

from benchmarks.harness import runner
from benchmarks.tests import tiny

TRAIN_LIMITS = {"loss_rel": 2e-4, "grad_norm_gap": 0.02,
                "grad_diff_wpe": 0.02, "change_norm_gap": 0.1}
SERVE_LIMITS = {"served_gap": 0.05}
E2E = {"train": ("train_tokens_per_s", "setup_s"),
       "serve": ("serve_tokens_per_s", "ttft_mean_ms", "itl_p95_ms",
                 "setup_s")}


def _run(traffic, seed, limits, seconds, proofs=False):
    cell = tiny.cell(traffic)
    cell.end_to_end = [{"name": n, "unit": "x"}
                       for n in E2E[cell.traffic["kind"]]]
    return runner.run_cell(cell, seed, seconds, False, time.perf_counter(),
                           need_chip=False, limits=limits, proofs=proofs)


def _check_line(r, kind):
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(r)[-1] == "compared"          # comes last in the line
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == set(E2E[kind])
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert all(set(c) == {"value", "limit"} for c in r["compared"].values())


def test_train_cell_runs_correct_and_its_control_and_fault_fail():
    r = _run("train-tiny", 2 ** 31 + 7, TRAIN_LIMITS, 1.0, proofs=True)
    _check_line(r, "train")
    assert r["correct"], r["compared"]
    # the fp8 control fails; int8 with a scale per row is about as exact as
    # the bfloat16 program and is only read (PERF.md section 2)
    assert not r["proofs"]["control_fp8"]["correct"], r["proofs"]
    assert "control_int8" in r["proofs"]
    assert not r["proofs"]["fault_half_batch"]["correct"], r["proofs"]


def test_serve_cell_runs_correct_and_its_control_and_fault_fail():
    r = _run("serve-tiny", 2 ** 31 + 9, SERVE_LIMITS, 3.0, proofs=True)
    _check_line(r, "serve")
    assert r["correct"], r["compared"]
    assert r["compared"]["served_tokens_compared"]["value"] >= 40
    assert not r["proofs"]["control_fp8"]["correct"], r["proofs"]
    assert not r["proofs"]["fault_token_altered"]["correct"], r["proofs"]


def test_train_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    import paddle_tpu as paddle

    monkeypatch.setattr(paddle.optimizer.AdamW, "step", lambda self: None)
    r = _run("train-tiny", 11, TRAIN_LIMITS, 0.5)
    assert not r["correct"]
    # nothing moved: both norms read 1 by the worst-leaf measure
    assert r["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert r["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_step_that_leaves_out_half_the_batch_is_not_correct(monkeypatch):
    from paddle_tpu.models import GPTForCausalLM

    whole = GPTForCausalLM.forward

    def half(self, input_ids, position_ids=None, labels=None):
        n = input_ids.shape[0] // 2
        return whole(self, input_ids[:n], position_ids,
                     None if labels is None else labels[:n])

    monkeypatch.setattr(GPTForCausalLM, "forward", half)
    r = _run("train-tiny", 12, TRAIN_LIMITS, 0.5)
    assert not r["correct"], r["compared"]


def test_served_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.serving.engine import ServingEngine

    land = ServingEngine._land_token
    count = [0]

    def altered(self, st, slot, token, now):
        count[0] += 1
        if count[0] % 7 == 0:
            token = (token + 1) % self._vocab_size
        return land(self, st, slot, token, now)

    monkeypatch.setattr(ServingEngine, "_land_token", altered)
    r = _run("serve-tiny", 13, SERVE_LIMITS, 3.0)
    assert not r["correct"], r["compared"]
    assert r["compared"]["served_gap"]["value"] > SERVE_LIMITS["served_gap"]


def test_a_compile_inside_the_window_fails_the_run(monkeypatch):
    from benchmarks.harness import serve_loop

    monkeypatch.setattr(serve_loop.ServeRun, "_warm_buckets",
                        lambda self: None)
    tiny.TRAFFIC["serve-cold"] = dict(tiny.TRAFFIC["serve-tiny"],
                                      ramp_finished=0, callers=1)
    try:
        r = _run("serve-cold", 14, SERVE_LIMITS, 3.0)
    finally:
        del tiny.TRAFFIC["serve-cold"]
    assert r["compared"]["compiled_in_window"]["value"] >= 1
    assert not r["correct"]


def test_worst_leaf_gap_measures_norms_against_the_median_leaf():
    from benchmarks.harness.compare import worst_leaf_gap

    ref = np.array([1.0, 2.0, 1e-9])
    assert worst_leaf_gap(ref, ref) == 0.0
    # the all-but-zero leaf is measured against the median leaf (1.0)
    assert worst_leaf_gap(np.array([1.0, 2.0, 0.5]), ref) == pytest.approx(0.5)
    assert worst_leaf_gap(np.array([0.0, 0.0, 0.0]), ref) == 1.0


def test_warm_up_brings_back_an_engine_whose_watchdog_a_compile_tripped():
    """A cold compile longer than the engine's stall threshold degrades it,
    and the router then refuses every submit (seen on the chip, PR 24)."""
    from benchmarks.entries import gpt_serve
    from benchmarks.harness.runner import Ctx
    import jax

    run = gpt_serve.build(Ctx(tiny.cell("serve-tiny"), 15, jax.devices()[:1],
                              time.perf_counter()))
    run.setup()
    run.engine.watchdog.begin_step()
    run.engine.watchdog.end_step(1e9)          # a step that "took" too long
    run.router.step()
    assert set(run.router.states().values()) == {"degraded"}
    run._recover(np.random.default_rng(0))
    assert set(run.router.states().values()) == {"healthy"}
    run._loop_once()                           # and the loop goes on
