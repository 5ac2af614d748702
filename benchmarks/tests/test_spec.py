"""BENCHMARK.json against the files it names, and the data-driven rule: a
cell, a traffic mix and a metric are found by name, with no table in code."""
import json
import os

import pytest

from benchmarks.harness import bounds, spec


def _bench():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_loads_and_names_an_entry_that_exists():
    for w in _bench()["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips in (1, 4)
        assert os.path.exists(os.path.join(
            spec.BENCH_DIR, "entries", cell.entry + ".py"))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_every_configuration_names_a_family_that_keeps_the_interface():
    """benchmarks/families/__init__.py says what a family provides."""
    for c in _bench()["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            family = spec.load_family(json.load(f)["entry"])
        for name in ("serve_model", "pool_args", "make_weights", "logits_at"):
            assert callable(getattr(family, name)), name
        assert family.CONTROLS
        assert callable(family.work.prompt_flops)
        assert callable(family.work.decode_flops)
        for kernel in family.work.KERNELS.values():
            assert kernel.names and kernel.kind in ("serve", "train")


def test_no_module_of_the_harness_names_a_key_or_class_of_one_trunk():
    """What is one trunk's lives under benchmarks/families/ (and in that
    family's entry modules): the harness, the readers, run.py and proofs.py
    name none of it, so a second family edits none of them."""
    import re

    trunk = re.compile(r"intermediate_size|\bwpe\b|GPTForCausalLM|\bgpt|"
                       r"num_attention_heads|layer_norm_epsilon|"
                       r"position_embeddings", re.IGNORECASE)
    paths = [os.path.join(spec.BENCH_DIR, n) for n in ("run.py", "proofs.py")]
    for sub in ("harness", "end_to_end", "layer_metrics"):
        d = os.path.join(spec.BENCH_DIR, sub)
        paths += [os.path.join(d, n) for n in sorted(os.listdir(d))
                  if n.endswith(".py")]
    for path in paths:
        with open(path) as f:
            for n, line in enumerate(f, 1):
                assert not trunk.search(line), f"{path}:{n}: {line.strip()}"


def test_every_metric_has_a_reader_of_its_own():
    bench = _bench()
    for kind, key in (("end_to_end", "end_to_end"),
                      ("layer_metrics", "per_layer")):
        for m in bench[key]:
            assert callable(spec.load_reader(kind, m["name"]).read)


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    bench = _bench()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells)


def test_a_cell_is_added_by_new_files_and_entries_only(tmp_path):
    """What a later PR does: a traffic file and BENCHMARK.json entries."""
    bench = _bench()
    root = tmp_path
    os.makedirs(root / "benchmarks" / "traffic")
    os.makedirs(root / "benchmarks" / "configs")
    src = os.path.join(spec.ROOT, bench["configs"][0]["file"])
    with open(src) as f:
        (root / bench["configs"][0]["file"]).write_text(f.read())
    (root / "benchmarks" / "traffic" / "serve-new.json").write_text(json.dumps(
        {"kind": "serve", "callers": 2, "strata": 2,
         "prompt_tokens": {"min": 8, "max": 16},
         "output_tokens": {"min": 2, "max": 4},
         "ramp_finished": 1, "check_tokens": 4}))
    bench["workloads"].append({"name": "gpt3-1.3b.serve-new",
                               "config": "gpt3-1.3b", "traffic": "serve-new",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt3-1.3b.serve-decode" in m.get("workloads", []):
            m["workloads"].append("gpt3-1.3b.serve-new")
    cell = spec.load_cell("gpt3-1.3b.serve-new", bench=bench, root=str(root))
    assert cell.entry == "gpt_serve"   # <config's entry>_<traffic's kind>
    assert {m["name"] for m in cell.end_to_end} >= {"serve_tokens_per_s",
                                                    "setup_s"}


def test_every_cell_has_limits_and_a_cell_without_them_does_not_run():
    from benchmarks.harness import runner
    from benchmarks.harness.compare import Compared, all_ok

    for w in _bench()["workloads"]:
        assert runner.load_limits(w["name"])
    with pytest.raises(SystemExit):
        runner.load_limits("gpt3-1.3b.no-such-cell")
    # numbers that are only read decide nothing: such a run is not correct
    assert not all_ok([Compared("read_only", 0.0, None)])
    assert all_ok([Compared("read_only", 9.0, None),
                   Compared("judged", 0.1, 0.2)])
    assert not all_ok([Compared("judged", 0.3, 0.2)])


def test_benchmark_json_keeps_to_the_contracts_form():
    import re

    bench = _bench()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(bench["paths"][0] + "/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert all(name.match(m["name"]) and unit.match(m["unit"])
               and m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


# ---- the bounds against the runs they were set from (benchmarks/bounds/)
def test_spread_is_the_range_without_the_run_farthest_from_the_median():
    # median 100.5; 110 is farthest and goes: (102 - 98) / 100.5
    assert bounds.spread([100, 98, 101, 110, 102, 99]) == 4 / 100.5
    # the low end is farthest here
    assert bounds.spread([10.0, 10.1, 9.0, 10.2]) == (10.2 - 10.0) / 10.05
    assert bounds.spread([5.0, 5.0, 5.0, 5.0]) == 0.0
    # at least twice the mean, at most eight times the wider, within 1-10%
    lo, hi = bounds.rule_range([[100, 101, 102, 103, 120],
                                [100, 100, 101, 101, 90]])
    assert lo == pytest.approx(3 / 102 + 1 / 100) and hi == 0.10
    assert bounds.rule_range([[1000.0, 1000.01, 1000.02, 999.99]] * 2) == \
        (0.01, 0.01)


def _check_record(cell, got):
    seeds = got["seeds"]
    assert got["cell"] == cell.name and len(got["commit"]) == 40
    assert len(set(seeds)) == len(seeds) >= 4
    assert len(got["sets"]) == 2    # the same seeds in both, in order
    for m in cell.end_to_end:
        for one in got["sets"]:
            values = one["runs"][m["name"]]
            assert len(values) == len(seeds)
            assert all(v > 0 for v in values)


def test_every_metric_a_cell_reports_has_its_runs_recorded():
    bench, rec = _bench(), bounds.load()
    assert set(rec["cells"]) == {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        _check_record(spec.load_cell(w["name"]), rec["cells"][w["name"]])


def test_every_bound_admits_each_cell_and_is_no_looser_than_the_rule_allows():
    """For every metric and every cell that reports it the bound lies at or
    above the rule's lowest for that cell's own two sets; for the cell that
    spreads most it lies at or below the rule's highest."""
    bench, rec = _bench(), bounds.load()
    assert bounds.complaints(bench, rec) == []
    rows = bounds.table(bench, rec)
    assert [r["metric"] for r in rows] == [m["name"]
                                           for m in bench["end_to_end"]]
    for r in rows:
        assert r["lowest"] <= r["bound"] <= r["highest"], r
        assert round(r["bound"] / bounds.STEP, 9) % 1 == 0   # half percents
        if r["metric"] in bounds.FIXED:
            assert r["bound"] == bounds.FIXED[r["metric"]]


# ---- a later PR's cell: its own record file, and no other record touched
def _with_a_new_serve_cell(tmp_path, runs=None):
    """BENCHMARK.json plus one serve cell, and a copy of the records with
    that cell's own file added (``runs``: metric -> its two sets; None: no
    record is brought)."""
    import shutil

    bench = _bench()
    new = "gpt3-1.3b.serve-new"
    bench["workloads"].append({"name": new, "config": "gpt3-1.3b",
                               "traffic": "serve-new", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt3-1.3b.serve-decode" in m.get("workloads", []):
            m["workloads"].append(new)
    directory = tmp_path / "bounds"
    shutil.copytree(bounds.RECORDS, directory)
    if runs is not None:
        (directory / (new + ".json")).write_text(json.dumps({
            "cell": new, "commit": "0" * 40, "measured": "made up: a test",
            "seeds": [1, 2, 3, 4],
            "sets": [{"call": f"set{i}",
                      "runs": {k: v[i] for k, v in runs.items()}}
                     for i in (0, 1)]}))
    return bench, bounds.load(str(directory)), directory


_QUIET = {   # spreads of 0.8% and 1.2% of 1000: twice the mean is 2%
    "serve_tokens_per_s": ([1000.0, 1004.0, 1008.0, 900.0],
                           [1000.0, 1006.0, 1012.0, 1100.0]),
    "ttft_mean_ms": ([100.0, 100.1, 100.2, 100.3],) * 2,
    "itl_p95_ms": ([40.0, 40.01, 40.02, 40.03],) * 2,
    "setup_s": ([50.0, 51.0, 52.0, 53.0],) * 2,
}


def test_a_cell_added_with_its_own_record_passes_and_touches_no_other(tmp_path):
    before = {name: open(os.path.join(bounds.RECORDS, name), "rb").read()
              for name in os.listdir(bounds.RECORDS) if name.endswith(".json")}
    bench, rec, directory = _with_a_new_serve_cell(tmp_path, _QUIET)
    assert bounds.complaints(bench, rec) == []
    assert {name: (directory / name).read_bytes() for name in before} == before
    # the cell that spreads most still decides how loose a bound may be
    rows = {r["metric"]: r for r in bounds.table(bench, rec)}
    assert rows["serve_tokens_per_s"]["cell"] == "gpt3-1.3b.serve-decode"


def test_a_cell_whose_own_sets_ask_for_more_than_a_bound_is_named(tmp_path):
    noisy = dict(_QUIET, itl_p95_ms=([40.0, 40.4, 40.8, 41.2],
                                     [40.0, 40.3, 40.6, 40.9]))
    bench, rec, _ = _with_a_new_serve_cell(tmp_path, noisy)
    said = bounds.complaints(bench, rec)
    assert len(said) == 1
    assert "cell gpt3-1.3b.serve-new, metric itl_p95_ms" in said[0]
    assert "steady the traffic" in said[0] and "`benchmark` PR" in said[0]


def test_a_cell_listed_in_workloads_with_no_record_names_the_missing_file(
        tmp_path):
    bench, rec, directory = _with_a_new_serve_cell(tmp_path, None)
    said = bounds.complaints(bench, rec)
    assert said == [f"cell gpt3-1.3b.serve-new: no record of runs at "
                    f"{directory / 'gpt3-1.3b.serve-new.json'}"]


def test_a_bound_looser_than_the_cell_that_spreads_most_allows_is_named():
    bench, rec = _bench(), bounds.load()
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["bound"] = 0.06      # 8 x the wider spread is 5.9%
    said = bounds.complaints(bench, rec)
    assert len(said) == 1 and "metric itl_p95_ms: bound 0.06" in said[0]
    assert "gpt3-1.3b.serve-decode" in said[0]


def test_the_serve_bounds_hold_the_64_slot_witness_where_the_range_reaches():
    """A new 64-slot closed loop has to spread by at most half a bound to be
    admitted under it: the witness's recorded runs say whether it would."""
    bench, rec = _bench(), bounds.load()
    wit = rec["witnesses"]["gpt3-1.3b.slots64"]
    by_name = {r["metric"]: r for r in bounds.table(bench, rec)}
    assert len(wit["sets"]) == 2
    for metric, held in wit["admitted"].items():
        sets = [one["runs"][metric] for one in wit["sets"]]
        assert all(len(v) == len(wit["seeds"]) for v in sets)
        mean = sum(bounds.spread(v) for v in sets) / len(sets)
        assert held["mean_spread"] == pytest.approx(mean, rel=1e-9)
        row = by_name[metric]
        assert held["admitted"] == (mean <= row["bound"] / 2)
        # not admitted only where the rule's range ends below it
        assert held["admitted"] or row["bound"] == row["highest"]
