"""BENCHMARK.json against the files it names, and the data-driven rule: a
cell, a traffic mix and a metric are found by name, with no table in code."""
import json
import os

from benchmarks.harness import spec


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
    import pytest

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
