"""What the next ``model_config`` PR does, done to a COPY of the benchmark:
a second model family and a cell of it enter by new files and BENCHMARK.json
entries alone, run ``correct`` through the copy's own harness on the CPU,
and not one file that was there has changed.

The family is a fixture (``fixture_family/``: the program's second trunk,
rotary positions, RMSNorm, gated MLP, 4 heads over 2 kv heads, at a test's
size) and no configuration of the benchmark. Its limit was set as PERF.md
sets a cell's (CPU, PR 35; 12 seeds of the program, the control and the
fault read beside each): readings beside ``limits/`` in the fixture.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import spec

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture_family")
CELL = "llama-tiny.serve-tiny-gqa"


def _files(root):
    out = {}
    for d, dirs, names in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            path = os.path.join(d, n)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The copy with the family and the cell added, what it held before,
    and what one run of the cell in it printed."""
    root = str(tmp_path_factory.mktemp("tree"))
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  ".pytest_cache"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    before = _files(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench_before = json.load(f)

    # ---- the PR: new files ...
    added = []
    for d, _, names in os.walk(FIXTURE):
        for n in names:
            rel = os.path.relpath(os.path.join(d, n), FIXTURE)
            to = os.path.join(root, rel if rel == "drive.py"
                              else os.path.join("benchmarks", rel))
            assert not os.path.exists(to), f"{rel} would overwrite a file"
            os.makedirs(os.path.dirname(to), exist_ok=True)
            shutil.copy(os.path.join(d, n), to)
            added.append(os.path.relpath(to, root))
    # ---- ... and entries: a configuration, a cell, and the cell's name
    # appended to the `workloads` of the metrics a serve cell reports
    bench = json.loads(json.dumps(bench_before))
    bench["configs"].append({
        "name": "llama-tiny", "source": "test size only",
        "file": "benchmarks/configs/llama-tiny.json", "reduced": [],
        "why": "a second family: rotary positions, RMSNorm, gated MLP, "
               "grouped kv heads"})
    bench["workloads"].append({
        "name": CELL, "config": "llama-tiny", "traffic": "serve-tiny-gqa",
        "chips": 1, "why": "grouped kv heads through the pool, "
                           "paged_attend and the kernel"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt3-1.3b.serve-decode" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=spec.ROOT)    # the program; `benchmarks` is the copy's
    p = subprocess.run([sys.executable, "drive.py", CELL, str(2 ** 31 + 35),
                        "3.0"], cwd=root, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    said = json.loads(p.stdout.strip().splitlines()[-1])
    return {"root": root, "before": before, "added": added,
            "bench_before": bench_before, "bench": bench, "said": said,
            "stderr": p.stderr}


def test_the_added_cell_runs_correct_through_the_copys_own_harness(grown):
    said, r = grown["said"], grown["said"]["result"]
    # the copy's modules, the copy's family, found by the names in the files
    assert said["family"] == "benchmarks.families.llama"
    assert said["family_file"].startswith(grown["root"])
    assert said["entry"] == "llama_serve"
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["served_tokens_compared"]["value"] >= 400
    assert set(r["metrics"]) == {"serve_tokens_per_s", "ttft_mean_ms",
                                 "itl_p95_ms", "setup_s"}
    assert "compiled_in_window" not in r["compared"]
    assert list(r)[-1] == "compared"
    assert "compared served_gap:" in grown["stderr"]


def test_its_control_and_an_altered_token_come_out_not_correct(grown):
    proofs = grown["said"]["result"]["proofs"]
    assert set(proofs) == {"control_fp8", "fault_token_altered"}
    for label, proof in proofs.items():
        assert not proof["correct"], (label, proof)
        gap = proof["compared"]["served_gap"]
        assert gap["value"] > gap["limit"]


def test_the_work_count_the_readers_use_is_the_added_familys_own(grown):
    """The sums by hand, for the record that drive.py made by hand (peaks of
    1 and 1, 4 s of kernel time, a window of 2 s): 4 query heads of 32 over
    2 kv heads, 2 layers, a gated MLP of 384, an untied head of 512."""
    h, q, kv, f, v, n = 128, 4 * 32, 2 * 32, 384, 512, 2
    blocks = (h * q + 2 * h * kv + q * h + 3 * h * f) * n
    pairs = [5 * 100 + 15, 7 * 0 + 28]            # the two prompts
    contexts = [106, 107, 8]
    attention = 4.0 * (sum(pairs) + sum(contexts)) * q * n
    flops = 2.0 * blocks * (5 + 7 + 3) + 2.0 * h * v * 5 + attention
    readers = grown["said"]["readers"]
    assert readers["mfu.serve"] == 100.0 * flops / 2.0
    kv_bytes = (105 + 7 + sum(contexts)) * 2 * kv * 2 * n   # not 2 * h
    assert readers["paged_attention_roofline"] == \
        100.0 * max(attention, kv_bytes) / 4.0
    # it counts no training and no flash kernel: those readers find nothing
    assert readers["mfu.train"] is None
    assert readers["flash_attention_roofline"] is None
    assert "mfu.serve" in grown["said"]["per_layer"]
    assert "paged_attention_roofline" in grown["said"]["per_layer"]


def test_its_own_record_of_runs_admits_it_under_the_bounds_that_are_there(
        grown):
    assert grown["said"]["complaints"] == []


def test_every_file_that_was_there_is_byte_identical_but_benchmark_json(grown):
    after = _files(grown["root"])
    changed = {p for p in grown["before"] if after.get(p) != grown["before"][p]}
    assert changed == {"BENCHMARK.json"}
    assert set(after) - set(grown["before"]) == set(grown["added"])
    # roughly what PERF.md section 1 says a new family and cell cost
    assert len(grown["added"]) <= 10, sorted(grown["added"])


def test_benchmark_json_only_gained_entries(grown):
    old, new = grown["bench_before"], grown["bench"]
    assert set(old) == set(new)
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
        assert len(new[key]) == len(old[key]) + 1
    for key in ("end_to_end", "per_layer"):
        assert len(new[key]) == len(old[key])
        for was, now in zip(old[key], new[key]):
            assert {k: x for k, x in now.items() if k != "workloads"} == \
                {k: x for k, x in was.items() if k != "workloads"}
            if "workloads" in was:
                assert now["workloads"][:len(was["workloads"])] == \
                    was["workloads"]
            else:
                assert "workloads" not in now
