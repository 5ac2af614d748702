"""The Olmo-Hybrid family in the benchmark, checked on the CPU at a tiny size:
its work count by hand, and its tiny twin (``olmo_twin/``: the same family
and keys at a test's widths, 4 layers L L L F, 2 heads of 32 x 64) entering
a COPY of the benchmark by new files and BENCHMARK.json entries alone, and
running ``correct`` through the copy's own harness while the fp8 control and
an altered token do not. The twin's limit was set as PERF.md sets a cell's:
readings beside ``olmo_twin/limits/``.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.families.olmo_hybrid import work
from benchmarks.harness import bounds, spec

HERE = os.path.dirname(os.path.abspath(__file__))
TWIN = os.path.join(HERE, "olmo_twin")
DRIVE = os.path.join(HERE, "fixture_family", "drive.py")
CELL = "olmo-hybrid-tiny.serve-tiny-chat"
REAL = "olmo-hybrid-7b.serve-chat32"


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


def grow(root):
    """Copy the benchmark to ``root`` and add the twin: its files, a
    configuration, a cell, and the cell's name wherever the real cell's
    stands. Returns (files before, files added)."""
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  ".pytest_cache"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    before = _files(root)
    added = []
    for d, _, names in os.walk(TWIN):
        for n in names:
            rel = os.path.relpath(os.path.join(d, n), TWIN)
            to = os.path.join(root, "benchmarks", rel)
            assert not os.path.exists(to), f"{rel} would overwrite a file"
            shutil.copy(os.path.join(d, n), to)
            added.append(os.path.relpath(to, root))
    shutil.copy(DRIVE, root)
    added.append("drive.py")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "olmo-hybrid-tiny", "source": "test size only",
        "file": "benchmarks/configs/olmo-hybrid-tiny.json", "reduced": [],
        "why": "the tiny twin of olmo-hybrid-7b"})
    bench["workloads"].append({
        "name": CELL, "config": "olmo-hybrid-tiny",
        "traffic": "serve-tiny-chat", "chips": 1,
        "why": "per-slot state beside paged K/V at a test's size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return before, added


def drive(root, seed, seconds=3.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    p = subprocess.run([sys.executable, "drive.py", CELL, str(seed),
                        str(seconds)], cwd=root, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tree"))
    before, added = grow(root)
    return {"root": root, "before": before, "added": added,
            "said": drive(root, 2 ** 31 + 44)}


def test_the_twin_runs_correct_through_the_copys_own_harness(grown):
    said, r = grown["said"], grown["said"]["result"]
    assert said["family"] == "benchmarks.families.olmo_hybrid"
    assert said["family_file"].startswith(grown["root"])
    assert said["entry"] == "olmo_hybrid_serve"
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["served_tokens_compared"]["value"] >= 400


def test_its_fp8_control_and_an_altered_token_come_out_not_correct(grown):
    proofs = grown["said"]["result"]["proofs"]
    assert set(proofs) == {"control_fp8", "fault_token_altered"}
    for label, proof in proofs.items():
        assert not proof["correct"], (label, proof)
        gap = proof["compared"]["served_gap"]
        assert gap["value"] > gap["limit"]


def test_every_file_that_was_there_is_byte_identical_but_benchmark_json(
        grown):
    after = _files(grown["root"])
    changed = {p for p in grown["before"]
               if after.get(p) != grown["before"][p]}
    assert changed == {"BENCHMARK.json"}
    assert set(after) - set(grown["before"]) == set(grown["added"])


def test_the_real_cells_record_admits_it_under_the_bounds_that_are_there(
        grown):
    assert grown["said"]["complaints"] == []
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bounds.complaints(bench, bounds.load()) == []


CFG = {"hidden_size": 3840, "intermediate_size": 11008,
       "num_hidden_layers": 16, "num_attention_heads": 30,
       "num_key_value_heads": 30, "vocab_size": 100352,
       "linear_num_value_heads": 30, "linear_key_head_dim": 96,
       "linear_value_head_dim": 192,
       "layer_types": ["linear_attention"] * 3 + ["full_attention"]}
CFG["layer_types"] = CFG["layer_types"] * 8     # 32 given, 16 used


def test_the_work_count_by_hand():
    """Olmo-Hybrid-7B, 16 layers: 12 linear, 4 full."""
    h, f, V = 3840, 11008, 100352
    linear = h * (2 * 2880 + 2 * 5760 + 2 * 30) + 5760 * h + 3 * h * f
    full = 4 * h * h + 3 * h * f
    assert work.matmul_params(CFG) == (12 * linear + 4 * full, h * V)
    # 215.5 M in a linear layer's matrices (its convolutions and norms, 0.05
    # M, are no matmul), 185.8 M in a full layer's
    assert round(linear / 1e6, 1) == 215.5 and round(full / 1e6, 1) == 185.8
    rec = 6.0 * 96 * 192 * 30 * 12
    assert work.recurrence_flops(CFG) == rec
    blocks = 12 * linear + 4 * full
    # a prompt of 5 new rows behind 100 cached keys, attention in the 4
    # full layers only (30 x 128 wide)
    assert work.prompt_flops(CFG, 5, 100) == \
        (2.0 * blocks + rec) * 5 + 2.0 * h * V + \
        4.0 * (5 * 100 + 15) * h * 4
    assert work.decode_flops(CFG, 8) == work.prompt_flops(CFG, 1, 7)
    # K/V of a token in the full layers only: 61,440 B (not 16 layers')
    assert work.kv_bytes_per_token(CFG) == 2 * 30 * 128 * 2 * 4 == 61440


def test_the_kernels_bytes_by_hand():
    record = {"prefill_lens": [5, 7], "prefill_cached": [0, 0],
              "decode_contexts": [106, 107, 8]}
    flops, byts, n = work.KERNELS["paged_attention"].work(CFG, record)
    assert n == 1
    assert byts == (5 + 7 + 106 + 107 + 8) * 61440
    assert flops == 4.0 * (15 + 28 + 106 + 107 + 8) * 3840 * 4
    flops, byts, n = work.KERNELS["gated_delta"].work(CFG, record)
    state = 2 * 4 * 30 * 96 * 192 * 12        # read + write, 12 layers
    rows = 4 * 30 * (2 * 96 + 2 * 192 + 2) * 12
    # the state once per prompt and per decode token, each row its own
    assert byts == state * (2 + 3) + rows * (5 + 7 + 3)
    assert flops == 6.0 * 96 * 192 * 30 * 12 * (5 + 7 + 3)
    assert work.KERNELS["gated_delta"].names == ("mosaic:gated_delta",)
