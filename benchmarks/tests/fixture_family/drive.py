"""Run inside a COPY of the benchmark (tests/test_second_family.py puts it at
the copy's root, beside BENCHMARK.json): drives the added cell through the
copy's own harness on the CPU and prints one JSON object.

    python drive.py <cell> <seed> <seconds>
"""
import json
import os
import sys
import time
import types

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)    # `benchmarks` is the copy's; the program comes
                            # from PYTHONPATH


def main(cell_name: str, seed: int, seconds: float) -> dict:
    from benchmarks.harness import bounds, runner, spec

    assert spec.ROOT == HERE, (spec.ROOT, HERE)
    with open(os.path.join(HERE, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = spec.load_cell(cell_name)
    result = runner.run_cell(cell, seed, seconds, False, time.perf_counter(),
                             need_chip=False, proofs=True)
    # the readers that count a model's work, on a record made by hand: they
    # ask this cell's family (no chip here, so the peaks are made up: 1, 1)
    record = {"kind": "serve", "wall_s": 2.0, "tokens": 10,
              "prefill_lens": [5, 7], "prefill_cached": [100, 0],
              "decode_contexts": [106, 107, 8]}
    run = types.SimpleNamespace(
        record=record, config=cell.config, chips=1,
        family=spec.load_family(cell.family),
        peaks={"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
        trace=types.SimpleNamespace(seconds_of=lambda *names: 4.0))
    readers = {name: spec.load_reader("layer_metrics", name).read(run)
               for name in ("mfu.serve", "paged_attention_roofline",
                            "mfu.train", "flash_attention_roofline")}
    return {"result": result, "family": run.family.__name__,
            "family_file": run.family.__file__, "entry": cell.entry,
            "per_layer": [m["name"] for m in cell.per_layer],
            "readers": readers,
            "complaints": bounds.complaints(bench, bounds.load())}


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]))))
