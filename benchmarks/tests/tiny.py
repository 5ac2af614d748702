"""A BENCHMARK.json-shaped dict at a size the CPU holds, for the tests."""
import os

from benchmarks.harness import spec

HERE = os.path.dirname(os.path.abspath(__file__))

TRAFFIC = {
    "train-tiny": {"kind": "train", "batch": 4, "seq_len": 128},
    "serve-tiny": {"kind": "serve", "callers": 6, "strata": 2,
                   "prompt_tokens": {"min": 20, "max": 60},
                   "output_tokens": {"min": 4, "max": 12},
                   "sessions": {"count": 3,
                                "context_tokens": {"min": 64, "max": 128}},
                   "ramp_finished": 3, "check_tokens": 600},
}


def cell(traffic_name: str, chips: int = 1) -> spec.Cell:
    import json

    with open(os.path.join(HERE, "configs", "gpt-tiny.json")) as f:
        config = json.load(f)
    return spec.Cell(name=f"gpt-tiny.{traffic_name}", chips=chips,
                     config_name="gpt-tiny", config=config,
                     traffic_name=traffic_name,
                     traffic=dict(TRAFFIC[traffic_name]),
                     end_to_end=[], per_layer=[])
