"""The work-counting functions against sums made by hand (the GPT family's
count, and what the harness keeps for every family), and the golden readings
of the four readers that ask a family for its count."""
import json
import os
import types

import pytest

from benchmarks.families.gpt import work
from benchmarks.harness import chip, spec
from benchmarks.harness.work import roofline_seconds

CFG = {"hidden_size": 4, "intermediate_size": 16, "num_hidden_layers": 2,
       "vocab_size": 10}


def test_matmul_params():
    # per layer: qkv 3*16 + out 16 + mlp 2*64 = 192; head 4*10
    assert work.matmul_params(CFG) == (384, 40)


def test_train_flops_per_token():
    # forward: 2*(384+40) = 848; attention 4 * ctx 2.5 * h 4 * 2 layers = 80
    assert work.train_flops_per_token(CFG, seq_len=4) == 3 * (848 + 80)


def test_flash_train_work():
    flops, byts = work.flash_train_work(CFG, batch=2, seq_len=4)
    pairs = 2 * 4 * 5 / 2          # causal pairs, diagonal included
    assert flops == 7 * 2 * pairs * 4 * 2
    assert byts == 12 * 2 * 4 * 4 * 2 * 2


def test_paged_attention_work():
    flops, byts = work.paged_attention_work(CFG, prefills=[3],
                                            decode_contexts=[5, 6])
    kv = 2 * 4 * 2 * 2             # K and V, h 4, bf16, 2 layers
    assert byts == (3 + 5 + 6) * kv
    assert flops == 4 * (3 * 4 / 2) * 4 * 2 + 4 * (5 + 6) * 4 * 2


def test_paged_attention_work_with_a_sessions_cached_keys():
    flops, byts = work.paged_attention_work(CFG, prefills=[3],
                                            decode_contexts=[], cached=[10])
    kv = 2 * 4 * 2 * 2
    assert byts == (3 + 10) * kv               # every key read once
    # 3 new rows meet 10 cached keys each and 1 + 2 + 3 of their own
    assert work.prompt_pairs(3, 10) == 36
    assert flops == 4 * 36 * 4 * 2


def test_serve_token_flops_counts_the_head_only_when_sampled():
    with_head = work.serve_token_flops(CFG, 5, sampled=True)
    without = work.serve_token_flops(CFG, 5, sampled=False)
    assert with_head - without == 2 * 40
    assert without == 2 * 384 + 4 * 5 * 4 * 2


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline_seconds(200.0, 10.0, peaks) == 2.0
    assert roofline_seconds(200.0, 50.0, peaks) == 5.0


# ---- golden readings: taken at 7f24578, before the work count moved under
# the family, from the four readers on one fixed synthetic record at GPT-3
# 1.3B's configuration (v5e peaks, one chip); held exactly, to the last bit
_SERVE = {"kind": "serve", "wall_s": 51.0625, "tokens": 44321,
          "prefill_lens": [38, 54, 76, 108, 54, 38, 108, 76],
          "prefill_cached": [974, 1003, 1033, 1064, 1096, 1129, 1163, 1198],
          "decode_contexts": [1012 + (7 * i) % 353 for i in range(3000)]}
_TRAIN = {"kind": "train", "wall_s": 51.03125, "steps": 159, "batch": 4,
          "seq_len": 1024, "tokens": 159 * 4096}


def _run(record, kernel_s):
    with open(os.path.join(spec.BENCH_DIR, "configs", "gpt3-1.3b.json")) as f:
        cfg = json.load(f)
    trace = types.SimpleNamespace(seconds_of=lambda *names: kernel_s)
    return types.SimpleNamespace(
        record=record, config=cfg, family=spec.load_family(cfg["entry"]),
        chips=1, peaks=chip.peaks("TPU v5 lite"), trace=trace)


@pytest.mark.parametrize("metric, record, kernel_s, golden", [
    ("mfu.serve", _SERVE, None, 0.09965251327225394),
    ("mfu.train", _TRAIN, None, 52.91512362763979),
    ("paged_attention_roofline", _SERVE, 2.375, 36.09434304283786),
    ("flash_attention_roofline", _TRAIN, 3.25, 35.873238472221786),
])
def test_a_reader_that_asks_the_family_reads_what_it_read_before(
        metric, record, kernel_s, golden):
    read = spec.load_reader("layer_metrics", metric).read
    assert read(_run(record, kernel_s)) == golden
    other = _TRAIN if record is _SERVE else _SERVE
    assert read(_run(other, kernel_s)) is None     # nothing to read there


def test_the_kernels_least_time_numerators_are_the_ones_counted_before():
    run = _run(_SERVE, 1.0)
    kernels = run.family.work.KERNELS
    assert kernels["paged_attention"].names == ("mosaic:paged_attention",)
    assert kernels["paged_attention"].work(run.config, _SERVE) == \
        (823229153280.0, 702080090112.0, 1)
    assert kernels["flash_attention"].names == ("mosaic:",)
    assert kernels["flash_attention"].work(run.config, _TRAIN) == \
        (1444518297600.0, 4831838208.0, 159)
    assert roofline_seconds(823229153280.0, 702080090112.0, run.peaks) == \
        0.8572406472673992
    assert run.family.work.train_flops_per_token(run.config, 1024) == \
        8168177664.0


def test_a_family_that_counts_no_such_kernel_gives_the_reader_nothing():
    run = _run(_SERVE, 1.0)
    run.family = types.SimpleNamespace(work=types.SimpleNamespace(KERNELS={}))
    read = spec.load_reader("layer_metrics", "paged_attention_roofline").read
    assert read(run) is None
