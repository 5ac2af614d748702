"""The work-counting functions against sums made by hand."""
from benchmarks.harness import work

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
    assert work.roofline_seconds(200.0, 10.0, peaks) == 2.0
    assert work.roofline_seconds(200.0, 50.0, peaks) == 5.0
