"""Whole-step share of the chips' bf16 peak: the operations the served
tokens require (weights for the rows that are new, attention at each token's
own context, its session's cached keys included, the head where a token is
sampled) over window x chips x peak."""
from benchmarks.harness import work


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or run.peaks is None or not rec["tokens"]:
        return None
    cfg = run.config
    blocks, head = work.matmul_params(cfg)
    flops = 0.0
    h_layers = int(cfg["hidden_size"]) * int(cfg["num_hidden_layers"])
    for p, c in zip(rec["prefill_lens"], rec["prefill_cached"], strict=True):
        flops += 2.0 * blocks * p + 2.0 * head + \
            4.0 * work.prompt_pairs(p, c) * h_layers
    for c in rec["decode_contexts"]:
        flops += work.serve_token_flops(cfg, c, sampled=True)
    return 100.0 * flops / (rec["wall_s"] * run.chips * run.peaks["bf16_flops"])
