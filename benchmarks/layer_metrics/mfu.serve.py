"""Whole-step share of the chips' bf16 peak: the operations the served
tokens require, as the run's family counts them (weights for the rows that
are new, attention at each token's own context, its session's cached keys
included, the head where a token is sampled), over window x chips x peak."""


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or run.peaks is None or not rec["tokens"]:
        return None
    cfg, work = run.config, run.family.work
    flops = 0.0
    for p, c in zip(rec["prefill_lens"], rec["prefill_cached"], strict=True):
        flops += work.prompt_flops(cfg, p, c)
    for c in rec["decode_contexts"]:
        flops += work.decode_flops(cfg, c)
    return 100.0 * flops / (rec["wall_s"] * run.chips * run.peaks["bf16_flops"])
