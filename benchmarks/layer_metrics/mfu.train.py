"""Whole-step share of the chips' bf16 peak: forward and backward operations
per token as the run's family counts them (attention included, recomputation
excluded) x tokens/s."""


def read(run):
    rec = run.record
    if rec["kind"] != "train" or run.peaks is None:
        return None
    flops = run.family.work.train_flops_per_token(run.config, rec["seq_len"]) \
        * rec["tokens"]
    return 100.0 * flops / (rec["wall_s"] * run.chips * run.peaks["bf16_flops"])
