"""All tokens of all steps that finished in the window over the window's
wall time (steps are chained; one ``block_until_ready`` closes it)."""


def read(run):
    rec = run.record
    if rec["kind"] != "train":
        return None
    return rec["tokens"] / rec["wall_s"]
