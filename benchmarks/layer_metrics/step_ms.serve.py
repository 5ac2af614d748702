"""Step program: window wall time over the engine steps in it."""


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or not rec["engine_steps"]:
        return None
    return 1e3 * rec["wall_s"] / rec["engine_steps"]
