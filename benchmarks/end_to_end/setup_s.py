"""Process start to window open: imports, model, seeded weights, compiles
(or their load from the cache), first steps or warm-up, ramp."""


def read(run):
    return run.setup_s
