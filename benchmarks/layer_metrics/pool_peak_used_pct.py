"""Pool: highest ``pool.used_pages`` over ``num_pages``, sampled each step."""


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or not rec["pages_per_step"]:
        return None
    return 100.0 * max(rec["pages_per_step"]) / rec["num_pages"]
