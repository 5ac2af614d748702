"""Scheduler: active slots over ``max_batch_slots``, averaged over the
window's engine steps."""


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or not rec["running_per_step"]:
        return None
    per = rec["running_per_step"]
    return 100.0 * sum(per) / (len(per) * rec["slots"])
