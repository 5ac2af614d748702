"""Scheduler: median submit -> admit of the requests admitted in the window,
from the program's request-trace ring (serving/tracing.py) on the host
clock."""
import statistics


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or not rec["queue_wait_s"]:
        return None
    return 1e3 * statistics.median(rec["queue_wait_s"])
