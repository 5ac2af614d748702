"""Scheduler: median, over the requests whose first token landed in the
window, of first-token time minus submit time. It stands beside the
end-to-end mean because it is not steady: a prompt takes one chunk step or
two, and where half the prompts take each the median hops between the two
(274 ms against 444 ms on three seeds, my chip run, PR 24)."""
import statistics


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or not rec["ttft_s"]:
        return None
    return 1e3 * statistics.median(rec["ttft_s"])
