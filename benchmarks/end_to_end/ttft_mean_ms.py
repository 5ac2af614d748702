"""Mean, over all requests whose first token landed in the window, of
first-token time minus submit time (the client's clock, queue wait
included). A window holds some tens of requests, which allows no tail: ten
samples must lie beyond a percentile."""


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or not rec["ttft_s"]:
        return None
    return 1e3 * sum(rec["ttft_s"]) / len(rec["ttft_s"])
