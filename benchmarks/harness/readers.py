"""Readings that more than one metric name carries. The contract splits a
quantity whose cells report different end-to-end metrics into one name per
metric moved (``device_idle_pct.serve``, ``device_idle_pct.train``); the
arithmetic is here once, and each name's file is one line."""


def device_idle_pct(run):
    """1 - union of device-operation intervals over the traced window."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def peak_hbm_pct(run):
    """``peak_bytes_in_use`` of the fullest chip over its ``bytes_limit``,
    read after the window and before the reference runs."""
    peak, limit = (run.device.get("memory_peak_bytes"),
                   run.device.get("memory_limit_bytes"))
    if not peak or not limit:
        return None
    return 100.0 * peak / limit
