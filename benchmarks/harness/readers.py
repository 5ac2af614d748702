"""Readings that more than one metric name carries. The contract splits a
quantity whose cells report different end-to-end metrics into one name per
metric moved (``device_idle_pct.serve``, ``device_idle_pct.train``); the
arithmetic is here once, and each name's file is one line."""
from .work import roofline_seconds


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


def kernel_roofline(run, kernel: str):
    """Share of its roofline that ``kernel`` reached: the least time the
    chip could take for the work the run's family counts for it
    (``family.work.KERNELS[kernel]``) over the kernel's device time in the
    trace. Nothing where the family counts no such kernel, the window is of
    another kind, or the trace holds none of its operations."""
    k = run.family.work.KERNELS.get(kernel)
    if k is None or run.record["kind"] != k.kind or run.trace is None \
            or run.peaks is None:
        return None
    kernel_s = run.trace.seconds_of(*k.names)
    if kernel_s <= 0.0:
        return None
    flops, byts, repeats = k.work(run.config, run.record)
    least = roofline_seconds(flops, byts, run.peaks) * repeats / run.chips
    return 100.0 * least / kernel_s
