"""Paged attention kernel (ops/pallas/paged_attention.py, ``name=
"paged_attention"``): the least time the chip could take for the K/V bytes
and operations that the window's tokens need at their own contexts, over the
kernel's device time in the trace."""
from benchmarks.harness import work

KERNEL_NAMES = ("mosaic:paged_attention",)


def read(run):
    rec = run.record
    if rec["kind"] != "serve" or run.trace is None or run.peaks is None:
        return None
    kernel_s = run.trace.seconds_of(*KERNEL_NAMES)
    if kernel_s <= 0.0:
        return None
    flops, byts = work.paged_attention_work(
        run.config, rec["prefill_lens"], rec["decode_contexts"],
        rec["prefill_cached"])
    least = work.roofline_seconds(flops, byts, run.peaks) / run.chips
    return 100.0 * least / kernel_s
