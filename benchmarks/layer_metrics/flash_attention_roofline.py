"""Flash attention forward + backward kernels of the train step: the least
time the chip could take for the steps' causal attention over the kernels'
device time in the trace. The program's three ``pallas_call``s carry no
name, so the trace shows them by the name of the traced function."""
from benchmarks.harness import work

# every Mosaic kernel of the train step: it has no other
KERNEL_NAMES = ("mosaic:",)


def read(run):
    rec = run.record
    if rec["kind"] != "train" or run.trace is None or run.peaks is None:
        return None
    kernel_s = run.trace.seconds_of(*KERNEL_NAMES)
    if kernel_s <= 0.0:
        return None
    flops, byts = work.flash_train_work(run.config, rec["batch"], rec["seq_len"])
    least = work.roofline_seconds(flops, byts, run.peaks) * rec["steps"] / run.chips
    return 100.0 * least / kernel_s
