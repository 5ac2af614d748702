"""Flash attention forward + backward kernels of the train step: the least
time the chip could take for the steps' causal attention, as the run's
family counts it, over the kernels' device time in the trace."""
from benchmarks.harness.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "flash_attention")
