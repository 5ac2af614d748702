"""Paged attention kernel (ops/pallas/paged_attention.py): the least time the
chip could take for the K/V bytes and operations that the window's tokens
need at their own contexts, as the run's family counts them, over the
kernel's device time in the trace."""
from benchmarks.harness.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "paged_attention")
