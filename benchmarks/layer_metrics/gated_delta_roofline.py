"""Gated delta-rule kernel (ops/pallas/gated_delta.py): the least time the
chip could take for the state bytes and operations that the window's tokens
need (each decode token and each prompt reads and writes its sequence's
state once a linear layer, every row its own q, k, v and o), as the run's
family counts them, over the kernel's device time in the trace. A family
that counts no such kernel reads nothing."""
from benchmarks.harness.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "gated_delta")
