"""Kernels: sum of ``kv_walked`` over sum of ``kv_held`` of the window's ``step``
spans: how often the paged kernel's grid walks a key that exists once."""
from benchmarks.harness.program_spans import kv_walk_amplification as read  # noqa: F401
