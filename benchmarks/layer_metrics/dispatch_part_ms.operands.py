"""Host loop: mean over the window's steps of their ``step.operands`` spans, the
children of ``step.dispatch`` (the program's trace ring)."""
from benchmarks.layer_metrics.program_parts import dispatch_operands_ms as read  # noqa: F401
