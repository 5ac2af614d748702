"""Step program: sum of ``rows`` over sum of ``bucket`` of the window's ``step``
spans: the share of the padded token grid that was real rows."""
from benchmarks.harness.program_spans import grid_fill_pct as read  # noqa: F401
