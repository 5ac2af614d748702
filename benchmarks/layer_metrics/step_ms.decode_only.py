"""Step program: mean ``step`` span of the steps with no chunk row
(``chunk_rows`` == 0 on the span's counters)."""
from benchmarks.harness.program_spans import step_decode_only_ms as read  # noqa: F401
