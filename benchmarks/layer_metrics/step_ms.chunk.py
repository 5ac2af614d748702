"""Step program: mean ``step`` span of the steps that carried a prompt chunk
(``chunk_rows`` > 0 on the span's counters)."""
from benchmarks.harness.program_spans import step_chunk_ms as read  # noqa: F401
