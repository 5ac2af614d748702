"""Peak HBM share of the serve cells (moves ``serve_tokens_per_s``)."""
from benchmarks.harness.readers import peak_hbm_pct as read  # noqa: F401
