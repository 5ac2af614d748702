"""Device idle share of the serve cells (moves ``serve_tokens_per_s``)."""
from benchmarks.harness.readers import device_idle_pct as read  # noqa: F401
