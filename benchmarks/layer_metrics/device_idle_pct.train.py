"""Device idle share of the train cells (moves ``train_tokens_per_s``)."""
from benchmarks.harness.readers import device_idle_pct as read  # noqa: F401
