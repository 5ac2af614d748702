"""Peak HBM share of the train cells (moves ``train_tokens_per_s``)."""
from benchmarks.harness.readers import peak_hbm_pct as read  # noqa: F401
