"""Set-up: seconds of the run's ``param.init`` spans on the program's trace ring."""
from benchmarks.layer_metrics.program_parts import setup_param_init_s as read  # noqa: F401
