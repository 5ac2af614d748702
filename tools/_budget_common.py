"""Shared helpers for the compile-only memory-budget tools
(llama7b_budget.py, gpt13_budget.py)."""
from __future__ import annotations

import os
import re
import sys


def reexec_cpu(child_env_flag: str, n_devices: int | None = None) -> None:
    """Re-exec into a CPU-pinned env (optional virtual-device count) — same
    pattern as __graft_entry__.dryrun_multichip. The budget tools compile
    and never run, so they never need the chip."""
    if os.environ.get(child_env_flag) == "1":
        return
    env = dict(os.environ)
    env[child_env_flag] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    if n_devices is not None:
        flags += f" --xla_force_host_platform_device_count={n_devices}"
    env["XLA_FLAGS"] = flags.strip()
    os.execve(sys.executable, [sys.executable, "-u"] + sys.argv, env)


def zero_init_parameters() -> None:
    """Patch Layer.create_parameter to zero-init: multi-billion-param fp32
    RNG normals on one core are minutes of wasted compute, and the values
    never matter — nothing executes in a compile-only budget."""
    import jax.numpy as jnp

    from paddle_tpu import dtypes
    from paddle_tpu.nn.layer_base import Layer
    from paddle_tpu.nn.param_attr import ParamAttr
    from paddle_tpu.tensor import Parameter

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        a = ParamAttr._to_attr(attr)
        if a is False:
            return None
        dt = dtypes.convert_dtype(dtype) or self._dtype
        p = Parameter(jnp.zeros(tuple(int(s) for s in shape), dt),
                      trainable=not (a is not None and not a.trainable),
                      name=(a.name if a is not None and a.name else None))
        if a is not None:
            p.optimize_attr["learning_rate"] = a.learning_rate
            p.regularizer = a.regularizer
        return p

    Layer.create_parameter = create_parameter
