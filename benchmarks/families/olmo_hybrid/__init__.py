"""The Olmo-Hybrid family (``"entry": "olmo_hybrid"``): the program's
``OlmoHybridForCausalLM`` (gated delta-rule layers and full attention layers
in one stack) and a plain float32 reference that runs one layer at a time.
``benchmarks/families/__init__.py`` says what a family provides."""
from . import work  # noqa: F401
from .model import pool_args, serve_model  # noqa: F401
from .reference import logits_at  # noqa: F401
from .weights import make_weights  # noqa: F401

CONTROLS = ("fp8",)
