"""The GPT family (``"entry": "gpt"``): the program's ``GPTForCausalLM`` and
the GPT-3 reference. ``benchmarks/families/__init__.py`` says what a family
provides; the train entry also uses this family's own modules."""
from . import work  # noqa: F401
from .model import pool_args, serve_model  # noqa: F401
from .reference import logits_at  # noqa: F401
from .weights import make_weights  # noqa: F401

CONTROLS = ("int8", "fp8")
