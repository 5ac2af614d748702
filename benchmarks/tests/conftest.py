"""The harness's own tests run on the CPU at a tiny size: set the platform
before JAX is imported, and put the checkout on the path."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
