"""Serving entry of the Olmo-Hybrid family: the harness's closed loop over
the family's model, pool and reference."""
from ..harness import serve_loop


def build(ctx) -> serve_loop.ServeRun:
    return serve_loop.ServeRun(ctx, ctx.family)
