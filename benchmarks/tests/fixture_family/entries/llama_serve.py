"""Serving entry of the second family: the harness's closed loop over it."""
from ..harness import serve_loop


def build(ctx) -> serve_loop.ServeRun:
    return serve_loop.ServeRun(ctx, ctx.family)
