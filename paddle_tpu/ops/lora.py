"""The per-row LoRA delta of batched multi-adapter serving (the stacks it
gathers from are ``serving/adapters.py``'s; docs/SERVING.md "Multi-LoRA
adapters")."""
from __future__ import annotations

import jax.numpy as jnp

from ._apply import apply_op, ensure_tensor

__all__ = ["lora_delta"]


def lora_delta(x, A, B, layer: int):
    """The fused per-row LoRA delta, applied inside the compiled step:
    ``delta[t] = B[t, layer] @ (A[t, layer] @ x[t])`` where ``A``/``B``
    are the PER-ROW gathered stacks (``[T, L, rank, in]`` /
    ``[T, L, out, rank]``) and ``layer`` is a Python constant baked into
    the trace. Rows pointing at slot 0 contribute exactly zero — the
    bit-identity guarantee for non-adapter tenants. One traced op per
    site per layer; XLA fuses the two small einsums into the
    surrounding projection."""
    def fn(xv, av, bv):
        al = av[:, layer]                       # [T, rank, in]
        bl = bv[:, layer]                       # [T, out, rank]
        h = jnp.einsum("tri,tsi->tsr", al, xv.astype(al.dtype))
        return jnp.einsum("tor,tsr->tso", bl, h).astype(xv.dtype)

    return apply_op(fn, [ensure_tensor(x), ensure_tensor(A),
                         ensure_tensor(B)], name="lora_delta")
