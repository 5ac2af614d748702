"""Batched multi-LoRA serving: stacked rank-r adapter weights that ride
the unified serving step as DATA (ISSUE 16).

An :class:`AdapterStore` holds up to ``capacity`` named LoRA adapters
for every projection site the trunk exposes (``model.lora_sites()``),
stacked along a leading adapter axis::

    A[site]: [capacity, n_layers, rank, in_dim ]
    B[site]: [capacity, n_layers, out_dim, rank]

Slot 0 is RESERVED as the zero-delta identity: its weights are all
zeros, so a request with no adapter (``adapter_id=None`` → slot 0)
computes ``base(x) + B0 @ (A0 @ x) == base(x) + 0`` — bit-identical to
a store-less engine. Registration is a pure VALUE write
(``.at[slot].set(...)``): shapes never change, so the compiled step —
which takes the stacked arrays as arguments and gathers each grid
row's adapter by index — never recompiles. That is the whole trick:
like seeds (PR 7), chunk rows (PR 11), and draft rows (PR 14), tenancy
is data, not program (docs/SERVING.md "Multi-LoRA adapters").
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

__all__ = ["AdapterStore", "random_adapter"]


class AdapterStore:
    """Named rank-r LoRA (A, B) pairs, stacked per projection site.

    ``sites`` is an ordered sequence of ``(name, in_dim, out_dim)``
    triples — one entry per projection the trunk offers a delta at,
    shared across layers (the layer axis is inside each array). The
    fixed site order is the contract with the compiled step:
    :meth:`arrays` flattens ``[A, B]`` per site in exactly this order,
    every step, whether or not any adapter is registered.
    """

    def __init__(self, sites: Sequence[Tuple[str, int, int]],
                 num_layers: int, rank: int = 4, capacity: int = 4,
                 dtype=jnp.float32):
        if capacity < 2:
            raise ValueError(
                f"capacity must be >= 2 (slot 0 is the reserved "
                f"zero-delta identity), got {capacity}")
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        # a trunk may offer no site: its steps carry no adapter arrays and
        # register() refuses
        self.sites = tuple((str(n), int(i), int(o)) for n, i, o in sites)
        self.num_layers = int(num_layers)
        self.rank = int(rank)
        self.capacity = int(capacity)
        self.dtype = dtype
        self._A: Dict[str, jnp.ndarray] = {}
        self._B: Dict[str, jnp.ndarray] = {}
        for name, d_in, d_out in self.sites:
            self._A[name] = jnp.zeros(
                (self.capacity, self.num_layers, self.rank, d_in), dtype)
            self._B[name] = jnp.zeros(
                (self.capacity, self.num_layers, d_out, self.rank), dtype)
        # slot 0 is the identity and is never in this map
        self._slots: Dict[str, int] = {}

    @classmethod
    def from_model(cls, model, rank: int = 4, capacity: int = 4,
                   dtype=jnp.float32) -> "AdapterStore":
        """Build a store shaped for ``model`` via its ``lora_sites()``
        contract: ``(sites, num_layers)`` with sites as
        ``(name, in_dim, out_dim)`` triples in trunk order."""
        sites, num_layers = model.lora_sites()
        return cls(sites, num_layers, rank=rank, capacity=capacity,
                   dtype=dtype)

    # ------------------------------------------------------------ registry
    def register(self, name: str, weights: Dict[str, tuple]) -> int:
        """Install (or hot-swap) adapter ``name``: ``weights`` maps each
        site name to an ``(A, B)`` pair with shapes
        ``[n_layers, rank, in_dim]`` / ``[n_layers, out_dim, rank]``.
        Every site must be present (a site with no delta is all-zero —
        explicitness beats a silent partial adapter). Returns the slot.

        The write is ``.at[slot].set(value)`` per array: same shapes,
        same dtypes — the compiled step that consumes these arrays is
        untouched, which is what makes fleet-wide hot-load recompile-
        free (``compile_counts()`` pins it)."""
        if name is None or name == "":
            raise ValueError("adapter name must be a non-empty string "
                             "(None means 'no adapter', slot 0)")
        if not self.sites:
            raise ValueError(f"adapter {name!r}: this model offers no LoRA "
                             "projection site")
        missing = [s for s, _, _ in self.sites if s not in weights]
        if missing:
            raise ValueError(
                f"adapter {name!r} missing sites {missing}; provide an "
                "all-zero (A, B) pair for sites without a delta")
        slot = self._slots.get(name)
        if slot is None:
            used = set(self._slots.values())
            free = [s for s in range(1, self.capacity) if s not in used]
            if not free:
                raise ValueError(
                    f"adapter store full ({self.capacity - 1} slots, "
                    f"holding {sorted(self._slots)}); unregister one or "
                    "raise adapter_capacity")
            slot = free[0]
        staged = []
        for site, d_in, d_out in self.sites:
            A, B = weights[site]
            A = np.asarray(A, self.dtype)
            B = np.asarray(B, self.dtype)
            want_a = (self.num_layers, self.rank, d_in)
            want_b = (self.num_layers, d_out, self.rank)
            if A.shape != want_a or B.shape != want_b:
                raise ValueError(
                    f"adapter {name!r} site {site!r}: expected A "
                    f"{want_a} / B {want_b}, got {A.shape} / {B.shape}")
            staged.append((site, A, B))
        # validate-then-write: a bad site above must not leave a
        # half-installed adapter behind
        for site, A, B in staged:
            self._A[site] = self._A[site].at[slot].set(A)
            self._B[site] = self._B[site].at[slot].set(B)
        self._slots[name] = slot
        return slot

    def unregister(self, name: str) -> None:
        """Zero the adapter's slot and free it. The zero write means a
        stale index racing the unregister degrades to the identity
        delta, never another tenant's weights."""
        slot = self._slots.pop(name)
        for site, _, _ in self.sites:
            self._A[site] = self._A[site].at[slot].set(0.0)
            self._B[site] = self._B[site].at[slot].set(0.0)

    # ------------------------------------------------------------- lookups
    def slot(self, name: Optional[str]) -> int:
        """``name`` → stacked-array index; ``None`` is the identity."""
        if name is None:
            return 0
        slot = self._slots.get(name)
        if slot is None:
            raise KeyError(
                f"adapter {name!r} not registered here (holding "
                f"{sorted(self._slots)})")
        return slot

    def holds(self, name: Optional[str]) -> bool:
        """True iff this store can serve ``name`` — what Router's
        ``select()`` filters placement on. Every store holds ``None``."""
        return name is None or name in self._slots

    def names(self) -> Tuple[str, ...]:
        return tuple(sorted(self._slots))

    def arrays(self) -> List[jnp.ndarray]:
        """The step's adapter arguments: ``[A, B]`` per site in the
        fixed site order — stable length and shapes for the life of the
        engine."""
        out: List[jnp.ndarray] = []
        for site, _, _ in self.sites:
            out.append(self._A[site])
            out.append(self._B[site])
        return out

    def __repr__(self) -> str:
        return (f"AdapterStore(sites={len(self.sites)}, "
                f"layers={self.num_layers}, rank={self.rank}, "
                f"capacity={self.capacity}, holding={list(self.names())})")


def random_adapter(store: AdapterStore, seed: int,
                   scale: float = 0.02) -> Dict[str, tuple]:
    """A seeded random weight dict shaped for ``store`` — tests, the
    bench drill, and the metrics demo all need *some* non-zero adapter;
    ``scale`` keeps the delta small enough that tiny models stay
    finite."""
    rng = np.random.default_rng(seed)
    out: Dict[str, tuple] = {}
    for site, d_in, d_out in store.sites:
        A = rng.standard_normal(
            (store.num_layers, store.rank, d_in)).astype(np.float32)
        B = rng.standard_normal(
            (store.num_layers, d_out, store.rank)).astype(np.float32)
        out[site] = (A * scale, B * scale)
    return out
