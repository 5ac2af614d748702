"""What every work count shares, whatever the model: the roofline's least
time, the causal pairs of a prompt, and the form in which a family says what
one of its kernels has to do. The counts themselves are the family's
(``benchmarks/families/<family>/work.py``): operations and bytes that the
ALGORITHM needs, from the requests' and batches' own lengths — never from a
kernel's grid or an engine's chunking — so that a share of a roofline or of
a peak reads the same work whatever implements it."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple


@dataclasses.dataclass(frozen=True)
class Kernel:
    """One kernel that has a ``<kernel>_roofline`` metric, as its family
    counts it. ``names``: the trace's operation names (prefixes) that are
    this kernel. ``kind``: the kind of window that runs it. ``work(cfg,
    record)`` returns (operations, bytes, repeats): the work of one unit (a
    train step; a serve window as a whole) and how many such units the
    window held; the least time is taken a unit and multiplied."""
    names: Tuple[str, ...]
    kind: str
    work: Callable[[Dict[str, Any], Dict[str, Any]], Tuple[float, float, int]]


def prompt_pairs(new: int, cached: int = 0) -> float:
    """Query-key pairs of ``new`` causal prompt rows that follow ``cached``
    keys already in the cache (the diagonal included)."""
    return new * cached + new * (new + 1) / 2.0


def roofline_seconds(flops: float, byts: float, peaks: Dict[str, float]) -> float:
    return max(flops / peaks["bf16_flops"], byts / peaks["hbm_bytes_per_s"])
