"""The comparison that decides ``correct``: each number beside its limit."""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Compared:
    name: str
    value: float
    limit: Optional[float]      # None: read and printed, not judged

    @property
    def ok(self) -> bool:
        if self.limit is None:
            return True
        return math.isfinite(self.value) and self.value <= self.limit

    def as_json(self):
        return {"value": self.value, "limit": self.limit}


def worst_leaf_gap(program: np.ndarray, ref: np.ndarray) -> float:
    """Worst leaf of |‖program‖ − ‖reference‖| (the gap between the norms,
    not the norm of a difference) over the larger of the reference's norm of
    that leaf and of the median leaf, since some leaves are all but zero."""
    program, ref = np.asarray(program, float), np.asarray(ref, float)
    if program.shape != ref.shape or not program.size:
        return float("inf")
    scale = np.maximum(ref, np.median(ref))
    scale = np.where(scale > 0, scale, 1.0)
    gap = np.abs(program - ref) / scale
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else float("inf")


def all_ok(compared: List[Compared]) -> bool:
    """Every judged number within its limit, and at least one judged: a run
    in which nothing was held to a limit is not a correct run."""
    return (any(c.limit is not None for c in compared)
            and all(c.ok for c in compared))
