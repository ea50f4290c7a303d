"""What a driver hands back to ``run.py``."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Compared:
    """One number of the correctness check, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]      # every end-to-end value measured
    ctx: dict                         # what the per-layer readers read
    compared: List[Compared] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)   # earlier lines
    trace: Optional[dict] = None      # neutral trace (traced runs)
    trace_window_s: Optional[float] = None

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(c.ok for c in self.compared)


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> List[Compared]:
    """Each value that has a limit, beside it.  A value that is not a
    number (a reference or a program that gave none) fails."""
    out = []
    for name, limit in limits.items():
        v = values.get(name, float("nan"))
        v = float(v)
        out.append(Compared(name, v if v == v else float("inf"),
                            float(limit)))
    return out
