"""Multi-plane block-coordinate Frank-Wolfe, written out plainly.

Paper Alg. 3 (Shah, Kolmogorov, Lampert 2015): each outer iteration
drops cached planes not returned by an oracle within the last ``ttl``
iterations, runs one exact pass (for each block in the pass's order:
call the exact max-oracle at the current ``w``, take the BCFW step with
exact line search, cache the returned plane in the block's first free
slot, or over the slot idle longest), then the given number of
approximate passes (the same step, with the best cached plane of the
block as the oracle).  Planes are cached here as labelings and scored
from them, which is the same plane in exact arithmetic.

The pass schedule (the orders, and how many approximate passes each
iteration ran) is an input: it is decided by a wall-clock rule in the
program under test, and the reference follows it.

``fault`` plants a known fault, for the readings that set a limit's
upper end: ``"half"`` runs each exact pass over half of its blocks,
``"alter"`` corrupts every exact oracle answer where it is produced.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

NEG = -1e30


@dataclass
class RefRow:
    dual: float
    primal: float

    @property
    def gap(self) -> float:
        return self.primal - self.dual


@dataclass
class RefRun:
    rows: List[RefRow] = field(default_factory=list)
    first_planes_norm: float = 0.0   # ||planes of the first exact pass||_F
    w_norm: float = 0.0              # ||w|| after the last iteration


def run(task, lam: float, cap: int, ttl: int,
        schedule: Sequence[Tuple[np.ndarray, Sequence[np.ndarray]]],
        fault: Optional[str] = None) -> RefRun:
    """Run ``len(schedule)`` outer iterations; ``schedule[k]`` is
    ``(exact_perm, [approx_perm, ...])``."""
    q = task.prec.q
    n, d = task.n, task.d
    phi_i = np.zeros((n, d + 1), task.prec.dtype)
    phi = np.zeros(d + 1, task.prec.dtype)
    lab_len = max(len(task.truth(i)) for i in range(n))
    slot_y = np.zeros((n, cap, lab_len), np.int64)
    valid = np.zeros((n, cap), bool)
    last = np.full((n, cap), -1, np.int64)
    out = RefRun()
    first_sq = 0.0

    def step(i, plane):
        nonlocal phi
        diff = q(phi_i[i] - plane)
        num = q(q(diff[:-1] @ phi[:-1]) - q(lam * diff[-1]))
        den = q(diff[:-1] @ diff[:-1])
        gamma = float(np.clip(q(num / den), 0.0, 1.0)) if den > 0 else 0.0
        new = q((1.0 - gamma) * phi_i[i] + gamma * plane)
        phi = q(phi + q(new - phi_i[i]))
        phi_i[i] = new

    for k, (perm, approx_perms) in enumerate(schedule):
        it = k + 1
        valid &= (it - last) <= ttl
        exact = perm[: n // 2] if fault == "half" else perm
        for i in exact:
            i = int(i)
            w = q(-phi[:-1] / lam)
            yy = task.decode(i, w)
            if fault == "alter":
                yy = task.alter(i, yy)
            plane = task.plane(i, yy)
            if k == 0:
                first_sq += float(plane @ plane)
            step(i, plane)
            keys = np.where(valid[i], last[i], -2 ** 31 + 1)
            s = int(np.argmin(keys))
            slot_y[i, s, : len(yy)] = yy
            valid[i, s] = True
            last[i, s] = it
        for aperm in approx_perms:
            for i in aperm:
                i = int(i)
                w = q(-phi[:-1] / lam)
                if valid[i].any():
                    L = len(task.truth(i))
                    slots = np.flatnonzero(valid[i])
                    sc = np.full(cap, NEG)
                    sc[slots] = task.scores(i, w, slot_y[i, slots, :L])
                    s = int(np.argmax(sc))
                    plane = task.plane(i, slot_y[i, s, :L])
                else:
                    s = 0
                    plane = np.zeros(d + 1, task.prec.dtype)
                step(i, plane)
                last[i, s] = it
        w = q(-phi[:-1] / lam)
        dual = float(q(q(-q(phi[:-1] @ phi[:-1]) / (2.0 * lam)) + phi[-1]))
        primal = float(q(q(0.5 * lam * q(w @ w)) + task.hinge_sum(w)))
        out.rows.append(RefRow(dual=dual, primal=primal))
        if k == 0:
            out.first_planes_norm = float(np.sqrt(first_sq))
        out.w_norm = float(np.sqrt(w @ w))
    return out
