"""Operations and bytes the algorithm needs, counted from shapes.

These are the yardstick of every roofline and utilization share the
benchmark prints: the work the mathematics requires, not what a given
implementation happens to execute (a one-hot matmul, padding to tiles,
recomputation).  A multiply-add counts as two operations; a max-plus
step (add, compare) as two.  Bytes are float32/int32 (4 bytes each).
"""
from __future__ import annotations

F32 = 4


def plane_scores(cap: int, d: int) -> tuple[int, int]:
    """One ``plane_scores`` call over a block's ``cap`` slots: scores
    ``P (cap, d) @ w (d) + b (cap)``.  Returns ``(ops, bytes)``: the
    planes with their offsets and ``w`` are read once."""
    return 2 * cap * d, (cap * (d + 1) + d) * F32


def viterbi_step(B: int, C: int) -> tuple[int, int]:
    """One max-plus step over a batch: ``m_out[b, c] = max_c' m[b, c'] +
    T[c', c]`` with its argmax.  Reads ``m (B, C)`` and ``T (C, C)``,
    writes ``m_out`` and the back pointers ``(B, C)`` each."""
    return 2 * B * C * C, (3 * B * C + C * C) * F32


def chain_oracle(L: float, f: int, C: int) -> float:
    """Loss-augmented Viterbi of one word of ``L`` positions, and its
    plane: unaries ``2 L f C``, the DP ``2 (L-1) C^2``, the joint
    features of the answer and of the truth ``2 L f``."""
    return 2 * L * f * C + 2 * (L - 1) * C * C + 2 * L * f


def block_update(d: int) -> int:
    """One BCFW step: the difference, two dot products, and the two
    convex updates of ``phi_i`` and ``phi``, each over ``d + 1``."""
    return 8 * (d + 1)


def training_iteration(n: int, d: int, oracle_ops: float,
                       approx_passes: int, ws_mean: float) -> float:
    """One MP-BCFW outer iteration and its evaluation: ``n`` exact
    oracles and block updates, ``approx_passes`` passes scoring the
    ``ws_mean`` valid planes of each of ``n`` blocks, and the
    evaluation's ``n`` oracles and ``n`` plane scores."""
    exact = n * (oracle_ops + block_update(d))
    approx = approx_passes * n * (2 * ws_mean * (d + 1) + block_update(d))
    evaluation = n * (oracle_ops + 2 * (d + 1))
    return exact + approx + evaluation


def chain_decode(L: float, f: int, C: int) -> float:
    """A served decode of one word: unaries and the Viterbi DP."""
    return 2 * L * f * C + 2 * (L - 1) * C * C
