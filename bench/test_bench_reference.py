"""The plain NumPy references against brute force at tiny sizes."""
import itertools

import numpy as np
import pytest

from benchkit.reference import BF16, F64
from benchkit.reference import mpbcfw
from benchkit.reference.chain import (ChainTask, path_score, viterbi,
                                      viterbi_batch)


def brute_best(unary, trans):
    L, C = unary.shape
    best, arg = -np.inf, None
    for y in itertools.product(range(C), repeat=L):
        s = path_score(unary, trans, np.asarray(y))
        if s > best:
            best, arg = s, np.asarray(y)
    return arg, best


@pytest.mark.parametrize("L,C", [(1, 3), (2, 4), (4, 3), (5, 2)])
def test_viterbi_matches_brute_force(L, C):
    rng = np.random.default_rng(L * 10 + C)
    for _ in range(5):
        u, t = rng.normal(size=(L, C)), rng.normal(size=(C, C))
        y, s = viterbi(u, t)
        want_y, want_s = brute_best(u, t)
        assert s == pytest.approx(want_s, abs=1e-12)
        assert path_score(u, t, y) == pytest.approx(want_s, abs=1e-12)
        np.testing.assert_array_equal(y, want_y)


def test_viterbi_batch_matches_per_example():
    rng = np.random.default_rng(0)
    B, Lmax, C = 7, 6, 4
    u = rng.normal(size=(B, Lmax, C))
    t = rng.normal(size=(C, C))
    lengths = rng.integers(1, Lmax + 1, size=B)
    got = viterbi_batch(u, t, lengths)
    for b in range(B):
        want, _ = viterbi(u[b, : lengths[b]], t)
        np.testing.assert_array_equal(got[b, : lengths[b]], want)
        assert not got[b, lengths[b]:].any()


def tiny_chain(n=4, Lmax=4, f=3, C=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, Lmax, f))
    y = rng.integers(0, C, size=(n, Lmax))
    lengths = rng.integers(2, Lmax + 1, size=n)
    mask = np.arange(Lmax)[None] < lengths[:, None]
    return ChainTask(np.where(mask[..., None], x, 0), np.where(mask, y, 0),
                     mask, C)


def test_chain_hinge_and_planes_match_brute_force():
    task = tiny_chain()
    w = np.random.default_rng(1).normal(size=task.d)
    total = 0.0
    for i in range(task.n):
        L = task.lengths[i]
        ys = np.asarray(list(itertools.product(range(task.C), repeat=L)))
        scores = task.scores(i, w, ys)
        # the plane algebra: <phi^{iy}, [w 1]> from the dense plane
        for yy, s in zip(ys[:5], scores[:5]):
            p = task.plane(i, yy)
            assert p[:-1] @ w + p[-1] == pytest.approx(s, abs=1e-12)
        best = scores.max()
        assert task.scores(i, w, task.decode(i, w)[None])[0] == \
            pytest.approx(best, abs=1e-12)
        total += best
    assert task.hinge_sum(w) == pytest.approx(total, abs=1e-12)


def run_reference(task, iters=3, prec=F64, fault=None, seed=0):
    rng = np.random.RandomState(seed)
    sched = [(rng.permutation(task.n), [rng.permutation(task.n)])
             for _ in range(iters)]
    task.prec = prec
    return mpbcfw.run(task, 1.0 / task.n, cap=4, ttl=2, schedule=sched,
                      fault=fault)


def test_reference_mpbcfw_dual_rises_and_bounds_the_primal():
    task = tiny_chain(n=6)
    out = run_reference(task, iters=4)
    duals = [r.dual for r in out.rows]
    assert all(b >= a - 1e-12 for a, b in zip(duals, duals[1:]))
    assert all(r.gap >= -1e-12 for r in out.rows)
    # the dual's optimum over brute force is the primal's: weak duality
    assert duals[-1] <= min(r.primal for r in out.rows) + 1e-12


def test_reference_faults_and_bf16_depart_from_the_reference():
    task = tiny_chain(n=8, f=6, seed=5)
    ref = run_reference(task)
    for kw in ({"fault": "half"}, {"fault": "alter"}, {"prec": BF16}):
        got = run_reference(tiny_chain(n=8, f=6, seed=5), **kw)
        assert max(abs(a.dual - b.dual) / abs(b.dual)
                   for a, b in zip(got.rows, ref.rows)) > 1e-4, kw
