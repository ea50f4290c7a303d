"""The evaluation program: primal, dual and primal at the average.

``evaluate_objectives`` runs one cached jitted program per oracle, ``n``,
``lam`` and averaging on/off, and fetches its three objectives at once.
These tests hold it to the eager objective helpers of
:mod:`repro.core.ssvm`, for every oracle family, and check that it
compiles nothing after its first call.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RunConfig, Solver, evaluate_objectives
from repro.api.solver import ssg_primal
from repro.core.averaging import extract
from repro.core.ssvm import dual_value, primal_value, weights_of
from repro.core.types import AveragingState
from repro.obs import spans

RTOL = 1e-5   # float32: the fused program sums in another order


def _point(problem, seed: int):
    """A dual vector and a two-track average of modest scale."""
    r = np.random.RandomState(seed)

    def vec():
        return jnp.asarray(0.05 * r.randn(problem.d + 1).astype(np.float32))
    avg = AveragingState(bar_exact=vec(), bar_approx=vec(),
                         k_exact=jnp.asarray(3, jnp.int32),
                         k_approx=jnp.asarray(5, jnp.int32))
    return vec(), avg


def _eager_primal(problem, phi, lam):
    return float(primal_value(problem, weights_of(phi, lam), lam))


@pytest.mark.parametrize("averaged", [False, True], ids=["plain", "avg"])
@pytest.mark.parametrize("family", ["chain", "multiclass", "graph"])
def test_jitted_objectives_match_eager_helpers(request, family, averaged):
    problem = request.getfixturevalue(f"{family}_problem")
    lam = 1.0 / problem.n
    phi, avg = _point(problem, seed=len(family))
    primal, dual, primal_avg = evaluate_objectives(
        problem, phi, avg if averaged else None, lam)
    assert all(isinstance(v, float) for v in (primal, dual, primal_avg))
    assert primal == pytest.approx(_eager_primal(problem, phi, lam),
                                   rel=RTOL)
    assert dual == pytest.approx(float(dual_value(phi, lam)), rel=RTOL)
    want_avg = (_eager_primal(problem, extract(avg, lam), lam) if averaged
                else primal)
    assert primal_avg == pytest.approx(want_avg, rel=RTOL)


@pytest.mark.parametrize("family", ["chain", "multiclass", "graph"])
def test_ssg_primal_matches_eager_helper(request, family):
    problem = request.getfixturevalue(f"{family}_problem")
    lam = 1.0 / problem.n
    w = jnp.asarray(0.1 * np.random.RandomState(7).randn(problem.d)
                    .astype(np.float32))
    want = float(primal_value(problem, w, lam))
    assert ssg_primal(problem, w, lam) == pytest.approx(want, rel=RTOL)


@pytest.mark.parametrize("averaged", [False, True], ids=["plain", "avg"])
def test_second_evaluation_compiles_nothing(chain_problem, averaged):
    lam = 1.0 / chain_problem.n
    phi, avg = _point(chain_problem, seed=11)
    avg = avg if averaged else None
    first = evaluate_objectives(chain_problem, phi, avg, lam)
    phi2 = 2.0 * phi                            # its own op, compiled here
    c0 = spans.compile_count()
    again = evaluate_objectives(chain_problem, phi2, avg, lam)
    assert spans.compile_count() == c0
    assert again != first                       # it did evaluate anew


def test_solver_rows_after_the_first_compile_nothing(chain_problem):
    # approx_batch covers max_approx_passes, so no row needs the
    # overflow program; wall-clock mode, so rows report their compiles.
    cfg = RunConfig(lam=1.0 / chain_problem.n, algo="mpbcfw", cap=8,
                    max_iters=4, max_approx_passes=4, approx_batch=4,
                    seed=3)
    rows = Solver(chain_problem, cfg).run().trace
    assert rows[0].compiles > 0
    assert [r.compiles for r in rows[1:]] == [0] * (len(rows) - 1)
