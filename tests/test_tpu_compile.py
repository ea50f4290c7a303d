"""Compile rehearsals for one TPU v5e chip, at the sizes the chip runs.

Nothing here runs on a TPU: XLA's TPU compiler compiles for a described
``v5e:2x2`` topology, which refuses what the chip would refuse (unaligned
tiles, too much VMEM, a program larger than HBM).  Each kernel case
asserts that the Pallas kernel really lowered to a ``tpu_custom_call``.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports this
file.  Code that asks ``jax.default_backend()`` still sees the CPU here,
so the fused-program case steers ``kernels.ops.use_pallas`` itself to
trace the branch the chip takes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.configs import paper
from repro.core import mpbcfw
from repro.core.types import SSVMProblem
from repro.kernels import approx_steps, gram, ops, plane_scores, viterbi

# What the TPU compiler reports as one v5e chip's HBM.
V5E_HBM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: its
    entries for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _row_major(one_chip, ndim):
    """The chip's own layout: dimensions major to minor in order.  A
    described topology's default layouts reverse them, which would add
    relayout copies of the plane cache that the chip never makes."""
    return Format(Layout(tuple(range(ndim))), one_chip)


def _rm(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=_row_major(one_chip, len(shape)))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(64, 4004), (64, 1298)])
def test_plane_scores_compiles(one_chip, shape):
    n, d = shape
    _assert_kernel(plane_scores.plane_scores.lower(
        _sds(one_chip, (n, d)), _sds(one_chip, (d,)),
        _sds(one_chip, (n,))).compile())


@pytest.mark.parametrize("batch", [8, 32])
def test_viterbi_decode_batch_compiles(one_chip, batch):
    L, C = paper.OCR.max_len, paper.OCR.num_classes
    _assert_kernel(jax.jit(viterbi.viterbi_decode_batch).lower(
        _sds(one_chip, (batch, L, C)), _sds(one_chip, (C, C)),
        _sds(one_chip, (batch, L), bool)).compile())


def test_gram_compiles(one_chip):
    _assert_kernel(gram.gram.lower(_sds(one_chip, (64, 4005))).compile())


@pytest.mark.parametrize("track_gap", [False, True], ids=["plain", "gap"])
def test_approx_block_steps_compiles(one_chip, track_gap):
    """One approximate pass as one kernel at the OCR size: its plane
    tiles, ``phi_i`` row groups and the gap vector's SMEM copy compile."""
    n, cap, d = paper.OCR.n, 64, 4004
    assert approx_steps.fits(n, cap, d, n)
    _assert_kernel(approx_steps.approx_block_steps.lower(
        _rm(one_chip, (n, cap, d + 1)), _rm(one_chip, (n, cap), bool),
        _rm(one_chip, (n,), jnp.int32), _rm(one_chip, (d + 1,)),
        _rm(one_chip, (n, d + 1)), _rm(one_chip, (d + 1,)),
        _rm(one_chip, (), jnp.int32),
        _rm(one_chip, (n,)) if track_gap else None, lam=1.0 / n).compile())


def _tiny_ocr_oracle():
    from repro.trainer.ssvm_head import build_problem

    return build_problem(dataclasses.replace(paper.OCR, n=2)).oracle


def test_fused_ocr_iteration_fits_one_chip(one_chip, monkeypatch):
    """The donated fused MP-BCFW outer iteration at the paper's OCR size
    (n=6877, d=4004, cap=64) fits one chip's HBM, runs each approximate
    pass as the ``approx_block_steps`` kernel, needs under 64 MB of
    temporaries beside the state, and hands the whole input state to
    its output.  Inputs and outputs take the chip's row-major layouts."""
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    sc = paper.OCR
    n, L, f = sc.n, sc.max_len, sc.f
    d = sc.num_classes * (f + sc.num_classes)
    # The oracle is a static argument: only its code matters, so a
    # 2-example problem supplies it.
    oracle = _tiny_ocr_oracle()
    data = {"x": _rm(one_chip, (n, L, f)),
            "y": _rm(one_chip, (n, L), jnp.int32),
            "mask": _rm(one_chip, (n, L), bool)}
    state = jax.eval_shape(lambda: mpbcfw.init_mp_state(
        SSVMProblem(n=n, d=d, data=None, oracle=None), 64))
    state = jax.tree_util.tree_map(
        lambda a: _rm(one_chip, a.shape, a.dtype), state)
    assert state.cache.planes.shape == (n, 64, d + 1)
    clock = jax.tree_util.tree_map(
        lambda a: _rm(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: mpbcfw.make_slope_clock(0.0, 0.0, 1.0,
                                                       1e-3)))
    args = (data, state, _rm(one_chip, (n,), jnp.int32),
            _rm(one_chip, (5, n), jnp.int32), clock, None)
    kw = dict(lam=1.0 / n, ttl=10, steps=10, run_all=False)
    out = jax.eval_shape(
        lambda *a: mpbcfw._outer_program(oracle, n, *a, **kw), *args)
    program = jax.jit(
        mpbcfw._outer_program, donate_argnames=("mp",),
        out_shardings=jax.tree_util.tree_map(
            lambda a: _row_major(one_chip, a.ndim), out),
        **mpbcfw._OUTER_STATIC)
    compiled = program.lower(oracle, n, *args, **kw).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total <= V5E_HBM_BYTES, mem
    assert mem.temp_size_in_bytes < 64e6, mem
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes, mem
    _assert_kernel(compiled)
    assert "approx_block_steps" in compiled.as_text()


@pytest.mark.parametrize("averaged", [False, True], ids=["plain", "avg"])
def test_ocr_evaluation_fits_beside_the_cache(one_chip, monkeypatch,
                                              averaged):
    """The evaluation program (primal, dual, primal at the average) at
    the paper's OCR size compiles for one chip into the HBM the plane
    cache leaves, the state it reads staying live."""
    from repro.api import solver
    from repro.core.averaging import init_averaging

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    sc = paper.OCR
    n, L, f = sc.n, sc.max_len, sc.f
    d = sc.num_classes * (f + sc.num_classes)
    data = {"x": _sds(one_chip, (n, L, f)),
            "y": _sds(one_chip, (n, L), jnp.int32),
            "mask": _sds(one_chip, (n, L), bool)}
    avg = (jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_averaging(d))) if averaged else None)
    compiled = solver._objectives_program.lower(
        _tiny_ocr_oracle(), n, data, _sds(one_chip, (d + 1,)), avg,
        lam=1.0 / n).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    cache_bytes = n * 64 * (d + 1) * 4
    assert total <= V5E_HBM_BYTES - cache_bytes, mem
