"""The ``approx_block_steps`` kernel against the approximate-pass scan.

The kernel runs in TPU interpret mode on the CPU and must reproduce
:func:`repro.core.mpbcfw.approx_pass_scan` to float32 rounding, choosing
the same slot at every step: over caches with holes below the occupied
bound, empty blocks and exact score ties, schedules that repeat a block
at the next step and two steps later (and blocks sharing a ``phi_i`` row
group), with and without the gap vector, from a running average that
already holds approximate steps.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from numpy.testing import assert_allclose, assert_array_equal

from repro.cache import CacheLayout, PlaneCache
from repro.core import mpbcfw
from repro.core.types import SSVMProblem
from repro.kernels import approx_steps, ops

RTOL = 1e-5

# Repeats at distance 1 (3,3,3) and 2 (5,3,5), blocks of one 8-row phi_i
# group back to back (8, 9, 15), the empty block 0, a tie block 2.
SCHEDULE = [3, 3, 3, 5, 3, 5, 0, 1, 2, 8, 9, 15, 7, 7, 11, 4, 6, 2, 10, 3]


def _state(n, cap, d, *, track_gap, seed=0):
    """An MP state with a hand-shaped cache: block 0 empty, block 1 one
    plane in a high slot, block 2 three identical planes (an exact tie),
    the rest random with holes; a running average of 3 approximate
    steps."""
    r = np.random.default_rng(seed)
    mp = mpbcfw.init_mp_state(SSVMProblem(n=n, d=d, data=None, oracle=None),
                              CacheLayout(cap=cap, track_gap=track_gap))
    planes = r.normal(size=(n, cap, d + 1)).astype(np.float32)
    valid = r.random((n, cap)) < 0.5
    valid[0] = False
    valid[1] = False
    valid[1, cap - 2] = True
    valid[2] = False
    valid[2, [1, 4, cap - 1]] = True
    planes[2, [4, cap - 1]] = planes[2, 1]
    phi_i = (0.1 * r.normal(size=(n, d + 1))).astype(np.float32)
    cache = mp.cache._replace(
        planes=jnp.asarray(planes), valid=jnp.asarray(valid),
        last_active=jnp.asarray(r.integers(0, 5, (n, cap)), jnp.int32))
    if track_gap:
        cache = cache._replace(gap=jnp.asarray(r.random(n), jnp.float32))
    inner = mp.inner._replace(phi_i=jnp.asarray(phi_i),
                              phi=jnp.asarray(phi_i.sum(0)))
    avg = mp.avg._replace(
        bar_approx=jnp.asarray(r.normal(size=d + 1), jnp.float32),
        k_approx=jnp.asarray(3, jnp.int32))
    return mp._replace(inner=inner, cache=cache, avg=avg,
                       outer_it=jnp.asarray(7, jnp.int32))


def _scan_slots(mp, perm, lam):
    """The slot the scan chose at each step: one single-step scan per
    step, each stamping its own iteration into ``last_active``."""
    one = jax.jit(lambda mp, p: mpbcfw.approx_pass_scan(mp, p, lam))
    slots = []
    for k, i in enumerate(perm):
        mp = one(mp._replace(outer_it=jnp.asarray(1000 + k, jnp.int32)),
                 jnp.asarray([i], jnp.int32))
        slots.append(int(jnp.argmax(mp.cache.last_active[i] == 1000 + k)))
    return np.asarray(slots)


@pytest.fixture
def on_tpu_interpreted(monkeypatch):
    """Take the chip's branch, with the kernel run by the interpreter."""
    def use(params):
        monkeypatch.setattr(ops, "use_pallas", lambda: True)
        monkeypatch.setattr(
            approx_steps, "approx_block_steps",
            functools.partial(approx_steps.approx_block_steps,
                              interpret=params))
    return use


def _assert_states_close(got, want):
    assert_allclose(got.inner.phi, want.inner.phi, rtol=RTOL, atol=1e-6)
    assert_allclose(got.inner.phi_i, want.inner.phi_i, rtol=RTOL, atol=1e-6)
    assert_allclose(got.avg.bar_approx, want.avg.bar_approx, rtol=RTOL,
                    atol=1e-6)
    assert int(got.inner.n_approx) == int(want.inner.n_approx)
    assert int(got.avg.k_approx) == int(want.avg.k_approx)
    assert_array_equal(got.cache.last_active, want.cache.last_active)
    assert_array_equal(got.cache.valid, want.cache.valid)
    assert_array_equal(got.cache.planes, want.cache.planes)
    if want.cache.gap is None:
        assert got.cache.gap is None
    else:
        assert_allclose(got.cache.gap, want.cache.gap, rtol=RTOL, atol=1e-6)


@pytest.mark.parametrize("n,cap,d,track_gap,dma", [
    (16, 8, 260, False, "on_wait"),
    (16, 64, 260, True, "eager"),
    (16, 8, 127, True, "on_wait"),      # d+1 = 128: no lane padding
    (9, 16, 4004, True, "eager"),       # OCR's d+1 = 4005, n not 8k
    (16, 64, 4004, False, "on_wait"),
], ids=["cap8", "cap64-gap", "aligned-gap", "d4004-gap", "cap64-d4004"])
def test_kernel_matches_scan(on_tpu_interpreted, n, cap, d, track_gap, dma):
    lam = 1.0 / n
    mp = _state(n, cap, d, track_gap=track_gap)
    perm = [i % n for i in SCHEDULE]
    want = mpbcfw.approx_pass_scan(mp, jnp.asarray(perm, jnp.int32), lam)
    want_slots = _scan_slots(mp, perm, lam)
    on_tpu_interpreted(pltpu.InterpretParams(dma_execution_mode=dma))
    assert mpbcfw.approx_kernel_runs(mp.cache, len(perm))
    got = mpbcfw.approx_pass(None, mp, jnp.asarray(perm, jnp.int32), lam)
    _assert_states_close(got, want)
    c = mp.cache
    _, _, _, _, slots = approx_steps.approx_block_steps(
        c.planes, c.valid, jnp.asarray(perm, jnp.int32), mp.inner.phi,
        mp.inner.phi_i, mp.avg.bar_approx, mp.avg.k_approx, c.gap, lam=lam)
    assert_array_equal(np.asarray(slots), want_slots)
    assert want_slots[perm.index(0)] == 0            # the empty block
    assert want_slots[perm.index(2)] in (1, 4, cap - 1)


def test_kernel_has_no_dma_races():
    """With DMAs issued eagerly, the interpreter's race detector sees no
    fetch of a ``phi_i`` group racing its own write-back: repeats at
    distance 2 wait for the write before fetching."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    n = 16
    mp = _state(n, 8, 260, track_gap=True)
    c = mp.cache
    approx_steps.approx_block_steps(
        c.planes, c.valid, jnp.asarray([i % n for i in SCHEDULE], jnp.int32),
        mp.inner.phi, mp.inner.phi_i, mp.avg.bar_approx, mp.avg.k_approx,
        c.gap, lam=1.0 / n,
        interpret=pltpu.InterpretParams(detect_races=True,
                                        dma_execution_mode="eager"))
    assert not interpret_pallas_call.races.races_found


def test_multi_approx_pass_runs_the_kernel_pass_for_pass(on_tpu_interpreted):
    """Two slope-free passes through ``multi_approx_pass`` (the occupancy
    derived once for both) equal the scan's, telemetry included."""
    n, cap, d = 16, 8, 260
    lam = 1.0 / n
    mp = _state(n, cap, d, track_gap=True, seed=1)
    perms = jnp.asarray(np.random.default_rng(2).permuted(
        np.tile(np.arange(n), (2, 1)), axis=1), jnp.int32)
    clock = mpbcfw.make_slope_clock(0.0, 0.0, 1.0, 1e-3)
    run = functools.partial(mpbcfw.multi_approx_pass, perms=perms,
                            clock=clock, lam=lam, run_all=True)
    want, _, want_stats = run(mp)
    on_tpu_interpreted(pltpu.InterpretParams())
    got, _, stats = run(mp)
    _assert_states_close(got, want)
    assert int(stats.passes_run) == int(want_stats.passes_run) == 2
    assert_allclose(stats.duals, want_stats.duals, rtol=RTOL)


def test_occupied_bound_and_bits_follow_valid():
    valid = np.zeros((5, 40), bool)
    valid[1, 0] = True
    valid[2, [3, 17, 33]] = True             # holes below the bound
    valid[3] = True
    valid[4, 39] = True
    hi = approx_steps.occupied_bound(jnp.asarray(valid))
    assert_array_equal(hi, [0, 1, 34, 40, 40])
    words = approx_steps.mask_words(40)
    bits = np.asarray(approx_steps.valid_bits(jnp.asarray(valid)))
    assert words == 2 and bits.shape == (5 * words,)
    unpacked = np.array([[(bits[i * words + s // 32] >> (s % 32)) & 1
                          for s in range(40)] for i in range(5)], bool)
    assert_array_equal(unpacked, valid)


def _cache_shape(n, cap, d, *, gram=False, dtype=jnp.float32):
    s = jax.ShapeDtypeStruct
    return PlaneCache(planes=s((n, cap, d + 1), dtype),
                      valid=s((n, cap), bool),
                      last_active=s((n, cap), jnp.int32),
                      gram=s((n, cap, cap), dtype) if gram else None)


@pytest.mark.parametrize("cache,steps,runs", [
    (_cache_shape(6877, 64, 4004), 6877, True),            # the OCR cell
    (_cache_shape(6877, 64, 4004, gram=True), 6877, False),
    (_cache_shape(64, 64, 40000), 64, False),              # VMEM plan
    (_cache_shape(100_000, 64, 512), 100_000, False),      # SMEM plan
    (_cache_shape(64, 10, 512), 64, False),                # 8-slot tiles
    (_cache_shape(4, 8, 512), 4, False),                   # no row tile
    (_cache_shape(64, 8, 512, dtype=jnp.bfloat16), 64, False),
], ids=["ocr", "gram", "vmem", "smem", "cap10", "n4", "bf16"])
def test_dispatch_takes_the_kernel_only_where_it_fits(monkeypatch, cache,
                                                      steps, runs):
    assert not mpbcfw.approx_kernel_runs(cache, steps)   # the CPU: the scan
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    assert mpbcfw.approx_kernel_runs(cache, steps) is runs


def test_kernel_plan_sizes():
    """The OCR cell's plan: 2 MiB of plane buffers, 3 MiB in all."""
    assert approx_steps.vmem_bytes(64, 4004) == 4 * 4096 * (128 + 64)
    assert approx_steps.smem_bytes(6877, 64, 6877) == 4 * 6877 * 6
    assert approx_steps.fits(6877, 64, 4004, 6877)


def test_empty_schedule_changes_nothing():
    mp = _state(8, 8, 127, track_gap=True)
    c = mp.cache
    phi, phi_i, bar, gap, slots = approx_steps.approx_block_steps(
        c.planes, c.valid, jnp.zeros((0,), jnp.int32), mp.inner.phi,
        mp.inner.phi_i, mp.avg.bar_approx, mp.avg.k_approx, c.gap,
        lam=0.125)
    assert slots.shape == (0,)
    for a, b in ((phi, mp.inner.phi), (phi_i, mp.inner.phi_i),
                 (bar, mp.avg.bar_approx), (gap, c.gap)):
        assert_array_equal(a, b)
