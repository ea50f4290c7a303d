"""Dispatch wrappers for the Pallas kernels.

On a TPU backend the wrappers call the compiled Pallas kernels; on any
other backend they call the pure-jnp references in :mod:`repro.kernels.ref`
instead — :func:`use_pallas` decides from ``jax.default_backend()`` alone.
Interpret mode is never chosen here: the kernel tests reach it by calling
a kernel module directly with ``interpret=True``.
"""
from __future__ import annotations

import jax

# The one invalid-slot score sentinel, shared by every masked scoring path
# (kernel defaults, the jnp references, and repro.cache which re-exports it
# as ``NEG_INF``).  Large enough to lose every argmax, small enough to stay
# exactly representable in float32.  Defined before the kernel imports
# below so the kernel modules can import it back from here without a
# cycle (lint rule R001 points every other -1e30 spelling at this name).
INVALID_SCORE = -1e30

from . import approx_steps as _apx      # noqa: E402
from . import flash_attention as _fa    # noqa: E402
from . import moe_ffn as _moe           # noqa: E402
from . import gram as _gram             # noqa: E402
from . import plane_scores as _ps       # noqa: E402
from . import plane_select as _psel     # noqa: E402
from . import viterbi as _vit           # noqa: E402
from . import ref                       # noqa: E402


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    """Compiled Pallas kernels on a TPU backend, the references elsewhere."""
    return on_tpu()


def plane_scores(planes, w, offsets, **kw):
    if use_pallas():
        return _ps.plane_scores(planes, w, offsets, **kw)
    return ref.plane_scores_ref(planes, w, offsets)


def plane_scores_masked(planes, w, offsets, valid, *, neg=INVALID_SCORE,
                        **kw):
    """Masked plane scoring over a flattened (local) cache view.

    ``planes (m, d)``, ``offsets (m,)``, ``valid (m,)`` is exactly the
    layout of ``workset.flat_view`` — of the *whole* cache on one device,
    or of one shard's ``(n_local*cap, d)`` slice inside a ``shard_map``
    body.  The kernel is launched on the caller's view as-is: per-shard
    tiles, no implicit gather or collective, so calling this under
    ``shard_map`` scores only the local planes (the mesh engine reduces
    the resulting per-shard partials itself, with its single per-pass
    ``psum``).  Invalid slots score ``neg`` so they never win an argmax.
    """
    scores = plane_scores(planes, w, offsets, **kw)
    return jax.numpy.where(valid, scores, jax.numpy.float32(neg))


def plane_select(planes, w, offsets, valid, *, neg=INVALID_SCORE, **kw):
    """Fused masked score + per-block argmax over a ``(n, cap, d)`` cache.

    The one-launch replacement for the two-step score-then-argmax on the
    approximate-oracle hot path: on TPU the ``plane_select`` Pallas kernel
    keeps the per-slot scores in VMEM and folds each slot straight into
    the running best/argmax tiles; elsewhere the jnp reference computes
    the identical quantities through the same flattened matvec the
    two-step path used (bitwise-equal scores).  Returns
    ``(best (n,), slot (n,) int32)``; blocks with no valid slot score
    ``neg`` with slot 0.
    """
    if use_pallas():
        return _psel.plane_select(planes, w, offsets, valid, neg=neg, **kw)
    return ref.plane_select_ref(planes, w, offsets, valid, neg)


def approx_kernel_fits(planes, steps: int) -> bool:
    """Whether an approximate pass of ``steps`` block steps over the plane
    cache ``planes (n, cap, d+1)`` runs as the ``approx_block_steps``
    kernel: on a TPU, with float32 planes, when the kernel's VMEM and
    SMEM plan fits (:func:`repro.kernels.approx_steps.fits`)."""
    n, cap, width = planes.shape
    return (use_pallas() and planes.dtype == jax.numpy.float32
            and _apx.fits(n, cap, width - 1, steps))


def approx_occupancy(valid):
    """``(hi, bits)`` of a ``(n, cap)`` valid mask, the per-block
    occupied bound and slot bit mask :func:`approx_block_steps` reads;
    they hold while no plane is inserted or evicted."""
    return _apx.occupied_bound(valid), _apx.valid_bits(valid)


def approx_block_steps(planes, valid, perm, phi, phi_i, bar, k0, gap, *,
                       lam, occupancy):
    """One approximate pass's block steps as one Pallas launch (TPU only;
    callers check :func:`approx_kernel_fits` and run the scan elsewhere).
    Returns ``(phi, phi_i, bar, gap, slots)``."""
    hi, bits = occupancy
    return _apx.approx_block_steps(planes, valid, perm, phi, phi_i, bar, k0,
                                   gap, lam=lam, hi=hi, bits=bits)


def viterbi_step(m, trans, **kw):
    if use_pallas():
        return _vit.viterbi_step(m, trans, **kw)
    return ref.viterbi_step_ref(m, trans)


def viterbi_decode_batch(unary, trans, mask, **kw):
    """Batched masked Viterbi decode (serving hot path).

    ``unary (B, L, C)``, ``trans (C, C)``, ``mask (B, L)``; returns
    ``(B, L)`` int32 labels, each row bit-for-bit
    ``chain.viterbi_decode`` on that example.  On TPU the inner max-plus
    step is the Pallas :func:`repro.kernels.viterbi.viterbi_step` kernel;
    elsewhere the jnp reference step runs inside the same fixed-shape
    scan, so the decode stays one compiled program per padding bucket on
    every backend.
    """
    if use_pallas():
        return _vit.viterbi_decode_batch(unary, trans, mask, **kw)
    return _vit.viterbi_decode_batch(unary, trans, mask,
                                     step_fn=ref.viterbi_step_ref, **kw)


def gram(planes, **kw):
    if use_pallas():
        return _gram.gram(planes, **kw)
    return ref.gram_ref(planes)


def flash_attention(q, k, v, sm_scale=None, **kw):
    if use_pallas():
        return _fa.flash_attention(q, k, v, sm_scale=sm_scale, **kw)
    return ref.flash_attention_ref(q, k, v, sm_scale)


def moe_ffn(xs, wg, wu, wd, **kw):
    if use_pallas():
        return _moe.moe_ffn(xs, wg, wu, wd, **kw)
    return ref.moe_ffn_ref(xs, wg, wu, wd)
