"""Pallas TPU kernel: one approximate pass of MP-BCFW in one launch.

The launch runs the block steps of one approximate pass (paper Alg. 3
step 4) in the order ``perm`` gives.  Step ``k`` on block ``i =
perm[k]`` is the scan body of :func:`repro.core.mpbcfw.approx_pass`:

  * ``w = -phi*/lam``;
  * the score ``<plane, [w 1]>`` of each of block ``i``'s cached planes,
    invalid slots at :data:`~repro.kernels.ops.INVALID_SCORE`;
  * the first-index argmax, or the zero plane with score 0 (slot 0) when
    the block holds no valid plane;
  * the clipped exact line search and the ``phi_i`` / ``phi`` update;
  * the approximate running average (``update_average(exact=False)``);
  * with a gap vector, the fold-in ``max(score - <phi_i_old, [w 1]>, 0)``.

Only occupied slots are read.  ``hi[i]`` is one past block ``i``'s
highest valid slot; step ``k`` copies slots ``[0, hi[i])`` of block
``i`` from the ``(n, cap, d+1)`` plane cache in HBM, in 8-slot tiles,
straight into VMEM, with no slice, pad or split of the offset column.
Which slots below ``hi[i]`` are valid comes from a bit mask, ``words``
int32 words a block, bit ``s % 32`` of word ``s // 32`` for slot ``s``.

``phi``, the running average and the gap vector stay on chip for the
whole pass.  Block ``i``'s ``phi_i`` row is fetched one step ahead,
beside the next block's plane tiles, and written back in place (the
``phi_i`` operand is aliased to its output).  The kernel is exact for
any index sequence: a block repeated at the next step takes the row the
step just wrote, and one repeated two steps later is fetched only after
that row's write-back has landed.

Everything is float32 on the VPU: scores are elementwise products and
sums, never a lower-precision matrix product.  The chosen slots are
returned so the caller stamps ``last_active`` with one scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import INVALID_SCORE

#: Slots a plane DMA moves: one float32 (8, 128) tile's sublanes.
TILE = 8
#: Bits of one valid-mask word.
WORD = 32
#: VMEM the kernel's buffers may take (:func:`vmem_bytes`), and the
#: scoped limit it compiles under (the rest is for spills).
VMEM_BUDGET = 16 * 1024 * 1024
VMEM_LIMIT = 32 * 1024 * 1024
#: SMEM its scalar operands may take (:func:`smem_bytes`), of a v5e
#: core's 1 MiB.
SMEM_BUDGET = 512 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_shape(d: int) -> tuple:
    """``(rows, lanes)`` of one DMA tile: ``TILE`` slots of a block, or
    ``TILE`` rows of ``phi_i``, each ``d+1`` floats padded to whole
    128-lane tiles, as the chip lays them out in HBM."""
    return TILE, _round_up(d + 1, 128)


def mask_words(cap: int) -> int:
    """int32 words of one block's valid-slot bit mask."""
    return -(-cap // WORD)


def vmem_bytes(cap: int, d: int) -> int:
    """VMEM of the kernel's buffers at ``cap`` slots of ``d+1`` floats:
    two plane buffers of ``cap`` rows, and eight ``(1, d+1)`` rows
    (``phi`` and the average, in and out; two fetched and two written
    ``phi_i`` rows), each padded to a full (8, 128) tile."""
    _, lanes = tile_shape(d)
    return 4 * lanes * (2 * _round_up(cap, TILE) + 8 * TILE)


def smem_bytes(n: int, cap: int, steps: int) -> int:
    """SMEM of the scalar operands: ``perm`` and the chosen slots
    (``steps`` each), ``hi`` and the gap vector (``n`` each), the valid
    masks (``n * words``)."""
    return 4 * (2 * steps + (2 + mask_words(cap)) * n)


def fits(n: int, cap: int, d: int, steps: int) -> bool:
    """Whether the kernel's plan holds at these shapes: 8-slot tiles
    divide ``cap``, there is a whole tile of ``phi_i`` rows, and its VMEM
    and SMEM stay within budget."""
    return (cap % TILE == 0 and n >= TILE
            and vmem_bytes(cap, d) <= VMEM_BUDGET
            and smem_bytes(n, cap, steps) <= SMEM_BUDGET)


def occupied_bound(valid: jnp.ndarray) -> jnp.ndarray:
    """``hi (n,) int32``: one past each block's highest valid slot, 0
    for an empty block."""
    cap = valid.shape[1]
    slot = jnp.arange(1, cap + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(valid, slot, 0), axis=1).astype(jnp.int32)


def valid_bits(valid: jnp.ndarray) -> jnp.ndarray:
    """``(n * words,) int32``: each block's valid slots as a bit mask,
    bit ``s % 32`` of word ``s // 32`` for slot ``s``."""
    n, cap = valid.shape
    words = mask_words(cap)
    v = jnp.pad(valid, ((0, 0), (0, words * WORD - cap)))
    v = v.reshape(n, words, WORD).astype(jnp.uint32)
    bits = jnp.left_shift(v, jnp.arange(WORD, dtype=jnp.uint32))
    packed = jnp.sum(bits, axis=2, dtype=jnp.uint32)   # disjoint bits
    return jax.lax.bitcast_convert_type(packed, jnp.int32).reshape(-1)


def _kernel(perm_ref, hi_ref, bits_ref, k0_ref, planes_hbm, phi_in, bar_in,
            phi_i_in, *rest, lam, d, words, track_gap):
    del phi_i_in    # aliased to phi_i_hbm: every read and write goes there
    if track_gap:
        (gap_in, phi_ref, bar_ref, phi_i_hbm, slots_ref, gap_hbm,
         pbuf, rows, news, gap_smem, psem, rsem, wsem, gsem) = rest
    else:
        (phi_ref, bar_ref, phi_i_hbm, slots_ref,
         pbuf, rows, news, psem, rsem, wsem) = rest
    steps = perm_ref.shape[0]
    lanes = phi_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    inrow = lane <= d           # the plane, not the tile's padding
    star = lane < d             # its linear part
    sub = jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)
    neg = jnp.float32(INVALID_SCORE)

    def tile_copy(j, t, s):
        r = pl.multiple_of(t * TILE, TILE)
        return pltpu.make_async_copy(
            planes_hbm.at[j, pl.ds(r, TILE), pl.ds(0, lanes)],
            pbuf.at[s, pl.ds(r, TILE)], psem.at[s])

    def tiles(j):
        return (hi_ref[j] + (TILE - 1)) // TILE

    def fetch_planes(j, s):
        def body(t, c):
            tile_copy(j, t, s).start()
            return c
        jax.lax.fori_loop(0, tiles(j), body, 0)

    def wait_planes(j, s):
        def body(t, c):
            tile_copy(j, t, s).wait()
            return c
        jax.lax.fori_loop(0, tiles(j), body, 0)

    # phi_i moves in groups of TILE rows, the (8, 128) tile's sublanes:
    # block i's row is row i % TILE of group i // TILE.
    def group(g):
        return phi_i_hbm.at[pl.ds(pl.multiple_of(g * TILE, TILE), TILE),
                            pl.ds(0, lanes)]

    def row_copy(g, s):
        return pltpu.make_async_copy(group(g), rows.at[s], rsem.at[s])

    def write_back(g, s):
        return pltpu.make_async_copy(news.at[s], group(g), wsem.at[s])

    if track_gap:
        gap_copy = pltpu.make_async_copy(gap_in, gap_smem, gsem)
        gap_copy.start()
    phi_ref[...] = phi_in[...]
    bar_ref[...] = bar_in[...]
    first = perm_ref[0]
    fetch_planes(first, 0)
    row_copy(first // TILE, 0).start()
    if track_gap:
        gap_copy.wait()

    def step(k, carry):
        s = k % 2
        o = 1 - s
        i = perm_ref[k]
        nxt = perm_ref[jnp.minimum(k + 1, steps - 1)]
        g, gp, gn = i // TILE, perm_ref[jnp.maximum(k - 1, 0)] // TILE, \
            nxt // TILE
        more = k + 1 < steps

        @pl.when(more)
        def _():
            fetch_planes(nxt, o)

        # Step k+1's group may be the one step k-1 is writing back.
        early = (k >= 1) & more & (gn == gp)

        @pl.when(early)
        def _():
            write_back(gp, o).wait()

        @pl.when(more)
        def _():
            row_copy(gn, o).start()

        wait_planes(i, s)
        row_copy(g, s).wait()

        # A group step k-1 also wrote was fetched before that write.
        @pl.when((k >= 1) & (g == gp))
        def _():
            rows[s] = news[o]

        phi = phi_ref[...]
        wext = jnp.where(star, -phi / lam, jnp.where(inrow, 1.0, 0.0))

        def score_tile(t, best_idx):
            best, idx = best_idx
            r = pl.multiple_of(t * TILE, TILE)
            prod = jnp.where(inrow, pbuf[s, pl.ds(r, TILE), :] * wext, 0.0)
            sc = jnp.sum(prod, axis=1, keepdims=True)
            word = bits_ref[i * words + t // (WORD // TILE)]
            shift = (t % (WORD // TILE)) * TILE
            bit = jax.lax.shift_right_logical(
                jnp.full((TILE, 1), word, jnp.int32), sub + shift) & 1
            sc = jnp.where(bit == 1, sc, neg)
            m = jnp.max(sc, axis=0, keepdims=True)
            loc = jnp.min(jnp.where(sc == m, sub, TILE), axis=0,
                          keepdims=True)
            up = m > best
            return jnp.where(up, m, best), jnp.where(up, r + loc, idx)

        best, idx = jax.lax.fori_loop(
            0, tiles(i), score_tile,
            (jnp.full((1, 1), neg, jnp.float32),
             jnp.zeros((1, 1), jnp.int32)))
        slot = idx[0, 0]
        found = hi_ref[i] > 0
        phat = jnp.where(found & inrow, pbuf[s, pl.ds(slot, 1), :], 0.0)
        score = jnp.where(found, best, 0.0)

        r = i % TILE
        raw = rows[s, pl.ds(r, 1), :]
        old = jnp.where(inrow, raw, 0.0)
        diff = old - phat
        num = (jnp.sum(jnp.where(star, diff * phi, 0.0), keepdims=True)
               - lam * jnp.sum(jnp.where(star, 0.0, diff), keepdims=True))
        den = jnp.sum(jnp.where(star, diff * diff, 0.0), keepdims=True)
        gamma = jnp.where(den > 0.0, num / jnp.maximum(den, 1e-30), 0.0)
        gamma = jnp.clip(gamma, 0.0, 1.0)
        new = (1.0 - gamma) * old + gamma * phat
        phi_new = phi + (new - old)
        phi_ref[...] = phi_new
        kf = jnp.full((1, 1), k0_ref[0] + k, jnp.int32).astype(jnp.float32)
        bar_ref[...] = ((kf / (kf + 2.0)) * bar_ref[...]
                        + (2.0 / (kf + 2.0)) * phi_new)
        if track_gap:
            gap = score - jnp.sum(old * wext, keepdims=True)
            gap_smem[i] = jnp.maximum(gap, 0.0)[0, 0]
        slots_ref[k] = slot

        @pl.when((k >= 1) & jnp.logical_not(early))
        def _():
            write_back(gp, o).wait()

        news[s] = rows[s]
        news[s, pl.ds(r, 1), :] = jnp.where(inrow, new, raw)
        write_back(g, s).start()
        return carry

    jax.lax.fori_loop(0, steps, step, 0)
    write_back(perm_ref[steps - 1] // TILE, (steps - 1) % 2).wait()
    if track_gap:
        out = pltpu.make_async_copy(gap_smem, gap_hbm, gsem)
        out.start()
        out.wait()


@functools.partial(jax.jit, static_argnames=("lam", "interpret"))
def approx_block_steps(planes, valid, perm, phi, phi_i, bar, k0, gap=None,
                       *, lam: float, hi=None, bits=None,
                       interpret=False):
    """Run one approximate pass's block steps over ``perm``.

    planes ``(n, cap, d+1)`` and valid ``(n, cap)``: the plane cache;
    perm ``(K,)`` int32: the blocks in visiting order (any sequence);
    phi ``(d+1,)``, phi_i ``(n, d+1)``: the dual state; bar ``(d+1,)``
    and k0 ``()`` int32: the approximate average and its count; gap
    ``(n,)`` or ``None``: the per-block gap estimates.  ``hi`` and
    ``bits`` (:func:`occupied_bound`, :func:`valid_bits`) are derived
    from ``valid`` when not given; a caller running several passes over
    one cache computes them once.

    Returns ``(phi, phi_i, bar, gap, slots)`` with ``slots (K,)`` the
    slot each step chose.  ``phi_i`` is updated in place.
    """
    n, cap, width = planes.shape
    steps = perm.shape[0]
    if steps == 0:
        return phi, phi_i, bar, gap, jnp.zeros((0,), jnp.int32)
    if hi is None:
        hi = occupied_bound(valid)
    if bits is None:
        bits = valid_bits(valid)
    track_gap = gap is not None
    # On the chip the kernel's HBM operands are (8, 128)-tiled, so their
    # rows and planes already lie padded in memory to whole tiles, and
    # its DMAs move whole tiles.  The interpreter keeps arrays at their
    # logical shapes: hand it the same padded ones.
    _, lanes = tile_shape(width - 1)
    if interpret:
        planes = jnp.pad(planes, ((0, 0), (0, 0), (0, lanes - width)))
        phi_i = jnp.pad(phi_i, ((0, _round_up(n, TILE) - n),
                                (0, lanes - width)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    row = jax.ShapeDtypeStruct((1, lanes), jnp.float32)
    in_specs = [smem, smem, smem, smem, hbm, vmem, vmem, hbm]
    out_specs = [vmem, vmem, hbm, smem]
    out_shape = [row, row, jax.ShapeDtypeStruct(phi_i.shape, phi_i.dtype),
                 jax.ShapeDtypeStruct((steps,), jnp.int32)]
    scratch = [pltpu.VMEM((2, cap, lanes), jnp.float32),
               pltpu.VMEM((2, TILE, lanes), jnp.float32),
               pltpu.VMEM((2, TILE, lanes), jnp.float32)]
    pad_row = lambda v: jnp.pad(v, (0, lanes - width)).reshape(1, lanes)  # noqa: E731
    args = [perm.astype(jnp.int32), hi, bits,
            jnp.reshape(k0, (1,)).astype(jnp.int32), planes,
            pad_row(phi), pad_row(bar), phi_i]
    aliases = {7: 2}
    if track_gap:
        in_specs.append(hbm)
        out_specs.append(hbm)
        out_shape.append(jax.ShapeDtypeStruct(gap.shape, gap.dtype))
        scratch.append(pltpu.SMEM((n,), jnp.float32))
        args.append(gap)
        aliases[8] = 4
    scratch += [pltpu.SemaphoreType.DMA((2,))] * 3
    if track_gap:
        scratch.append(pltpu.SemaphoreType.DMA(()))
    out = pl.pallas_call(
        functools.partial(_kernel, lam=lam, d=width - 1,
                          words=mask_words(cap), track_gap=track_gap),
        out_shape=tuple(out_shape),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="approx_block_steps",
        interpret=interpret,
    )(*args)
    phi_i = out[2][:n, :width] if interpret else out[2]
    return (out[0][0, :width], phi_i, out[1][0, :width],
            out[4] if track_gap else None, out[3])
