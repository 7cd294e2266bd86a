"""``gather_intersect`` — fused row-gather + K-way AND + popcount kernel.

The resident-RIG enumerator (``repro.core.mjoin``, method
``frontier-device-resident``) keeps every packed RIG adjacency matrix
concatenated into one device-resident uint32 matrix ``(R, W)``.  A level
dispatch then needs only the ``(F, K)`` int32 *row indices* of the
constraint rows — this kernel gathers those rows out of the resident
matrix, AND-reduces them across K, and popcounts each result row, all on
device.  Compared to the ``intersect`` kernel it replaces the host-side
``(F, K, W)`` gather + transfer with an ``(F, K)`` index upload: the slab
traffic drops from ``F*K*W*4`` bytes to ``F*K*4`` bytes per dispatch.

Grid: ``(F/bf,)``.  Each program reads its ``bf*K`` row indices from an
SMEM block and issues one async copy per index from the resident matrix
(``pltpu.ANY`` — HBM for large matrices) into a VMEM scratch.  A DMA
slice must be aligned to the (8, 128) tiling, so each copy fetches the
8-row tile that holds the wanted row; the row itself is then selected in
VMEM (a sublane mask + sum, exact because the other seven rows are
masked to zero).  The resident matrix therefore has a multiple of 8 rows
(:func:`repro.jaxgm.device_graph.pack_resident_rig` pads it).  K is
static and unrolled.  Outputs stay padded to the grid (callers slice rows
on the host side); AND rows are sliced to the level's true lane count
``w32`` inside the jit so the device-to-host copy is exact.

The ``gather_intersect_xla`` variant is the same contraction expressed as
a plain XLA gather + AND + ``population_count`` — the default executor on
non-TPU backends, where it beats both the Pallas interpreter (by orders
of magnitude) and the host path (the resident matrix never leaves the
device).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .packed import WORD, first_set_bits


SUBLANES = 8                 # rows per aligned resident-matrix fetch


def _gather_intersect_kernel(idx_ref, mat_ref, and_ref, cnt_ref, tiles,
                             sems, *, bf: int, k_rows: int):
    # one DMA per (frontier row, constraint): the aligned 8-row tile that
    # holds resident row idx[r*K + c] lands in scratch slot r*K + c.
    # Start all copies, then wait — the issue loop overlaps with in-flight
    # transfers.
    copies = []
    for slot in range(bf * k_rows):
        start = pl.multiple_of(idx_ref[0, slot] // SUBLANES * SUBLANES,
                               SUBLANES)
        copies.append(pltpu.make_async_copy(
            mat_ref.at[pl.ds(start, SUBLANES), :], tiles.at[slot],
            sems.at[slot]))
    for dma in copies:
        dma.start()
    for dma in copies:
        dma.wait()
    sub = jax.lax.broadcasted_iota(jnp.int32, tiles.shape[1:], 0)
    for r in range(bf):
        acc = None
        for c in range(k_rows):                # K is static and small
            slot = r * k_rows + c
            tile = jax.lax.bitcast_convert_type(tiles[slot], jnp.int32)
            picked = jnp.where(sub == idx_ref[0, slot] % SUBLANES, tile, 0)
            row = jnp.sum(picked, axis=0, keepdims=True)       # (1, W)
            acc = row if acc is None else acc & row
        acc = jax.lax.bitcast_convert_type(acc, jnp.uint32)
        and_ref[r:r + 1, :] = acc
        pc = jax.lax.population_count(acc).astype(jnp.int32)
        cnt_ref[r:r + 1, :] = pc.sum(axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("w32", "bf", "interpret"))
def gather_intersect_pallas(matrix: jax.Array, idx: jax.Array, *, w32: int,
                            bf: int = 8, interpret: bool = False):
    """matrix: uint32 (R, W) resident, R a multiple of 8; idx: int32 (F, K)
    row indices -> (and_rows uint32 (Fp, w32), counts int32 (Fp,)) with
    Fp = F rounded up to ``bf`` (callers pad F themselves to bound
    retraces and slice rows back; padding index rows should point at an
    all-zero resident row).

    ``w32`` is the level's true lane count: AND rows are cut to it before
    leaving the device.  Counts are exact regardless — resident rows are
    zero beyond their own true width, so padding lanes AND to zero.
    """
    f, k_rows = idx.shape
    r, w = matrix.shape
    assert r % SUBLANES == 0, f"resident rows {r} not a multiple of 8"
    fp = -(-f // bf) * bf
    if fp != f:
        idx = jnp.pad(idx, ((0, fp - f), (0, 0)))
    and_rows, counts = pl.pallas_call(
        functools.partial(_gather_intersect_kernel, bf=bf, k_rows=k_rows),
        grid=(fp // bf,),
        in_specs=[
            pl.BlockSpec((None, 1, bf * k_rows), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((bf, w), lambda i: (i, 0)),
            pl.BlockSpec((bf, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((fp, w), jnp.uint32),
            jax.ShapeDtypeStruct((fp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bf * k_rows, SUBLANES, w), jnp.uint32),
            pltpu.SemaphoreType.DMA((bf * k_rows,)),
        ],
        interpret=interpret,
    )(idx.reshape(fp // bf, 1, bf * k_rows), matrix)
    return and_rows[:, :w32], counts[:, 0]


@functools.partial(jax.jit, static_argnames=("w32",))
def gather_intersect_xla(matrix: jax.Array, idx: jax.Array, *, w32: int):
    """XLA expression of the same fused contraction (non-TPU executor).

    Same contract as :func:`gather_intersect_pallas` minus the grid
    rounding: returns ``(and_rows (F, w32), counts (F,))`` for the full
    (caller-padded) F.
    """
    rows = matrix[idx]                         # (F, K, W) device gather
    acc = rows[:, 0]
    for c in range(1, rows.shape[1]):
        acc = acc & rows[:, c]
    counts = jax.lax.population_count(acc).astype(jnp.int32).sum(axis=1)
    return acc[:, :w32], counts


@functools.partial(jax.jit, static_argnames=("n_i", "size"))
def expand_pairs(and_rows: jax.Array, *, n_i: int, size: int):
    """Device-side frontier expansion: set bits of ``and_rows`` (uint32
    ``(F, w32)``, little-endian lanes) below column ``n_i`` -> the first
    ``size`` (row, column) pairs in row-major (= lexicographic) order, as
    int32 vectors (slots past the last pair hold (0, 0)).

    ``size`` is a static page bound: callers bucket it (and slice the
    valid prefix themselves) so the number of retraces stays logarithmic.
    The selection runs on device without a dense unpack
    (:func:`repro.kernels.packed.first_set_bits`) — the host receives only
    the compact pair page instead of an ``(F, n_i)`` boolean slab.
    """
    _, w = and_rows.shape
    live = np.clip(n_i - WORD * np.arange(w), 0, WORD).astype(np.uint64)
    lane_mask = jnp.asarray(((1 << live) - 1).astype(np.uint32))
    rid, cid, valid = first_set_bits(and_rows & lane_mask, size)
    return jnp.where(valid, rid, 0), jnp.where(valid, cid, 0)
