"""``intersect`` — k-way bitset AND + popcount Pallas TPU kernel.

The MJoin candidate step (Alg. 5, lines 5–7): for a frontier of F partial
matches, each constrained by K packed adjacency rows (gathered upstream),
produce the intersected candidate bitset and its cardinality.  Keeping the
AND-reduce + popcount fused avoids a (F, N) boolean round-trip through HBM.

Grid: (F/bf, W/bw); the K axis is tiny (number of bound neighbours of the
current query node, ≤ max degree of the pattern) and is unrolled in-kernel.
Counts are accumulated across W blocks in a VMEM scratch and written on the
last block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _intersect_kernel(rows_ref, and_ref, cnt_ref, acc_ref, *, k_rows: int):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = rows_ref[...]                       # (bf, K, bw) uint32
    acc = tile[:, 0]
    for i in range(1, k_rows):                 # K is static and small
        acc = acc & tile[:, i]
    and_ref[...] = acc
    pc = jax.lax.population_count(acc).astype(jnp.int32)   # (bf, bw)
    acc_ref[...] += pc.sum(axis=1, keepdims=True)

    @pl.when(w == pl.num_programs(1) - 1)
    def _done():
        cnt_ref[...] = acc_ref[...]


@jax.jit
def intersect_xla(rows: jax.Array):
    """XLA expression of the same fused AND-reduce + popcount.

    rows: uint32 (F, K, W) -> (and_rows uint32 (F, W), counts int32 (F,)).
    The default executor on non-TPU backends, where it beats the Pallas
    interpreter by orders of magnitude while keeping the contraction on
    the device runtime (shapes are identical, so results are too).
    """
    acc = rows[:, 0]
    for i in range(1, rows.shape[1]):
        acc = acc & rows[:, i]
    counts = jax.lax.population_count(acc).astype(jnp.int32).sum(axis=1)
    return acc, counts


@functools.partial(jax.jit, static_argnames=("bf", "bw", "interpret"))
def intersect_pallas(rows: jax.Array, *, bf: int = 128, bw: int = 512,
                     interpret: bool = False):
    """rows: uint32 (F, K, W) -> (and_rows uint32 (F, W), counts int32 (F,)).

    Shapes need not be block multiples: inputs are zero-padded up to the
    grid (zero rows AND to zero and popcount to zero, so padding never
    perturbs real counts) and outputs sliced back.
    """
    f, k_rows, w = rows.shape
    bf = min(bf, f)
    bw = min(bw, w)
    fp = -(-f // bf) * bf
    wp = -(-w // bw) * bw
    if (fp, wp) != (f, w):
        rows = jnp.pad(rows, ((0, fp - f), (0, 0), (0, wp - w)))
    grid = (fp // bf, wp // bw)
    and_rows, counts = pl.pallas_call(
        functools.partial(_intersect_kernel, k_rows=k_rows),
        grid=grid,
        in_specs=[pl.BlockSpec((bf, k_rows, bw), lambda i, j: (i, 0, j))],
        out_specs=[
            pl.BlockSpec((bf, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bf, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((fp, wp), jnp.uint32),
            jax.ShapeDtypeStruct((fp, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bf, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rows)
    return and_rows[:f, :w], counts[:f, 0]
