"""``closure_step`` — packed boolean matrix squaring Pallas TPU kernel.

One round of ``R' = R | (R·R > 0)`` with *both* operands and the output kept
bit-packed (uint32).  Repeated ⌈log₂ diameter⌉ times this yields the
transitive closure — the descendant-edge substrate of the TPU path, replacing
the paper's CPU-oriented BFL probes with MXU work (see DESIGN.md §5.2).

Per grid step (i, j, k):
  * unpack tile R[i,k] -> (bm, bk) bf16, R[k,j] -> (bk, bn),
  * MXU matmul accumulate into a VMEM f32 scratch,
  * final k: OR with the original R[i,j] tile and *repack* to uint32.

Grid: (M/bm, N_words/wn, K/bk), contraction innermost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD = 32


def _unpack_tile(words):
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, WORD), 2)
    bits = (words[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(words.shape[0], -1)


def _pack_tile(bits):
    # bits: (bm, bn) int/bool -> (bm, bn/32) uint32
    bm, bn = bits.shape
    w = bits.reshape(bm, bn // WORD, WORD).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jax.lax.broadcasted_iota(
        jnp.uint32, (1, 1, WORD), 2))
    return (w * weights).sum(axis=-1).astype(jnp.uint32)


def _closure_kernel(ra_ref, rb_ref, rc_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _unpack_tile(ra_ref[...]).astype(jnp.float32)     # (bm, bk)
    b = _unpack_tile(rb_ref[...]).astype(jnp.float32)     # (bk, bn)
    acc_ref[...] += jax.lax.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        new_bits = acc_ref[...] > 0                        # (bm, bn) bool
        orig = rc_ref[...]                                 # (bm, bn/32) uint32
        o_ref[...] = orig | _pack_tile(new_bits)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def closure_step_pallas(r_words: jax.Array, *, bm: int = 256, bn: int = 1024,
                        bk: int = 1024, interpret: bool = False) -> jax.Array:
    """R' = R | (R·R > 0); r_words uint32 (N, N/32) -> same shape."""
    n, wn_total = r_words.shape
    assert wn_total * WORD == n, "closure requires a square packed matrix"
    bm = min(bm, n)
    bn = min(bn, n)
    bk = min(bk, n)
    assert n % bm == 0 and n % bn == 0 and n % bk == 0
    grid = (n // bm, n // bn, n // bk)
    wn = bn // WORD
    return pl.pallas_call(
        _closure_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // WORD), lambda i, j, k: (i, k)),   # R[i,k]
            pl.BlockSpec((bk, wn), lambda i, j, k: (k, j)),           # R[k,j]
            pl.BlockSpec((bm, wn), lambda i, j, k: (i, j)),           # R[i,j]
        ],
        out_specs=pl.BlockSpec((bm, wn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, wn_total), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r_words, r_words, r_words)


def transitive_closure(adj_words: jax.Array, n_steps: int | None = None,
                       step_fn=None, **kw) -> jax.Array:
    """Full closure by repeated squaring: ⌈log2(N)⌉ rounds reach any diameter.

    ``step_fn`` defaults to :func:`closure_step_pallas`; pass
    ``ref.closure_step_ref`` (or the blocked jnp variant in ops.py) on CPU.
    """
    import math
    n = adj_words.shape[0]
    steps = n_steps if n_steps is not None else max(1, math.ceil(math.log2(max(n, 2))))
    fn = step_fn or (lambda r: closure_step_pallas(r, **kw))
    r = adj_words
    for _ in range(steps):
        r = fn(r)
    return r
