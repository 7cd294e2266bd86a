"""``bitmm`` — bit-packed boolean matmul Pallas TPU kernel.

The workhorse of the TPU-adapted matcher: computes ``Y = f(A · X)`` where
``A`` is a 0/1 matrix stored bit-packed (uint32 words, 32x less HBM traffic
than bf16) and ``X`` is a small dense 0/1 right operand (e.g. the FB
candidate matrix transposed, B = number of query nodes).

TPU adaptation of the paper's roaring-bitmap ``bitBat`` batch op (§5.5):
instead of word-wise AND/OR on a scalar core, each grid step splits a
``(bm, bk/32)`` word tile of A *in VMEM* into its 32 bit planes — plane
``b`` is ``(words >> b) & 1``, the columns ``{32 j + b}`` of the dense
tile — and feeds each plane to the MXU as a bf16 ``(bm, bk/32)`` operand
against the matching rows of X.  No lane interleaving is needed: the
wrapper permutes X's rows once into plane-major order, so every plane
product reads a contiguous, sublane-aligned slice.  The epilogue applies
either

* ``threshold`` — ``Y = (A@X) > 0``   (existence semantics: simulation), or
* ``sum``       — ``Y = A@X``         (count semantics: RIG edge counts).

Grid: ``(M/bm, K/bk)`` with the contraction dimension innermost
(``arbitrary``), accumulating into a VMEM scratch tile.  A's word block is
either the whole row or :data:`K_BLOCK` bits (128 uint32 lanes), the two
shapes the TPU's (8, 128) block rule admits; operands wider than one block
must be padded to a :data:`K_BLOCK` multiple upstream
(:func:`repro.jaxgm.device_graph.from_host` does).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD = 32
K_BLOCK = 128 * WORD        # contraction bits per grid step: 128 word lanes


def k_block(kdim: int) -> int:
    """Contraction block for a ``kdim``-bit operand: the whole width when
    it fits one block, else :data:`K_BLOCK`."""
    return kdim if kdim <= K_BLOCK else K_BLOCK


def plane_major(x: jax.Array, bk: int) -> jax.Array:
    """Permute X's rows so each K block lists bit plane 0's rows, then
    plane 1's, ...: block row ``b * (bk/32) + j`` holds X row
    ``32 j + b`` of that block — the column order of the kernel's planes."""
    kdim, b = x.shape
    wk = bk // WORD
    return (x.reshape(kdim // bk, wk, WORD, b).transpose(0, 2, 1, 3)
            .reshape(kdim, b))


def _bitmm_kernel(a_ref, x_ref, o_ref, acc_ref, *, threshold: bool):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int32 view: an arithmetic shift still leaves bit b at position 0, and
    # int32 -> float is a conversion Mosaic lowers (uint32 -> float is not)
    words = jax.lax.bitcast_convert_type(a_ref[...], jnp.int32)  # (bm, wk)
    wk = words.shape[1]
    acc = acc_ref[...]
    for b in range(WORD):                          # static: 32 bit planes
        plane = ((words >> b) & 1).astype(jnp.float32).astype(jnp.bfloat16)
        xs = x_ref[b * wk:(b + 1) * wk, :].astype(jnp.bfloat16)
        acc += jax.lax.dot(plane, xs, preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == pl.num_programs(1) - 1)
    def _epilogue():
        acc = acc_ref[...]
        if threshold:
            o_ref[...] = (acc > 0).astype(o_ref.dtype)
        else:
            o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("threshold", "bm", "bk", "interpret"))
def bitmm_pallas(a_words: jax.Array, x: jax.Array, *, threshold: bool = True,
                 bm: int = 256, bk: int | None = None,
                 interpret: bool = False) -> jax.Array:
    """Y = f(unpack(a_words) @ x).

    a_words: uint32 (M, K/32); x: (K, B) 0/1 float/bool (exact in bf16);
    Y: (M, B) float32.  ``bk`` defaults to :func:`k_block`; it must be the
    whole K or a multiple of :data:`K_BLOCK` dividing K.  ``bm`` is cut to
    its largest common divisor with M (a multiple of 8 for aligned M).  B
    is kept whole (it is small — query width).
    """
    m, wk = a_words.shape
    kdim, b = x.shape
    assert wk * WORD == kdim, (wk, kdim)
    bk = k_block(kdim) if bk is None else bk
    assert kdim % bk == 0 and (bk == kdim or bk % K_BLOCK == 0), (kdim, bk)
    bm = math.gcd(bm, m)
    grid = (m // bm, kdim // bk)
    xp = plane_major(x.astype(jnp.float32), bk)
    return pl.pallas_call(
        functools.partial(_bitmm_kernel, threshold=threshold),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // WORD), lambda i, k: (i, k)),
            pl.BlockSpec((bk, b), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bm, b), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, b), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a_words, xp)
