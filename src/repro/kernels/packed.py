"""JAX-side packed-bitset utilities (uint32 words).

Device-side mirror of ``repro.core.bitset`` (which uses uint64 + numpy).
TPU vector registers operate on 32-bit lanes, so the device path packs into
``uint32``: bit ``i`` of a universe lives in word ``i >> 5``, position
``i & 31`` (little-endian), matching the unpack order used inside the Pallas
kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32


def n_words(n: int) -> int:
    return (n + WORD - 1) // WORD


def pad_to_words(n: int) -> int:
    return n_words(n) * WORD


def pack(mask: jax.Array) -> jax.Array:
    """bool (..., n) -> uint32 (..., ceil(n/32)), little-endian bit order."""
    n = mask.shape[-1]
    pad = (-n) % WORD
    if pad:
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-1] + (pad,), dtype=mask.dtype)], -1)
    m = mask.reshape(mask.shape[:-1] + (-1, WORD)).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32))
    return (m * weights).sum(axis=-1).astype(jnp.uint32)


def unpack(words: jax.Array, n: int | None = None) -> jax.Array:
    """uint32 (..., W) -> bool (..., n)."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    out = bits.reshape(words.shape[:-1] + (-1,)).astype(jnp.bool_)
    return out if n is None else out[..., :n]


def popcount(words: jax.Array) -> jax.Array:
    """Per-element popcount; reduce with .sum() as needed."""
    return jax.lax.population_count(words.astype(jnp.uint32)).astype(jnp.int32)


def first_set_bits(words: jax.Array, size: int):
    """The first ``size`` set bits of ``words`` (uint32 ``(F, W)``) in
    row-major order, as ``(row, col, valid)`` int32/int32/bool ``(size,)``
    vectors with ``col`` in ``[0, 32 W)``; slots past the last set bit have
    ``valid`` False (and arbitrary row/col).

    Same answer as ``nonzero(unpack(words), size=size)`` without the dense
    ``(F, 32 W)`` unpack or a sort: one popcount prefix sum over the
    words, a binary search per slot for its word, then a 32-lane rank
    select inside that word.
    """
    f, w = words.shape
    flat = words.reshape(-1)
    pc = jax.lax.population_count(flat).astype(jnp.int32)
    incl = jnp.cumsum(pc)
    slots = jnp.arange(size, dtype=jnp.int32)
    word = jnp.searchsorted(incl, slots, side="right").astype(jnp.int32)
    word = jnp.minimum(word, f * w - 1)
    rank = slots - (incl[word] - pc[word])          # rank inside the word
    bits = ((flat[word][:, None] >> jnp.arange(WORD, dtype=jnp.uint32))
            & jnp.uint32(1)).astype(jnp.int32)      # (size, 32)
    pos = jnp.argmax(jnp.cumsum(bits, axis=1) > rank[:, None],
                     axis=1).astype(jnp.int32)
    return word // w, (word % w) * WORD + pos, slots < incl[-1]


def pack_host(mask: np.ndarray) -> np.ndarray:
    """Host (numpy) twin of :func:`pack`: bool ``(..., n)`` -> uint32
    ``(..., ceil(n/32))``, bit-identical, without touching a device."""
    mask = np.asarray(mask, dtype=bool)
    pad = (-mask.shape[-1]) % WORD
    if pad:
        mask = np.concatenate(
            [mask, np.zeros(mask.shape[:-1] + (pad,), dtype=bool)], -1)
    return np.ascontiguousarray(
        np.packbits(mask, axis=-1, bitorder="little")).view(np.uint32)


def pack_numpy_u64_to_u32(words64: np.ndarray) -> np.ndarray:
    """Reinterpret the host path's packed uint64 words as device uint32 words
    (little-endian layouts are bit-compatible)."""
    return np.ascontiguousarray(words64).view(np.uint32)
