"""Pallas kernel correctness sweeps (interpret mode) vs the ref.py oracles.

Every kernel is exercised across shapes (including tile-boundary and
non-square cases), densities and block sizes; results are exact-integer /
boolean so assertions are equality, not allclose-with-tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, packed, ref
from repro.kernels.bitmm import bitmm_pallas
from repro.kernels.closure import closure_step_pallas
from repro.kernels.intersect import intersect_pallas


def _rand_packed(rng, m, k, density=0.2):
    dense = rng.random((m, k)) < density
    words = np.asarray(packed.pack(jnp.asarray(dense)))
    return dense, jnp.asarray(words)


# ------------------------------------------------------------------- packed
@pytest.mark.parametrize("n", [1, 31, 32, 33, 255, 1024])
def test_pack_unpack_roundtrip(n):
    rng = np.random.default_rng(n)
    mask = jnp.asarray(rng.random((3, n)) < 0.3)
    words = packed.pack(mask)
    assert words.dtype == jnp.uint32
    out = packed.unpack(words, n)
    assert np.array_equal(np.asarray(out), np.asarray(mask))
    # the host packer ships bit-identical words without touching a device
    assert np.array_equal(packed.pack_host(np.asarray(mask)),
                          np.asarray(words))


def test_popcount():
    words = jnp.asarray([[0, 1, 3, 0xFFFFFFFF]], dtype=jnp.uint32)
    assert int(packed.popcount(words).sum()) == 0 + 1 + 2 + 32


def test_u64_u32_bridge():
    from repro.core import bitset as hb
    rng = np.random.default_rng(0)
    mask = rng.random(300) < 0.4
    w64 = hb.pack(mask)
    w32 = packed.pack_numpy_u64_to_u32(w64)
    got = packed.unpack(jnp.asarray(w32), 300)
    assert np.array_equal(np.asarray(got), mask)


# -------------------------------------------------------------------- bitmm
@pytest.mark.parametrize("m,k,b", [(128, 256, 8), (256, 1024, 16),
                                   (512, 2048, 4), (128, 128, 128)])
@pytest.mark.parametrize("threshold", [True, False])
def test_bitmm_pallas_vs_ref(m, k, b, threshold):
    rng = np.random.default_rng(m + k + b)
    dense, words = _rand_packed(rng, m, k)
    x = jnp.asarray(rng.random((k, b)) < 0.3, dtype=jnp.float32)
    want = ref.bitmm_ref(words, x, threshold=threshold)
    got = bitmm_pallas(words, x, threshold=threshold, bm=128,
                       interpret=True)
    if threshold:
        assert np.array_equal(np.asarray(got) > 0, np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


# bk is the whole K or a multiple of 4096 bits (128 word lanes): the block
# shapes the TPU's (8, 128) rule admits for the packed A operand
@pytest.mark.parametrize("bm,bk", [(64, 4096), (128, 8192), (256, 4096)])
def test_bitmm_block_shapes(bm, bk):
    rng = np.random.default_rng(bm * bk)
    m, k, b = 256, 8192, 8
    dense, words = _rand_packed(rng, m, k, density=0.05)
    x = jnp.asarray(rng.random((k, b)) < 0.5, dtype=jnp.float32)
    want = ref.bitmm_ref(words, x)
    got = bitmm_pallas(words, x, bm=bm, bk=bk, interpret=True)
    assert np.array_equal(np.asarray(got) > 0, np.asarray(want))


@pytest.mark.parametrize("impl", ["blocked", "reference"])
def test_bitmm_impls_agree(impl):
    rng = np.random.default_rng(7)
    m, k, b = 128, 512, 8
    _, words = _rand_packed(rng, m, k)
    x = jnp.asarray(rng.random((k, b)) < 0.4, dtype=jnp.float32)
    want = np.asarray(ref.bitmm_ref(words, x))
    got = np.asarray(ops.bitmm(words, x, impl=impl))
    assert np.array_equal(got, want)


def test_bitmm_empty_and_full():
    m, k, b = 128, 256, 8
    zero = jnp.zeros((m, k // 32), jnp.uint32)
    ones = jnp.full((m, k // 32), 0xFFFFFFFF, jnp.uint32)
    x = jnp.ones((k, b), jnp.float32)
    assert not np.asarray(bitmm_pallas(zero, x, interpret=True)).any()
    got = np.asarray(bitmm_pallas(ones, x, threshold=False, interpret=True))
    np.testing.assert_allclose(got, k)


# ------------------------------------------------------------------ closure
@pytest.mark.parametrize("n", [128, 256, 512])
def test_closure_step_vs_ref(n):
    rng = np.random.default_rng(n)
    _, words = _rand_packed(rng, n, n, density=0.02)
    want = np.asarray(ref.closure_step_ref(words))
    got = np.asarray(closure_step_pallas(words, bm=128, bn=128, bk=128,
                                         interpret=True))
    assert np.array_equal(got, want)


def test_full_closure_matches_host_reachability():
    from repro.core.reachability import ReachabilityIndex
    from repro.data.graphs import random_labeled_graph
    from repro.kernels import packed as pk

    graph = random_labeled_graph(100, avg_degree=2.5, n_labels=3, seed=3)
    n_pad = 128
    dense = np.zeros((n_pad, n_pad), dtype=bool)
    dense[:graph.n, :graph.n] = graph.adjacency_matrix()
    words = pk.pack(jnp.asarray(dense))
    closed = ops.transitive_closure(words, impl="reference")
    got = np.asarray(pk.unpack(closed, n_pad))[:graph.n, :graph.n]
    want = ReachabilityIndex.build(graph).dense()
    assert np.array_equal(got, want)


# ---------------------------------------------------------------- intersect
@pytest.mark.parametrize("f,k,w", [(128, 2, 16), (256, 4, 64), (128, 1, 128)])
def test_intersect_pallas_vs_ref(f, k, w):
    rng = np.random.default_rng(f + k + w)
    rows = jnp.asarray(
        rng.integers(0, 2**32, size=(f, k, w), dtype=np.uint64).astype(np.uint32))
    want_rows, want_counts = ref.intersect_ref(rows)
    got_rows, got_counts = intersect_pallas(rows, bf=128, bw=16, interpret=True)
    assert np.array_equal(np.asarray(got_rows), np.asarray(want_rows))
    assert np.array_equal(np.asarray(got_counts), np.asarray(want_counts))


def test_intersect_disjoint_rows_count_zero():
    f, w = 128, 16
    a = np.zeros((f, 2, w), dtype=np.uint32)
    a[:, 0] = 0xAAAAAAAA
    a[:, 1] = 0x55555555
    got_rows, got_counts = intersect_pallas(jnp.asarray(a), interpret=True)
    assert not np.asarray(got_rows).any()
    assert not np.asarray(got_counts).any()


# --------------------------------------------------------- gather_intersect
def _gather_ref(matrix, idx):
    """Numpy oracle: per-row gather + AND-reduce + popcount (uint32)."""
    rows = matrix[np.asarray(idx)]                     # (F, K, W)
    acc = rows[:, 0]
    for i in range(1, rows.shape[1]):
        acc = acc & rows[:, i]
    counts = np.array([int(np.unpackbits(
        r.view(np.uint8)).sum()) for r in acc], dtype=np.int32)
    return acc, counts


@pytest.mark.parametrize("f,k,w", [(1, 1, 128), (5, 2, 128), (16, 3, 256),
                                   (33, 4, 128)])
def test_gather_intersect_pallas_vs_ref(f, k, w):
    from repro.kernels.gather_intersect import (gather_intersect_pallas,
                                                gather_intersect_xla)
    rng = np.random.default_rng(f * 100 + k)
    matrix = rng.integers(0, 1 << 32, size=(40, w), dtype=np.uint32)
    matrix[-1] = 0                                     # the zero row
    idx = rng.integers(0, 40, size=(f, k)).astype(np.int32)
    want_rows, want_counts = _gather_ref(matrix, idx)
    for fn in (gather_intersect_xla,
               lambda m, i, w32: gather_intersect_pallas(
                   m, i, w32=w32, interpret=True)):
        got_rows, got_counts = fn(jnp.asarray(matrix), jnp.asarray(idx),
                                  w32=w)
        got_rows = np.asarray(got_rows)[:f]            # rows stay padded
        assert np.array_equal(got_rows, want_rows)
        assert np.array_equal(np.asarray(got_counts)[:f], want_counts)


def test_gather_intersect_zero_row_padding_is_inert():
    """Padded dispatch rows target the all-zero matrix row: their AND and
    popcount must both be zero, never garbage."""
    from repro.kernels.gather_intersect import gather_intersect_pallas
    matrix = np.full((8, 128), 0xFFFFFFFF, dtype=np.uint32)
    matrix[-1] = 0
    idx = np.full((3, 2), 7, dtype=np.int32)           # all -> zero row
    rows, counts = gather_intersect_pallas(jnp.asarray(matrix),
                                           jnp.asarray(idx), w32=128,
                                           interpret=True)
    # the kernel returns padded rows; the caller's contract is [:f]
    assert not np.asarray(rows)[:3].any()
    assert not np.asarray(counts)[:3].any()


def test_expand_pairs_bit_order_and_limit():
    """expand_pairs must agree with the host little-endian unpack order
    and clip to the first `size` pairs (lexicographic pushdown)."""
    from repro.core import bitset
    from repro.kernels.gather_intersect import expand_pairs
    rng = np.random.default_rng(9)
    n_i = 70                                           # ragged tail
    w64 = bitset.n_words(n_i)
    host_rows = rng.integers(0, 1 << 63, size=(6, w64), dtype=np.uint64)
    host_rows &= bitset.tail_mask(n_i) if hasattr(bitset, "tail_mask") \
        else host_rows
    bits = bitset.unpack(host_rows, n_i)
    want_r, want_c = np.nonzero(bits)
    rows32 = np.ascontiguousarray(host_rows).view(np.uint32)
    total = len(want_r)
    for size in (total, total + 5, max(1, total // 2)):
        rid, cid = expand_pairs(jnp.asarray(rows32), n_i=n_i, size=size)
        k = min(size, total)
        assert np.array_equal(np.asarray(rid)[:k], want_r[:k])
        assert np.array_equal(np.asarray(cid)[:k], want_c[:k])
