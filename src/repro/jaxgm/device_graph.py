"""Device-resident packed data graph for the TPU-adapted matcher.

Holds the four packed operand matrices of the §5.5 bitset algebra —
adjacency, adjacency-transpose, reachability closure, closure-transpose —
as ``uint32`` words padded to a block multiple, plus node labels.  Built
either from a host :class:`~repro.core.graph.DataGraph` (closure from the
host index) or entirely on device (closure via the ``closure`` kernel /
blocked squaring).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import DataGraph
from ..core.slabgeom import resident_rows
from ..kernels import ops, packed
from ..kernels.bitmm import K_BLOCK
from ..obs.ledger import get_ledger

PAD_LABEL = -2  # label id of padding nodes: never matches any query label


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceGraph:
    n: int                      # real node count
    n_pad: int                  # padded universe (multiple of block)
    labels: jax.Array           # int32 (n_pad,), PAD_LABEL on padding
    adj: jax.Array              # uint32 (n_pad, n_pad/32) children rows
    adj_t: jax.Array            # parents rows
    reach: jax.Array            # descendant rows (≺, path len >= 1)
    reach_t: jax.Array          # ancestor rows

    # --- pytree plumbing (n/n_pad are static aux data) ---
    def tree_flatten(self):
        return ((self.labels, self.adj, self.adj_t, self.reach, self.reach_t),
                (self.n, self.n_pad))

    @classmethod
    def tree_unflatten(cls, aux, children):
        labels, adj, adj_t, reach, reach_t = children
        n, n_pad = aux
        return cls(n=n, n_pad=n_pad, labels=labels, adj=adj, adj_t=adj_t,
                   reach=reach, reach_t=reach_t)

    @property
    def n_words(self) -> int:
        return self.n_pad // 32


def _repack_pad(words64: np.ndarray, n_pad: int) -> np.ndarray:
    """Host uint64-packed rows -> uint32 rows over n_pad, packed on the
    host: little-endian uint64 words re-viewed as uint32 are bit-identical
    to :func:`repro.kernels.packed.pack`, and bits past the universe are
    zero, so nothing but the packed matrix is ever shipped."""
    words32 = packed.pack_numpy_u64_to_u32(words64)
    rows, w32 = words32.shape
    out = np.zeros((n_pad, n_pad // 32), dtype=np.uint32)
    w = min(w32, n_pad // 32)
    out[:rows, :w] = words32[:, :w]
    return out


def padded_size(n: int, block: int) -> int:
    """Universe size the device matcher pads ``n`` nodes to: a ``block``
    multiple, and beyond one bitmm contraction block a multiple of it
    (the packed operand's word blocks must be lane-aligned)."""
    n_pad = -(-n // block) * block
    if n_pad > K_BLOCK:
        n_pad = -(-n_pad // K_BLOCK) * K_BLOCK
    return n_pad


def from_host(graph: DataGraph, block: int = 512,
              closure_on_device: bool = False,
              impl: str = "auto") -> DeviceGraph:
    n = graph.n
    n_pad = padded_size(n, block)
    labels = np.full(n_pad, PAD_LABEL, dtype=np.int32)
    labels[:n] = graph.labels

    adj = _repack_pad(graph.adj_bits(), n_pad)
    adj_t = _repack_pad(graph.adj_bits_t(), n_pad)
    if closure_on_device:
        reach = np.asarray(ops.transitive_closure(jnp.asarray(adj), impl=impl))
        dense = np.unpackbits(reach.view(np.uint8), axis=1,
                              bitorder="little").astype(bool)
        reach_t = packed.pack_host(dense.T)
    else:
        ridx = graph.reachability()
        reach = _repack_pad(ridx.reach_bits, n_pad)
        reach_t = _repack_pad(ridx.bits_t(), n_pad)
    dg = DeviceGraph(n=n, n_pad=n_pad,
                     labels=jnp.asarray(labels),
                     adj=jnp.asarray(adj), adj_t=jnp.asarray(adj_t),
                     reach=jnp.asarray(reach), reach_t=jnp.asarray(reach_t))
    shipped = (labels.nbytes + adj.nbytes + adj_t.nbytes
               + reach.nbytes + reach_t.nbytes)
    get_ledger().transfers.h2d("label_build", shipped,
                               getattr(graph, "graph_key", "-"))
    return dg


def pack_resident_rig(rig):
    """Concatenate a RIG's per-edge packed adjacency into one uint32
    matrix for the resident gather-intersect path.

    Every ``rig.fwd[e]`` / ``rig.bwd[e]`` uint64 matrix is re-viewed as
    little-endian uint32 lanes (bit-compatible with the host packing) and
    stacked row-wise into ``(R, W)`` with ``W`` = the widest edge's lane
    count rounded to 128; rows are zero-extended beyond their true width,
    so AND/popcount over the common width is exact.  A dedicated all-zero
    row follows the edge rows — index padding targets it so padded
    dispatch rows contribute nothing — and R is padded with zero rows to
    a multiple of 8, the tile the ``gather_intersect`` kernel fetches.

    Returns ``(matrix32, fwd_off, bwd_off, zero_row)``: constraint row
    ``(edge e, forward, local src id i)`` lives at ``fwd_off[e] + i``
    (``bwd_off[e] + i`` for backward rows).

    Resident footprint: ``round8(Σ_e |cos(src_e)| + |cos(dst_e)| + 1) * W
    * 4`` bytes (:func:`repro.core.slabgeom.resident_matrix_bytes`) —
    linear in RIG nodes per edge, not in enumerated frontiers.
    """
    mats = list(rig.fwd) + list(rig.bwd)
    w_lanes = 128
    for m in mats:
        w_lanes = max(w_lanes, 2 * m.shape[1])
    w_lanes = -(-w_lanes // 128) * 128
    zero_row = sum(m.shape[0] for m in mats)
    matrix = np.zeros((resident_rows(zero_row + 1), w_lanes), dtype=np.uint32)
    fwd_off: list = []
    bwd_off: list = []
    off = 0
    for offs, group in ((fwd_off, rig.fwd), (bwd_off, rig.bwd)):
        for m in group:
            offs.append(off)
            s, w64 = m.shape
            if s:
                matrix[off:off + s, :2 * w64] = np.ascontiguousarray(
                    m).view(np.uint32).reshape(s, 2 * w64)
            off += s
    return matrix, fwd_off, bwd_off, zero_row
