"""Device executors for the host-driven frontier MJoin.

``repro.core.mjoin`` enumerates with host-side frontier tables; this
module holds the two device execution paths for the per-level constraint
work:

* :class:`DeviceIntersector` — the *slab-shipping* path
  (``frontier-device``): the host gathers the ``(F, K, W)`` constraint
  rows, ships the slab, and the device AND-reduces + popcounts it.
* :class:`ResidentIntersector` — the *resident* path
  (``frontier-device-resident``): every packed RIG adjacency matrix is
  concatenated and uploaded **once** after ``BuildRIG``
  (:func:`repro.jaxgm.device_graph.pack_resident_rig`); each level then
  ships only the ``(F, K)`` int32 constraint-row indices and the fused
  ``gather_intersect`` kernel does the gather + AND + popcount on device.
  Frontier expansion (set-bit -> (row, column) pairs) also runs on device
  (:func:`repro.kernels.gather_intersect.expand_pairs`), so the host
  receives compact pair pages instead of dense boolean slabs.

Both executors resolve a common ``mode``:

* ``"pallas"``    — the compiled TPU kernels (default on TPU backends);
* ``"xla"``       — the same contractions as plain jitted XLA (default
  elsewhere: orders of magnitude faster than the Pallas interpreter and
  still measures the real transfer gap between the two paths);
* ``"interpret"`` — the Pallas kernels under the interpreter (CI
  equivalence tests for the kernel logic itself).

Set the module global ``DEFAULT_MODE`` to pin a mode process-wide (the
equivalence suite sets ``"interpret"``).

Executables are compiled **ahead of time** per shape and the compile wall
time is recorded in ``compile_s``, separately from ``kernel_s`` — the
fenced per-call device time.  Earlier versions folded first-call
compilation into ``kernel_s``, skewing traces and BENCH rows.

Padding geometry (F to the next pow2 >= 128, W to a multiple of 128
uint32 lanes, K to pow2 with all-ones AND-identity rows) comes from
:mod:`repro.core.slabgeom` — the same formulas budget enforcement uses,
so ``Budget.max_slab_bytes`` bounds the *real* device allocation
(``peak_slab_bytes`` / ``peak_dispatch_bytes`` expose it).
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.slabgeom import (padded_slab_bytes, padded_slab_shape,
                             pow2_at_least, resident_dispatch_bytes,
                             resident_rows_cap)
from ..kernels.gather_intersect import (expand_pairs, gather_intersect_pallas,
                                        gather_intersect_xla)
from ..kernels.intersect import intersect_pallas, intersect_xla
from ..obs.ledger import get_ledger
from ..robust.breaker import governed

__all__ = ["DeviceIntersector", "ResidentIntersector", "resolve_mode",
           "DEFAULT_MODE", "resident_fingerprint"]

# process-wide mode pin: None = auto (pallas on TPU, xla elsewhere)
DEFAULT_MODE: Optional[str] = None

_MODES = ("pallas", "xla", "interpret")


def resolve_mode(mode: Optional[str] = None) -> str:
    mode = DEFAULT_MODE if mode is None else mode
    if mode is None:
        mode = "pallas" if jax.default_backend() == "tpu" else "xla"
    if mode not in _MODES:
        raise ValueError(f"unknown device mode: {mode!r} "
                         f"(expected one of {_MODES})")
    return mode


def resident_fingerprint(rig) -> tuple:
    """Shape signature of a RIG's packed matrices.  BuildRIG is
    deterministic per (graph, canonical query), so a cached
    :class:`ResidentIntersector` whose fingerprint matches a freshly built
    RIG was packed from identical matrices and can be re-attached without
    re-uploading."""
    return (tuple(m.shape for m in rig.fwd),
            tuple(m.shape for m in rig.bwd))


class DeviceIntersector:
    """AND-reduce + popcount one ``(F, K, W)`` uint64 frontier block.

    Callable: ``rows (F, K, W64) uint64 -> (and_rows (F, W64) uint64,
    counts (F,) int64)``.  ``interpret`` is a legacy alias: ``True`` pins
    the interpreter, ``False`` the compiled Pallas kernel; prefer
    ``mode`` (see module docstring).
    """

    def __init__(self, interpret: Optional[bool] = None,
                 mode: Optional[str] = None):
        if mode is None and interpret is not None:
            mode = "interpret" if interpret else "pallas"
        self.mode = resolve_mode(mode)
        self.calls = 0
        self.kernel_s = 0.0       # fenced per-call device time (no compile)
        self.compile_s = 0.0      # one-time AOT compile time per shape
        self.peak_slab_bytes = 0  # largest padded slab actually allocated
        self.h2d_bytes = 0        # cumulative host->device slab traffic
        self.d2h_bytes = 0        # cumulative device->host readback traffic
        # ledger attribution key; the slab intersector is a process-global
        # singleton shared across graphs, so callers may retag per dispatch
        self.ledger_key = "-"
        self._compiled = {}

    @property
    def interpret(self) -> bool:
        return self.mode == "interpret"

    def _executor(self, fp: int, kp: int, wp: int):
        key = (fp, kp, wp)
        fn = self._compiled.get(key)
        if fn is None:
            spec = jax.ShapeDtypeStruct((fp, kp, wp), jnp.uint32)
            t0 = time.perf_counter()
            if self.mode == "xla":
                fn = intersect_xla.lower(spec).compile()
            else:
                bw = max(d for d in (512, 256, 128) if wp % d == 0)
                fn = intersect_pallas.lower(
                    spec, bf=128, bw=bw,
                    interpret=self.mode == "interpret").compile()
            self.compile_s += time.perf_counter() - t0
            self._compiled[key] = fn
        return fn

    def __call__(self, rows_u64: np.ndarray, breaker=None
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Pad, compile (once per shape) and dispatch one slab.  The
        executor is built before the dispatch, outside ``breaker``: a
        kernel that does not compile raises to the caller instead of
        being retried and degraded like a failed dispatch."""
        f, k, w64 = rows_u64.shape
        w = 2 * w64                                     # uint32 lanes
        rows = np.ascontiguousarray(rows_u64).view(np.uint32)
        rows = rows.reshape(f, k, w)
        fp, kp, wp = padded_slab_shape(f, k, w64)
        if (fp, kp, wp) != (f, k, w):
            padded = np.zeros((fp, kp, wp), dtype=np.uint32)
            padded[:f, :k, :w] = rows
            if kp != k:          # AND-identity rows keep real lanes intact
                padded[:f, k:, :w] = np.uint32(0xFFFFFFFF)
            rows = padded
        self.peak_slab_bytes = max(self.peak_slab_bytes,
                                   padded_slab_bytes(f, k, w64))
        fn = self._executor(fp, kp, wp)

        def dispatch():
            # rows is padded, so rows.nbytes == padded_slab_bytes(f, k,
            # w64): the ledger charge equals the dispatched bytes by
            # construction (charged per attempt: retries ship again)
            self.h2d_bytes += rows.nbytes
            get_ledger().transfers.h2d("slab_ship", rows.nbytes,
                                       self.ledger_key)
            # fence with block_until_ready so kernel_s is true device
            # time, not async-dispatch latency
            t0 = time.perf_counter()
            out = fn(jnp.asarray(rows))
            jax.block_until_ready(out)
            self.kernel_s += time.perf_counter() - t0
            return out

        and32, counts = governed(breaker, dispatch)
        self.calls += 1
        and_np = np.asarray(and32)
        counts_np = np.asarray(counts)
        d2h = and_np.nbytes + counts_np.nbytes
        self.d2h_bytes += d2h
        get_ledger().transfers.d2h("slab_ship", d2h, self.ledger_key)
        and_rows = np.ascontiguousarray(and_np[:f, :w]).view(np.uint64)
        return and_rows, counts_np[:f].astype(np.int64)


class _ResidentSlab:
    """Opaque handle for one dispatched slab: the padded AND rows, still
    on device, awaiting an optional :meth:`ResidentIntersector.expand`."""

    __slots__ = ("acc", "f")

    def __init__(self, acc, f: int):
        self.acc = acc
        self.f = f


class ResidentIntersector:
    """Device-resident RIG executor (see module docstring).

    Built once per RIG via :meth:`build` (cached on ``rig.resident`` by
    ``repro.core.mjoin.resident_intersector``); ``nbytes`` is the resident
    matrix footprint and ``upload_s`` the fenced one-time upload.
    """

    # smallest device->host pair page; larger pages double from here so
    # expand executors stay few per level
    PAGE_BUCKET = 1024

    def __init__(self, matrix32: np.ndarray, fwd_off: List[int],
                 bwd_off: List[int], zero_row: int,
                 mode: Optional[str] = None, key: str = "-"):
        self.mode = resolve_mode(mode)
        self.key = key
        t0 = time.perf_counter()
        self.matrix = jnp.asarray(matrix32)
        jax.block_until_ready(self.matrix)
        self.upload_s = time.perf_counter() - t0
        self.nbytes = int(self.matrix.size) * 4
        ledger = get_ledger()
        ledger.transfers.h2d("resident_upload", self.nbytes, key)
        # the packed matrix stays device-resident until close(): charge the
        # resident ledger now, credit on close (conservation invariant)
        self._alloc = ledger.resident.charge(key, self.nbytes)
        self.w_lanes = int(self.matrix.shape[1])
        self.fwd_off = fwd_off
        self.bwd_off = bwd_off
        self.zero_row = zero_row
        self.fingerprint: Optional[tuple] = None
        self.calls = 0            # gather-intersect dispatches
        self.expand_calls = 0     # pair-page dispatches
        self.h2d_bytes = 0        # cumulative host->device index traffic
        self.d2h_bytes = 0        # cumulative device->host readback traffic
        self.kernel_s = 0.0       # fenced per-call device time (no compile)
        self.compile_s = 0.0      # one-time AOT compile time per shape
        self.peak_dispatch_bytes = 0
        self._compiled = {}

    @classmethod
    def build(cls, rig, mode: Optional[str] = None) -> "ResidentIntersector":
        from .device_graph import pack_resident_rig
        matrix32, fwd_off, bwd_off, zero_row = pack_resident_rig(rig)
        res = cls(matrix32, fwd_off, bwd_off, zero_row, mode=mode,
                  key=getattr(rig, "graph_key", "-"))
        res.fingerprint = resident_fingerprint(rig)
        return res

    @property
    def closed(self) -> bool:
        return self._alloc is None

    def close(self) -> int:
        """Release the device-resident matrix and credit the ledger.
        Idempotent; returns the bytes credited (0 if already closed)."""
        credited = get_ledger().resident.credit(self._alloc)
        self._alloc = None
        matrix, self.matrix = getattr(self, "matrix", None), None
        self._compiled = {}
        if matrix is not None:
            try:
                matrix.delete()
            except Exception:
                pass            # already deleted / backend shutting down
        return credited

    def __del__(self):
        # GC safety net: an executor dropped without close() must still
        # credit the ledger or the conservation invariant drifts
        try:
            self.close()
        except Exception:
            pass

    def rows_cap(self, max_bytes: int, k: int, at_most: int) -> int:
        """Largest slab height whose padded dispatch transient fits
        ``max_bytes`` (0 = infeasible: route the level through the host)."""
        return resident_rows_cap(max_bytes, k, self.w_lanes, at_most)

    # ------------------------------------------------------------ executors
    def _intersect_exec(self, fp: int, k: int, w32: int):
        key = ("isect", fp, k, w32)
        fn = self._compiled.get(key)
        if fn is None:
            mspec = jax.ShapeDtypeStruct(self.matrix.shape, jnp.uint32)
            ispec = jax.ShapeDtypeStruct((fp, k), jnp.int32)
            t0 = time.perf_counter()
            if self.mode == "xla":
                fn = gather_intersect_xla.lower(mspec, ispec,
                                                w32=w32).compile()
            else:
                fn = gather_intersect_pallas.lower(
                    mspec, ispec, w32=w32, bf=8,
                    interpret=self.mode == "interpret").compile()
            self.compile_s += time.perf_counter() - t0
            self._compiled[key] = fn
        return fn

    def _expand_exec(self, fp: int, w32: int, n_i: int, size: int):
        key = ("expand", fp, w32, n_i, size)
        fn = self._compiled.get(key)
        if fn is None:
            aspec = jax.ShapeDtypeStruct((fp, w32), jnp.uint32)
            t0 = time.perf_counter()
            fn = expand_pairs.lower(aspec, n_i=n_i, size=size).compile()
            self.compile_s += time.perf_counter() - t0
            self._compiled[key] = fn
        return fn

    # ------------------------------------------------------------------ API
    def intersect(self, cs, slab: np.ndarray, w64: int, breaker=None
                  ) -> Tuple[_ResidentSlab, np.ndarray]:
        """One level dispatch for one frontier slab.

        ``cs`` is the level's constraint list ``(prefix_pos, edge, isf)``
        (as built by ``repro.core.mjoin._constraints``), ``slab`` the
        ``(F, i)`` frontier rows, ``w64`` the level's packed row width in
        uint64 words.  Ships only the ``(F, K)`` int32 index matrix;
        returns the on-device AND rows (handle) plus host popcounts.  The
        executor is compiled before the dispatch, outside ``breaker``.
        """
        f, k = len(slab), len(cs)
        idx = np.empty((f, k), dtype=np.int32)
        for c, (j, ei, isf) in enumerate(cs):
            off = self.fwd_off[ei] if isf else self.bwd_off[ei]
            idx[:, c] = off + slab[:, j]
        fp = pow2_at_least(f)
        if fp != f:
            # padding rows gather the dedicated all-zero resident row, so
            # padded AND rows are zero: counts and expands never see them
            pad = np.full((fp - f, k), self.zero_row, dtype=np.int32)
            idx = np.vstack([idx, pad])
        w32 = 2 * w64
        fn = self._intersect_exec(fp, k, w32)

        def dispatch():
            # idx is padded, so idx.nbytes == pow2_at_least(f) * k * 4:
            # charged bytes equal shipped bytes
            self.h2d_bytes += idx.nbytes
            get_ledger().transfers.h2d("index_vectors", idx.nbytes,
                                       self.key)
            t0 = time.perf_counter()
            out = fn(self.matrix, jnp.asarray(idx))
            jax.block_until_ready(out)
            self.kernel_s += time.perf_counter() - t0
            return out

        self.peak_dispatch_bytes = max(
            self.peak_dispatch_bytes,
            resident_dispatch_bytes(f, k, self.w_lanes))
        acc, counts = governed(breaker, dispatch)
        self.calls += 1
        counts_np = np.asarray(counts)
        self.d2h_bytes += counts_np.nbytes
        get_ledger().transfers.d2h("index_vectors", counts_np.nbytes,
                                   self.key)
        return (_ResidentSlab(acc, f),
                counts_np[:f].astype(np.int64))

    def expand(self, handle: _ResidentSlab, n_i: int, want: int,
               breaker=None) -> Tuple[np.ndarray, np.ndarray]:
        """First ``want`` set-bit ``(row, column)`` pairs of a dispatched
        slab, in row-major (= lexicographic) order — computed on device,
        shipped as one compact page.  Page sizes are bucketed to powers of
        two (>= ``PAGE_BUCKET``) so each level compiles O(log) executors."""
        if want <= 0:
            z = np.empty(0, dtype=np.int64)
            return z, z
        if self.mode == "xla":
            # Plain-XLA mode means no accelerator: fetch the packed AND
            # rows and extract on the host, where unpackbits + nonzero is
            # an order of magnitude faster than XLA's serialized nonzero
            # lowering.  Pallas/interpret modes extract on device, where
            # shipping the compact (row, col) page beats shipping rows.
            def dispatch():
                t0 = time.perf_counter()
                lanes = (n_i + 31) // 32      # fetch only the live lanes
                rows = np.asarray(handle.acc[:handle.f, :lanes])
                self.d2h_bytes += rows.nbytes
                get_ledger().transfers.d2h("pair_extract_d2h", rows.nbytes,
                                           self.key)
                self.kernel_s += time.perf_counter() - t0
                return rows

            rows = governed(breaker, dispatch)
            bits = np.unpackbits(np.ascontiguousarray(rows).view(np.uint8),
                                 axis=1, bitorder="little")[:, :n_i]
            rid, cid = np.nonzero(bits)
            self.expand_calls += 1
            return rid[:want].astype(np.int64), cid[:want].astype(np.int64)
        size = pow2_at_least(want, self.PAGE_BUCKET)
        fp, w32 = handle.acc.shape
        fn = self._expand_exec(int(fp), int(w32), n_i, size)

        def dispatch():
            t0 = time.perf_counter()
            out = fn(handle.acc)
            jax.block_until_ready(out)
            self.kernel_s += time.perf_counter() - t0
            return out

        rid, cid = governed(breaker, dispatch)
        self.expand_calls += 1
        rid_np, cid_np = np.asarray(rid), np.asarray(cid)
        page = rid_np.nbytes + cid_np.nbytes   # full pages ship, then slice
        self.d2h_bytes += page
        get_ledger().transfers.d2h("pair_extract_d2h", page, self.key)
        return (rid_np[:want].astype(np.int64),
                cid_np[:want].astype(np.int64))
