"""Compute-on-compressed resident columns: RLE / delta+RLE codecs.

Device-resident OpLog columns were stored fully decompressed, so per-doc
residency, H2D staging bytes, and the tiered store's warm->hot promotion
cost all scaled linearly with history size. This module keeps the
resident representation encoded end to end, following LSM-OPD's
compute-on-compressed argument (arXiv:2508.11862) and the reference's
own RLE/delta columnar storage format:

* **run-length** for the low-cardinality columns (``action``,
  ``value_tag``, ``insert``, ``width``, ``expand``, ``mark_name_idx``,
  ``prop``, ``obj_dense``): runs of one repeated value.
* **delta+RLE** for the monotone / striding columns (the packed-key
  columns ``id_key`` / ``obj_key`` / ``elem_key``, plus ``elem_ref`` /
  ``value_int`` whose typing-chain shapes are stride runs): each run is
  an arithmetic sequence ``(start, stride, length)``. The per-run table
  of a sorted key column doubles as an offset-value coding
  (arXiv:2209.08420) of the ``(counter, actor)`` composite: a
  Lamport-order membership probe is a searchsorted over ``R`` run heads
  plus O(1) stride arithmetic, instead of a searchsorted over all ``N``
  packed keys (``StrideRuns.join``).
* **dense passthrough** for everything that doesn't compress: a column
  whose run count crosses the ratio gate demotes to dense (accounted at
  its dense size, counted via ``oplog.compress_fallback{column,reason}``)
  so degenerate histories never pay encode+decode for nothing.

The resident bundle (``CompressedOpColumns``) is maintained
*incrementally*: tail appends — the dominant shape, every
``OpLog.append_changes`` / ``ops/host_batch._tail_write`` splice —
extend the last run in place instead of re-encoding
(``StrideRuns.extend_tail``); a splice that inserts before the tail
keeps the encodings of the rows below its first insertion point
(``keep_prefix``) and the next sync encodes the rest; anything that
rewrites the resident prefix (actor-rank remaps, re-resolved MISSING
references) invalidates the bundle and the next consumer re-encodes
lazily.

``AUTOMERGE_TPU_COMPRESSED=0`` restores the dense path everywhere (the
A/B and differential-oracle knob — read per call, so one process can
compare both modes).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import numpy as np


def enabled() -> bool:
    """Whether compressed residency is active (default on)."""
    return os.environ.get("AUTOMERGE_TPU_COMPRESSED", "1") != "0"


def gate_ratio() -> float:
    """Run-count demotion gate: a column with more than ``gate * rows``
    runs stores nothing and accounts dense
    (``AUTOMERGE_TPU_COMPRESS_GATE``, default 0.5)."""
    try:
        return float(os.environ.get("AUTOMERGE_TPU_COMPRESS_GATE", "0.5"))
    except ValueError:
        return 0.5


def run_gate(n_runs: int, n_rows: int) -> bool:
    """True when a run table is degenerate enough that a column must ship
    (and compute) dense — the same two-axis demotion rule the resident
    bundle applies: the run count crosses ``gate_ratio()`` of the rows,
    or the run table wouldn't even undercut the dense int32 image (one
    (w, cum) int32 pair per run vs one int32 per row)."""
    return n_runs > gate_ratio() * n_rows or 2 * n_runs >= n_rows


class StrideRuns:
    """One column as arithmetic-sequence runs.

    ``starts`` are row offsets (ascending, ``starts[0] == 0``),
    ``vals`` the per-run start values, ``strides`` the per-run step
    (all int64; a pure-RLE encode pins every stride to 0). ``n`` is the
    decoded length. ``is_sorted`` marks a strictly-increasing column —
    the precondition for ``join``.
    """

    __slots__ = ("starts", "vals", "strides", "n", "dtype", "is_sorted",
                 "stride_mode")

    def __init__(self, starts, vals, strides, n, dtype, is_sorted,
                 stride_mode=True):
        self.starts = starts
        self.vals = vals
        self.strides = strides
        self.n = int(n)
        self.dtype = np.dtype(dtype)
        self.is_sorted = bool(is_sorted)
        self.stride_mode = bool(stride_mode)

    # -- construction --------------------------------------------------------

    @classmethod
    def encode(cls, arr, stride: bool = True) -> "StrideRuns":
        """Encode one column. ``stride=False`` produces pure RLE (every
        run a repeated value) — the low-cardinality column mode."""
        arr = np.asarray(arr)
        dtype = arr.dtype
        x = arr.astype(np.int64, copy=False)
        n = len(x)
        if n == 0:
            z = np.empty(0, np.int64)
            return cls(z, z, z, 0, dtype, True, stride)
        if n == 1:
            z = np.zeros(1, np.int64)
            return cls(z, x.copy(), np.zeros(1, np.int64), 1, dtype, True,
                       stride)
        d = np.diff(x)
        if stride:
            # row p >= 2 starts a new run when the step into it differs
            # from the step before it; row 1 always rides run 0
            b = np.flatnonzero(d[1:] != d[:-1]) + 2
        else:
            b = np.flatnonzero(d != 0) + 1
        starts = np.concatenate([[0], b]).astype(np.int64)
        lengths = np.diff(np.concatenate([starts, [n]]))
        vals = x[starts]
        if stride:
            safe = np.minimum(starts, n - 2)
            strides = np.where(lengths > 1, d[safe], 0).astype(np.int64)
        else:
            strides = np.zeros(len(starts), np.int64)
        return cls(starts, vals, strides, n, dtype, bool(np.all(d > 0)),
                   stride)

    # -- primitives ----------------------------------------------------------

    @property
    def run_count(self) -> int:
        return len(self.starts)

    @property
    def nbytes(self) -> int:
        """Actual resident footprint of the encoded form."""
        return self.starts.nbytes + self.vals.nbytes + self.strides.nbytes

    def lengths(self) -> np.ndarray:
        return np.diff(np.concatenate([self.starts, [self.n]]))

    def decode(self) -> np.ndarray:
        if self.n == 0:
            return np.empty(0, self.dtype)
        ln = self.lengths()
        off = np.arange(self.n, dtype=np.int64) - np.repeat(self.starts, ln)
        out = np.repeat(self.vals, ln) + np.repeat(self.strides, ln) * off
        return out.astype(self.dtype, copy=False)

    def last_value(self) -> int:
        ln = self.n - 1 - int(self.starts[-1])
        return int(self.vals[-1] + self.strides[-1] * ln)

    def slice(self, lo: int, hi: int) -> "StrideRuns":
        """The encoded form of ``decode()[lo:hi]`` without decoding the
        whole column (run-walking: clip the overlapping runs)."""
        lo = max(int(lo), 0)
        hi = min(int(hi), self.n)
        if hi <= lo:
            z = np.empty(0, np.int64)
            return StrideRuns(z, z, z, 0, self.dtype, True, self.stride_mode)
        j0 = int(np.searchsorted(self.starts, lo, side="right")) - 1
        j1 = int(np.searchsorted(self.starts, hi, side="left"))
        starts = self.starts[j0:j1].copy()
        vals = self.vals[j0:j1].copy()
        strides = self.strides[j0:j1].copy()
        vals[0] += strides[0] * (lo - starts[0])
        starts[0] = lo
        starts -= lo
        return StrideRuns(starts, vals, strides, hi - lo, self.dtype,
                          self.is_sorted, self.stride_mode)

    def extend_tail(self, tail) -> None:
        """Append ``tail`` in place: the boundary run extends instead of
        re-encoding the resident prefix (the tail-append fast path).
        O(len(tail) + new runs)."""
        tail = np.asarray(tail).astype(np.int64, copy=False)
        k = len(tail)
        if k == 0:
            return
        if self.n == 0:
            e = StrideRuns.encode(tail.astype(self.dtype, copy=False),
                                  stride=self.stride_mode)
            self.starts, self.vals, self.strides = e.starts, e.vals, e.strides
            self.n, self.is_sorted = e.n, e.is_sorted
            return
        pure_rle = not self.stride_mode
        e = StrideRuns.encode(tail, stride=not pure_rle)
        last = self.last_value()
        d0 = int(tail[0]) - last
        if d0 <= 0:
            self.is_sorted = False
        if not e.is_sorted:
            self.is_sorted = False
        n0 = self.n
        L = n0 - int(self.starts[-1])  # length of the resident last run
        st = int(self.strides[-1])
        l0 = int(e.lengths()[0])
        st0 = int(e.strides[0])
        merge = False
        new_stride = st
        if pure_rle:
            merge = d0 == 0 and st0 == 0
            new_stride = 0
        elif L >= 2:
            merge = d0 == st and (l0 == 1 or st0 == st)
        else:  # singleton resident run adopts whatever stride continues it
            merge = l0 == 1 or st0 == d0
            new_stride = d0
        drop = 1 if merge else 0
        if merge:
            self.strides[-1] = new_stride
        self.starts = np.concatenate([self.starts, e.starts[drop:] + n0])
        self.vals = np.concatenate([self.vals, e.vals[drop:]])
        self.strides = np.concatenate([self.strides, e.strides[drop:]])
        self.n = n0 + k

    def splice(self, pos: int, values) -> "StrideRuns":
        """Encoded form after inserting ``values`` at row ``pos``. The
        ``pos == n`` tail case extends runs in place (and returns self);
        interior splices re-encode — the generic, rare path."""
        if pos == self.n:
            self.extend_tail(values)
            return self
        x = self.decode()
        out = np.concatenate([
            x[:pos],
            np.asarray(values).astype(self.dtype, copy=False),
            x[pos:],
        ])
        return StrideRuns.encode(out, stride=self.stride_mode)

    # -- the offset-value-coded membership join ------------------------------

    def join(self, keys, missing: int) -> np.ndarray:
        """Row indices of ``keys`` in this (strictly sorted) column —
        ``join_rows`` over the run table: searchsorted over R run heads
        + stride arithmetic, instead of over all N rows. Requires
        ``is_sorted``."""
        if not self.is_sorted:
            raise ValueError("join requires a strictly sorted column")
        keys = np.asarray(keys, np.int64)
        if self.run_count == 0 or len(keys) == 0:
            return np.full(len(keys), missing, np.int32)
        j = np.searchsorted(self.vals, keys, side="right") - 1
        inside = j >= 0
        jc = np.clip(j, 0, self.run_count - 1)
        rel = keys - self.vals[jc]
        st = self.strides[jc]
        ln = self.lengths()[jc]
        st_safe = np.where(st > 0, st, 1)
        q = rel // st_safe
        hit = (
            inside
            & (rel >= 0)
            & (rel % st_safe == 0)
            & (q < ln)
            & ((st > 0) | (rel == 0))
        )
        row = self.starts[jc] + q
        return np.where(hit, row, np.int64(missing)).astype(np.int32)


# -- the resident bundle ------------------------------------------------------

# (column attr, codec mode, dense itemsize). Mode "rle" = repeated-value
# runs, "delta" = stride runs. Row columns index by log.n; the pred_*
# edge columns (by len(pred_src)) ride the same machinery below.
ROW_SPEC = (
    ("action", "rle", 4),
    ("insert", "rle", 1),
    ("prop", "rle", 4),
    ("value_tag", "rle", 4),
    ("width", "rle", 4),
    ("expand", "rle", 1),
    ("mark_name_idx", "rle", 4),
    ("obj_dense", "rle", 4),
    ("id_key", "delta", 8),
    ("obj_key", "delta", 8),
    ("elem_key", "delta", 8),
    ("elem_ref", "delta", 4),
    ("value_int", "delta", 8),
)
EDGE_SPEC = (
    ("pred_src", "delta", 4),
    ("pred_tgt", "delta", 4),
    ("pred_key", "delta", 8),
)

_DENSE = "dense"  # per-column demotion marker


class CompressedOpColumns:
    """The incrementally-maintained compressed image of one OpLog's
    resident columns: per-column ``StrideRuns`` (or the dense-demotion
    marker), each with its own covered-row cursor so a lazy consumer
    only ever encodes the un-covered tail. The authority for true
    resident bytes (``nbytes``), the dense equivalent
    (``dense_nbytes``), and the offset-value-coded id join."""

    __slots__ = ("entries", "covered", "demoted")

    def __init__(self):
        self.entries: Dict[str, object] = {}
        self.covered: Dict[str, int] = {}
        self.demoted: Dict[str, str] = {}

    # -- introspection -------------------------------------------------------

    def all_dense(self, names) -> bool:
        """True when every tracked column in ``names`` is dense-demoted —
        the snapshot writer's short-circuit signal: there are no run
        tables to serialize, so the compressed-encode walk can be skipped
        entirely (storage/runsnap.py counts ``compact.dense_shortcut``).
        Zero-row run entries (e.g. empty pred columns) count as dense-
        compatible: they hold no runs either way."""
        seen_dense = False
        for nm in names:
            e = self.entries.get(nm)
            if e is _DENSE:
                seen_dense = True
            elif e is None or e.n:
                return False
        return seen_dense

    def runs_for(self, name: str, rows: int):
        """The live StrideRuns for ``name`` iff it covers exactly
        ``rows`` rows; None for dense-demoted / stale / untracked
        columns (callers then serialize the dense array verbatim)."""
        ent = self.entries.get(name)
        if ent is None or ent is _DENSE or ent.n != rows:
            return None
        return ent

    # -- maintenance ---------------------------------------------------------

    def _sync_col(self, name: str, mode: str, arr, total: int,
                  itemsize: int = 8) -> None:
        from .. import obs

        cov = self.covered.get(name, 0)
        ent = self.entries.get(name)
        if cov > total or (ent is not None and ent is not _DENSE
                           and ent.n != cov):
            # the resident prefix moved under us (or the cursor is
            # ahead of the column): rebuild from scratch
            ent = None
            cov = 0
        if ent is _DENSE:
            self.covered[name] = total
            return
        arr = np.asarray(arr)
        if ent is None:
            ent = StrideRuns.encode(arr[:total], stride=(mode == "delta"))
        elif cov < total:
            ent.extend_tail(arr[cov:total].astype(np.int64, copy=False)
                            if arr.dtype != np.int64 else arr[cov:total])
        # demotion gate, both axes: run-structure degeneracy (run count
        # past the ratio gate) and plain bytes (an encoded column must
        # never cost more than its dense self — 24 B/run vs itemsize/row)
        if total and (
            ent.run_count > gate_ratio() * total
            or ent.nbytes >= total * itemsize
        ):
            obs.count("oplog.compress_fallback",
                      labels={"column": name, "reason": "ratio"})
            self.entries[name] = _DENSE
            self.demoted[name] = "ratio"
        else:
            self.entries[name] = ent
        self.covered[name] = total

    def sync(self, log) -> "CompressedOpColumns":
        """Bring every tracked column's encoding up to the log's current
        row/edge counts (tail-encode only what is new)."""
        n = log.n
        q = len(log.pred_src)
        for name, mode, item in ROW_SPEC:
            arr = getattr(log, name)
            if arr is None:  # assembler-built logs defer elem_key
                self.entries.pop(name, None)
                self.covered[name] = 0
                continue
            if name in ("insert", "expand"):
                arr = np.asarray(arr, np.bool_).view(np.int8)
            self._sync_col(name, mode, arr, n, item)
        for name, mode, item in EDGE_SPEC:
            arr = getattr(log, name)
            if arr is None:
                self.entries.pop(name, None)
                self.covered[name] = 0
                continue
            self._sync_col(name, mode, arr, q, item)
        return self

    def keep_prefix(self, p0: int, edges: int, refs: dict) -> None:
        """After a splice that left resident rows below ``p0`` and the
        first ``edges`` edges in place: keep only their encodings, so the
        next ``sync`` encodes the rest. ``refs`` maps each row-reference
        column to its values before the splice; such a column keeps only
        the part before its first reference to a moved row (>= ``p0``)."""
        for spec, keep in ((ROW_SPEC, p0), (EDGE_SPEC, edges)):
            for name, _mode, _item in spec:
                ent = self.entries.get(name)
                if ent is None or ent is _DENSE:
                    continue
                k = min(keep, ent.n)
                old = refs.get(name)
                if old is not None:
                    hit = np.flatnonzero(np.asarray(old[:k]) >= p0)
                    if len(hit):
                        k = int(hit[0])
                if k < ent.n:
                    self.entries[name] = ent.slice(0, k)
                    self.covered[name] = k

    def extend_id(self, d_id) -> Optional[StrideRuns]:
        """Extend ONLY the id_key runs with a tail delta (the append
        path's eager extension, so the offset-value join can run against
        the post-splice column before the rest of the bundle syncs).
        Returns the extended runs, or None when id_key is demoted."""
        ent = self.entries.get("id_key")
        if ent is None or ent is _DENSE:
            return None
        ent.extend_tail(d_id)
        self.covered["id_key"] = ent.n
        return ent if ent.is_sorted else None

    def id_runs(self) -> Optional[StrideRuns]:
        ent = self.entries.get("id_key")
        if ent is None or ent is _DENSE or not ent.is_sorted:
            return None
        return ent

    # -- accounting ----------------------------------------------------------

    def nbytes(self, log) -> int:
        """True resident bytes of the column set under this encoding
        (demoted columns count dense)."""
        total = 0
        for name, _, item in ROW_SPEC + EDGE_SPEC:
            ent = self.entries.get(name)
            rows = self.covered.get(name, 0)
            if ent is None or ent is _DENSE:
                total += rows * item
            else:
                total += ent.nbytes
        return total

    def dense_nbytes(self, log) -> int:
        return sum(
            self.covered.get(name, 0) * item
            for name, _, item in ROW_SPEC + EDGE_SPEC
        )

    def ratio(self, log) -> float:
        c = self.nbytes(log)
        return (self.dense_nbytes(log) / c) if c else 1.0

    def run_counts(self) -> Dict[str, int]:
        return {
            name: (-1 if ent is _DENSE else ent.run_count)
            for name, ent in self.entries.items()
        }

    # -- integrity (integrity.py device-mirror audit) ------------------------

    def verify_against(self, log) -> list:
        """Differential oracle check: decode every encoded column and
        compare it to the dense host array it claims to represent.
        Returns the names of mismatching columns (empty = faithful).
        Read-only — verification must observe the bundle as consumers
        would, not repair it."""
        bad = []
        n = log.n
        q = len(log.pred_src)
        for name, _mode, _item in ROW_SPEC + EDGE_SPEC:
            ent = self.entries.get(name)
            if ent is None or ent is _DENSE:
                continue
            arr = getattr(log, name)
            if arr is None:
                bad.append(name)  # encoded rows for a column the log lost
                continue
            rows = q if name in ("pred_src", "pred_tgt", "pred_key") else n
            cov = self.covered.get(name, 0)
            if cov > rows or ent.n != cov:
                bad.append(name)
                continue
            if name in ("insert", "expand"):
                arr = np.asarray(arr, np.bool_).view(np.int8)
            want = np.asarray(arr[:cov]).astype(np.int64, copy=False)
            if not np.array_equal(ent.decode().astype(np.int64, copy=False),
                                  want):
                bad.append(name)
        return bad
