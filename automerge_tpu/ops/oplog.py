"""Columnar op-log: the device representation of a document's op set.

The reference's *storage* format (rust/automerge/src/storage/document/
doc_op_columns.rs — obj/key/id/insert/action/val/succ columns) is the
blueprint for this layout, not its in-memory B-tree: ops live as a
struct-of-arrays so an entire multi-replica merge is a handful of sorts,
scatters and segmented reductions on device (see ops/merge.py).

Lamport order (reference: types.rs:517-521) compares (counter, actor-bytes).
The host flattens changes, ranks actors by byte order, packs every OpId into
an int64 ``counter << ACTOR_BITS | actor_rank`` key, and **sorts the whole
log by that key once** — after which the row index itself is a dense int32
Lamport rank. All cross-op references (pred targets, RGA reference elements,
containing objects) are resolved to row indices host-side with vectorized
searchsorted, so the device kernel is pure int32: no 64-bit emulation on
TPU, no device-side joins, comparisons are plain row-index comparisons.
"""

from __future__ import annotations

import os
import warnings
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from ..storage.change import StoredChange
from ..types import ActorId, ScalarValue, str_width

# Up to 2^20 distinct actors per merged log; counters up to 2^43
# (single authority: types.ACTOR_BITS).
from ..types import ACTOR_BITS  # noqa: E402
ACTOR_MASK = (1 << ACTOR_BITS) - 1
PAD_ACTION = 15
# the make actions (object-creating ops; reference: types.rs action
# indices 0/2/4/6) — single authority for the columnar layers
MAKE_ACTIONS = (0, 2, 4, 6)

# elem_ref sentinels (column is an int32 row index otherwise)
ELEM_HEAD = -1  # insert at list HEAD
ELEM_MAP = -2  # a map op (no element reference)
ELEM_MISSING = -3  # reference element not in this log

# value_tag codes (aligned with storage value-metadata type codes where
# they exist; reference: value.rs ValueType)
TAG_NULL = 0
TAG_FALSE = 1
TAG_TRUE = 2
TAG_UINT = 3
TAG_INT = 4
TAG_F64 = 5
TAG_STR = 6
TAG_BYTES = 7
TAG_COUNTER = 8
TAG_TIMESTAMP = 9
TAG_UNKNOWN = 10

_TAG_FOR = {
    "null": TAG_NULL,
    "uint": TAG_UINT,
    "int": TAG_INT,
    "f64": TAG_F64,
    "str": TAG_STR,
    "bytes": TAG_BYTES,
    "counter": TAG_COUNTER,
    "timestamp": TAG_TIMESTAMP,
    "unknown": TAG_UNKNOWN,
}


def join_rows(sorted_keys: np.ndarray, keys, missing: int) -> np.ndarray:
    """Row indices of ``keys`` in the sorted packed-id column
    ``sorted_keys`` (``missing`` for absent keys) — the one vectorized
    id->row join shared by log finalize, the incremental append path and
    the cross-doc host staging (ops/host_batch.py)."""
    from .. import native

    keys = np.asarray(keys, np.int64)
    if native.available():
        return native.join_rows(sorted_keys, keys, missing)
    n = len(sorted_keys)
    pos = np.searchsorted(sorted_keys, keys)
    posc = np.clip(pos, 0, max(n - 1, 0)).astype(np.int32)
    hit = (sorted_keys[posc] == keys) if n else np.zeros(len(keys), bool)
    return np.where(hit, posc, np.int32(missing)).astype(np.int32)


def pack_id(ctr: int, rank: int) -> int:
    return (int(ctr) << ACTOR_BITS) | int(rank)


def unpack_id(key: int) -> Tuple[int, int]:
    return int(key) >> ACTOR_BITS, int(key) & ACTOR_MASK


class OpLog:
    """A merged, deduplicated change set flattened into Lamport-ordered
    op columns.

    Host-side (int64/object) state: ``id_key`` packed op ids, ``obj_key``
    packed object ids, the ``values`` heap, actor/prop tables. Device-facing
    int32 columns: action/insert/prop/value_tag/value_i32/width plus
    resolved references ``elem_ref``, ``obj_dense``, ``pred_src``/
    ``pred_tgt`` (see padded_columns).
    """

    __slots__ = (
        "actors",
        "props",
        "values",
        "changes",
        "mark_names",
        "n",
        "n_objs",
        "id_key",
        "obj_key",
        "obj_table",
        "obj_dense",
        "prop",
        "elem_ref",
        "action",
        "insert",
        "value_tag",
        "value_int",
        "width",
        "pred_src",
        "pred_tgt",
        "expand",
        "mark_name_idx",
        "elem_key",
        "pred_key",
        "n_miss_elem",
        "n_miss_pred",
        "_actor_order",
        "_hash_set",
        "_bufs",
        "_comp",
    )

    def __init__(self):
        self.actors: List[ActorId] = []
        self.props: List[str] = []
        self.values: List[ScalarValue] = []
        self.changes: List[StoredChange] = []
        self.mark_names: List[str] = []
        self.n = 0
        self.n_objs = 1
        self.elem_key = None
        self.pred_key = None
        # unresolved-reference counts (elem_ref == ELEM_MISSING rows /
        # pred_tgt < 0 edges), maintained across appends: the cross-doc
        # host staging fast path is only sound when there is nothing to
        # re-resolve, and a full-column scan per drain to find that out
        # would cost O(resident) per document
        self.n_miss_elem = 0
        self.n_miss_pred = 0
        self._actor_order = None
        self._hash_set = None
        self._bufs = None
        # the incrementally-maintained compressed column image
        # (ops/compressed.py); None = stale/absent, rebuilt lazily
        self._comp = None

    # -- construction --------------------------------------------------

    @classmethod
    def from_changes(
        cls, changes: Iterable[StoredChange], fast: bool = None
    ) -> "OpLog":
        """Flatten changes (deduped by hash) into Lamport-ordered columns.

        Order-independent: visibility and RGA order depend only on op ids
        and pred links, never on application order — which is what makes the
        N-way fan-in merge a single batched kernel instead of the
        reference's per-op seek/insert loop (automerge.rs:1258-1280).

        ``fast`` selects the vectorized column extraction (native codecs,
        ops/extract.py); default: use it when available and every change
        retains its column bytes. Falls back to the per-op python path.
        """
        log = cls()
        seen = set()
        deduped: List[StoredChange] = []
        actor_bytes = set()
        for ch in changes:
            if ch.hash in seen:
                continue
            seen.add(ch.hash)
            deduped.append(ch)
            for a in ch.actors:
                actor_bytes.add(bytes(a))
        log.changes = deduped
        ranked = sorted(actor_bytes)
        rank_of = {a: i for i, a in enumerate(ranked)}
        log.actors = [ActorId(a) for a in ranked]
        if len(ranked) >= (1 << ACTOR_BITS):
            raise ValueError("too many actors for packed id encoding")

        if fast is None:
            from .. import native

            fast = native.available() and all(
                ch.op_col_data is not None or ch.cached_cols is not None
                for ch in deduped
            )
        from .. import obs

        if fast:
            from .. import native
            from .assemble import AssembleError, assemble_log
            from .extract import ExtractError

            try:
                with obs.span("device.extract", changes=len(deduped)):
                    return assemble_log(log, deduped, rank_of)
            except (
                AssembleError, ExtractError, native.NativeUnavailable,
                ValueError,
            ) as e:
                if os.environ.get("AUTOMERGE_TPU_DEBUG"):
                    raise
                warnings.warn(
                    f"native log assembly failed ({e!r}); "
                    "falling back to the batch extraction path",
                    RuntimeWarning,
                    stacklevel=2,
                )
            try:
                with obs.span("device.extract", changes=len(deduped)):
                    return cls._collect_fast(log, deduped, rank_of)
            except (ExtractError, native.NativeUnavailable, ValueError) as e:
                if os.environ.get("AUTOMERGE_TPU_DEBUG"):
                    raise
                warnings.warn(
                    f"vectorized op extraction failed ({e!r}); "
                    "falling back to the per-op path",
                    RuntimeWarning,
                    stacklevel=2,
                )
        with obs.span("device.extract", changes=len(deduped)):
            return cls._collect_slow(log, deduped, rank_of)

    @classmethod
    def _collect_slow(cls, log, deduped, rank_of) -> "OpLog":
        prop_of: Dict[str, int] = {}
        mark_of: Dict[str, int] = {}
        id_key, obj, prop, elem = [], [], [], []
        action, insert, vtag, vint, width = [], [], [], [], []
        pred_src, pred_key = [], []
        expand, mark_idx = [], []
        values: List[ScalarValue] = []

        for ch in deduped:
            ranks = [rank_of[bytes(a)] for a in ch.actors]
            author = ranks[0]
            for i, cop in enumerate(ch.ops):
                row = len(id_key)
                id_key.append(pack_id(ch.start_op + i, author))
                if cop.obj[0] == 0:
                    obj.append(0)
                else:
                    obj.append(pack_id(cop.obj[0], ranks[cop.obj[1]]))
                if cop.key.prop is not None:
                    prop.append(prop_of.setdefault(cop.key.prop, len(prop_of)))
                    elem.append(-1)
                else:
                    e = cop.key.elem
                    prop.append(-1)
                    elem.append(0 if e[0] == 0 else pack_id(e[0], ranks[e[1]]))
                action.append(int(cop.action))
                insert.append(bool(cop.insert))
                v = cop.value
                vtag.append(_value_tag(v))
                vint.append(_int_payload(v))
                values.append(v)
                width.append(str_width(v.value) if v.tag == "str" else 1)
                for pc, pa in cop.pred:
                    pred_src.append(row)
                    pred_key.append(pack_id(pc, ranks[pa]))
                expand.append(bool(cop.expand))
                if cop.mark_name is not None:
                    mark_idx.append(mark_of.setdefault(cop.mark_name, len(mark_of)))
                else:
                    mark_idx.append(-1)

        log.props = [p for p, _ in sorted(prop_of.items(), key=lambda kv: kv[1])]
        log.mark_names = [m for m, _ in sorted(mark_of.items(), key=lambda kv: kv[1])]
        return cls._finalize(
            log,
            np.asarray(id_key, np.int64),
            np.asarray(obj, np.int64),
            np.asarray(prop, np.int32),
            np.asarray(elem, np.int64),
            np.asarray(action, np.int32),
            np.asarray(insert, np.bool_),
            np.asarray(vtag, np.int32),
            np.asarray(vint, np.int64),
            np.asarray(width, np.int32),
            np.asarray(expand, np.bool_),
            np.asarray(mark_idx, np.int32),
            np.asarray(pred_src, np.int64),
            np.asarray(pred_key, np.int64),
            values,
        )

    @classmethod
    def _collect_fast(cls, log, deduped, rank_of) -> "OpLog":
        """Batch-vectorized extraction: change column bytes -> numpy arrays.

        The native core decodes every change's op columns in one pass per
        column kind (native/extract_batch.cpp) — including string interning
        for map keys / mark names — then actor indices are rank-translated
        with a single table gather (extract.ranked_batch, shared with the
        host bulk rebuild) before the shared Lamport sort. No per-change
        Python or FFI work at all.
        """
        from .extract import ranked_batch

        r = ranked_batch(deduped, rank_of)
        a = r["a"]
        N = a["n"]
        mark_idx = (
            a["mark_ids"] if a["mark_ids"] is not None else np.full(N, -1, np.int32)
        )
        log.props = list(a["key_table"])
        log.mark_names = list(a["mark_table"])
        return cls._finalize(
            log,
            r["id_key"],
            r["obj"],
            r["prop_ids"].astype(np.int32),
            r["elem"],
            a["action"],
            a["insert"],
            np.minimum(a["vcode"], TAG_UNKNOWN).astype(np.int32),
            a["value_int"],
            a["width"],
            a["expand"],
            mark_idx.astype(np.int32),
            r["pred_src"],
            r["pred_key"],
            (a["vcode"], a["voff"], a["vlen"], a["vraw"]),
        )

    @classmethod
    def _finalize(
        cls,
        log,
        id_key,
        obj,
        prop,
        elem,
        action,
        insert,
        vtag,
        vint,
        width,
        expand,
        mark_idx,
        pred_src,
        pred_key,
        values,
    ) -> "OpLog":
        """Sort everything into Lamport order and resolve references."""
        n = len(id_key)
        log.n = n

        # one argsort makes row index == dense Lamport rank
        order = np.argsort(id_key, kind="stable")
        log.id_key = id_key[order]
        obj = np.asarray(obj, np.int64)[order]
        log.obj_key = obj
        log.prop = np.asarray(prop, np.int32)[order]
        elem = np.asarray(elem, np.int64)[order]
        log.action = np.asarray(action, np.int32)[order]
        log.insert = np.asarray(insert, np.bool_)[order]
        log.value_tag = np.asarray(vtag, np.int32)[order]
        log.value_int = np.asarray(vint, np.int64)[order]
        log.width = np.asarray(width, np.int32)[order]
        log.expand = np.asarray(expand, np.bool_)[order]
        log.mark_name_idx = np.asarray(mark_idx, np.int32)[order]
        if isinstance(values, tuple):  # lazy heap: (code, off, len, raw)
            from .extract import LazyValues

            code, off, ln, raw = values
            log.values = LazyValues(code[order], off[order], ln[order], raw)
        else:
            log.values = [values[i] for i in order]

        # resolve cross-op references to row indices (vectorized joins)
        inv = np.empty(n, np.int32)  # old row -> new row
        inv[order] = np.arange(n, dtype=np.int32)

        def rows_of(keys: np.ndarray, missing: int) -> np.ndarray:
            return join_rows(log.id_key, keys, missing)

        # element references: HEAD=-1, map op=-2, missing=-3
        log.elem_ref = np.where(
            log.prop >= 0,
            np.int32(ELEM_MAP),
            np.where(elem == 0, np.int32(ELEM_HEAD), rows_of(elem, ELEM_MISSING)),
        ).astype(np.int32)

        # dense object ids: 0 = root, then by packed object id order.
        # Candidate ids come from the make ops (every object IS a make
        # op's id) — O(#objects log #objects) instead of np.unique's full
        # O(n log n) sort; a log whose ops reference objects with no make
        # op in it (partial histories) falls back to the exact unique.
        make_rows = np.flatnonzero(np.isin(log.action, MAKE_ACTIONS))
        cand = np.unique(np.concatenate([[0], log.id_key[make_rows]]))
        pos = np.searchsorted(cand, obj)
        posc = np.clip(pos, 0, len(cand) - 1)
        if np.all(cand[posc] == obj):
            log.obj_table = cand
            log.obj_dense = posc.astype(np.int32)
        else:
            # partial history: some referenced object has no make op here.
            # The table still UNIONS the make candidates so childless
            # objects resolve identically on both paths (consumers
            # searchsorted into obj_table without a membership check).
            log.obj_table = np.unique(np.concatenate([cand, obj]))
            log.obj_dense = np.searchsorted(log.obj_table, obj).astype(np.int32)
        log.n_objs = len(log.obj_table)

        # pred references -> (src row, tgt row) pairs
        pred_src = np.asarray(pred_src, np.int64)
        pred_key = np.asarray(pred_key, np.int64)
        log.pred_src = inv[pred_src] if len(pred_src) else np.empty(0, np.int32)
        tgt = rows_of(pred_key, -1) if len(pred_key) else np.empty(0, np.int32)
        log.pred_tgt = tgt.astype(np.int32)
        # packed reference keys retained for the incremental append path
        # (re-resolving MISSING refs when the referenced op arrives later)
        log.elem_key = elem
        log.pred_key = pred_key
        log.n_miss_elem = int(np.count_nonzero(log.elem_ref == ELEM_MISSING))
        log.n_miss_pred = int(np.count_nonzero(log.pred_tgt < 0))
        return log

    @classmethod
    def from_documents(cls, docs: Sequence) -> "OpLog":
        """Union of several documents' histories (the N-way fan-in input).

        AutoDocs are committed first — the device log is built from change
        history, so pending transaction ops would otherwise be silently
        absent (the reference's AutoCommit likewise commits at every
        save/merge/sync boundary, autocommit.rs:582)."""
        from ..types import using_text_encoding

        changes: List[StoredChange] = []
        encoding = None
        for d in docs:
            commit = getattr(d, "commit", None)
            if commit is not None:
                commit()
            doc = getattr(d, "doc", d)  # AutoDoc or Document
            if getattr(doc, "open_transactions", None):
                raise ValueError(
                    "document has an open manual transaction; commit or "
                    "roll it back before building a device log"
                )
            # None means "follow the process default" — resolve it before
            # comparing, else a default-encoding doc mixed with an
            # explicit-encoding doc slips past the check
            from ..types import get_text_encoding

            d_enc = getattr(doc, "text_encoding", None) or get_text_encoding()
            if encoding is None:
                encoding = d_enc
            elif d_enc != encoding:
                raise ValueError(
                    f"documents carry conflicting text encodings "
                    f"({encoding!r} vs {d_enc!r}); width columns would "
                    "silently disagree — re-encode one side first"
                )
            changes.extend(a.stored for a in doc.history)
        # width columns follow the documents' (verified-uniform) text
        # encoding; in the reference the unit is fixed per build
        with using_text_encoding(encoding):
            return cls.from_changes(changes)

    # -- device prep -----------------------------------------------------

    def columns(self, covered: np.ndarray = None, include_aorder: bool = False):
        """The device-facing column dict WITHOUT capacity padding — the
        host merge engine consumes it as-is (merge_columns pads lazily
        when it routes to the jit kernel, whose shapes must bucket).

        ``include_aorder`` attaches the compacted actor-order layout the
        condensed all-device kernel reads (bench/tests opt in; the default
        paths skip the extra device upload).
        """
        if covered is None:
            covered = np.ones(self.n, np.bool_)
        return {
            "action": self.action,
            "insert": np.asarray(self.insert, np.bool_),
            "prop": self.prop,
            "elem_ref": self.elem_ref,
            "obj_dense": self.obj_dense,
            "value_tag": self.value_tag,
            "value_i32": self.value_int.astype(np.int32),
            "width": self.width,
            "covered": np.asarray(covered, np.bool_),
            "pred_src": self.pred_src,
            "pred_tgt": self.pred_tgt,
            **({"aorder": self.actor_order()} if include_aorder else {}),
        }

    def padded_columns(self, min_capacity: int = 16, covered: np.ndarray = None,
                       include_aorder: bool = False):
        """Pad to power-of-two capacities for shape-stable jit.

        Everything is int32/bool — deliberately: int64 is emulated on TPU.
        Counter payloads are truncated to int32 on device (exact int64
        totals are recovered host-side from ``value_int`` when needed).

        ``covered`` is the per-row clock mask for historical reads
        (default: every op covered — the current-state resolution).
        """
        return pad_columns(
            self.columns(covered=covered, include_aorder=include_aorder),
            self.n_objs, min_capacity,
        )

    def actor_order(self) -> np.ndarray:
        """INSERT rows in ACTOR-CONCATENATED order: each actor's element
        ops consecutive, counters ascending. In this order a typing chain
        is a contiguous stretch (the per-op RGA references point at the
        author's previous op), which is what lets the condensed device
        linearization find chains with scans instead of pointer-chasing
        (ops/merge.device_linearize_condensed)."""
        ao = self._actor_order
        if ao is None:
            rank = (self.id_key & ACTOR_MASK).astype(np.int64)
            perm = np.argsort(rank, kind="stable").astype(np.int32)
            ao = perm[np.asarray(self.insert, bool)[perm]]
            self._actor_order = ao
        return ao

    def condensed_run_count(self) -> int:
        """Exact chain-run count of device_linearize_condensed, computed
        host-side with vector passes — picks the kernel's rcap bucket."""
        n = self.n
        if n == 0:
            return 1
        ins = np.asarray(self.insert, bool)
        er = self.elem_ref
        rows = np.arange(n, dtype=np.int64)
        # first_child[p] = LAST insert row referencing p (ascending
        # prepend: later rows shadow earlier, fancy assignment keeps the
        # last write)
        fc = np.full(n, -1, np.int64)
        em = ins & (er >= 0)
        fc[er[em]] = rows[em]
        erc = np.clip(er, 0, n - 1)
        is_cont = em & (fc[erc] == rows)
        vs = self.actor_order()
        prev = np.concatenate([[-9], vs[:-1]])
        cont = is_cont[vs] & (er[vs] == prev)
        return max(int((~cont).sum()), 1)

    def covered_mask(self, clock_max_op: np.ndarray) -> np.ndarray:
        """Vectorized ``Clock::covers`` (reference: clock.rs:71-77): row i is
        covered iff its counter <= clock_max_op[actor rank]. ``clock_max_op``
        is the dense per-rank max-op vector (0 = actor not in clock)."""
        ctr = self.id_key >> ACTOR_BITS
        rank = (self.id_key & ACTOR_MASK).astype(np.int64)
        return ctr <= np.asarray(clock_max_op, np.int64)[rank]

    # -- compressed residency (ops/compressed.py) ---------------------------

    def compressed(self, sync: bool = True):
        """The compressed image of the resident columns, or None when
        ``AUTOMERGE_TPU_COMPRESSED=0``. Maintained incrementally: tail
        appends extend the last runs, a splice before the tail keeps the
        runs below its first insertion point, prefix rewrites invalidate;
        the next call encodes what is missing."""
        from . import compressed as C

        if not C.enabled():
            return None
        if self._comp is None:
            self._comp = C.CompressedOpColumns()
        if sync:
            self._comp.sync(self)
        return self._comp

    def dense_column_nbytes(self) -> int:
        """Dense-equivalent footprint of the resident column set (what
        the pre-compression representation held per doc). Columns not
        materialized yet (``elem_key``/``pred_key`` on assembler-built
        logs) count zero on BOTH sides of the ratio — phantom bytes in
        the numerator would inflate ``compress_ratio`` and overcharge
        the dense-mode admission estimate."""
        from . import compressed as C

        q = len(self.pred_src)
        return sum(
            self.n * item
            for name, _, item in C.ROW_SPEC
            if getattr(self, name) is not None
        ) + sum(
            q * item
            for name, _, item in C.EDGE_SPEC
            if getattr(self, name) is not None
        )

    def resident_column_nbytes(self) -> int:
        """True resident bytes of the column set under the active mode
        (compressed runs where the ratio gate admits them, dense
        otherwise)."""
        comp = self.compressed()
        if comp is None:
            return self.dense_column_nbytes()
        return comp.nbytes(self)

    def compress_ratio(self) -> float:
        comp = self.compressed()
        if comp is None:
            return 1.0
        return comp.ratio(self)

    # -- host-side id helpers ---------------------------------------------

    def export_id(self, key: int) -> str:
        if key == 0:
            return "_root"
        ctr, rank = unpack_id(key)
        return f"{ctr}@{self.actors[rank].to_hex()}"

    def import_id(self, exid: str) -> int:
        if exid == "_root":
            return 0
        ctr_s, actor_hex = exid.split("@", 1)
        target = bytes.fromhex(actor_hex)
        for rank, a in enumerate(self.actors):
            if a.bytes == target:
                return pack_id(int(ctr_s), rank)
        raise KeyError(f"unknown actor in id {exid!r}")

    def row_of_id(self, key: int) -> int:
        pos = int(np.searchsorted(self.id_key, key))
        if pos < self.n and self.id_key[pos] == key:
            return pos
        raise KeyError(f"no op with id {self.export_id(key)}")

    # -- incremental append -------------------------------------------------

    def hashes(self) -> set:
        hs = self._hash_set
        if hs is None:
            hs = self._hash_set = {ch.hash for ch in self.changes}
        return hs

    def _ensure_ref_keys(self) -> bool:
        """Materialize the packed reference-key columns (``elem_key`` per
        row, ``pred_key`` per edge) the append path splices and re-resolves.
        Logs built by ``_finalize`` carry them; assembler-built logs
        reconstruct them from the resolved row refs — impossible only when
        a ref is MISSING (partial history), in which case the caller falls
        back to a full rebuild."""
        if self.elem_key is None:
            er = self.elem_ref
            if self.n and np.any(er == ELEM_MISSING):
                return False
            safe = np.clip(er, 0, max(self.n - 1, 0))
            self.elem_key = np.where(
                er == ELEM_MAP,
                np.int64(-1),
                np.where(er == ELEM_HEAD, np.int64(0), self.id_key[safe]),
            ).astype(np.int64)
        if self.pred_key is None:
            if len(self.pred_tgt) and np.any(self.pred_tgt < 0):
                return False
            self.pred_key = (
                self.id_key[self.pred_tgt].astype(np.int64)
                if len(self.pred_tgt)
                else np.empty(0, np.int64)
            )
        return True

    def _splice_col(self, name, old, new_vals, rs, m):
        """One column's splice into a capacity-bucketed backing buffer.

        Tail appends into a still-roomy buffer write only the k new slots;
        everything else allocates at the bucket capacity, copies the rows
        below the first insertion point and merges the suffix
        (``RowSplice.into``)."""
        old = np.asarray(old)
        new_vals = np.asarray(new_vals).astype(old.dtype, copy=False)
        n = len(old)
        buf = self._bufs.get(name)
        if rs.tail and buf is not None and old.base is buf and len(buf) >= m:
            buf[n:m] = new_vals
            return buf[:m]
        nbuf = np.empty(_capacity(m), old.dtype)
        out = nbuf[:m]
        rs.into(out, old, new_vals)
        self._bufs[name] = nbuf
        return out

    def append_changes(self, changes: Iterable[StoredChange]):
        """Splice new changes into the existing columns WITHOUT re-collecting
        prior replicas: extract only the fresh changes (vectorized, through
        the per-change-hash column cache), merge their rows into the
        Lamport order with searchsorted position arithmetic (only the
        resident rows after the first insertion point move: ``RowSplice``),
        re-resolve references that touch the delta, and report the dirty
        object set.

        Returns an ``AppendInfo`` on success, or ``None`` when the log
        cannot be updated in place (no retained column bytes, partial
        history with unreconstructable refs, packed-id collisions) — the
        caller then rebuilds via ``from_changes``. New actors are handled
        in place: actor ranks are byte-ordered, so inserting actors remaps
        every packed key through a MONOTONE rank map, which preserves the
        existing sort order.

        Caller contract: the active text encoding must match the one the
        resident columns were built under (as in ``from_documents``).
        """
        from .. import obs

        known = self.hashes()
        fresh: List[StoredChange] = []
        batch_seen = set()
        for ch in changes:
            if ch.hash is None or ch.hash in known or ch.hash in batch_seen:
                continue
            batch_seen.add(ch.hash)
            fresh.append(ch)
        if not fresh:
            return AppendInfo(RowSplice.append(self.n, 0),
                              np.empty(0, np.int64), None, False, 0)
        if any(
            ch.op_col_data is None and ch.cached_cols is None for ch in fresh
        ):
            obs.count("oplog.append_fallback", labels={"reason": "no_columns"})
            return None
        if not self._ensure_ref_keys():
            obs.count("oplog.append_fallback", labels={"reason": "missing_refs"})
            return None

        # -- actor universe (monotone rank remap keeps old order sorted) --
        old_bytes = [a.bytes for a in self.actors]
        delta_bytes = {bytes(a) for ch in fresh for a in ch.actors}
        actors_changed = not delta_bytes.issubset(old_bytes_set := set(old_bytes))
        if actors_changed:
            all_bytes = sorted(old_bytes_set | delta_bytes)
            if len(all_bytes) >= (1 << ACTOR_BITS):
                obs.count("oplog.append_fallback", labels={"reason": "too_many_actors"})
                return None
        else:
            all_bytes = old_bytes
        rank_of = {b: i for i, b in enumerate(all_bytes)}
        if actors_changed:
            rank_map = np.fromiter(
                (rank_of[b] for b in old_bytes), np.int64, count=len(old_bytes)
            )

            def remap_packed(key):
                key = np.asarray(key, np.int64)
                idx = np.where(key > 0, key, 0) & ACTOR_MASK
                return np.where(
                    key > 0,
                    ((key >> ACTOR_BITS) << ACTOR_BITS) | rank_map[idx],
                    key,
                )
        else:
            def remap_packed(key):
                return np.asarray(key, np.int64)

        # -- extract ONLY the fresh changes -------------------------------
        with obs.span("device.extract", changes=len(fresh)):
            r = self._extract_delta(fresh, rank_of)
        if r is None:
            return None
        a = r["a"]
        k = int(a["n"])

        n = self.n
        old_id = remap_packed(self.id_key) if n else np.empty(0, np.int64)

        if k == 0:
            # dependency-only changes: commit bookkeeping, no rows
            self._commit_actors(all_bytes, actors_changed, remap_packed, old_id)
            self.changes.extend(fresh)
            known.update(batch_seen)
            return AppendInfo(RowSplice.append(n, 0), np.empty(0, np.int64),
                              None, actors_changed, len(fresh))

        order = np.argsort(r["id_key"], kind="stable")
        d_id = r["id_key"][order]
        if np.any(d_id[1:] == d_id[:-1]):
            obs.count("oplog.append_fallback", labels={"reason": "dup_op_id"})
            return None
        pos = np.searchsorted(old_id, d_id)
        if n:
            posc = np.clip(pos, 0, n - 1)
            if np.any(old_id[posc] == d_id):
                obs.count("oplog.append_fallback", labels={"reason": "id_collision"})
                return None
        rs = RowSplice(n, pos)
        tail = rs.tail
        m = n + k
        # offset-value-coded id join: the compressed id_key runs (delta+
        # RLE over the packed (counter, actor) composites), extended
        # eagerly with the delta, answer every reference join below over
        # R run heads + stride arithmetic instead of a searchsorted over
        # all N resident keys (ops/compressed.py StrideRuns.join)
        idruns = None
        if tail and not actors_changed and n:
            from . import compressed as C

            if C.enabled():
                comp = self._comp
                if comp is None:
                    comp = self._comp = C.CompressedOpColumns()
                comp._sync_col("id_key", "delta", self.id_key, n)
                idruns = comp.extend_id(d_id)
        new_rows = rs.new_rows
        if self._bufs is None:
            self._bufs = {}

        # -- string tables (old ids stable; new names appended) ------------
        props, d_prop = _merge_table(self.props, a["key_table"],
                                     r["prop_ids"], order)
        mark_ids = a.get("mark_ids")
        if mark_ids is None:
            mark_names = list(self.mark_names)
            d_mark = np.full(k, -1, np.int32)
        else:
            mark_names, d_mark = _merge_table(self.mark_names,
                                              a["mark_table"], mark_ids, order)

        # -- splice the plain per-row columns ------------------------------
        sp = lambda name, old, new: self._splice_col(  # noqa: E731
            name, old, new, rs, m
        )
        id_new = sp("id_key", old_id, d_id)
        obj_new = sp("obj_key", remap_packed(self.obj_key), r["obj"][order])
        ek_new = sp("elem_key", remap_packed(self.elem_key), r["elem"][order])
        action_new = sp("action", self.action, a["action"][order])
        prop_new = sp("prop", self.prop, d_prop)
        insert_new = sp("insert", np.asarray(self.insert, np.bool_),
                        np.asarray(a["insert"], np.bool_)[order])
        vtag_new = sp("value_tag", self.value_tag,
                      np.minimum(a["vcode"], TAG_UNKNOWN)[order])
        vint_new = sp("value_int", self.value_int, a["value_int"][order])
        width_new = sp("width", self.width, a["width"][order])
        expand_new = sp("expand", np.asarray(self.expand, np.bool_),
                        np.asarray(a["expand"], np.bool_)[order])
        mark_new = sp("mark_name_idx", self.mark_name_idx, d_mark)

        def rows_of(keys):
            if idruns is not None:
                obs.count("oplog.ovc_join", n=len(keys))
                return idruns.join(keys, ELEM_MISSING)
            return join_rows(id_new, keys, ELEM_MISSING)

        # -- element references --------------------------------------------
        old_er = rs.map(self.elem_ref)
        d_ek = r["elem"][order]
        d_er = np.where(
            d_ek == -1,
            np.int32(ELEM_MAP),
            np.where(d_ek == 0, np.int32(ELEM_HEAD), rows_of(d_ek)),
        ).astype(np.int32)
        er_new = sp("elem_ref", old_er.astype(np.int32, copy=False), d_er)
        # previously-MISSING refs may now resolve (their target arrived)
        rere_rows = np.empty(0, np.int64)
        n_miss_elem = 0
        miss = np.flatnonzero(er_new == ELEM_MISSING)
        if len(miss):
            res = rows_of(ek_new[miss])
            got = res != ELEM_MISSING
            n_miss_elem = int(len(miss) - np.count_nonzero(got))
            if np.any(got):
                er_new[miss[got]] = res[got]
                rere_rows = miss[got]

        # -- pred edges (appended at the end; order is irrelevant) ---------
        q = len(self.pred_src)
        old_ps = rs.map(self.pred_src)
        old_pt = rs.map(self.pred_tgt)
        inv = np.empty(k, np.int64)
        inv[order] = np.arange(k)
        d_ps = new_rows[inv[r["pred_src"]]].astype(np.int32) \
            if len(r["pred_src"]) else np.empty(0, np.int32)
        d_pk = r["pred_key"]
        d_pt = rows_of(d_pk).astype(np.int32) if len(d_pk) \
            else np.empty(0, np.int32)
        d_pt = np.where(d_pt == ELEM_MISSING, np.int32(-1), d_pt)
        qm = q + len(d_ps)
        edges = RowSplice.append(q, len(d_ps))
        cat = lambda name, old, new: self._splice_col(  # noqa: E731
            name, old, new, edges, qm
        )
        ps_new = cat("pred_src", old_ps, d_ps)
        pt_new = cat("pred_tgt", old_pt, d_pt)
        pk_new = cat("pred_key", remap_packed(self.pred_key), d_pk)
        # previously-unresolved pred targets may now resolve
        rere_pred = np.empty(0, np.int64)
        n_miss_pred = 0
        pmiss = np.flatnonzero(pt_new == -1)
        if len(pmiss):
            res = rows_of(pk_new[pmiss])
            got = res != ELEM_MISSING
            n_miss_pred = int(len(pmiss) - np.count_nonzero(got))
            if np.any(got):
                pt_new[pmiss[got]] = res[got]
                rere_pred = pmiss[got]

        # -- object table / dense ids --------------------------------------
        old_table = remap_packed(self.obj_table)
        make_new = d_id[np.isin(a["action"][order], MAKE_ACTIONS)]
        add = np.concatenate([make_new, r["obj"][order]])
        new_table = np.union1d(old_table, add)
        if len(new_table) == len(old_table):
            obj_remap = None
            od_old = self.obj_dense
            self.obj_table = new_table
        else:
            obj_remap = np.searchsorted(new_table, old_table).astype(np.int32)
            od_old = obj_remap[self.obj_dense]
            self.obj_table = new_table
        od_new = np.searchsorted(new_table, r["obj"][order]).astype(np.int32)
        od_all = sp("obj_dense", od_old.astype(np.int32, copy=False), od_new)

        # -- values heap ----------------------------------------------------
        self._splice_values(a, order, rs, m)

        # -- dirty objects (NEW dense numbering) ---------------------------
        parts = [od_new, np.searchsorted(new_table, make_new)]
        if len(rere_rows):
            parts.append(od_all[rere_rows])
        if len(rere_pred):
            src = ps_new[rere_pred]
            tgt = pt_new[rere_pred]
            parts.append(od_all[src])
            parts.append(od_all[np.clip(tgt, 0, m - 1)])
        if len(d_pt):
            hit = d_pt >= 0
            if np.any(hit):
                parts.append(od_all[d_pt[hit]])
        dirty = np.unique(np.concatenate(parts)).astype(np.int64)

        # -- compressed image: rows below p0 kept their index and values,
        # so their encodings hold and the next sync encodes the rest.
        # Actor remaps rewrite every packed key, new objects amid the
        # resident ones renumber obj_dense, and re-resolved MISSING
        # references mutate elem_ref / pred_tgt in place: those
        # invalidate, and the next consumer re-encodes lazily
        if self._comp is not None:
            if (actors_changed or len(rere_rows) or len(rere_pred)
                    or (obj_remap is not None and not tail)):
                self._comp = None
            elif not tail:
                self._comp.keep_prefix(rs.p0, q, {
                    "elem_ref": self.elem_ref, "pred_src": self.pred_src,
                    "pred_tgt": self.pred_tgt,
                })

        # -- commit ---------------------------------------------------------
        self.id_key = id_new
        self.obj_key = obj_new
        self.elem_key = ek_new
        self.action = action_new
        self.prop = prop_new
        self.insert = insert_new
        self.value_tag = vtag_new
        self.value_int = vint_new
        self.width = width_new
        self.expand = expand_new
        self.mark_name_idx = mark_new
        self.elem_ref = er_new
        self.obj_dense = od_all
        self.pred_src = ps_new
        self.pred_tgt = pt_new
        self.pred_key = pk_new
        self.props = props
        self.mark_names = mark_names
        self.n = m
        self.n_objs = len(new_table)
        self.n_miss_elem = n_miss_elem
        self.n_miss_pred = n_miss_pred
        self.actors = [ActorId(b) for b in all_bytes]
        self._actor_order = None
        self.changes.extend(fresh)
        known.update(batch_seen)
        obs.count("oplog.append_rows", n=k)
        count_splice(actors_changed, tail, rs.n_moved)
        obs.event(
            "oplog.append", rows=k, total=m, tail=int(tail),
            moved=rs.n_moved, dirty_objs=len(dirty),
            actors_changed=int(actors_changed),
        )
        return AppendInfo(rs, dirty, obj_remap, actors_changed, len(fresh),
                          n_pred_old=q, rere_elem_rows=rere_rows,
                          rere_pred_edges=rere_pred)

    def _commit_actors(self, all_bytes, actors_changed, remap_packed, old_id):
        if not actors_changed:
            return
        self.id_key = old_id
        self.obj_key = remap_packed(self.obj_key)
        self.elem_key = remap_packed(self.elem_key)
        self.pred_key = remap_packed(self.pred_key)
        self.obj_table = remap_packed(self.obj_table)
        self.actors = [ActorId(b) for b in all_bytes]
        self._actor_order = None
        self._comp = None  # every packed key was rank-remapped
        # remapped arrays no longer alias the backing buffers
        self._bufs = {}

    def _extract_delta(self, fresh, rank_of):
        """ranked_batch-shaped columns for the fresh changes only, through
        whichever vectorized path is available (cached-cols assembler
        input first, then raw batch extraction)."""
        from .. import native

        try:
            from .assemble import AssembleError, ranked_from_caches

            return ranked_from_caches(list(fresh), rank_of)
        except (AssembleError, native.NativeUnavailable, ValueError):
            pass
        except Exception:
            if os.environ.get("AUTOMERGE_TPU_DEBUG"):
                raise
        try:
            from .extract import ExtractError, ranked_batch

            return ranked_batch(list(fresh), rank_of)
        except (ExtractError, native.NativeUnavailable, ValueError):
            from .. import obs

            obs.count("oplog.append_fallback", labels={"reason": "extract_failed"})
            return None

    def _splice_values(self, a, order, rs, m):
        from .extract import LazyValues

        vals = self.values
        d_code = a["vcode"][order].astype(np.int32)
        d_off = a["voff"][order].astype(np.int64)
        d_ln = a["vlen"][order].astype(np.int64)
        d_raw = a["vraw"]
        if isinstance(vals, LazyValues):
            base = len(vals.raw)
            code = self._splice_col("vcode", vals.code, d_code, rs, m)
            off = self._splice_col("voff", vals.off, d_off + base, rs, m)
            ln = self._splice_col("vlen", vals.ln, d_ln, rs, m)
            # append-only raw heap: a bytearray grows geometrically, so a
            # delta stream costs O(delta) amortized instead of re-copying
            # the resident bytes each append (offsets of old rows never
            # move, so sharing the buffer with prior LazyValues is safe)
            raw = vals.raw
            if not isinstance(raw, bytearray):
                raw = bytearray(raw)
            raw += d_raw
            nv = LazyValues(code, off, ln, raw, cap=vals.cap)
            nv.hits, nv.misses = vals.hits, vals.misses
            self.values = nv
            return
        # eager python list (slow collection path): object-array splice
        dv = LazyValues(d_code, d_off, d_ln, d_raw)
        arr = np.empty(m, object)
        rs.into(arr, vals, [dv[i] for i in range(len(d_code))])
        self.values = arr.tolist()


class RowSplice:
    """Where a splice puts the k inserted rows among n resident ones.

    ``pos`` are the inserted rows' sorted insertion points in the resident
    order. Resident rows below ``p0 = pos[0]`` keep their index; resident
    row ``p0 + i`` moves to ``moved[i]``; ``new_rows`` are the inserted
    rows' indices after the splice. So a per-row array splices as a copy
    of its first ``p0`` rows plus a merge of its suffix (``into``), and a
    row reference changes only where it is ``>= p0`` (``map``): the work
    follows the rows after the first insertion point, not the log."""

    __slots__ = ("n", "p0", "moved", "new_rows")

    def __init__(self, n: int, pos):
        pos = np.asarray(pos, np.int64)
        k = len(pos)
        p0 = int(pos[0]) if k else n
        self.n = n
        self.p0 = p0
        self.new_rows = pos + np.arange(k, dtype=np.int64)
        cnt = np.bincount(pos - p0, minlength=n - p0 + 1)
        self.moved = (np.arange(p0, n, dtype=np.int64)
                      + np.cumsum(cnt[:n - p0]))

    @classmethod
    def append(cls, n: int, k: int) -> "RowSplice":
        """k rows after the n resident ones."""
        return cls(n, np.full(k, n, np.int64))

    @property
    def tail(self) -> bool:
        return self.p0 == self.n

    @property
    def n_moved(self) -> int:
        """Resident rows that land at a new index."""
        return self.n - self.p0

    def map(self, rows):
        """Resident row indices (or references, whose negative sentinels
        pass through) to their indices after the splice. Returns ``rows``
        itself where nothing moves: do not write into the result."""
        rows = np.asarray(rows)
        if self.tail or not len(rows):
            return rows
        hit = np.flatnonzero(rows >= self.p0)
        if not len(hit):
            return rows
        out = rows.copy()
        out[hit] = self.moved[rows[hit] - self.p0]
        return out

    def into(self, out, old, new) -> None:
        """Write the merged rows into ``out`` (length n + k): ``old`` the n
        resident values, ``new`` the k inserted ones or one fill value."""
        p0 = self.p0
        out[:p0] = old[:p0]
        if self.tail:
            out[p0:] = new
        else:
            out[self.moved] = old[p0:]
            out[self.new_rows] = new


def count_splice(actors_changed: bool, tail: bool, moved: int) -> None:
    """Count one row-carrying splice: ``oplog.splices{mode}`` (``remap``
    where new actors rewrote every packed key, else ``tail`` or
    ``suffix``) and the resident rows it moved
    (``oplog.splice_rows_moved``)."""
    from .. import obs

    mode = "remap" if actors_changed else "tail" if tail else "suffix"
    obs.count("oplog.splices", labels={"mode": mode})
    obs.count("oplog.splice_rows_moved", n=moved)


class AppendInfo:
    """What an in-place ``OpLog.append_changes`` did — everything a resident
    consumer (DeviceDoc) needs to splice its own row-indexed state.

    ``splice`` says where the rows went (``RowSplice``; its ``map`` moves
    resident row ids through it); ``new_rows`` are the spliced rows'
    positions; ``dirty_objs`` are the dense object ids (NEW numbering)
    whose resolution is stale; ``obj_remap`` maps old dense ids -> new
    (None = identity)."""

    __slots__ = (
        "splice", "n_old", "n_new", "new_rows", "tail", "dirty_objs",
        "obj_remap", "actors_changed", "n_changes", "n_pred_old",
        "rere_elem_rows", "rere_pred_edges",
    )

    def __init__(self, splice, dirty_objs, obj_remap, actors_changed,
                 n_changes, n_pred_old=0, rere_elem_rows=None,
                 rere_pred_edges=None):
        self.splice = splice
        self.n_old = splice.n
        self.n_new = len(splice.new_rows)
        self.new_rows = splice.new_rows
        self.tail = splice.tail
        self.dirty_objs = dirty_objs
        self.obj_remap = obj_remap
        self.actors_changed = actors_changed
        self.n_changes = n_changes
        # edge bookkeeping for host-side delta resolution: edges before
        # index n_pred_old are carried; rere_* name previously-MISSING
        # references that resolved when their target arrived in this append
        self.n_pred_old = n_pred_old
        self.rere_elem_rows = (
            rere_elem_rows if rere_elem_rows is not None
            else np.empty(0, np.int64)
        )
        self.rere_pred_edges = (
            rere_pred_edges if rere_pred_edges is not None
            else np.empty(0, np.int64)
        )


def _merge_table(old: List[str], delta_table, ids, order) -> Tuple[List[str], np.ndarray]:
    """Union a delta's string table into the resident one (old ids stable,
    new names appended) and translate the delta's per-row ids."""
    merged = list(old)
    k = len(order)
    if not delta_table:
        return merged, np.full(k, -1, np.int32)
    pos_of = {s: i for i, s in enumerate(merged)}
    remap = np.empty(len(delta_table), np.int32)
    for j, s in enumerate(delta_table):
        gi = pos_of.get(s)
        if gi is None:
            gi = len(merged)
            merged.append(s)
            pos_of[s] = gi
        remap[j] = gi
    ids = np.asarray(ids)
    out = np.where(
        ids >= 0, remap[np.clip(ids, 0, len(delta_table) - 1)], np.int32(-1)
    ).astype(np.int32)
    return merged, out[order]


def _value_tag(v: ScalarValue) -> int:
    if v.tag == "bool":
        return TAG_TRUE if v.value else TAG_FALSE
    return _TAG_FOR.get(v.tag, TAG_UNKNOWN)


def _int_payload(v: ScalarValue) -> int:
    if v.tag in ("int", "uint", "counter", "timestamp"):
        return int(v.value)
    if v.tag == "bool":
        return int(v.value)
    return 0


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _capacity(n: int, minimum: int = 16) -> int:
    """Jit-bucket capacity: powers of two up to 8k, then multiples of 8k —
    snug enough that padded work stays within ~12% of the real row count."""
    n = max(n, minimum)
    if n <= 8192:
        return _next_pow2(n)
    return ((n + 8191) // 8192) * 8192


def _pad(a: np.ndarray, size: int, fill) -> np.ndarray:
    if len(a) == size:
        return a
    out = np.full(size, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


# what pad_columns writes past a column's rows; ``obj_dense`` pads with
# the object count and ``aorder`` with the row capacity
PAD_FILLS = {
    "action": PAD_ACTION,
    "insert": False,
    "prop": -1,
    "elem_ref": ELEM_MAP,
    "value_tag": TAG_NULL,
    "value_i32": 0,
    "width": 0,
    "covered": False,
    "pred_src": 0,
    "pred_tgt": -1,
}


def pad_columns(cols, n_objs: int, min_capacity: int = 16,
                min_preds: int = 0):
    """Pad a columns() dict to jit-bucket capacities (idempotent: already
    bucket-sized arrays pass through untouched). The pred columns take
    at least ``min_preds`` rows' bucket."""
    p = _capacity(len(cols["action"]), min_capacity)
    q = _capacity(max(len(cols["pred_src"]), min_preds), min_capacity)
    # compacted element order: pad slots carry the out-of-range sentinel
    # p (the kernel tests "slot < P" for validity)
    fills = dict(PAD_FILLS, obj_dense=np.int32(n_objs), aorder=p)
    return {
        k: _pad(
            np.asarray(v),
            q if k.startswith("pred_") else p,
            fills.get(k, 0),
        )
        for k, v in cols.items()
    }


def host_forest(cols_np):
    """Sibling forest (is_elem, parent_row, first_child, next_sib) from
    numpy columns — the host mirror of ops/merge.py forest(). Children
    order is descending row (= descending Lamport, query/insert.rs),
    built with one lexsort."""
    action = np.asarray(cols_np["action"])
    P = len(action)
    insert = np.asarray(cols_np["insert"]).astype(bool) & (action != PAD_ACTION)
    elem_ref = np.asarray(cols_np["elem_ref"])
    obj_dense = np.asarray(cols_np["obj_dense"])
    N = 2 * P + 3
    S = N - 1
    parent_row = np.where(
        insert,
        np.where(
            elem_ref == ELEM_HEAD,
            P + obj_dense,
            np.where(elem_ref >= 0, elem_ref, S),
        ),
        S,
    ).astype(np.int32)
    er = np.flatnonzero(insert).astype(np.int32)
    order = np.lexsort((-er, parent_row[er]))
    sp = parent_row[er][order]
    sr = er[order]
    first_child = np.full(N, -1, np.int32)
    next_sib = np.full(N, -1, np.int32)
    if len(sr):
        first = np.concatenate([[True], sp[1:] != sp[:-1]])
        first_child[sp[first]] = sr[first]
        same = np.concatenate([sp[1:] == sp[:-1], [False]])
        nxt = np.concatenate([sr[1:], np.array([-1], np.int32)])
        next_sib[sr] = np.where(same, nxt, -1)
    return insert, parent_row, first_child, next_sib


def host_linearize(cols_np) -> np.ndarray:
    """Document-order element indices computed host-side from the numpy
    columns, overlapping the device kernel.

    Element order depends ONLY on the insert forest (elem_ref / insert /
    obj_dense) — never on visibility (historical views of one log share
    one element order) — so the host can rank it from the same arrays it
    just uploaded, with zero extra device traffic: a lexsort builds the
    sibling lists and the native preorder walk ranks them.
    """
    from .. import native, obs

    with obs.span("host.linearize", rows=len(cols_np["insert"])):
        insert, parent_row, first_child, next_sib = host_forest(cols_np)
        P = len(insert)
        elem_index = native.preorder_index(
            first_child, next_sib, parent_row, P
        )
        return np.where(insert, elem_index, np.int32(-1))
