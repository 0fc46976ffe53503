"""DeviceDoc: read API over a kernel-resolved op log.

The batched alternative to the host OpStore for N-way merges: build an
OpLog from many replicas' changes, run ops/merge.py once on device, then
answer reads from the resolved columns. Mirrors the reference ReadDoc
surface (reference: rust/automerge/src/read.rs:32-236) including the
historical ``*_at`` variants: ``at(heads)`` re-resolves visibility under a
clock mask (vectorized ``Clock::covers``, reference: clock.rs:71-77) while
sharing the log and the RGA element order with the current-state view —
element order depends only on the insert forest, never on the clock.

Also a patch source: ``diff(before_heads, after_heads)`` emits the same
path-qualified patches as the host differ (patches/diff.py) straight from
two clock-masked kernel resolutions, so the device merge can feed
materialized views / ``apply_patches`` without a host re-apply
(reference: rust/automerge/src/automerge/diff.rs log_diff).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..obs import prof as _prof
from ..core.marks import Mark
from ..patches.patch import (
    DeleteMap,
    DeleteSeq,
    FlagConflict,
    IncrementPatch,
    Insert,
    Patch,
    PutMap,
    PutSeq,
    SpliceText,
)
from ..types import ObjType, is_make_action, objtype_for_action
from .merge import conflict_flags, merge_columns
from .oplog import (
    ELEM_HEAD, ELEM_MISSING, MAKE_ACTIONS, ACTOR_BITS, OpLog, RowSplice,
    TAG_COUNTER,
)

_MAKE_OBJ = {0: ObjType.MAP, 2: ObjType.LIST, 4: ObjType.TEXT, 6: ObjType.TABLE}

# one jax Mesh per device count, shared across every DeviceDoc with mesh
# residency enabled (see enable_mesh)
_MESH_CACHE: Dict[int, object] = {}


def order_elem_rows(log: "OpLog", elem_index: np.ndarray,
                    obj_rows: np.ndarray) -> np.ndarray:
    """Element rows of one sequence object in DOCUMENT order: the insert
    rows the linearization ranked, sorted by their rank. The single
    definition of the element-order rule shared by DeviceDoc reads and
    the stale-store read path (core/bulk_load.stale_text)."""
    obj_rows = np.asarray(obj_rows, np.int64)
    erows = obj_rows[
        np.asarray(log.insert)[obj_rows] & (elem_index[obj_rows] >= 0)
    ]
    return erows[np.argsort(elem_index[erows], kind="stable")]


def _bisect_groups(rows: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                   want: np.ndarray) -> np.ndarray:
    """For each query i, ``lo[i]`` plus the number of ``rows[lo[i]:hi[i]]``
    below ``want[i]``, each such slice ascending: a binary search of all
    queries at once (each takes the power-of-two steps that keep it below
    ``want``), O(queries * log rows) whatever the number of slices."""
    at = np.array(lo, np.int64)
    hi = np.asarray(hi, np.int64)
    step = 1 << int((hi - at).max(initial=0)).bit_length() >> 1
    while step:
        nxt = at + step
        at += step * ((nxt <= hi) & (rows[np.minimum(nxt, hi) - 1] < want))
        step >>= 1
    return at


_OBJ_REPLACEMENT = "￼"
_PUT = 1
_DELETE = 3
_INCREMENT = 5
_MARK = 7


class DeviceDoc:
    def __init__(
        self,
        log: OpLog,
        res: Dict[str, np.ndarray],
        covered: Optional[np.ndarray] = None,
        base: Optional["DeviceDoc"] = None,
    ):
        # whale-doc mesh residency (opt-in, see enable_mesh); views share
        # the base's mesh state; AUTOMERGE_TPU_MESH_DEVICES is probed
        # LAZILY on the first full re-resolution (never at construction:
        # a many-doc server must not enumerate devices per open)
        self._mesh = None if base is None else base._mesh
        self._mesh_min_rows = 0 if base is None else base._mesh_min_rows
        self._mesh_env_tried = False if base is None else base._mesh_env_tried
        self.log = log
        self.res = res
        n = log.n
        self._base = base if base is not None else self
        self.covered = (
            covered if covered is not None else np.ones(n, np.bool_)
        )
        self.visible = res["visible"][:n]
        self.winner = res["winner"][:n]
        self.conflicts = res["conflicts"][:n]
        if base is None:
            self.elem_index = res["elem_index"][:n]
            self._views: Dict[tuple, "DeviceDoc"] = {}
            self._hash_index = {ch.hash: ch for ch in log.changes}
            self._rank_of = {a.bytes: i for i, a in enumerate(log.actors)}
            self._pending: Dict[bytes, object] = {}
            # object id -> object type, from make ops (+ root)
            self._obj_type: Dict[int, ObjType] = {0: ObjType.MAP}
            for r in np.flatnonzero(np.isin(log.action[:n], MAKE_ACTIONS)):
                self._obj_type[int(log.id_key[r])] = _MAKE_OBJ[int(log.action[r])]
            # row ranges by object
            order = np.argsort(log.obj_key[:n], kind="stable")
            self._rows_by_obj = order.astype(np.int64)
            self._obj_sorted = log.obj_key[:n][order]
            self._all_elems_cache: Dict[int, List[int]] = {}
            self._res_bufs: Dict[str, np.ndarray] = {}
            # successor bookkeeping, maintained incrementally across
            # appends (host mirror of merge.succ_resolution under the
            # base's all-covered clock) — what lets delta resolution
            # recompute visibility without a kernel pass
            self.succ_count = np.zeros(n, np.int32)
            self.inc_count = np.zeros(n, np.int32)
            if len(log.pred_src):
                tgt = np.asarray(log.pred_tgt)
                src = np.asarray(log.pred_src)
                hit = tgt >= 0
                is_inc = np.asarray(log.action)[src] == _INCREMENT
                np.add.at(self.succ_count, tgt[hit & ~is_inc], 1)
                np.add.at(self.inc_count, tgt[hit & is_inc], 1)
        else:
            self.elem_index = base.elem_index
            self._obj_type = base._obj_type
            self._rows_by_obj = base._rows_by_obj
            self._obj_sorted = base._obj_sorted
        self._recompute_counters()

    def _recompute_counters(self) -> None:
        # exact int64 counter totals, host-side, gated by this view's clock
        # (the device kernel keeps the int32 fast path; reference counters
        # are i64, value.rs:369)
        log = self.log
        self.counter_val = np.asarray(log.value_int).copy()
        if len(log.pred_src):
            mask = (
                (log.action[log.pred_src] == _INCREMENT)
                & (log.pred_tgt >= 0)
                & self.covered[log.pred_src]
            )
            np.add.at(
                self.counter_val,
                log.pred_tgt[mask],
                log.value_int[log.pred_src[mask]],
            )

    # -- construction -------------------------------------------------------

    @classmethod
    def merge(cls, docs: Sequence) -> "DeviceDoc":
        """N-way fan-in merge of documents (AutoDoc or Document)."""
        return cls.resolve(OpLog.from_documents(docs))

    # the outputs the read API consumes; everything else stays on device
    READ_FETCH = (
        "visible", "winner", "conflicts", "elem_index",
        "obj_vis_len", "obj_text_width",
    )
    # historical views reuse the base view's element order
    VIEW_FETCH = (
        "visible", "winner", "conflicts", "obj_vis_len", "obj_text_width",
    )

    @classmethod
    def resolve(cls, log: OpLog) -> "DeviceDoc":
        obs.count("device.kernel_launches", labels={"path": "per_doc"})
        _prof.note("launches")
        res = merge_columns(
            log.columns(), fetch=cls.READ_FETCH, n_objs=log.n_objs,
            n_props=len(log.props),
        )
        return cls(log, res)

    # -- incremental updates ------------------------------------------------
    #
    # The persistent-DeviceDoc path: new changes (from sync or local
    # commits) are spliced into the resident OpLog (OpLog.append_changes),
    # and only the objects the delta touches are re-resolved — a subset
    # kernel run over the dirty rows instead of a from-scratch rebuild.
    # When the dirty fraction crosses AUTOMERGE_TPU_DIRTY_FRACTION
    # (default 0.5) the whole log is re-resolved in one pass (still no
    # re-extraction) — the SynchroStore-style cost model: amortize while
    # deltas are small, recompute when they are not.

    def apply_changes(self, changes: Sequence, *, incremental: bool = True) -> int:
        """Integrate new StoredChanges into this resident document.

        Returns the number of changes integrated this call. Changes whose
        dependencies are not yet present are buffered and integrated when
        the gap fills (``pending_changes``). Duplicate (re-delivered)
        changes are no-ops. Only valid on the base (current-state) view.
        """
        if self._base is not self:
            raise ValueError("apply_changes on a historical view; use the base doc")
        # the umbrella span covers the WHOLE host apply — dedup, causal
        # ordering, splice, delta resolution — so a drain-cycle profiler
        # report attributes the staging wall clock without gaps (the
        # stage spans inside are its breakdown)
        with obs.span("device.apply", changes=len(changes)):
            ready = self._take_ready(changes)
            if not ready:
                return 0
            # an empty resident log (a device doc opened before any
            # history existed) has no actor table to splice into: the
            # rebuild path IS the initial build
            if incremental and self.log.n:
                with obs.span("device.stage.splice", changes=len(ready)):
                    info = self.log.append_changes(ready)
            else:
                info = None
            if info is None:
                obs.count("device.apply_rebuild")
                self._rebuild(list(self.log.changes) + ready)
                return len(ready)
            self._apply_append(info, ready)
            if info.n_new and not self._delta_resolve(info):
                self._reresolve(info.dirty_objs)
            self._export_doc_gauges()
        return len(ready)

    def apply_batches(self, batches: Sequence[Sequence]) -> int:
        """Integrate a stream of delta batches as a cross-document
        generation of one (``batched.stage_and_resolve``): the same
        staging and launch the serving layer's batcher runs, on every
        backend. Returns the number of changes integrated."""
        from .batched import stage_and_resolve

        if self._base is not self:
            raise ValueError("apply_batches on a historical view; use the base doc")
        if len(batches) > 1:
            # the serving layer's sync coalescing lands here: how many
            # per-message applies each drain amortized is the signal
            obs.count("device.coalesced_batches", n=len(batches))
        results, _ = stage_and_resolve([(self, batches)])
        r = results[id(self)]
        if r.error is not None:
            raise r.error
        return r.applied

    def stage_batches(self, batches: Sequence[Sequence]):
        """Host-side half of the cross-document batched apply
        (ops/batched.py): dedup + causal-order + OpLog splice exactly as
        ``apply_batches`` would over the same batches, but the dirty-set
        kernel resolution is NOT dispatched — it is returned as a
        ``BatchStage`` for the caller to pack into one shared multi-doc
        launch.

        Returns ``(applied, stage_or_None)``. ``None`` means resolution
        already completed inside this call: the delta was empty/pure
        bookkeeping, or the log had to rebuild (empty/partial resident
        history). Where the dirty fraction trips the full re-resolution
        cost model (``_resolves_whole``), the stage is a full one
        (``batched.full_stage``): every row and object of the document.
        """
        if self._base is not self:
            raise ValueError("stage_batches on a historical view; use the base doc")
        # same umbrella as apply_changes: the whole host staging half is
        # one contiguous device.apply region for cycle attribution
        with obs.span("device.apply", batches=len(batches)):
            ready = self._take_ready([ch for b in batches for ch in b])
            return self._stage_ready(ready)

    def stage_ready(self, ready: Sequence):
        """``stage_batches`` over an already-deduped/causally-ordered
        ready list — the scalar per-doc fallback (and differential
        oracle) of the cross-doc vectorized staging in
        ops/host_batch.py, which runs ``_take_ready``'s halves itself."""
        if self._base is not self:
            raise ValueError("stage_ready on a historical view; use the base doc")
        with obs.span("device.apply", changes=len(ready)):
            return self._stage_ready(ready)

    def _stage_ready(self, ready):
        from .batched import BatchStage, full_stage

        if not ready:
            return 0, None
        if self.log.n:
            with obs.span("device.stage.splice", changes=len(ready)):
                info = self.log.append_changes(ready)
        else:
            info = None
        if info is None:
            obs.count("device.apply_rebuild")
            self._rebuild(list(self.log.changes) + ready)
            return len(ready), None
        self._apply_append(info, ready)
        if not info.n_new:
            return len(ready), None
        dirty = np.asarray(info.dirty_objs, np.int64)
        rows = self._subset_rows(dirty)
        self._export_doc_gauges()
        if self._resolves_whole(rows, dirty):
            return len(ready), full_stage(self)
        return len(ready), BatchStage(self, rows, dirty)

    def pending_changes(self) -> int:
        """Changes buffered awaiting missing dependencies."""
        return len(self._pending)

    def _take_ready(self, changes: Sequence) -> list:
        """Dedup + causal-order the incoming batch against what the log
        already holds; buffer changes with missing deps. The two halves
        are timed separately (``device.stage.dedup`` /
        ``device.stage.causal_order``) — the drain-cycle profiler's host
        stage attribution starts here. The cross-doc host staging path
        (ops/host_batch.py) calls the two span-free halves directly and
        wraps each ONCE for a whole multi-document drain."""
        with obs.span("device.stage.dedup", changes=len(changes)):
            self._dedup_into_pending(changes)
        with obs.span("device.stage.causal_order",
                      pending=len(self._pending)):
            return self._drain_ready_pending()

    def _dedup_into_pending(self, changes: Sequence) -> None:
        have = self._hash_index
        pend = self._pending
        for ch in changes:
            h = ch.hash
            if h is None or h in have or h in pend:
                continue
            pend[h] = ch

    def _drain_ready_pending(self) -> list:
        have = self._hash_index
        pend = self._pending
        ready: list = []
        ready_set: set = set()
        progress = True
        while progress and pend:
            progress = False
            for h in list(pend):
                ch = pend[h]
                if all(d in have or d in ready_set
                       for d in ch.dependencies):
                    ready.append(ch)
                    ready_set.add(h)
                    del pend[h]
                    progress = True
        if pend:
            obs.count("device.apply_deferred", n=len(pend))
        return ready

    def _rebuild(self, changes: list) -> None:
        """Full fallback: re-extract and re-resolve everything in place."""
        pend = self._pending
        mesh_state = (self._mesh, self._mesh_min_rows, self._mesh_env_tried)
        log = OpLog.from_changes(changes)
        obs.count("device.kernel_launches", labels={"path": "per_doc"})
        _prof.note("launches")
        res = merge_columns(
            log.columns(), fetch=self.READ_FETCH, n_objs=log.n_objs,
            n_props=len(log.props),
        )
        self.__init__(log, res)
        self._pending = pend
        self._mesh, self._mesh_min_rows, self._mesh_env_tried = mesh_state
        self._export_doc_gauges()

    # per-doc accounting label (doc.resident_ops / doc.device_bytes):
    # set by the durable layer when this resident doc serves a named
    # document; None (the default) keeps the export path a no-op
    obs_name = None

    # last resident_nbytes() figure, stamped by the OWNING thread (the
    # apply path under the document lock). Cross-thread readers — the
    # DocStore evict sweeper's admission estimate — read this cache
    # instead of calling resident_nbytes(), because computing it syncs
    # the log's compressed image (a mutation) and must never race an
    # in-flight append. None until first computed.
    _resident_cache = None

    def resident_nbytes(self) -> int:
        """True device-path resident footprint of this document: the
        column image a drain ships/holds (compressed runs where the
        ratio gate admits them — ops/compressed.py; dense-equivalent
        with ``AUTOMERGE_TPU_COMPRESSED=0``) plus the per-row resolution
        readbacks. The number the DocStore admission policy budgets.

        Syncs the compressed image — call only from the thread that
        owns the document (apply paths, gauge export, bench); lock-free
        observers use ``resident_nbytes_estimate``."""
        n = self.log.resident_column_nbytes() + sum(
            a.nbytes for a in self.res.values()
        )
        self._resident_cache = n
        return n

    def resident_nbytes_estimate(self) -> int:
        """Read-only resident estimate for cross-thread observers: the
        owner-stamped cache when available, else the dense arithmetic
        (pure reads — never touches the compressed image)."""
        n = self._resident_cache
        if n is not None:
            return n
        return self.log.dense_column_nbytes() + sum(
            a.nbytes for a in self.res.values()
        )

    def dense_nbytes(self) -> int:
        """What the same residency costs fully decompressed — the
        pre-compression accounting, kept as the ratio denominator."""
        return self.log.dense_column_nbytes() + sum(
            a.nbytes for a in self.res.values()
        )

    def compress_ratio(self) -> float:
        r = self.resident_nbytes()
        return (self.dense_nbytes() / r) if r else 1.0

    def audit_columns(self) -> list:
        """Integrity spot-check of the resident image: sync the
        compressed bundle and verify every encoded column against the
        dense host oracle (``CompressedOpColumns.verify_against``).
        Returns mismatching column names — non-empty means this mirror
        must not serve reads and should be dropped for rebuild. Call
        from the thread that owns the document (the scrubber holds the
        doc lock)."""
        comp = self.log.compressed(sync=True)
        if comp is None:
            return []  # dense mode IS the oracle — nothing encoded to audit
        return comp.verify_against(self.log)

    def _export_doc_gauges(self) -> None:
        if self.obs_name is None:
            return
        labels = {"doc": self.obs_name}
        obs.gauge_set("doc.resident_ops", self.log.n, labels=labels)
        # TRUE resident bytes (the compressed image a drain actually
        # ships), not the dense-equivalent array bytes — the admission
        # policy must see real footprint; the ratio gauge rides along so
        # dashboards can see how hard each doc compresses
        resident = self.resident_nbytes()
        obs.gauge_set("doc.device_bytes", resident, labels=labels)
        obs.gauge_set(
            "doc.compress_ratio",
            round(self.dense_nbytes() / resident, 4) if resident else 1.0,
            labels=labels,
        )

    def _apply_append(self, info, ready: Sequence) -> None:
        """Splice this view's resolution arrays and host caches through an
        AppendInfo (positions move; values of clean objects are reused)."""
        log = self.log
        m = log.n
        n_old, rs = info.n_old, info.splice
        for ch in ready:
            self._hash_index[ch.hash] = ch
        if info.actors_changed:
            # the log's packed ids were rank-remapped in place; every host
            # cache keyed by a packed id must follow the same monotone map
            new_rank = {a.bytes: i for i, a in enumerate(log.actors)}
            remap = {old: new_rank[b] for b, old in self._rank_of.items()}
            self._obj_type = {
                (
                    k
                    if k == 0
                    else ((k >> ACTOR_BITS) << ACTOR_BITS)
                    | remap[k & ((1 << ACTOR_BITS) - 1)]
                ): v
                for k, v in self._obj_type.items()
            }
            self._rank_of = new_rank
        self._views.clear()
        if info.n_new == 0:
            if info.actors_changed:
                self._all_elems_cache.clear()
            return
        with obs.span("device.materialize", rows=info.n_new):
            nr = np.asarray(info.new_rows, np.int64)
            mk = nr[np.isin(np.asarray(log.action)[nr], MAKE_ACTIONS)]
            for r in mk:
                self._obj_type[int(log.id_key[r])] = _MAKE_OBJ[int(log.action[r])]

            # resolution arrays: old values carried, positions remapped;
            # the new rows' objects are all dirty and re-resolved next.
            # Capacity-bucketed buffers make the tail-append fast path
            # O(delta): only the k new slots are written.
            win_old = rs.map(np.asarray(self.winner, np.int32))
            vis = self._res_splice("visible", np.asarray(self.visible, np.bool_),
                                   m, rs, False)
            win = self._res_splice("winner", win_old, m, rs, -1)
            con = self._res_splice("conflicts", np.asarray(self.conflicts, np.int32),
                                   m, rs, 0)
            ei = self._res_splice("elem_index", np.asarray(self.elem_index, np.int32),
                                  m, rs, -1)
            orm = info.obj_remap
            n_objs_old = len(orm) if orm is not None else log.n_objs
            ovl = np.zeros(log.n_objs + 2, np.int32)
            otw = np.zeros(log.n_objs + 2, np.int32)
            old_ovl = np.asarray(self.res["obj_vis_len"])
            old_otw = np.asarray(self.res["obj_text_width"])
            take = min(n_objs_old, len(old_ovl))
            if orm is None:
                ovl[:take] = old_ovl[:take]
                otw[:take] = old_otw[:take]
            else:
                ovl[orm[:take]] = old_ovl[:take]
                otw[orm[:take]] = old_otw[:take]
            self.res = {
                "visible": vis, "winner": win, "conflicts": con,
                "elem_index": ei, "obj_vis_len": ovl, "obj_text_width": otw,
            }
            self.visible = vis
            self.winner = win
            self.conflicts = con
            self.elem_index = ei
            self.covered = np.ones(m, np.bool_)

            # successor bookkeeping and exact counter totals ride the same
            # splice, then absorb the delta's edges (kept fresh regardless
            # of which resolution path runs)
            self.succ_count = self._res_splice(
                "succ_count", self.succ_count, m, rs, 0
            )
            self.inc_count = self._res_splice(
                "inc_count", self.inc_count, m, rs, 0
            )
            value_int = np.asarray(log.value_int)
            cv = self._res_splice("counter_val", self.counter_val, m, rs, 0)
            cv[nr] = value_int[nr]
            self.counter_val = cv
            ps = np.asarray(log.pred_src)
            pt = np.asarray(log.pred_tgt)
            eidx = np.concatenate([
                np.arange(info.n_pred_old, len(ps), dtype=np.int64),
                np.asarray(info.rere_pred_edges, np.int64),
            ])
            if len(eidx):
                src = ps[eidx]
                tgt = pt[eidx]
                ok = tgt >= 0
                src, tgt = src[ok], tgt[ok]
                is_inc = np.asarray(log.action)[src] == _INCREMENT
                np.add.at(self.succ_count, tgt[~is_inc], 1)
                np.add.at(self.inc_count, tgt[is_inc], 1)
                np.add.at(self.counter_val, tgt[is_inc], value_int[src[is_inc]])

            # object-sorted row index, (object, row) order: the resident
            # order survives the row moves, and the delta's rows merge into
            # it at their insertion points — no full argsort
            old_rbo = rs.map(self._rows_by_obj)
            obj_key = np.asarray(log.obj_key)
            old_keys = (obj_key[old_rbo] if info.actors_changed
                        else self._obj_sorted)
            d_keys = obj_key[nr]
            ordx = np.lexsort((nr, d_keys))
            d_rows = nr[ordx]
            d_keys = d_keys[ordx]
            at = np.searchsorted(old_keys, d_keys, side="right")
            if not rs.tail:
                at = _bisect_groups(
                    old_rbo, np.searchsorted(old_keys, d_keys, side="left"),
                    at, d_rows,
                )
            order = RowSplice(n_old, at)
            rbo = np.empty(m, np.int64)
            keys = np.empty(m, np.int64)
            order.into(rbo, old_rbo, d_rows)
            order.into(keys, old_keys, d_keys)
            self._rows_by_obj = rbo
            self._obj_sorted = keys

            if info.tail and not info.actors_changed:
                for d in np.asarray(info.dirty_objs):
                    self._all_elems_cache.pop(int(log.obj_table[d]), None)
            else:
                self._all_elems_cache.clear()

    # host delta resolution ---------------------------------------------------
    #
    # The O(delta) path: visibility/winners recomputed ONLY for the key
    # groups the delta touches (from the incrementally-maintained succ/inc
    # counters), and document order spliced by anchor arithmetic — valid
    # because a tail append's ids exceed every resident id, so each new
    # element is its anchor's FIRST child (descending-Lamport sibling
    # order) and a new subtree lands immediately after its anchor. Falls
    # back (returns False) to the object-granularity kernel re-resolution
    # when its assumptions don't hold (non-tail splice, re-resolved refs,
    # unranked anchors).

    def _delta_resolve(self, info) -> bool:
        log = self.log
        m = log.n
        if not info.tail or len(info.rere_elem_rows):
            return False
        nr = np.asarray(info.new_rows, np.int64)
        action = np.asarray(log.action)
        insert = np.asarray(log.insert, np.bool_)
        er = np.asarray(log.elem_ref)
        prop = np.asarray(log.prop)
        od = np.asarray(log.obj_dense)

        ni = nr[insert[nr]]
        anch = er[ni]
        if len(ni) and np.any(anch == ELEM_MISSING):
            return False  # unresolved anchor: cannot place incrementally
        old_anchor = anch[(anch >= 0) & (anch < info.n_old)]
        if len(old_anchor) and np.any(self.elem_index[old_anchor] < 0):
            return False  # anchor itself unranked

        # touched rows: the delta's own + targets of its (re)resolved edges
        ps = np.asarray(log.pred_src)
        pt = np.asarray(log.pred_tgt)
        eidx = np.concatenate([
            np.arange(info.n_pred_old, len(ps), dtype=np.int64),
            np.asarray(info.rere_pred_edges, np.int64),
        ])
        touched = pt[eidx][pt[eidx] >= 0] if len(eidx) else np.empty(0, np.int64)
        cand = np.unique(np.concatenate([nr, touched])).astype(np.int64)
        c_map = prop[cand] >= 0
        c_seq = cand[~c_map]
        if len(c_seq) and np.any(~insert[c_seq] & (er[c_seq] < 0)):
            return False  # sentinel-keyed update groups: let the kernel decide

        with obs.span("device.delta_resolve", rows=len(cand)):
            # group membership (two vectorized passes over the columns)
            heads = np.unique(np.where(insert[c_seq], c_seq, er[c_seq]))
            member = np.zeros(m, np.bool_)
            if len(heads):
                head_mask = np.zeros(m, np.bool_)
                head_mask[heads] = True
                member |= head_mask
                member |= (
                    (~insert) & (er >= 0) & head_mask[np.clip(er, 0, m - 1)]
                )
            n_props = max(len(log.props), 1)
            if np.any(c_map):
                mkeys = np.unique(
                    od[cand[c_map]].astype(np.int64) * n_props
                    + prop[cand[c_map]]
                )
                gid_all = od.astype(np.int64) * n_props + prop
                pos = np.searchsorted(mkeys, gid_all)
                posc = np.clip(pos, 0, len(mkeys) - 1)
                member |= (prop >= 0) & (mkeys[posc] == gid_all)
            rows = np.flatnonzero(member)

            # visibility over the affected rows (merge.visibility mirror;
            # the base clock covers everything)
            vt = np.asarray(log.value_tag)
            act = action[rows]
            never = (act == _DELETE) | (act == _INCREMENT) | (act == _MARK)
            is_counter = (act == _PUT) & (vt[rows] == TAG_COUNTER)
            sc = self.succ_count[rows]
            ic = self.inc_count[rows]
            vis = ~never & np.where(is_counter, sc == 0, (sc + ic) == 0)
            self.visible[rows] = vis

            # winners/conflicts per affected group (rows ascend = Lamport)
            gkey = np.where(
                prop[rows] >= 0,
                np.int64(m) + od[rows].astype(np.int64) * n_props + prop[rows],
                np.where(insert[rows], rows, er[rows].astype(np.int64)),
            )
            order = np.argsort(gkey, kind="stable")
            gs = gkey[order]
            vs = vis[order]
            rr = rows[order]
            newseg = np.concatenate([[True], gs[1:] != gs[:-1]])
            seg = np.cumsum(newseg) - 1
            nseg = int(seg[-1]) + 1 if len(seg) else 0
            win = np.full(nseg, -1, np.int64)
            np.maximum.at(win, seg, np.where(vs, rr, -1))
            cnt = np.zeros(nseg, np.int64)
            np.add.at(cnt, seg, vs.astype(np.int64))

            # per-object stats adjust by the member elements' before/after
            # contributions — winners only ever change inside member groups
            el_rows = rr[insert[rr]]
            w = np.asarray(log.width)
            wold = self.winner[el_rows]
            old_len = wold >= 0
            old_w = np.where(old_len, w[np.clip(wold, 0, m - 1)], 0)

            self.winner[rr] = win[seg]
            self.conflicts[rr] = conflict_flags(cnt[seg])

            wnew = self.winner[el_rows]
            new_len = wnew >= 0
            new_w = np.where(new_len, w[np.clip(wnew, 0, m - 1)], 0)
            o = od[el_rows]
            np.add.at(
                self.res["obj_vis_len"], o,
                new_len.astype(np.int32) - old_len.astype(np.int32),
            )
            np.add.at(
                self.res["obj_text_width"], o,
                (new_w - old_w).astype(np.int32),
            )

            # document order: splice the new subtrees in by anchor position
            if len(ni):
                self._splice_elem_order(ni)
        obs.count("device.delta_resolve")
        return True

    def _splice_elem_order(self, ni: np.ndarray) -> None:
        """elem_index update for a tail append's new insert rows: new
        elements form subtrees hanging off old anchors (or object HEADs);
        each subtree's preorder lands immediately after its anchor, and
        older elements shift by the block sizes inserted before them."""
        log = self.log
        er = np.asarray(log.elem_ref)
        od = np.asarray(log.obj_dense)
        insert = np.asarray(log.insert, np.bool_)
        ei = self.elem_index

        ni_l = ni.tolist()
        er_l = er[ni].tolist()
        od_l = od[ni].tolist()
        loc = {r: j for j, r in enumerate(ni_l)}
        kids: Dict[int, list] = {}
        roots: Dict[tuple, list] = {}  # (obj dense, anchor row | -1=HEAD) -> locals
        for j in range(len(ni_l) - 1, -1, -1):  # descending id = sibling order
            a = er_l[j]
            pj = loc.get(a)
            if pj is not None:
                kids.setdefault(pj, []).append(j)
            elif a == ELEM_HEAD or a >= 0:
                roots.setdefault((od_l[j], a if a >= 0 else -1), []).append(j)
        # per-object anchor blocks in subtree preorder
        by_obj: Dict[int, list] = {}  # obj -> [(anchor_pos, [rows...])]
        for (o, a), starts in roots.items():
            block: list = []
            stack = list(reversed(starts))
            while stack:
                j = stack.pop()
                block.append(ni_l[j])
                stack.extend(reversed(kids.get(j, ())))
            p_a = -1 if a < 0 else int(ei[a])
            by_obj.setdefault(o, []).append((p_a, block))
        for o, blocks in by_obj.items():
            blocks.sort()
            pa = np.asarray([p for p, _ in blocks], np.int64)
            sizes = np.asarray([len(b) for _, b in blocks], np.int64)
            cum = np.concatenate([[0], np.cumsum(sizes)])
            # older elements of this object shift by the blocks before them
            obj_key = int(log.obj_table[o])
            orows = self._obj_rows(obj_key)
            # the delta's own rows still carry elem_index -1, so the >= 0
            # filter leaves exactly the resident elements
            old_el = orows[insert[orows] & (ei[orows] >= 0)]
            if len(old_el):
                shift = cum[np.searchsorted(pa, ei[old_el], side="left")]
                ei[old_el] += shift.astype(ei.dtype)
            for bi, (p_a, block) in enumerate(blocks):
                start = p_a + cum[bi] + 1
                ei[np.asarray(block, np.int64)] = (
                    start + np.arange(len(block))
                ).astype(ei.dtype)

    # dirty-set re-resolution ------------------------------------------------

    def _subset_rows(self, dirty: np.ndarray) -> np.ndarray:
        base = self._base
        if len(dirty) == 1 and base is self:
            # one dirty object — the dominant serve-delta shape: its rows
            # are one contiguous slice of the maintained object-sorted
            # index, O(subset) instead of a full-log membership scan.
            # Rows within an object ascend in _rows_by_obj (stable
            # construction + ordered merges); the stable integer sort is
            # a near-free belt-and-braces pass that keeps the ascending
            # (= Lamport) contract the subset kernel relies on.
            key = int(self.log.obj_table[int(dirty[0])])
            lo = np.searchsorted(self._obj_sorted, key, side="left")
            hi = np.searchsorted(self._obj_sorted, key, side="right")
            return np.sort(self._rows_by_obj[lo:hi], kind="stable")
        od = np.asarray(self.log.obj_dense)
        idx = np.searchsorted(dirty, od)
        member = (idx < len(dirty)) & (
            dirty[np.clip(idx, 0, len(dirty) - 1)] == od
        )
        return np.flatnonzero(member)

    def _subset_cols(self, rows: np.ndarray, dirty: np.ndarray):
        """Column dict over the dirty objects' rows only, with references
        renumbered subset-locally (rows stay ascending = Lamport order)."""
        log = self.log
        m = log.n
        S = len(rows)
        full2sub = np.full(m, -1, np.int32)
        full2sub[rows] = np.arange(S, dtype=np.int32)
        er = np.asarray(log.elem_ref)[rows]
        er_sub = np.where(
            er >= 0, full2sub[np.clip(er, 0, m - 1)], er
        ).astype(np.int32)
        # a ref outside the subset would mean a cross-object element ref
        # (malformed); degrade it to MISSING rather than mis-index
        er_sub = np.where((er >= 0) & (er_sub < 0), np.int32(ELEM_MISSING), er_sub)
        ps = np.asarray(log.pred_src)
        pt = np.asarray(log.pred_tgt)
        if len(ps):
            src_sub = full2sub[np.clip(ps, 0, m - 1)]
            emask = src_sub >= 0
            tgt = pt[emask]
            tgt_sub = np.where(
                tgt >= 0, full2sub[np.clip(tgt, 0, m - 1)], -1
            ).astype(np.int32)
            sub_ps = src_sub[emask].astype(np.int32)
        else:
            sub_ps = np.empty(0, np.int32)
            tgt_sub = np.empty(0, np.int32)
        return {
            "action": np.asarray(log.action)[rows],
            "insert": np.asarray(log.insert, np.bool_)[rows],
            "prop": np.asarray(log.prop)[rows],
            "elem_ref": er_sub,
            "obj_dense": np.searchsorted(dirty, np.asarray(log.obj_dense)[rows]).astype(np.int32),
            "value_tag": np.asarray(log.value_tag)[rows],
            "value_i32": np.asarray(log.value_int)[rows].astype(np.int32),
            "width": np.asarray(log.width)[rows],
            "covered": np.ones(S, np.bool_),
            "pred_src": sub_ps,
            "pred_tgt": tgt_sub,
        }

    def _scatter_subset(self, rows, dirty, res_sub) -> None:
        S = len(rows)
        D = len(dirty)
        self.visible[rows] = np.asarray(res_sub["visible"])[:S]
        w = np.asarray(res_sub["winner"])[:S]
        self.winner[rows] = np.where(
            w >= 0, rows[np.clip(w, 0, max(S - 1, 0))], -1
        ).astype(np.int32)
        self.conflicts[rows] = np.asarray(res_sub["conflicts"])[:S]
        self.elem_index[rows] = np.asarray(res_sub["elem_index"])[:S]
        self.res["obj_vis_len"][dirty] = np.asarray(res_sub["obj_vis_len"])[:D]
        self.res["obj_text_width"][dirty] = np.asarray(res_sub["obj_text_width"])[:D]

    def _res_splice(self, name, old, m, rs, fill):
        """Splice one per-row resolution array through a capacity-bucketed
        backing buffer (tail appends write only the new slots; other
        splices copy the rows below the first insertion point and merge
        the suffix)."""
        from .oplog import _capacity

        buf = self._res_bufs.get(name)
        if rs.tail and buf is not None and old.base is buf and len(buf) >= m:
            buf[rs.n:m] = fill
            return buf[:m]
        nbuf = np.empty(_capacity(m), old.dtype)
        out = nbuf[:m]
        rs.into(out, old, fill)
        self._res_bufs[name] = nbuf
        return out

    def _resolves_whole(self, rows, dirty) -> bool:
        """The cost model: re-resolve the whole document when the dirty
        objects hold more than ``AUTOMERGE_TPU_DIRTY_FRACTION`` (default
        0.5) of its rows, or when every object is dirty."""
        import os

        limit = float(os.environ.get("AUTOMERGE_TPU_DIRTY_FRACTION", "0.5"))
        return len(rows) / self.log.n > limit or len(dirty) >= self.log.n_objs

    def _reresolve(self, dirty) -> None:
        log = self.log
        m = log.n
        dirty = np.asarray(dirty, np.int64)
        if m == 0 or not len(dirty):
            return
        rows = self._subset_rows(dirty)
        frac = len(rows) / m
        if self._resolves_whole(rows, dirty):
            # cost model says re-resolving everything is cheaper than the
            # bookkeeping win (still NO re-extraction — columns are resident)
            obs.count("device.reresolve_full")
            obs.event("device.reresolve", mode="full", rows=m,
                        dirty_rows=len(rows), frac=round(frac, 4))
            res = self._mesh_resolve()
            if res is None:
                obs.count("device.kernel_launches", labels={"path": "per_doc"})
                _prof.note("launches")
                res = merge_columns(
                    log.columns(), fetch=self.READ_FETCH,
                    n_objs=log.n_objs, n_props=len(log.props),
                )
            n = log.n
            vis = np.asarray(res["visible"])[:n]
            win = np.asarray(res["winner"])[:n]
            con = np.asarray(res["conflicts"])[:n]
            ei = np.asarray(res["elem_index"])[:n]
            self.res["visible"][:] = vis
            self.res["winner"][:] = win
            self.res["conflicts"][:] = con
            self.res["elem_index"][:] = ei
            ovl = np.asarray(res["obj_vis_len"])
            otw = np.asarray(res["obj_text_width"])
            take = min(len(ovl), len(self.res["obj_vis_len"]))
            self.res["obj_vis_len"][:take] = ovl[:take]
            self.res["obj_text_width"][:take] = otw[:take]
            return
        obs.count("device.reresolve_subset")
        obs.event("device.reresolve", mode="subset", rows=m,
                    dirty_rows=len(rows), frac=round(frac, 4))
        cols = self._subset_cols(rows, dirty)
        obs.count("device.kernel_launches", labels={"path": "per_doc"})
        _prof.note("launches")
        res_sub = merge_columns(
            cols, fetch=self.READ_FETCH, n_objs=len(dirty),
            n_props=len(log.props),
        )
        with obs.span("device.scatter", rows=len(rows)):
            self._scatter_subset(rows, dirty, res_sub)

    # -- whale-doc mesh residency (parallel/sharding.py) ---------------------
    #
    # Opt-in: full-log re-resolutions of a document too big for one chip
    # route through the sharded merge (every phase split over a
    # jax.sharding.Mesh). The resident columns are handed over PERMUTED
    # into object-id-range-contiguous layout (the incrementally-maintained
    # ``_rows_by_obj`` order), so each device's row slice holds whole
    # object key groups and the per-group winner recompute stays
    # chip-local; the stable sort keeps rows ascending (= Lamport
    # ascending) within every object, preserving the winner rule, and all
    # row references are remapped through the permutation both ways.

    def enable_mesh(
        self, n_devices: Optional[int] = None, min_rows: Optional[int] = None
    ) -> bool:
        """Turn on mesh residency. Returns False — and stays on the
        single-device path — when fewer than ``n_devices`` (default: all,
        at least 2) devices exist.
        ``min_rows`` (env AUTOMERGE_TPU_MESH_MIN_ROWS, default 4096)
        keeps small re-resolutions on one chip."""
        import os

        import jax

        if self._base is not self:
            raise ValueError("enable_mesh on a historical view; use the base doc")
        devs = jax.devices()
        want = n_devices or len(devs)
        if want < 2 or len(devs) < want:
            obs.count("device.mesh_unavailable", labels={"reason": "single_device"})
            return False
        from ..parallel.sharding import default_mesh

        # one Mesh per device count, shared by every DeviceDoc (a Mesh is
        # just a device grid — rebuilding it per document is pure waste)
        mesh = _MESH_CACHE.get(want)
        if mesh is None:
            mesh = _MESH_CACHE[want] = default_mesh(want, devices=devs[:want])
        self._mesh = mesh
        self._mesh_min_rows = int(
            min_rows
            if min_rows is not None
            else os.environ.get("AUTOMERGE_TPU_MESH_MIN_ROWS", "4096")
        )
        return True

    def disable_mesh(self) -> None:
        self._mesh = None

    def on_mesh(self) -> bool:
        """Whether a full re-resolution of this document runs over the
        mesh: mesh residency is on (``enable_mesh``, or
        AUTOMERGE_TPU_MESH_DEVICES, probed once) and the log is at least
        ``min_rows`` long."""
        if self._mesh is None and not self._mesh_env_tried:
            self._mesh_env_tried = True
            import os

            nd = os.environ.get("AUTOMERGE_TPU_MESH_DEVICES")
            if nd:
                self.enable_mesh(int(nd))
        return self._mesh is not None and self.log.n >= self._mesh_min_rows

    def _mesh_resolve(self) -> Optional[Dict[str, np.ndarray]]:
        """One sharded full-log resolution over the mesh, or None when
        mesh residency is off or below threshold. A mesh failure raises."""
        if not self.on_mesh():
            return None
        try:
            return self._mesh_resolve_inner()
        except Exception as e:
            obs.count("device.mesh_unavailable", labels={"reason": "error"})
            obs.event("device.mesh_error", error=str(e)[:200])
            raise

    def _mesh_resolve_inner(self) -> Dict[str, np.ndarray]:
        from ..parallel.sharding import sharded_merge_columns
        from .oplog import pad_columns

        log = self.log
        m = log.n
        with obs.span("device.mesh_resolve", rows=m):
            # object-range permutation: new position i holds old row
            # perm[i]; _rows_by_obj is obj-sorted and row-ascending
            # within each object (stable), exactly what we need
            perm = np.asarray(self._rows_by_obj, np.int64)
            inv = np.empty(m, np.int64)
            inv[perm] = np.arange(m, dtype=np.int64)
            cols = log.columns()
            pc = {
                k: np.asarray(cols[k])[perm]
                for k in ("action", "insert", "prop", "obj_dense",
                          "value_tag", "value_i32", "width", "covered")
            }
            er = np.asarray(cols["elem_ref"])[perm]
            pc["elem_ref"] = np.where(
                er >= 0, inv[np.clip(er, 0, m - 1)], er
            ).astype(np.int32)
            ps = np.asarray(cols["pred_src"])
            pt = np.asarray(cols["pred_tgt"])
            pc["pred_src"] = (
                inv[ps].astype(np.int32) if len(ps) else ps
            )
            pc["pred_tgt"] = (
                np.where(pt >= 0, inv[np.clip(pt, 0, m - 1)], pt).astype(np.int32)
                if len(pt)
                else pt
            )
            pc = pad_columns(pc, log.n_objs)
            n_dev = self._mesh.devices.size
            if len(pc["action"]) % n_dev:
                obs.count("device.mesh_unavailable",
                          labels={"reason": "shape"})
                return None
            out = sharded_merge_columns(
                pc, mesh=self._mesh, n_objs=log.n_objs,
                n_props=len(log.props),
            )
            # un-permute the per-row outputs; winner VALUES are permuted
            # row ids and map back through perm itself
            res: Dict[str, np.ndarray] = {}
            for k in ("visible", "conflicts", "elem_index"):
                a = np.asarray(out[k])[:m]
                o = np.empty(m, a.dtype)
                o[perm] = a
                res[k] = o
            res["conflicts"] = conflict_flags(res["conflicts"])
            w = np.asarray(out["winner"])[:m]
            w_o = np.where(w >= 0, perm[np.clip(w, 0, m - 1)], -1)
            wo = np.empty(m, np.int32)
            wo[perm] = w_o.astype(np.int32)
            res["winner"] = wo
            res["obj_vis_len"] = np.asarray(out["obj_vis_len"])[: log.n_objs + 2]
            res["obj_text_width"] = np.asarray(
                out["obj_text_width"]
            )[: log.n_objs + 2]
            return res

    # -- historical views ---------------------------------------------------

    def current_heads(self) -> List[bytes]:
        """Change hashes no other change in the log depends on."""
        base = self._base
        deps = {d for ch in base.log.changes for d in ch.dependencies}
        return sorted(h for h in base._hash_index if h not in deps)

    def _clock_vec(self, heads: Sequence[bytes]) -> np.ndarray:
        """Dense per-actor-rank max-op vector for the clock at ``heads``
        (the ancestor traversal of change_graph.rs:128-142, host-side)."""
        base = self._base
        vec = np.zeros(len(base.log.actors), np.int64)
        stack = list(heads)
        seen = set()
        while stack:
            h = stack.pop()
            if h in seen:
                continue
            seen.add(h)
            ch = base._hash_index.get(h)
            if ch is None:
                raise KeyError(f"unknown head {h.hex()}")
            rank = base._rank_of[bytes(ch.actor)]
            if ch.max_op > vec[rank]:
                vec[rank] = ch.max_op
            stack.extend(ch.dependencies)
        return vec

    def at(self, heads: Optional[Sequence[bytes]]) -> "DeviceDoc":
        """The document as of ``heads``: same log, same element order,
        visibility re-resolved under the clock mask (one kernel run,
        cached per heads set)."""
        base = self._base
        if heads is None:
            return base
        key = tuple(sorted(heads))
        view = base._views.get(key)
        if view is None:
            covered = base.log.covered_mask(base._clock_vec(heads))
            obs.count("device.kernel_launches", labels={"path": "per_doc"})
            _prof.note("launches")
            res = merge_columns(
                base.log.padded_columns(covered=covered),
                fetch=self.VIEW_FETCH,
                n_objs=base.log.n_objs,
                n_props=len(base.log.props),
            )
            view = DeviceDoc(base.log, res, covered=covered, base=base)
            base._views[key] = view
        return view

    def _view(self, heads) -> "DeviceDoc":
        return self if heads is None else self.at(heads)

    # -- row selection ------------------------------------------------------

    def _obj_rows(self, obj_key: int) -> np.ndarray:
        lo = np.searchsorted(self._obj_sorted, obj_key, side="left")
        hi = np.searchsorted(self._obj_sorted, obj_key, side="right")
        return self._rows_by_obj[lo:hi]

    def _check_obj(self, obj_key: int) -> ObjType:
        t = self._obj_type.get(obj_key)
        if t is None:
            raise KeyError(f"no such object {self.log.export_id(obj_key)}")
        return t

    def _all_elems(self, obj_key: int) -> List[int]:
        """ALL element rows of a sequence in document order — including
        invisible and mark elements (the host ``SeqObject.elements()``
        walk; order is clock-independent so this lives on the base)."""
        base = self._base
        cached = base._all_elems_cache.get(obj_key)
        if cached is None:
            cached = order_elem_rows(
                base.log, base.elem_index, base._obj_rows(obj_key)
            ).tolist()
            base._all_elems_cache[obj_key] = cached
        return cached

    # -- value rendering ----------------------------------------------------

    def _render(self, row: int):
        a = int(self.log.action[row])
        if is_make_action(a):
            return (
                "obj",
                objtype_for_action(a),
                self.log.export_id(int(self.log.id_key[row])),
            )
        if a == _PUT and int(self.log.value_tag[row]) == TAG_COUNTER:
            return ("counter", int(self.counter_val[row]))
        return ("scalar", self.log.values[row])

    # -- reads (mirror core/document.py) ------------------------------------

    def object_type(self, obj: str) -> ObjType:
        return self._check_obj(self.log.import_id(obj))

    def keys(self, obj: str = "_root", heads=None) -> List[str]:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        view._check_obj(ok)
        rows = view._obj_rows(ok)
        props = {
            int(view.log.prop[r])
            for r in rows
            if view.log.prop[r] >= 0 and view.winner[r] >= 0
        }
        return sorted(view.log.props[p] for p in props)

    def map_entries(self, obj: str = "_root", heads=None) -> List[Tuple[str, object, str]]:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        view._check_obj(ok)
        best: Dict[int, int] = {}
        for r in view._obj_rows(ok):
            p = int(view.log.prop[r])
            if p >= 0 and view.winner[r] >= 0:
                best[p] = int(view.winner[r])
        out = [
            (
                view.log.props[p],
                view._render(w),
                view.log.export_id(int(view.log.id_key[w])),
            )
            for p, w in best.items()
        ]
        out.sort(key=lambda kv: kv[0])
        return out

    def _seq_elems(self, obj_key: int) -> List[Tuple[int, int]]:
        """Visible elements of a sequence: [(elem_row, winner_row)] in order."""
        return [
            (r, int(self.winner[r]))
            for r in self._all_elems(obj_key)
            if self.winner[r] >= 0
        ]

    def list_items(self, obj: str, heads=None) -> List[Tuple[object, str]]:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        view._check_obj(ok)
        return [
            (view._render(w), view.log.export_id(int(view.log.id_key[w])))
            for _, w in view._seq_elems(ok)
        ]

    def text(self, obj: str, heads=None) -> str:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        view._check_obj(ok)
        parts = []
        for _, w in view._seq_elems(ok):
            v = view.log.values[w]
            parts.append(v.value if v.tag == "str" else _OBJ_REPLACEMENT)
        return "".join(parts)

    def length(self, obj: str = "_root", heads=None) -> int:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        t = view._check_obj(ok)
        if t in (ObjType.MAP, ObjType.TABLE):
            return len(view.keys(obj))
        dense = int(np.searchsorted(view.log.obj_table, ok))
        if t == ObjType.TEXT:
            return int(view.res["obj_text_width"][dense])
        return int(view.res["obj_vis_len"][dense])

    def get_all(self, obj: str, prop, heads=None) -> List[Tuple[object, str]]:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        t = view._check_obj(ok)
        rows = view._obj_rows(ok)
        if isinstance(prop, str):
            if t not in (ObjType.MAP, ObjType.TABLE):
                raise ValueError("map lookup requires a map object")
            try:
                p = view.log.props.index(prop)
            except ValueError:
                return []
            vis = [int(r) for r in rows if int(view.log.prop[r]) == p and view.visible[r]]
        else:
            elems = view._seq_elems(ok)
            if prop < 0:
                return []
            if t == ObjType.TEXT:
                # integer index is a character position: accumulate winner
                # widths, matching the host nth's width-aware semantics
                er = None
                at = 0
                for r, w in elems:
                    at += int(view.log.width[w])
                    if prop < at:
                        er = r
                        break
                if er is None:
                    return []
            else:
                if not 0 <= prop < len(elems):
                    return []
                er = elems[prop][0]
            vis = [
                int(r)
                for r in rows
                if view.visible[r]
                and (
                    (view.log.insert[r] and int(r) == er)
                    or (not view.log.insert[r] and int(view.log.elem_ref[r]) == er)
                )
            ]
        vis.sort()  # rows are in Lamport order; winner last
        return [
            (view._render(r), view.log.export_id(int(view.log.id_key[r])))
            for r in vis
        ]

    def get(self, obj: str, prop, heads=None):
        vals = self.get_all(obj, prop, heads)
        return vals[-1] if vals else None

    def map_range(self, obj: str = "_root", start=None, end=None, heads=None):
        """(key, value, id) for map keys in [start, end) (read.rs map_range)."""
        from ..utils.ranges import filter_map_range

        return filter_map_range(self.map_entries(obj, heads=heads), start, end)

    def list_range(self, obj: str, start: int = 0, end=None, heads=None):
        """(index, value, id) for indices in [start, end) (read.rs list_range).
        Renders only the requested rows of the materialized element order."""
        view = self._view(heads)
        ok = view.log.import_id(obj)
        view._check_obj(ok)
        elems = view._seq_elems(ok)
        stop = len(elems) if end is None else min(end, len(elems))
        return [
            (
                i,
                view._render(elems[i][1]),
                view.log.export_id(int(view.log.id_key[elems[i][1]])),
            )
            for i in range(max(start, 0), stop)
        ]

    def values(self, obj: str = "_root", heads=None):
        """Winner (value, id) pairs (read.rs values)."""
        view = self._view(heads)
        ok = view.log.import_id(obj)
        t = view._check_obj(ok)
        if t in (ObjType.MAP, ObjType.TABLE):
            return [(val, vid) for _, val, vid in view.map_entries(obj)]
        return view.list_items(obj)

    def parents(self, obj: str, heads=None) -> List[Tuple[str, object]]:
        """Path from ``obj`` up to the root (read.rs parents/parents_at):
        walks the make ops' containing objects through the log columns,
        resolving sequence indices at the given heads."""
        view = self._view(heads)
        log = view.log
        key = log.import_id(obj)
        view._check_obj(key)
        path: List[Tuple[str, object]] = []
        while key != 0:
            row = log.row_of_id(key)
            parent_key = int(log.obj_key[row])
            parent_exid = log.export_id(parent_key)
            p = int(log.prop[row])
            if p >= 0:
                path.append((parent_exid, log.props[p]))
            else:
                # element ordinal among VISIBLE elements (1 each, matching
                # Document._elem_index); None when the element is invisible
                base = view._base
                er = row if log.insert[row] else int(log.elem_ref[row])
                view._check_obj(parent_key)
                idx = 0
                found = None
                for r in base._all_elems(parent_key):
                    visible = int(view.winner[r]) >= 0
                    if r == er:
                        found = idx if visible else None
                        break
                    if visible:
                        idx += 1
                path.append((parent_exid, found))
            key = parent_key
        return path

    # -- cursors (reference: cursor.rs, automerge.rs seek_opid) -------------

    def get_cursor(self, obj: str, position: int, heads=None) -> str:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        t = view._check_obj(ok)
        if t in (ObjType.MAP, ObjType.TABLE):
            raise ValueError("cursors only apply to sequences")
        at = 0
        for r, w in view._seq_elems(ok):
            at += int(view.log.width[w]) if t == ObjType.TEXT else 1
            if position < at:
                return view.log.export_id(int(view.log.id_key[r]))
        raise ValueError(f"cursor position {position} out of bounds")

    def get_cursor_position(self, obj: str, cursor: str, heads=None) -> int:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        t = view._check_obj(ok)
        if t in (ObjType.MAP, ObjType.TABLE):
            raise ValueError("cursors only apply to sequences")
        target = view.log.import_id(cursor)
        index = 0
        for r in view._all_elems(ok):
            if int(view.log.id_key[r]) == target:
                return index
            w = int(view.winner[r])
            if w >= 0:
                index += int(view.log.width[w]) if t == ObjType.TEXT else 1
        raise ValueError(f"cursor {cursor!r} not found in {obj!r}")

    # -- marks (reference: marks.rs MarkStateMachine, automerge.rs:1370) ----

    def marks(self, obj: str, heads=None) -> List[Mark]:
        view = self._view(heads)
        ok = view.log.import_id(obj)
        t = view._check_obj(ok)
        if t in (ObjType.MAP, ObjType.TABLE):
            raise ValueError("marks on a non-sequence object")
        log = view.log
        is_text = t == ObjType.TEXT
        open_marks: List[Tuple[int, str, object]] = []  # (begin id_key, name, value)
        index = 0
        spans: Dict[str, List[Mark]] = {}
        for r in view._all_elems(ok):
            if int(log.action[r]) == _MARK:
                # mark begin/end ops are covered-or-absent, never "visible"
                # (core/marks.py visible_or_mark)
                if not view.covered[r]:
                    continue
                mi = int(log.mark_name_idx[r])
                if mi >= 0:  # begin
                    open_marks.append(
                        (int(log.id_key[r]), log.mark_names[mi], log.values[r].to_py())
                    )
                    # packed id order == lamport order (rank = actor byte rank)
                    open_marks.sort()
                else:  # end: pairs with begin id (ctr-1, same actor)
                    begin = int(log.id_key[r]) - (1 << ACTOR_BITS)
                    open_marks = [e for e in open_marks if e[0] != begin]
                continue
            w = int(view.winner[r])
            if w < 0:
                continue
            width = int(log.width[w]) if is_text else 1
            current: Dict[str, object] = {}
            for _, name, value in open_marks:  # lamport-ascending: last wins
                current[name] = value
            for name, value in current.items():
                runs = spans.setdefault(name, [])
                if runs and runs[-1].end == index and runs[-1].value == value:
                    runs[-1].end = index + width
                else:
                    runs.append(Mark(index, index + width, name, value))
            index += width
        out = [
            m
            for runs in spans.values()
            for m in runs
            if m.value is not None  # null-valued spans are unmarks
        ]
        out.sort(key=lambda m: (m.start, m.name))
        return out

    # -- diff / patches -----------------------------------------------------

    def diff(self, before_heads, after_heads=None) -> List[Patch]:
        """Patches turning the state at ``before_heads`` into the state at
        ``after_heads`` (None = current). Same shape and ordering as the
        host differ; computed from two clock-masked kernel resolutions."""
        vb = self.at(before_heads if before_heads is not None else [])
        va = self._view(after_heads)
        patches: List[Patch] = []
        _diff_obj(vb, va, 0, [], patches)
        return patches

    def make_patches(self) -> List[Patch]:
        """Patches materializing the whole current state (applying them to
        an empty dict reproduces ``hydrate()`` — the current_state analogue,
        reference: automerge/current_state.rs)."""
        return self.diff([])

    # -- materialization ----------------------------------------------------

    def hydrate(self, obj: str = "_root", heads=None):
        view = self._view(heads)
        return view._hydrate(view.log.import_id(obj))

    def _hydrate(self, obj_key: int):
        t = self._check_obj(obj_key)
        if t in (ObjType.MAP, ObjType.TABLE):
            return {
                name: self._hydrate_val(val)
                for name, val, _ in self.map_entries(self.log.export_id(obj_key))
            }
        if t == ObjType.TEXT:
            return self.text(self.log.export_id(obj_key))
        return [
            self._hydrate_val(self._render(w)) for _, w in self._seq_elems(obj_key)
        ]

    def _hydrate_val(self, rendered):
        kind = rendered[0]
        if kind == "obj":
            return self._hydrate(self.log.import_id(rendered[2]))
        if kind == "counter":
            return rendered[1]
        return rendered[1].to_py()


# -- the device differ (mirrors patches/diff.py walk) ------------------------


def _patch_value(view: DeviceDoc, row: int):
    """Patch value of a winning op: hydrated subtree / counter / scalar."""
    a = int(view.log.action[row])
    if is_make_action(a):
        return view._hydrate(int(view.log.id_key[row]))
    if a == _PUT and int(view.log.value_tag[row]) == TAG_COUNTER:
        return int(view.counter_val[row])
    return view.log.values[row].to_py()


def _is_counter_row(log: OpLog, row: int) -> bool:
    return int(log.action[row]) == _PUT and int(log.value_tag[row]) == TAG_COUNTER


def _diff_obj(vb, va, obj_key, path, patches):
    t = va._check_obj(obj_key)
    exid = va.log.export_id(obj_key)
    if t in (ObjType.MAP, ObjType.TABLE):
        _diff_map(vb, va, obj_key, exid, path, patches)
    elif t == ObjType.TEXT:
        _diff_text(vb, va, obj_key, exid, path, patches)
    else:
        _diff_list(vb, va, obj_key, exid, path, patches)


def _diff_map(vb, va, obj_key, exid, path, patches):
    log = va.log
    groups: Dict[int, int] = {}  # prop -> representative row
    for r in va._obj_rows(obj_key):
        p = int(log.prop[r])
        if p >= 0 and p not in groups:
            groups[p] = int(r)
    for p in sorted(groups, key=lambda p: log.props[p]):
        rep = groups[p]
        key = log.props[p]
        wb = int(vb.winner[rep])
        wa = int(va.winner[rep])
        if wa < 0:
            if wb >= 0:
                patches.append(Patch(exid, list(path), DeleteMap(key)))
            continue
        conflict = int(va.conflicts[rep]) > 1
        if wb < 0 or wb != wa:
            patches.append(
                Patch(exid, list(path), PutMap(key, _patch_value(va, wa), conflict))
            )
        elif _is_counter_row(log, wa):
            delta = int(va.counter_val[wa]) - int(vb.counter_val[wa])
            if delta:
                patches.append(Patch(exid, list(path), IncrementPatch(key, delta)))
        elif conflict and int(vb.conflicts[rep]) <= 1:
            patches.append(Patch(exid, list(path), FlagConflict(key)))
        if is_make_action(int(log.action[wa])) and wb == wa:
            _diff_obj(
                vb, va, int(log.id_key[wa]), path + [(exid, key)], patches
            )


def _diff_list(vb, va, obj_key, exid, path, patches):
    log = va.log
    idx = 0
    pending_ins = None  # (index, [values])
    for r in va._all_elems(obj_key):
        wb = int(vb.winner[r])
        wa = int(va.winner[r])
        if wa < 0 and wb < 0:
            continue
        if wa >= 0 and wb < 0:
            if pending_ins is None:
                pending_ins = (idx, [])
            pending_ins[1].append(_patch_value(va, wa))
            idx += 1
            continue
        if pending_ins is not None:
            patches.append(Patch(exid, list(path), Insert(*pending_ins)))
            pending_ins = None
        if wa < 0:
            last = patches[-1] if patches else None
            if (
                last is not None
                and last.obj == exid
                and isinstance(last.action, DeleteSeq)
                and last.action.index == idx
            ):
                last.action.length += 1
            else:
                patches.append(Patch(exid, list(path), DeleteSeq(idx)))
            continue
        conflict = int(va.conflicts[r]) > 1
        if wb != wa:
            patches.append(
                Patch(exid, list(path), PutSeq(idx, _patch_value(va, wa), conflict))
            )
        elif _is_counter_row(log, wa):
            delta = int(va.counter_val[wa]) - int(vb.counter_val[wa])
            if delta:
                patches.append(Patch(exid, list(path), IncrementPatch(idx, delta)))
        elif conflict and int(vb.conflicts[r]) <= 1:
            patches.append(Patch(exid, list(path), FlagConflict(idx)))
        if is_make_action(int(log.action[wa])) and wb == wa:
            _diff_obj(vb, va, int(log.id_key[wa]), path + [(exid, idx)], patches)
        idx += 1
    if pending_ins is not None:
        patches.append(Patch(exid, list(path), Insert(*pending_ins)))


def _diff_text(vb, va, obj_key, exid, path, patches):
    log = va.log
    idx = 0
    pending = None  # [index, str] for inserts
    for r in va._all_elems(obj_key):
        wb = int(vb.winner[r])
        wa = int(va.winner[r])
        if wa < 0 and wb < 0:
            continue
        sa = _char(log, wa) if wa >= 0 else None
        sb = _char(log, wb) if wb >= 0 else None
        if wa >= 0 and wb < 0:
            if pending is None:
                pending = [idx, ""]
            pending[1] += sa
            idx += len(sa)
            continue
        if pending is not None:
            patches.append(Patch(exid, list(path), SpliceText(pending[0], pending[1])))
            pending = None
        if wa < 0:
            last = patches[-1] if patches else None
            if (
                last is not None
                and last.obj == exid
                and isinstance(last.action, DeleteSeq)
                and last.action.index == idx
            ):
                last.action.length += len(sb)
            else:
                patches.append(Patch(exid, list(path), DeleteSeq(idx, len(sb))))
            continue
        if wb != wa and (sa != sb):
            patches.append(Patch(exid, list(path), DeleteSeq(idx, len(sb))))
            patches.append(Patch(exid, list(path), SpliceText(idx, sa)))
        idx += len(sa)
    if pending is not None:
        patches.append(Patch(exid, list(path), SpliceText(pending[0], pending[1])))


def _char(log: OpLog, row: int) -> str:
    v = log.values[row]
    return v.value if v.tag == "str" else _OBJ_REPLACEMENT
