"""Vectorized cross-document host staging: columnar passes over many
documents' pending changes, with per-doc offset ranges.

PR 7 collapsed N kernel launches per drain cycle into one, and the PR 11
observatory then measured the consequence: ~70% of batched-drain wall
clock was *host-side Python* — dominated by the per-document
``OpLog.append_changes`` splice and the per-document column extraction,
each a few dozen small-numpy calls whose dispatch overhead dwarfs the
actual work at serve-sized deltas. This module batches that host half
the same way ``ops/batched.py`` batched the device half: many documents'
pending changes are packed into ONE set of shared numpy column arrays
(disjoint per-doc row ranges) and the staging pipeline runs as a handful
of columnar passes instead of per-doc loops:

* **pack** — one shared column extraction over every document's ready
  changes (``ranked_from_caches`` with a union actor table), then packed
  (actor_rank, counter) keys are translated global->doc with one flat
  LUT gather per key column. The packed int64 key IS the offset-value
  coding of the (counter, actor) composite (arXiv:2209.08420): a
  Lamport-order comparison is a single int64 compare, never a Python
  tuple.
* **sort** — ONE ``lexsort`` over ``(doc, id_key)`` Lamport-orders every
  document's delta at once (contiguous doc ranges keep the result
  sliceable per doc), and duplicate-id / tail checks run as shared
  vector passes.
* **splice** — per document, a *specialized* tail-append splice: the
  passes are organized per column encoding (plain payload columns,
  packed-key columns, row-reference columns, string-table columns), the
  control-flow-duplication playbook of arXiv:2302.10098 — instead of the
  generic per-column splice machinery branching per call. Row references
  resolve through the shared ``join_rows`` id join; the resolution-array
  and successor-counter bookkeeping of ``DeviceDoc._apply_append`` runs
  in the same specialized form.

Soundness: the fast path is entered ONLY when its assumptions are
checked to hold — resident log non-empty with retained column bytes, no
unresolved (MISSING) references outstanding (``OpLog.n_miss_elem`` /
``n_miss_pred``, maintained incrementally), no new actors (a monotone
rank remap would touch every resident key), strictly-tail Lamport
position, and an object table that only extends at its end. Everything
else falls back per document to the scalar ``DeviceDoc.stage_ready``
path, which stays both the fallback and the differential oracle
(tests/test_host_batch.py asserts column-level OpLog equality and
identical materialized documents between the two).

Feed point: ``ops/batched.stage_and_resolve``, which every device feed
takes — ``CrossDocBatcher`` (the serving drain: submitters hand raw
batches to the flush leader, which stages every co-arriving document in
one vectorized pass before the shared kernel launch),
``DeviceDoc.apply_batches`` (a generation of one), ``apply_cross_doc``
(the bench/CI driver) and the cluster follower apply path
(``cluster/node.py`` drains coalesced ``replApply`` runs through it).
Its chunked pipeline calls ``stage_docs`` per chunk itself.

Profiler stages: ``host_pack`` / ``host_sort`` / ``host_splice`` join
the PR 11 taxonomy, so ``drain.attributed_fraction`` stays >= 0.9 on
this path and ``perf-report`` shows where the staging wall went.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import obs
from ..obs import prof as _prof
from .device_doc import _INCREMENT, _MAKE_OBJ
from .extract import LazyValues
from .oplog import (
    ACTOR_BITS,
    ACTOR_MASK,
    ELEM_HEAD,
    ELEM_MAP,
    ELEM_MISSING,
    TAG_UNKNOWN,
    _capacity,
    _merge_table,
    count_splice,
    join_rows,
)


def _tail_write(bufs: dict, name: str, old: np.ndarray, new, mm: int):
    """One buffered tail write — the ``_splice_col`` / ``_res_splice``
    fast path with the generic per-call machinery (asarray, dtype
    coercion, row-map branching) stripped: the hot staging loop pays a
    buffer check and a slice assignment per column, nothing else.
    ``new`` is the appended values (or a scalar fill). Capacity
    bucketing and buffer reuse match ``OpLog._splice_col`` exactly, so
    the scalar path can keep splicing the same buffers afterwards.

    Compressed-residency contract: a tail write never moves the
    resident prefix, so the log's compressed column image
    (ops/compressed.py) stays valid across it — each encoded column's
    covered-row cursor lags and the next sync extends the LAST RUN with
    the appended slice instead of re-encoding (StrideRuns.extend_tail);
    the id_key runs are even extended eagerly in ``_splice_doc`` so the
    reference joins run offset-value-coded."""
    n = len(old)
    buf = bufs.get(name)
    if buf is not None and old.base is buf and len(buf) >= mm:
        buf[n:mm] = new
        return buf[:mm]
    nbuf = np.empty(_capacity(mm), old.dtype)
    nbuf[:n] = old
    nbuf[n:mm] = new
    bufs[name] = nbuf
    return nbuf[:mm]


class _DocPlan:
    """One document's slot in the shared staging pass."""

    __slots__ = (
        "dev", "ready", "label", "c0", "c1", "r0", "r1", "p0", "p1", "k",
        "rank_of", "all_bytes", "actors_changed",
    )

    def __init__(self, dev, ready, label):
        self.dev = dev
        self.ready = ready
        self.label = label
        self.c0 = self.c1 = self.r0 = self.r1 = self.p0 = self.p1 = 0
        self.k = 0
        # the document's (possibly extended) actor universe: delta
        # actors not yet resident insert by byte rank — a MONOTONE remap
        # of every resident packed key, handled in the splice pass
        # rather than falling back (every first contact with a new
        # editor would otherwise stage scalar)
        self.rank_of: Dict[bytes, int] = {}
        self.all_bytes: List[bytes] = []
        self.actors_changed = False


class DocResult:
    """Per-document outcome of ``stage_docs``."""

    __slots__ = ("applied", "error", "vectorized")

    def __init__(self):
        self.applied = 0
        self.error: Optional[BaseException] = None
        self.vectorized = False


def _admit(dev, ready, label) -> Optional[_DocPlan]:
    """Fast-path admission: every assumption the specialized tail splice
    relies on, checked up front so the splice itself never aborts
    mid-mutation. Returns the planned slot (with its actor-universe
    resolution) or None for the scalar path."""
    log = dev.log
    if log.n == 0 or log.n_miss_elem or log.n_miss_pred:
        return None
    if not isinstance(log.values, LazyValues):
        return None
    for ch in ready:
        if ch.op_col_data is None and ch.cached_cols is None:
            return None
    if not log._ensure_ref_keys():
        return None
    p = _DocPlan(dev, ready, label)
    old_rank = dev._rank_of
    delta_bytes = {bytes(a) for ch in ready for a in ch.actors}
    if delta_bytes <= old_rank.keys():
        p.rank_of = old_rank
        p.all_bytes = [a.bytes for a in log.actors]
    else:
        all_bytes = sorted(old_rank.keys() | delta_bytes)
        if len(all_bytes) >= (1 << ACTOR_BITS):
            return None
        p.all_bytes = all_bytes
        p.rank_of = {b: i for i, b in enumerate(all_bytes)}
        p.actors_changed = True
    return p


def _extract_all(plans: List[_DocPlan]):
    """One shared column extraction over every planned document's ready
    changes, under a union actor-rank table. Returns ``(r, g_bytes)`` or
    None (callers then fall back per doc — nothing was mutated)."""
    from .. import native
    from .assemble import AssembleError, ranked_from_caches

    changes = [ch for p in plans for ch in p.ready]
    g_bytes = sorted({bytes(a) for ch in changes for a in ch.actors})
    if len(g_bytes) >= (1 << ACTOR_BITS):
        return None
    g_rank = {b: i for i, b in enumerate(g_bytes)}
    try:
        r = ranked_from_caches(changes, g_rank)
    except (AssembleError, native.NativeUnavailable, ValueError):
        return None
    except Exception:
        if os.environ.get("AUTOMERGE_TPU_DEBUG"):
            raise
        return None
    return r, g_bytes


def _doc_string_table(ready, attr: str) -> List[str]:
    """First-occurrence union of one document's per-change string tables
    (the exact table ``ranked_from_caches`` would build for these
    changes alone). Identical table objects — synthesized batches share
    one — contribute once."""
    seen_tables = set()
    have = set()
    out: List[str] = []
    for ch in ready:
        t = getattr(ch.cached_cols, attr, None)
        if not t or id(t) in seen_tables:
            continue
        seen_tables.add(id(t))
        for s in t:
            if s not in have:
                have.add(s)
                out.append(s)
    return out


def _local_ids(g_ids, g_pos: Dict[str, int], doc_table: List[str],
               g_table_len: int) -> np.ndarray:
    """Translate global-table string ids to doc-table ids (-1 rides
    through) with one LUT gather."""
    g2d = np.full(max(g_table_len, 1), -1, np.int32)
    for i, s in enumerate(doc_table):
        g2d[g_pos[s]] = i
    ids = np.asarray(g_ids)
    return np.where(
        ids >= 0, g2d[np.clip(ids, 0, None)], np.int32(-1)
    ).astype(np.int32)


def stage_docs(work) -> Tuple[List, Dict[int, DocResult]]:
    """Stage many documents' drained device feeds through shared
    columnar passes.

    ``work``: iterable of ``(device_doc, batches)`` pairs (duplicate
    documents merge into one staging, like ``apply_cross_doc``).
    Returns ``(stages, results)``: the pack-eligible ``BatchStage`` list
    for ``resolve_stages``, and a per-document ``DocResult`` keyed by
    ``id(device_doc)`` (applied count, error, which path ran). Documents
    failing a fast-path assumption stage through the scalar
    ``DeviceDoc.stage_ready`` — bit-identical by construction.

    Each call is self-contained (dedup, the union actor table, and all
    offset ranges are per call), which is what lets the double-buffered
    drain (``apply_cross_doc`` with ``AUTOMERGE_TPU_DRAIN_PIPELINE``)
    run THIS staging for chunk N+1 while chunk N's packed kernel is
    still in flight — the host seconds spent here under a live launch
    are the drain's ``overlap_s``.
    """
    from .batched import BatchStage, full_stage

    # -- dedup + causal order + admission: one span each for the whole
    # drain (the spans cover the surrounding glue too, so the cycle
    # profiler's attributed fraction holds even at tiny drain sizes)
    with obs.span("device.stage.dedup", docs=len(work)
                  if isinstance(work, list) else 0):
        merged: Dict[int, tuple] = {}
        order: List[int] = []
        for dev, batches in work:
            if dev._base is not dev:
                raise ValueError(
                    "stage_docs on a historical view; use the base doc"
                )
            key = id(dev)
            if key in merged:
                merged[key][1].extend(batches)
            else:
                merged[key] = (dev, list(batches))
                order.append(key)
        results: Dict[int, DocResult] = {k: DocResult() for k in order}
        flat: Dict[int, list] = {}
        for key in order:
            dev, batches = merged[key]
            flat[key] = [ch for b in batches for ch in b]
            dev._dedup_into_pending(flat[key])
    entries: List[tuple] = []  # (dev, ready, label)
    vec: List[_DocPlan] = []
    scalar: List[tuple] = []  # (key, dev, ready, label)
    with obs.span("device.stage.causal_order", docs=len(order)):
        for i, key in enumerate(order):
            dev = merged[key][0]
            ready = dev._drain_ready_pending()
            label = getattr(dev, "obs_name", None) or f"doc{i}"
            entries.append((key, dev, ready, label))
        for key, dev, ready, label in entries:
            if not ready:
                continue
            plan = _admit(dev, ready, label)
            if plan is not None:
                vec.append(plan)
                results[key].vectorized = True
            else:
                scalar.append((key, dev, ready, label))

    stages: List = []

    g = None
    if vec:
        with obs.span("host.pack", docs=len(vec)):
            g = _pack_global(vec)
        if g is None:
            for p in vec:
                results[id(p.dev)].vectorized = False
                scalar.append((id(p.dev), p.dev, p.ready, p.label))
            obs.count("host_batch.fallback_docs",
                      n=len(vec), labels={"reason": "extract"})
            vec = []

    if vec:
        with obs.span("host.sort", rows=g["N"], docs=len(vec)):
            demoted = _sort_global(vec, g)
        dem_ids = {id(p) for p in demoted}
        for p in demoted:
            results[id(p.dev)].vectorized = False
            scalar.append((id(p.dev), p.dev, p.ready, p.label))
        if demoted:
            obs.count("host_batch.fallback_docs",
                      n=len(demoted), labels={"reason": "order"})
        vec = [p for p in vec if id(p) not in dem_ids]

    if vec:
        rows_total = spliced = 0
        with obs.span("host.splice", docs=len(vec)):
            for p in vec:
                res = results[id(p.dev)]
                t0 = time.perf_counter()
                try:
                    outcome = _splice_doc(p, g)
                except BaseException as e:  # noqa: BLE001 — isolate the doc
                    res.error = e
                    obs.count("host_batch.fallback_docs",
                              labels={"reason": "error"})
                    continue
                finally:
                    _prof.note_doc(p.label, time.perf_counter() - t0)
                kind = outcome[0]
                if kind == "scalar":
                    # a pre-mutation admission check failed late: the
                    # document is untouched, the scalar path takes it
                    res.vectorized = False
                    scalar.append((id(p.dev), p.dev, p.ready, p.label))
                    continue
                res.applied = len(p.ready)
                rows_total += p.k
                spliced += 1
                if p.k:
                    count_splice(p.actors_changed, True, 0)
                if kind == "stage":
                    stages.append(BatchStage(p.dev, outcome[1], outcome[2]))
                elif kind == "full":
                    stages.append(full_stage(p.dev))
        obs.count("oplog.append_rows", n=rows_total)
        obs.count("host_batch.docs", n=spliced)
        obs.event("host_batch.splice", docs=spliced, rows=rows_total)

    for key, dev, ready, label in scalar:
        res = results[key]
        t0 = time.perf_counter()
        try:
            applied, st = dev.stage_ready(ready)
            res.applied = applied
            if st is not None:
                stages.append(st)
        except BaseException as e:  # noqa: BLE001
            res.error = e
        _prof.note_doc(label, time.perf_counter() - t0)

    return stages, results


# -- the shared passes --------------------------------------------------------


def _pack_global(plans: List[_DocPlan]):
    """Extraction + packed-key translation for every planned document.
    Returns the shared-array context dict, or None when the one-shot
    extraction is unavailable (callers fall back per doc)."""
    ext = _extract_all(plans)
    if ext is None:
        return None
    r, g_bytes = ext
    a = r["a"]
    N = int(a["n"])
    row_off = np.asarray(a["row_off"], np.int64)
    pred_off = np.asarray(a["pred_row_off"], np.int64)
    raw_off = np.asarray(a["raw_off"], np.int64)
    raw_ln = np.asarray(a["raw_ln"], np.int64)

    c = 0
    k_of = np.empty(len(plans), np.int64)
    q_of = np.empty(len(plans), np.int64)
    for di, p in enumerate(plans):
        p.c0, p.c1 = c, c + len(p.ready)
        c = p.c1
        p.r0, p.r1 = int(row_off[p.c0]), int(row_off[p.c1])
        p.p0, p.p1 = int(pred_off[p.c0]), int(pred_off[p.c1])
        p.k = p.r1 - p.r0
        k_of[di] = p.k
        q_of[di] = p.p1 - p.p0

    # global->doc actor-rank translation: one flat LUT, one gather per
    # packed-key column. Rank order is byte order on both sides, so the
    # restriction of the global ranking to a document's universe is
    # exactly that document's ranking.
    G = max(len(g_bytes), 1)
    lut = np.zeros(len(plans) * G, np.int64)
    for di, p in enumerate(plans):
        base = di * G
        ro = p.rank_of
        for gi, b in enumerate(g_bytes):
            rk = ro.get(b)
            if rk is not None:
                lut[base + gi] = rk
    doc_of_row = np.repeat(np.arange(len(plans), dtype=np.int64), k_of)
    base_row = doc_of_row * G

    def translate(key):
        key = np.asarray(key, np.int64)
        idx = np.where(key > 0, key & ACTOR_MASK, 0)
        return np.where(
            key > 0,
            ((key >> ACTOR_BITS) << ACTOR_BITS) | lut[base_row + idx],
            key,
        )

    id_t = translate(r["id_key"])
    obj_t = translate(r["obj"])
    elem_t = translate(r["elem"])
    pk = np.asarray(r["pred_key"], np.int64)
    if len(pk):
        doc_of_pred = np.repeat(np.arange(len(plans), dtype=np.int64), q_of)
        pk_t = ((pk >> ACTOR_BITS) << ACTOR_BITS) | lut[
            doc_of_pred * G + (pk & ACTOR_MASK)
        ]
    else:
        pk_t = pk

    g_key_table = a["key_table"] or []
    g_mark_table = a["mark_table"] or []
    return {
        "N": N,
        "a": a,
        "r": r,
        "doc_of_row": doc_of_row,
        "id_t": id_t,
        "obj_t": obj_t,
        "elem_t": elem_t,
        "pk_t": pk_t,
        "raw_off": raw_off,
        "raw_ln": raw_ln,
        "n_changes": c,
        "key_pos": {s: i for i, s in enumerate(g_key_table)},
        "mark_pos": {s: i for i, s in enumerate(g_mark_table)},
    }


def _sort_global(plans: List[_DocPlan], g) -> List[_DocPlan]:
    """One Lamport sort for every document's delta, shared dup/tail
    checks, and the global->sorted gather of every row column. Returns
    the plans demoted to the scalar path."""
    a = g["a"]
    N = g["N"]
    doc_of_row = g["doc_of_row"]
    order_g = np.lexsort((g["id_t"], doc_of_row))
    inv_g = np.empty(N, np.int64)
    inv_g[order_g] = np.arange(N, dtype=np.int64)
    id_s = g["id_t"][order_g]

    # duplicate op ids within one document -> that doc goes scalar (the
    # scalar path then reports the canonical append_fallback/rebuild)
    bad = set()
    if N > 1:
        same = (doc_of_row[1:] == doc_of_row[:-1]) & (id_s[1:] == id_s[:-1])
        if np.any(same):
            bad.update(doc_of_row[1:][same].tolist())

    g["order_g"] = order_g
    g["inv_g"] = inv_g
    g["id_s"] = id_s
    g["obj_s"] = g["obj_t"][order_g]
    g["elem_s"] = g["elem_t"][order_g]
    g["action_s"] = np.asarray(a["action"], np.int32)[order_g]
    g["insert_s"] = np.asarray(a["insert"], np.bool_)[order_g]
    g["vtag_s"] = np.minimum(
        np.asarray(a["vcode"]), TAG_UNKNOWN
    ).astype(np.int32)[order_g]
    g["vint_s"] = np.asarray(a["value_int"], np.int64)[order_g]
    g["width_s"] = np.asarray(a["width"], np.int32)[order_g]
    g["expand_s"] = np.asarray(a["expand"], np.bool_)[order_g]
    g["vcode_s"] = np.asarray(a["vcode"], np.int32)[order_g]
    g["voff_s"] = np.asarray(a["voff"], np.int64)[order_g]
    g["vlen_s"] = np.asarray(a["vlen"], np.int64)[order_g]
    g["prop_s"] = np.asarray(g["r"]["prop_ids"], np.int32)[order_g]
    mark_ids = a["mark_ids"]
    g["mark_s"] = (
        np.asarray(mark_ids, np.int32)[order_g] if mark_ids is not None
        else None
    )

    demoted = []
    for di, p in enumerate(plans):
        if di in bad:
            demoted.append(p)
            continue
        if p.k:
            log = p.dev.log
            om = int(log.id_key[-1])
            if p.actors_changed:
                # compare against the POST-remap resident maximum (the
                # monotone remap preserves order, so the max row stays
                # the max)
                om = ((om >> ACTOR_BITS) << ACTOR_BITS) | p.rank_of[
                    log.actors[om & ACTOR_MASK].bytes
                ]
            if int(id_s[p.r0]) <= om:
                demoted.append(p)  # not a strict tail append -> scalar
    return demoted


def _splice_doc(p: _DocPlan, g):
    """The specialized tail splice for one document: replays exactly
    what ``OpLog.append_changes`` + ``DeviceDoc._apply_append`` +
    ``stage_batches`` would do for this (tail, same-actors, LazyValues)
    delta, organized as per-encoding column passes with the shared
    arrays pre-sorted. Returns ("stage", rows, dirty) | ("full",) |
    ("done",) | ("scalar",).

    No mutation happens until every admission check has passed: the
    only pre-commit writes go to scratch capacity buffers the resident
    arrays do not read past ``n``.
    """
    dev = p.dev
    log = dev.log
    ready = p.ready
    k = p.k
    n = log.n

    if k == 0:
        if p.actors_changed:
            # a zero-op change can still introduce its actor: the scalar
            # path owns the universe-only commit (_commit_actors)
            return ("scalar",)
        # dependency-only changes: bookkeeping, no rows (the scalar
        # path's n_new == 0 branch)
        log.changes.extend(ready)
        log.hashes().update(ch.hash for ch in ready)
        for ch in ready:
            dev._hash_index[ch.hash] = ch
        dev._views.clear()
        return ("done",)

    sl = slice(p.r0, p.r1)
    d_id = g["id_s"][sl]
    d_obj = g["obj_s"][sl]
    d_action = g["action_s"][sl]

    # -- actor-universe extension: monotone rank remap of the resident
    # packed keys (pure copies — nothing committed until the end; byte
    # order is rank order on both sides, so relative order of every
    # resident key is preserved and sortedness survives)
    if p.actors_changed:
        rank_map = np.fromiter(
            (p.rank_of[b] for b in (a.bytes for a in log.actors)),
            np.int64, count=len(log.actors),
        )

        def remap_packed(key):
            key = np.asarray(key, np.int64)
            idx = np.where(key > 0, key, 0) & ACTOR_MASK
            return np.where(
                key > 0,
                ((key >> ACTOR_BITS) << ACTOR_BITS) | rank_map[idx],
                key,
            )

        old_id = remap_packed(log.id_key)
        old_obj = remap_packed(log.obj_key)
        old_ek = remap_packed(log.elem_key)
        old_pk = remap_packed(log.pred_key)
        old_table = remap_packed(log.obj_table)
    else:
        old_id = log.id_key
        old_obj = log.obj_key
        old_ek = log.elem_key
        old_pk = log.pred_key
        old_table = log.obj_table

    # -- object table: must only extend at its end ------------------------
    # make actions are exactly the even codes below 8 (MAKE_ACTIONS =
    # 0/2/4/6): two compares beat np.isin's sort machinery per doc
    make_mask = (d_action < 8) & ((d_action & 1) == 0)
    make_new = d_id[make_mask]
    pos = np.searchsorted(old_table, d_obj)
    posc = np.clip(pos, 0, len(old_table) - 1)
    found = old_table[posc] == d_obj
    all_found = bool(np.all(found))
    if len(make_new) == 0 and all_found:
        add = make_new  # steady state: no new objects in this delta
    else:
        add_parts = [make_new]
        if not all_found:
            add_parts.append(d_obj[~found])
        add = np.unique(np.concatenate(add_parts))
    if len(add) and int(add[0]) <= int(old_table[-1]):
        # a new object id at or below the resident maximum would splice
        # INTO the table (dense-id remap of every resident row) — the
        # scalar path owns that case. Nothing has been mutated yet.
        obs.count("host_batch.fallback_docs", labels={"reason": "obj_order"})
        return ("scalar",)
    m = n + k

    # -- compressed residency: extend the id_key runs with the delta so
    # the reference joins below run offset-value-coded (searchsorted
    # over R run heads + stride arithmetic) instead of over all m rows;
    # the rest of the compressed image extends lazily on next sync — a
    # tail append never moves the resident prefix (ops/compressed.py)
    from . import compressed as _C

    idruns = None
    if _C.enabled() and not p.actors_changed:
        comp = log._comp
        if comp is None:
            comp = log._comp = _C.CompressedOpColumns()
        comp._sync_col("id_key", "delta", log.id_key, n)
        idruns = comp.extend_id(d_id)

    # -- packed-key and payload columns (tail writes only) ----------------
    if log._bufs is None:
        log._bufs = {}
    bufs = log._bufs
    tw = _tail_write
    id_new = tw(bufs, "id_key", old_id, d_id, m)
    obj_new = tw(bufs, "obj_key", old_obj, d_obj, m)
    ek_new = tw(bufs, "elem_key", old_ek, g["elem_s"][sl], m)
    action_new = tw(bufs, "action", log.action, d_action, m)
    insert_new = tw(bufs, "insert", log.insert, g["insert_s"][sl], m)
    vtag_new = tw(bufs, "value_tag", log.value_tag, g["vtag_s"][sl], m)
    vint_new = tw(bufs, "value_int", log.value_int, g["vint_s"][sl], m)
    width_new = tw(bufs, "width", log.width, g["width_s"][sl], m)
    expand_new = tw(bufs, "expand", log.expand, g["expand_s"][sl], m)

    # -- string-table columns ---------------------------------------------
    doc_keys = _doc_string_table(ready, "key_table")
    if doc_keys:
        props, d_prop = _merge_table(
            log.props, doc_keys,
            _local_ids(g["prop_s"][sl], g["key_pos"], doc_keys,
                       len(g["key_pos"])),
            np.arange(k),
        )
    else:
        # no change in this delta carries map keys: ids are all -1
        props = log.props
        d_prop = np.full(k, -1, np.int32)
    if g["mark_s"] is None:
        mark_names = log.mark_names
        d_mark = np.full(k, -1, np.int32)
    else:
        doc_marks = _doc_string_table(ready, "mark_table")
        if doc_marks:
            mark_names, d_mark = _merge_table(
                log.mark_names, doc_marks,
                _local_ids(g["mark_s"][sl], g["mark_pos"], doc_marks,
                           len(g["mark_pos"])),
                np.arange(k),
            )
        else:
            mark_names = log.mark_names
            d_mark = np.full(k, -1, np.int32)
    prop_new = tw(bufs, "prop", log.prop, d_prop, m)
    mark_new = tw(bufs, "mark_name_idx", log.mark_name_idx, d_mark, m)

    # -- row-reference columns (resolve through the shared id join) -------
    def _id_join(keys):
        if idruns is not None:
            obs.count("oplog.ovc_join", n=len(keys))
            return idruns.join(keys, ELEM_MISSING)
        return join_rows(id_new, keys, ELEM_MISSING)

    d_ek = g["elem_s"][sl]
    d_er = np.where(
        d_ek == -1,
        np.int32(ELEM_MAP),
        np.where(
            d_ek == 0, np.int32(ELEM_HEAD),
            _id_join(d_ek),
        ),
    ).astype(np.int32)
    er_new = tw(bufs, "elem_ref", log.elem_ref, d_er, m)
    n_miss_elem = int(np.count_nonzero(d_er == ELEM_MISSING))

    q = len(log.pred_src)
    p0, p1 = p.p0, p.p1
    src_g = g["r"]["pred_src"][p0:p1]
    if len(src_g):
        d_ps = (n + (g["inv_g"][src_g] - p.r0)).astype(np.int32)
        d_pk = g["pk_t"][p0:p1]
        d_pt = _id_join(d_pk)
        d_pt = np.where(
            d_pt == ELEM_MISSING, np.int32(-1), d_pt
        ).astype(np.int32)
    else:
        d_ps = np.empty(0, np.int32)
        d_pk = np.empty(0, np.int64)
        d_pt = np.empty(0, np.int32)
    qm = q + len(d_ps)
    ps_new = tw(bufs, "pred_src", log.pred_src, d_ps, qm)
    pt_new = tw(bufs, "pred_tgt", log.pred_tgt, d_pt, qm)
    pk_new = tw(bufs, "pred_key", old_pk, d_pk, qm)
    n_miss_pred = int(np.count_nonzero(d_pt == -1))

    # -- object table / dense ids -----------------------------------------
    if len(add):
        new_table = np.concatenate([old_table, add])
        od_new = np.searchsorted(new_table, d_obj).astype(np.int32)
    else:
        new_table = old_table
        od_new = posc.astype(np.int32)
    od_all = tw(bufs, "obj_dense", log.obj_dense, od_new, m)

    # -- values heap (LazyValues, append-only raw) ------------------------
    vals = log.values
    c1 = p.c1
    raw0 = int(g["raw_off"][p.c0])
    raw1 = (
        int(g["raw_off"][c1]) if c1 < g["n_changes"]
        else int(g["raw_off"][-1] + g["raw_ln"][-1])
    )
    base = len(vals.raw)
    code = tw(bufs, "vcode", vals.code, g["vcode_s"][sl], m)
    off = tw(bufs, "voff", vals.off, g["voff_s"][sl] - raw0 + base, m)
    ln = tw(bufs, "vlen", vals.ln, g["vlen_s"][sl], m)
    raw = vals.raw
    if not isinstance(raw, bytearray):
        raw = bytearray(raw)
    raw += g["a"]["vraw"][raw0:raw1]
    nv = LazyValues(code, off, ln, raw, cap=vals.cap)
    nv.hits, nv.misses = vals.hits, vals.misses

    # -- dirty objects (new dense numbering) ------------------------------
    one_obj = bool(od_new[0] == od_new[-1]) and bool(
        np.all(od_new == od_new[0])
    )
    if one_obj and len(make_new) == 0 and len(d_pt) == 0:
        # single-object insert-only delta (a typing burst): the dirty
        # set is that one object
        dirty = od_new[:1].astype(np.int64)
    else:
        parts = [od_new.astype(np.int64),
                 np.searchsorted(new_table, make_new)]
        if len(d_pt):
            hit = d_pt >= 0
            if np.any(hit):
                parts.append(od_all[d_pt[hit]].astype(np.int64))
        dirty = np.unique(np.concatenate(parts)).astype(np.int64)

    # -- commit the log ----------------------------------------------------
    log.id_key = id_new
    log.obj_key = obj_new
    log.elem_key = ek_new
    log.action = action_new
    log.prop = prop_new
    log.insert = insert_new
    log.value_tag = vtag_new
    log.value_int = vint_new
    log.width = width_new
    log.expand = expand_new
    log.mark_name_idx = mark_new
    log.elem_ref = er_new
    log.obj_dense = od_all
    log.pred_src = ps_new
    log.pred_tgt = pt_new
    log.pred_key = pk_new
    log.props = props
    log.mark_names = mark_names
    log.values = nv
    log.n = m
    log.n_objs = len(new_table)
    log.obj_table = new_table
    log.n_miss_elem = n_miss_elem
    log.n_miss_pred = n_miss_pred
    if p.actors_changed:
        from ..types import ActorId

        log.actors = [ActorId(b) for b in p.all_bytes]
        log._comp = None  # every resident packed key was rank-remapped
    log._actor_order = None
    log.changes.extend(ready)
    log.hashes().update(ch.hash for ch in ready)

    # -- DeviceDoc bookkeeping (the _apply_append tail specialization) ----
    for ch in ready:
        dev._hash_index[ch.hash] = ch
    if p.actors_changed:
        # host caches keyed by packed ids follow the same monotone map
        remap = {
            old: p.rank_of[b] for b, old in dev._rank_of.items()
        }
        dev._obj_type = {
            (
                key
                if key == 0
                else ((key >> ACTOR_BITS) << ACTOR_BITS)
                | remap[key & ACTOR_MASK]
            ): v
            for key, v in dev._obj_type.items()
        }
        dev._rank_of = dict(p.rank_of)
    dev._views.clear()
    nr = np.arange(n, m, dtype=np.int64)
    if len(make_new):
        for r_ in nr[make_mask]:
            dev._obj_type[int(log.id_key[r_])] = _MAKE_OBJ[int(log.action[r_])]

    rbufs = dev._res_bufs
    vis = tw(rbufs, "visible", dev.visible, False, m)
    win = tw(rbufs, "winner", dev.winner, -1, m)
    con = tw(rbufs, "conflicts", dev.conflicts, 0, m)
    ei = tw(rbufs, "elem_index", dev.elem_index, -1, m)
    old_ovl = dev.res["obj_vis_len"]
    old_otw = dev.res["obj_text_width"]
    if (
        len(add) == 0
        and len(old_ovl) == log.n_objs + 2
        and old_ovl.flags.writeable
        and old_otw.flags.writeable
    ):
        # table unchanged and the stat arrays are already exactly the
        # right (owned) shape: carry them forward in place, resetting
        # only the two pad slots — what the scalar path's fresh-zeros-
        # plus-copy produces. A doc fresh from resolve() holds padded
        # read-only device readbacks instead; those take the copy path.
        ovl = old_ovl
        otw = old_otw
        ovl[log.n_objs:] = 0
        otw[log.n_objs:] = 0
    else:
        ovl = np.zeros(log.n_objs + 2, np.int32)
        otw = np.zeros(log.n_objs + 2, np.int32)
        oo = np.asarray(old_ovl)
        ot = np.asarray(old_otw)
        take = min(len(old_table), len(oo))
        ovl[:take] = oo[:take]
        otw[:take] = ot[:take]
    dev.res = {
        "visible": vis, "winner": win, "conflicts": con,
        "elem_index": ei, "obj_vis_len": ovl, "obj_text_width": otw,
    }
    dev.visible = vis
    dev.winner = win
    dev.conflicts = con
    dev.elem_index = ei
    # the base view's covered mask is all-true by definition: extend it
    # through the same capacity buffer instead of a fresh O(rows) ones()
    dev.covered = tw(rbufs, "covered", dev.covered, True, m)

    dev.succ_count = tw(rbufs, "succ_count", dev.succ_count, 0, m)
    dev.inc_count = tw(rbufs, "inc_count", dev.inc_count, 0, m)
    value_int = np.asarray(log.value_int)
    cv = tw(rbufs, "counter_val", dev.counter_val, 0, m)
    cv[n:m] = value_int[n:m]
    dev.counter_val = cv
    if qm > q:
        src = ps_new[q:qm]
        tgt = pt_new[q:qm]
        ok = tgt >= 0
        src, tgt = src[ok], tgt[ok]
        is_inc = np.asarray(log.action)[src] == _INCREMENT
        np.add.at(dev.succ_count, tgt[~is_inc], 1)
        np.add.at(dev.inc_count, tgt[is_inc], 1)
        np.add.at(dev.counter_val, tgt[is_inc], value_int[src[is_inc]])

    # object-sorted row index: merge the delta into the resident order
    old_rbo = dev._rows_by_obj
    if p.actors_changed:
        # _obj_sorted holds packed VALUES: re-gather from the remapped
        # column (monotone remap preserved the sort)
        old_keys = np.asarray(log.obj_key)[:n][old_rbo]
    else:
        old_keys = dev._obj_sorted
    rbo = np.empty(m, np.int64)
    keys = np.empty(m, np.int64)
    if one_obj:
        # single-object delta: one contiguous insertion block — three
        # slice copies instead of the bincount/cumsum merge
        okey = int(d_obj[0])
        at = int(np.searchsorted(old_keys, okey, side="right"))
        rbo[:at] = old_rbo[:at]
        keys[:at] = old_keys[:at]
        rbo[at:at + k] = nr
        keys[at:at + k] = okey
        rbo[at + k:] = old_rbo[at:]
        keys[at + k:] = old_keys[at:]
    else:
        d_keys = np.asarray(log.obj_key)[nr]
        ordx = np.lexsort((nr, d_keys))
        d_rows = nr[ordx]
        d_keys = d_keys[ordx]
        pos2 = np.searchsorted(old_keys, d_keys, side="right")
        cnt = np.bincount(pos2, minlength=n + 1)
        old_pos = np.arange(n, dtype=np.int64) + np.cumsum(cnt[:n])
        rbo[old_pos] = old_rbo
        keys[old_pos] = old_keys
        new_pos = pos2 + np.arange(k, dtype=np.int64)
        rbo[new_pos] = d_rows
        keys[new_pos] = d_keys
    dev._rows_by_obj = rbo
    dev._obj_sorted = keys

    if p.actors_changed:
        dev._all_elems_cache.clear()
    else:
        for d in dirty:
            dev._all_elems_cache.pop(int(log.obj_table[d]), None)

    # -- a subset or a full stage (the stage_batches decision) -----------
    rows = dev._subset_rows(dirty)
    dev._export_doc_gauges()
    if dev._resolves_whole(rows, dirty):
        return ("full",)
    return ("stage", rows, dirty)
