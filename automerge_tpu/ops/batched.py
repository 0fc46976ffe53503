"""Cross-document batched device merge: one kernel launch per drain cycle.

A server draining N hot documents used to pay N separate kernel
dispatches — one dirty-set re-resolution per ``DeviceDoc`` — even though
the serve layer already hands the drain over as multi-document work
(serve/shards.py) and each dispatch is launch-overhead-bound at serve
sizes. This module multiplies those dispatches away: the coalesced
deltas of many small documents are packed into ONE ragged super-batch
(per-doc subset columns concatenated with row/object-id offsets, padded
to a shared capacity bucket so jit caches stay warm) and succ
resolution, visibility, winner recompute and dirty-set re-resolution run
as a single kernel launch, results scattered back per document.

Soundness: every group id in the resolution kernel (sequence runs keyed
by run-head row, map groups keyed by (object, prop)) is derived from row
and object ids, so offsetting each document's subset rows and dense
object ids into disjoint ranges keeps all key groups disjoint across
documents — the packed kernel resolves each document exactly as its own
subset launch would, bit for bit (asserted by tests/test_batched_merge).
Rows stay ascending within each document, preserving the "max row = max
Lamport" winner rule.

Every device feed stages and resolves through ``stage_and_resolve``:
``host_batch.stage_docs`` over the drained documents, then
``resolve_stages`` over their stages, on every backend. Its callers:

* ``CrossDocBatcher`` — the serving-layer collector: workers draining
  different documents submit concurrently; the first submitter of a
  generation becomes the flush leader, waits a tiny window
  (``AUTOMERGE_TPU_BATCH_WINDOW_MS``) for co-arriving documents, then
  stages and launches once for everyone (the group-commit pattern the
  journal fsync combiner already uses). Submitters hold their document
  lock while waiting, so per-doc single-writer discipline is preserved:
  nothing else can touch a document between its host-side stage and the
  scatter of its kernel results.
* ``DeviceDoc.apply_batches`` — a generation of one document.
* ``apply_cross_doc(work)`` — synchronous, for a known set of
  documents. The bench / CI driver.
* the cluster follower's ``replApply`` drain (cluster/node.py).

A stage is one of two kinds. A *subset* stage covers the dirty
objects' rows. A *full* stage covers every row and object of its
document: the staging half returns one where the dirty fraction trips
the full-re-resolution cost model (``AUTOMERGE_TPU_DIRTY_FRACTION``),
which is the common case for a small document, whose change dirties the
objects that hold most of its rows. ``dispatch_stages`` decides once,
for both kinds, where a stage resolves:

* alone in its generation: per document (``DeviceDoc._reresolve``);
* peeled by the whale rule: per document. A document whose stage rows
  exceed ``AUTOMERGE_TPU_BATCH_FALLBACK_RATIO`` (default 0.5, strict) of
  the combined batch is peeled off — padding 99 small documents up to a
  whale's capacity bucket (and making them wait out its kernel) costs
  more than the launch it saves;
* a full stage of a document on a mesh: per document, over the mesh;
* every other stage: packed into the shared launch.

Every packed launch counts ``device.kernel_launches{path=batched}`` and
each stage it carries ``device.batch_stages{kind=full|subset}``; the
per-doc and sharded dispatch sites carry the launch counter with their
own ``path`` label, so "launches per drain cycle" and "documents per
shared launch" are directly observable.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..obs import prof as _prof

# the READ_FETCH surface a DeviceDoc subset scatter consumes
_FETCH = (
    "visible", "winner", "conflicts", "elem_index",
    "obj_vis_len", "obj_text_width",
)
_PACK_COLS = (
    "action", "insert", "prop", "elem_ref", "obj_dense", "value_tag",
    "value_i32", "width", "covered", "pred_src", "pred_tgt",
)


# the longest a flush leader waits past the batch window for the drains
# in flight to join its generation (CrossDocBatcher.in_flight)
FILL_WAIT_S = 1.0


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class BatchStage:
    """One document's staged host append awaiting kernel resolution:
    the rows to resolve (``rows`` are log row indices, ``dirty`` the
    dense object ids they belong to) plus the document itself for the
    scatter. ``full`` says the stage covers every row and object of the
    document (``full_stage``)."""

    __slots__ = ("doc", "rows", "dirty", "full", "trace")

    def __init__(self, doc, rows: np.ndarray, dirty: np.ndarray,
                 full: bool = False):
        self.doc = doc
        self.rows = rows
        self.dirty = dirty
        self.full = full
        # the submitting request's trace context (trace_id, span_id), so
        # the shared launch span can link back to every request it served
        self.trace = None

    @property
    def n_rows(self) -> int:
        return len(self.rows)


def full_stage(doc) -> BatchStage:
    """The stage of a whole-document re-resolution: every row, every
    object."""
    return BatchStage(doc, np.arange(doc.log.n, dtype=np.int64),
                      np.arange(doc.log.n_objs, dtype=np.int64), full=True)


def plan_stages(
    stages: Sequence[BatchStage], fallback_ratio: Optional[float] = None
) -> Tuple[List[BatchStage], List[BatchStage]]:
    """Split staged documents into (packed batch, per-doc fallbacks).

    A document is peeled (largest first, totals recomputed after each
    peel) while its subset rows STRICTLY exceed ``fallback_ratio`` of
    the remaining batch total — the whale rule. Ratio >= 1 never peels
    (a doc cannot exceed the total it is part of); ratio 0 peels
    everything down to the smallest document.
    """
    if fallback_ratio is None:
        fallback_ratio = _env_float("AUTOMERGE_TPU_BATCH_FALLBACK_RATIO", 0.5)
    batch = sorted(stages, key=lambda s: s.n_rows)
    whales: List[BatchStage] = []
    total = sum(s.n_rows for s in batch)
    while len(batch) > 1 and batch[-1].n_rows > fallback_ratio * total:
        w = batch.pop()
        total -= w.n_rows
        whales.append(w)
    return batch, whales


def _pack(stages: Sequence[BatchStage]):
    """Concatenate per-doc subset columns into one super-batch.

    Row references (``elem_ref``/``pred_src``/``pred_tgt``) shift by the
    document's row offset, dense object ids by its object offset;
    negative sentinels (HEAD / map / missing) pass through untouched.
    Returns (cols, metas, n_rows, n_objs) with metas =
    [(stage, row_off, n_rows, obj_off, n_objs)].
    """
    parts = {k: [] for k in _PACK_COLS}
    metas = []
    row_off = 0
    obj_off = 0
    for st in stages:
        sub = st.doc._subset_cols(st.rows, st.dirty)
        er = sub["elem_ref"]
        sub["elem_ref"] = np.where(er >= 0, er + row_off, er).astype(np.int32)
        sub["obj_dense"] = (sub["obj_dense"] + obj_off).astype(np.int32)
        sub["pred_src"] = (sub["pred_src"] + row_off).astype(np.int32)
        pt = sub["pred_tgt"]
        sub["pred_tgt"] = np.where(pt >= 0, pt + row_off, pt).astype(np.int32)
        for k in parts:
            parts[k].append(np.asarray(sub[k]))
        S, D = len(st.rows), len(st.dirty)
        metas.append((st, row_off, S, obj_off, D))
        row_off += S
        obj_off += D
    cols = {k: np.concatenate(v) for k, v in parts.items()}
    return cols, metas, row_off, obj_off


def _dispatch_packed(cols, n_objs: int, n_props: int):
    """The host + dispatch half of a packed launch: pad, stage plain
    columns, dispatch the kernel WITHOUT reading back, and rank element
    order host-side while it flies. Returns an in-flight handle for
    ``_collect_packed``."""
    from .merge import prepare_resolution
    from .oplog import host_linearize, pad_columns

    useful = len(cols["action"])
    with obs.span("device.pack", rows=useful):
        # pred edges padded to the rows' bucket at least, so the launch's
        # shapes follow its rows alone where a document's ops have about
        # one predecessor each
        cols = pad_columns(cols, n_objs, min_preds=useful)
    P = len(cols["action"])
    # occupancy at the pack site: padded-vs-useful rows were invisible
    # before, and the ratio is the first input the super-batch tuner
    # needs (a batch padded 10x past its useful rows is burning its win)
    obs.count("device.batch_rows", n=useful)
    obs.count("device.batch_padding_rows", n=P - useful)
    _prof.note("useful_rows", useful)
    _prof.note("padded_rows", P - useful)
    _prof.note("launches")
    obs.count("device.kernel_launches", labels={"path": "batched"})
    # the super-batch ships plain: which of its columns would run-encode
    # follows the documents that happen to drain together, and would
    # pick the program; plain columns leave it to the bucketed shapes,
    # so a set-up that met a launch's shapes has compiled its program
    dispatch = prepare_resolution(cols, n_objs, n_props)
    out = dispatch()  # async dispatch
    with obs.span("device.linearize", rows=P):
        ei = host_linearize(cols)
    return {"out": out, "ei": ei, "P": P}


def _collect_packed(handle):
    """The blocking half of a packed launch: read the resolution back
    (``conflicts`` as ``merge.conflict_flags``)."""
    from .merge import conflict_flags, readback

    with readback(handle["out"], handle["P"]):
        res = {
            k: np.asarray(handle["out"][k])
            for k in ("visible", "winner", "conflicts",
                      "obj_vis_len", "obj_text_width")
        }
    res["conflicts"] = conflict_flags(res["conflicts"])
    res["elem_index"] = handle["ei"]
    return res


def _scatter(metas, res) -> None:
    """Slice the packed results back per document and scatter them into
    each DeviceDoc's resolution arrays (winner values return to
    subset-local numbering — the contract of ``_scatter_subset``)."""
    for st, r0, S, o0, D in metas:
        w = res["winner"][r0 : r0 + S]
        res_sub = {
            "visible": res["visible"][r0 : r0 + S],
            "winner": np.where(w >= 0, w - r0, -1).astype(np.int32),
            "conflicts": res["conflicts"][r0 : r0 + S],
            "elem_index": res["elem_index"][r0 : r0 + S],
            "obj_vis_len": res["obj_vis_len"][o0 : o0 + D],
            "obj_text_width": res["obj_text_width"][o0 : o0 + D],
        }
        st.doc._scatter_subset(st.rows, st.dirty, res_sub)


def dispatch_stages(
    stages: Sequence[BatchStage], fallback_ratio: Optional[float] = None
) -> dict:
    """The dispatch half of ``resolve_stages``: a stage alone in
    ``stages``, a whale (``plan_stages``) and a full stage of a document
    on a mesh resolve per-doc immediately (they never pipeline); the
    rest pack into ONE kernel launch that is dispatched but NOT
    collected. The returned handle feeds ``collect_stages`` — possibly
    after the caller has staged more host work under the in-flight
    launch (the drain pipeline)."""
    own, rest = [], []
    for st in stages:
        alone = len(stages) == 1 or (st.full and st.doc.on_mesh())
        (own if alone else rest).append(st)
    batch, whales = plan_stages(rest, fallback_ratio)
    for w in whales:
        obs.count("device.batched_fallback")
    for st in own + whales:
        st.doc._reresolve(st.dirty)
    handle = None
    metas = None
    if batch:
        for st in batch:
            obs.count("device.batch_stages",
                      labels={"kind": "full" if st.full else "subset"})
        links = [st.trace for st in batch if st.trace is not None]
        with obs.span("device.batched", links=links, docs=len(batch)):
            obs.observe("device.batch_docs", len(batch))
            with obs.span("device.pack", docs=len(batch)):
                cols, metas, n_rows, n_objs = _pack(batch)
            n_props = max(
                (len(st.doc.log.props) for st in batch), default=1
            )
            handle = _dispatch_packed(cols, n_objs, max(n_props, 1))
    return {
        "batched": len(batch),
        "fallback": len(own) + len(whales),
        "handle": handle,
        "metas": metas,
    }


def collect_stages(disp: dict) -> dict:
    """The blocking half of ``resolve_stages``: read the packed launch
    back and scatter the results into each document."""
    if disp["handle"] is not None:
        with obs.span("device.batched", docs=disp["batched"]):
            res = _collect_packed(disp["handle"])
            with obs.span("device.scatter", docs=disp["batched"]):
                _scatter(disp["metas"], res)
    return {"batched": disp["batched"], "fallback": disp["fallback"]}


def resolve_stages(
    stages: Sequence[BatchStage], fallback_ratio: Optional[float] = None
) -> dict:
    """Resolve staged documents: whales per-doc, the rest in ONE packed
    launch. Returns {"batched": n_docs, "fallback": n_docs}."""
    return collect_stages(dispatch_stages(stages, fallback_ratio))


def stage_and_resolve(
    work,
    *,
    fallback_ratio: Optional[float] = None,
    max_docs_per_launch: Optional[int] = None,
    traces: Optional[dict] = None,
):
    """Stage ``work`` — ``(device_doc, batches)`` pairs, duplicates
    merged — through ``host_batch.stage_docs`` and resolve the stages
    with ``resolve_stages``, at most ``max_docs_per_launch`` of them a
    launch (None = all in one). ``traces`` maps ``id(device_doc)`` to
    the trace context the shared launch span links back to.

    Returns ``(results, resolved)``: the per-document
    ``host_batch.DocResult`` keyed by ``id(device_doc)`` (a document
    whose staging failed carries its error and has no stage), and
    ``{"batched": n_docs, "fallback": n_docs}``. A failed launch
    raises."""
    from . import host_batch

    stages, results = host_batch.stage_docs(work)
    if traces:
        for st in stages:
            st.trace = traces.get(id(st.doc))
    resolved = {"batched": 0, "fallback": 0}
    step = max_docs_per_launch or len(stages) or 1
    for lo in range(0, len(stages), step):
        r = resolve_stages(stages[lo : lo + step], fallback_ratio)
        resolved["batched"] += r["batched"]
        resolved["fallback"] += r["fallback"]
    return results, resolved


def pipeline_enabled() -> bool:
    """Whether ``apply_cross_doc``'s chunked drain double-buffers: chunk
    N's packed kernel flies while chunk N+1 runs its host staging. Host
    seconds spent under an in-flight launch are noted as ``overlap_s``
    and surface as ``drain.overlap_fraction``."""
    return os.environ.get("AUTOMERGE_TPU_DRAIN_PIPELINE", "1") != "0"


def apply_cross_doc(
    work,
    *,
    fallback_ratio: Optional[float] = None,
    max_docs_per_launch: Optional[int] = None,
    pipeline: Optional[bool] = None,
) -> dict:
    """Synchronous multi-document apply: ``work`` is an iterable of
    ``(device_doc, batches)`` pairs (``batches`` = a sequence of change
    batches, as ``apply_batches`` takes). Stages every document
    host-side, then resolves the stages in shared packed launches of at
    most ``max_docs_per_launch`` documents (None = all in one). The
    first document whose staging failed raises its error once the
    others are resolved.

    Returns {"applied": total changes, "batched": docs resolved in
    packed launches, "fallback": docs resolved per-doc}.

    When ``max_docs_per_launch`` splits the drain into several launches
    and the pipeline is enabled (``pipeline`` kwarg, defaulting to
    ``AUTOMERGE_TPU_DRAIN_PIPELINE`` which is on), the chunks
    double-buffer: chunk N's packed kernel stays in
    flight while chunk N+1 runs its host staging (dedup / causal-order /
    pack / Lamport-sort / splice), and only then is chunk N collected.
    Host seconds spent under an in-flight launch are noted as
    ``overlap_s`` → ``drain.overlap_fraction``.
    """
    from . import host_batch

    # the same DeviceDoc may appear several times in ``work``; its
    # batches must merge into ONE staging — a later append splices the
    # log and would silently invalidate an earlier stage's row/object
    # indices. stage_docs merges within a call; merging up front keeps
    # each document inside one chunk of the pipelined drain
    merged: dict = {}
    order: List[int] = []
    for dev, batches in work:
        k = id(dev)
        if k in merged:
            merged[k][1].extend(batches)
        else:
            merged[k] = (dev, list(batches))
            order.append(k)

    out = {"applied": 0, "batched": 0, "fallback": 0}

    def _account(r):
        out["batched"] += r["batched"]
        out["fallback"] += r["fallback"]

    def _applied(results):
        out["applied"] += sum(r.applied for r in results.values())
        return next((r.error for r in results.values()
                     if r.error is not None), None)

    if pipeline is None:
        pipeline = pipeline_enabled()
    step = max_docs_per_launch or len(order) or 1
    error = None
    if pipeline and len(order) > step:
        # double-buffered drain: chunk the WORK (not the stages) so each
        # chunk's host staging runs while the previous chunk's packed
        # kernel is in flight; each stage_docs call is self-contained
        # (dedup, actor table and offsets are per call)
        pending = None
        try:
            for lo in range(0, len(order), step):
                t0 = time.perf_counter()
                stages, results = host_batch.stage_docs(
                    [merged[k] for k in order[lo : lo + step]]
                )
                error = error or _applied(results)
                d = dispatch_stages(stages, fallback_ratio)
                if pending is not None:
                    # everything since the loop top ran under pending's
                    # in-flight launch — the pipeline's measurable win
                    _prof.note("overlap_s", time.perf_counter() - t0)
                    _account(collect_stages(pending))
                pending = d
        except BaseException:
            if pending is not None:
                p, pending = pending, None
                collect_stages(p)
            raise
        if pending is not None:
            _account(collect_stages(pending))
    else:
        results, resolved = stage_and_resolve(
            [merged[k] for k in order], fallback_ratio=fallback_ratio,
            max_docs_per_launch=max_docs_per_launch,
        )
        error = _applied(results)
        _account(resolved)
    _prof.note("docs", len(order))
    _prof.note("changes", out["applied"])
    if error is not None:
        raise error
    return out


# -- the serving-layer collector ---------------------------------------------


class _Submission:
    """One document's raw drained batches awaiting its generation's
    flush: the submitter keeps holding its document lock while the flush
    leader stages every co-arriving document in one columnar pass."""

    __slots__ = ("dev", "batches", "trace", "applied", "error")

    def __init__(self, dev, batches, trace):
        self.dev = dev
        self.batches = batches
        self.trace = trace
        self.applied = 0
        self.error: Optional[BaseException] = None


class _Generation:
    __slots__ = ("subs", "done")

    def __init__(self):
        self.subs: List[_Submission] = []
        self.done = threading.Event()


class CrossDocBatcher:
    """Group-commit collector for concurrent per-document workers.

    ``apply(dev, batches)`` hands the document's drained device feed
    (the caller MUST hold that document's execution lock) to the next
    shared launch and blocks until it has resolved. The first submitter
    of a generation is the leader: it waits ``window_ms`` for
    co-arriving documents (waking early at ``max_docs``), closes the
    generation, and runs ``stage_and_resolve`` for everyone.

    ``in_flight``, where the caller sets it (the serving layer passes its
    shard pool's), counts the drains running or ready to run. Once the
    window is out the leader keeps waiting while fewer documents than
    that have joined, up to ``FILL_WAIT_S``: drains that are past their
    queue reach the batcher within a drain's host work, so generations
    fill instead of closing on whichever drain happened to come first.
    With no other drain in flight the leader closes at the window, as
    without ``in_flight``.
    """

    def __init__(
        self,
        *,
        window_ms: Optional[float] = None,
        max_docs: Optional[int] = None,
        fallback_ratio: Optional[float] = None,
    ):
        self.window = (
            window_ms
            if window_ms is not None
            else _env_float("AUTOMERGE_TPU_BATCH_WINDOW_MS", 2.0)
        ) / 1000.0
        self.max_docs = int(
            max_docs
            if max_docs is not None
            else _env_float("AUTOMERGE_TPU_BATCH_DOCS", 32)
        )
        self.fallback_ratio = fallback_ratio
        self._cv = threading.Condition(threading.Lock())
        self._gen = _Generation()
        self.in_flight: Optional[Callable[[], int]] = None

    def apply(self, dev, batches) -> int:
        """Resolve ``dev``'s drained batches in the next shared launch;
        blocks until resolved. Returns changes applied.

        The submitter hands its RAW batches over and the generation's
        flush leader stages every co-arriving document in one shared
        columnar pass (host_batch.stage_docs) before the shared kernel
        launch — the submitter keeps holding its document lock while it
        waits, so the single-writer discipline holds."""
        sub = _Submission(dev, list(batches), obs.current_trace_context())
        with self._cv:
            gen = self._gen
            gen.subs.append(sub)
            leader = len(gen.subs) == 1
            if not leader:
                self._cv.notify_all()  # the leader counts the arrivals
        if leader:
            self._lead(gen)
        else:
            gen.done.wait()
        if sub.error is not None:
            raise sub.error
        return sub.applied

    def _lead(self, gen: _Generation) -> None:
        """The (single) flush leader: wait out the batch window for
        co-arriving documents, and past it for the drains in flight
        (``in_flight``, up to ``FILL_WAIT_S``), close the generation,
        flush it. The wait is a ``device.batch_window`` span: ``docs`` in
        the generation when it closed, ``reason`` ``full`` (``max_docs``
        or every drain in flight joined) or ``timeout``."""
        t0 = time.monotonic()
        with obs.span("device.batch_window") as sp:
            with self._cv:
                while True:
                    docs = len(gen.subs)
                    waited = time.monotonic() - t0
                    if docs >= self.max_docs:
                        full = True
                        break
                    if waited < self.window:
                        self._cv.wait(self.window - waited)
                        continue
                    full = (self.in_flight is None
                            or docs >= self.in_flight())
                    if full or waited >= FILL_WAIT_S:
                        break
                    # a drain that ends without joining does not notify:
                    # look again each window
                    self._cv.wait(max(self.window, 1e-3))
                if self._gen is gen:  # close the generation we lead
                    self._gen = _Generation()
            sp.fields["docs"] = docs
            sp.fields["reason"] = "full" if full else "timeout"
        self._flush(gen)

    def _flush(self, gen: _Generation) -> None:
        """Close one generation: stage every submission in one
        vectorized pass, launch once (``stage_and_resolve``), and release
        every waiter. A failure reaches every waiter."""
        try:
            traces = {}
            for s in gen.subs:
                if s.trace is not None:
                    traces.setdefault(id(s.dev), s.trace)
            results, _ = stage_and_resolve(
                [(s.dev, s.batches) for s in gen.subs],
                fallback_ratio=self.fallback_ratio, traces=traces,
            )
            n_changes = 0
            for s in gen.subs:
                r = results.get(id(s.dev))
                if r is not None:
                    s.applied = r.applied
                    s.error = r.error
                    n_changes += r.applied
            _prof.note("docs", len(gen.subs))
            _prof.note("changes", n_changes)
        except BaseException as e:
            # every document of the generation sees the failure: its
            # changes were spliced but never resolved, so its caller must
            # not acknowledge the device work
            obs.count("device.batched_error")
            obs.event("device.batched_error", error=str(e)[:200])
            for s in gen.subs:
                if s.error is None:
                    s.error = e
        finally:
            gen.done.set()
