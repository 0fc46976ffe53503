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

Two entry points:

* ``apply_cross_doc(work)`` — synchronous: stage every document's
  drained batches (``DeviceDoc.stage_batches``), resolve them in shared
  launches. The bench / CI driver.
* ``CrossDocBatcher`` — the serving-layer collector: workers draining
  different documents submit concurrently; the first submitter of a
  generation becomes the flush leader, waits a tiny window
  (``AUTOMERGE_TPU_BATCH_WINDOW_MS``) for co-arriving documents, then
  packs and launches once for everyone (the group-commit pattern the
  journal fsync combiner already uses). Submitters hold their document
  lock while waiting, so per-doc single-writer discipline is preserved:
  nothing else can touch a document between its host-side stage and the
  scatter of its kernel results.

Fallback: a document whose subset rows exceed
``AUTOMERGE_TPU_BATCH_FALLBACK_RATIO`` (default 0.5, strict) of the
combined batch is peeled off and resolved through the existing per-doc
path — padding 99 small documents up to a whale's capacity bucket (and
making them wait out its kernel) costs more than the launch it saves.
Documents whose dirty fraction trips the per-doc full-re-resolution
cost model never reach the packer (``stage_batches`` resolves them
per-doc immediately, same as ``apply_changes`` would).

Every packed launch counts ``device.kernel_launches{path=batched}``;
the per-doc and sharded dispatch sites carry the same counter with
their own ``path`` label, so "launches per drain cycle" is directly
observable (and asserted by the ``serve_batched`` bench config).
"""

from __future__ import annotations

import os
import threading
import time
from typing import List, Optional, Sequence, Tuple

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


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class BatchStage:
    """One document's staged host append awaiting kernel resolution:
    the dirty-object subset (``rows`` are log row indices, ``dirty`` the
    dense dirty-object ids) plus the document itself for the scatter."""

    __slots__ = ("doc", "rows", "dirty", "error", "trace")

    def __init__(self, doc, rows: np.ndarray, dirty: np.ndarray):
        self.doc = doc
        self.rows = rows
        self.dirty = dirty
        self.error: Optional[BaseException] = None
        # the submitting request's trace context (trace_id, span_id), so
        # the shared launch span can link back to every request it served
        self.trace = None

    @property
    def n_rows(self) -> int:
        return len(self.rows)


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
    """The host + dispatch half of a packed launch: pad, stage
    (run-native run tables or the eager-expand staging), dispatch the
    kernel WITHOUT reading back, and rank element order host-side while
    it flies — exactly like the per-doc dispatch
    (DeviceDoc._dispatch_async). Returns an in-flight handle for
    ``_collect_packed``."""
    from .merge import prepare_resolution
    from .oplog import host_linearize, pad_columns

    useful = len(cols["action"])
    with obs.span("device.pack", rows=useful):
        cols = pad_columns(cols, n_objs)
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
    # the super-batch ships compressed: runs are packed under the same
    # _capacity buckets as the rows, so jit caches stay warm and
    # device_put moves run tables, not dense rows; with run-native
    # kernels the tables are the kernel's input itself
    dispatch = prepare_resolution(cols, n_objs, n_props)
    with obs.span("device.kernel", rows=P), \
            _prof.annotate("amtpu.batched_launch"):
        out = dispatch()  # async dispatch
    with obs.span("device.linearize", rows=P):
        ei = host_linearize(cols)
    return {"out": out, "ei": ei, "P": P}


def _collect_packed(handle):
    """The blocking half of a packed launch: read the resolution back."""
    with obs.span("device.readback", rows=handle["P"]):
        res = {
            k: np.asarray(handle["out"][k])
            for k in ("visible", "winner", "conflicts",
                      "obj_vis_len", "obj_text_width")
        }
    res["elem_index"] = handle["ei"]
    return res


def _launch_packed(cols, n_objs: int, n_props: int):
    """One kernel launch over the padded super-batch; element order is
    ranked host-side overlapped with the kernel."""
    return _collect_packed(_dispatch_packed(cols, n_objs, n_props))


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
    """The dispatch half of ``resolve_stages``: whales resolve per-doc
    immediately (they never pipeline), the rest pack into ONE kernel
    launch that is dispatched but NOT collected. The returned handle
    feeds ``collect_stages`` — possibly after the caller has staged more
    host work under the in-flight launch (the drain pipeline)."""
    batch, whales = plan_stages(stages, fallback_ratio)
    for w in whales:
        obs.count("device.batched_fallback")
        w.doc._reresolve(w.dirty)
    handle = None
    metas = None
    if batch:
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
        "fallback": len(whales),
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


def pipeline_enabled() -> bool:
    """Whether the drain double-buffers: chunk N's packed kernel flies
    while chunk N+1 runs its host pack/sort/splice. Host seconds spent
    under an in-flight launch are noted as ``overlap_s`` and surface as
    ``drain.overlap_fraction``."""
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
    most ``max_docs_per_launch`` documents (None = all in one).

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
    # the same DeviceDoc may appear several times in ``work``; its
    # batches must merge into ONE staging — a later append splices the
    # log and would silently invalidate an earlier stage's row/object
    # indices (apply_batches remaps its in-flight handle for exactly
    # this; the stage path merges up front instead)
    merged: dict = {}
    order: List[int] = []
    for dev, batches in work:
        k = id(dev)
        if k in merged:
            merged[k][1].extend(batches)
        else:
            merged[k] = (dev, list(batches))
            order.append(k)

    from . import host_batch

    def _stage_chunk(keys, idx0):
        """Stage one chunk of documents host-side; returns
        (stages, applied). Self-contained per call — host_batch.stage_docs
        dedups within the call and the chunks are disjoint documents."""
        applied = 0
        stages: List[BatchStage] = []
        if host_batch.enabled():
            # the vectorized cross-doc staging: dedup/causal-order/
            # extract/Lamport-sort/splice run as shared columnar passes
            # with per-doc offset ranges; ineligible documents stage
            # through the scalar path inside (host_batch.stage_docs
            # merges duplicates itself, but the merge above also backs
            # the scalar branch below)
            stages, results = host_batch.stage_docs(
                [merged[k] for k in keys]
            )
            for r in results.values():
                if r.error is not None:
                    raise r.error
                applied += r.applied
        else:
            for i, k in enumerate(keys):
                dev, batches = merged[k]
                t0 = time.perf_counter()
                n, st = dev.stage_batches(batches)
                _prof.note_doc(
                    getattr(dev, "obs_name", None) or f"doc{idx0 + i}",
                    time.perf_counter() - t0,
                )
                applied += n
                if st is not None:
                    stages.append(st)
        return stages, applied

    out = {"applied": 0, "batched": 0, "fallback": 0}

    def _account(r):
        out["batched"] += r["batched"]
        out["fallback"] += r["fallback"]

    if pipeline is None:
        pipeline = pipeline_enabled()
    step = max_docs_per_launch or len(order) or 1
    if pipeline and len(order) > step:
        # double-buffered drain: chunk the WORK (not the stages) so each
        # chunk's host staging runs while the previous chunk's packed
        # kernel is in flight
        pending = None
        try:
            for lo in range(0, len(order), step):
                t0 = time.perf_counter()
                stages, n = _stage_chunk(order[lo : lo + step], lo)
                out["applied"] += n
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
        stages, n = _stage_chunk(order, 0)
        out["applied"] += n
        sstep = max_docs_per_launch or len(stages) or 1
        for lo in range(0, len(stages), sstep):
            _account(resolve_stages(stages[lo : lo + sstep], fallback_ratio))
    _prof.note("docs", len(order))
    _prof.note("changes", out["applied"])
    return out


# -- the serving-layer collector ---------------------------------------------


class _Submission:
    """One document's raw drained batches awaiting the leader-staged
    vectorized flush (host_batch mode): the submitter keeps holding its
    document lock while the flush leader stages every co-arriving
    document in one columnar pass."""

    __slots__ = ("dev", "batches", "trace", "applied", "error")

    def __init__(self, dev, batches, trace):
        self.dev = dev
        self.batches = batches
        self.trace = trace
        self.applied = 0
        self.error: Optional[BaseException] = None


class _Generation:
    __slots__ = ("stages", "subs", "done")

    def __init__(self):
        self.stages: List[BatchStage] = []  # scalar (submitter-staged)
        self.subs: List[_Submission] = []  # vectorized (leader-staged)
        self.done = threading.Event()


class CrossDocBatcher:
    """Group-commit collector for concurrent per-document workers.

    ``apply(dev, batches)`` stages the document's drained device feed
    (the caller MUST hold that document's execution lock) and blocks
    until a shared launch has resolved it. The first stager of a
    generation is the leader: it waits up to ``window_ms`` for
    co-arriving documents (waking early at ``max_docs``), closes the
    generation, and runs ``resolve_stages`` for everyone.

    ``mode``: "1" always batches, "0" never (callers fall back to
    ``apply_batches``), "auto" batches only on accelerator backends —
    on CPU the per-doc host delta-resolution path is faster than any
    kernel, packed or not.
    """

    def __init__(
        self,
        *,
        window_ms: Optional[float] = None,
        max_docs: Optional[int] = None,
        fallback_ratio: Optional[float] = None,
        mode: Optional[str] = None,
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
        self.mode = (
            mode
            if mode is not None
            else os.environ.get("AUTOMERGE_TPU_SERVE_BATCHED", "auto")
        )
        # generations at least this many docs wide flush as TWO
        # half-launches so the second half's pack/linearize runs under
        # the first half's in-flight kernel (the drain pipeline); small
        # generations keep the single launch — splitting them would
        # trade kernel occupancy for overlap that can't cover the cost
        self.pipeline_min_docs = int(
            _env_float("AUTOMERGE_TPU_PIPELINE_MIN_DOCS", 16)
        )
        self._cv = threading.Condition(threading.Lock())
        self._gen = _Generation()
        self._active: Optional[bool] = None

    def active(self) -> bool:
        """Whether device feeds should route through this batcher."""
        if self._active is None:
            if self.mode == "0":
                self._active = False
            elif self.mode == "auto":
                import jax

                self._active = jax.default_backend() != "cpu"
            else:
                self._active = True
        return self._active

    def apply(self, dev, batches) -> int:
        """Stage ``dev``'s drained batches and resolve them in the next
        shared launch; blocks until resolved. Returns changes applied.

        With the vectorized host staging active (the default,
        ``AUTOMERGE_TPU_HOST_BATCH``), the submitter hands its RAW
        batches over and the generation's flush leader stages every
        co-arriving document in one shared columnar pass
        (host_batch.stage_docs) before the shared kernel launch — the
        submitter keeps holding its document lock while it waits, so the
        single-writer discipline is unchanged. With the knob off, each
        submitter stages its own document (the scalar per-doc path) and
        only the launch is shared, exactly as before."""
        if not self.active():
            return dev.apply_batches(batches)
        from . import host_batch

        if host_batch.enabled():
            return self._apply_leader_staged(dev, batches)
        t0 = time.perf_counter()
        applied, stage = dev.stage_batches(batches)
        _prof.note("docs")
        _prof.note("changes", applied)
        _prof.note_doc(
            getattr(dev, "obs_name", None), time.perf_counter() - t0
        )
        if stage is None:
            return applied
        # attribute the (possibly other-thread) shared launch back to
        # this submitter's propagated trace, if one is active
        stage.trace = obs.current_trace_context()
        with self._cv:
            gen = self._gen
            gen.stages.append(stage)
            # leadership is elected over BOTH submission kinds: a
            # mid-generation AUTOMERGE_TPU_HOST_BATCH flip can mix
            # leader-staged subs and submitter-staged stages in one
            # generation, and exactly ONE leader must flush it
            leader = len(gen.stages) + len(gen.subs) == 1
            if not leader and len(gen.stages) + len(gen.subs) >= self.max_docs:
                self._cv.notify_all()  # wake the leader early
        if leader:
            self._lead(gen)
        else:
            gen.done.wait()
        if stage.error is not None:
            raise stage.error
        return applied

    def _apply_leader_staged(self, dev, batches) -> int:
        sub = _Submission(dev, list(batches), obs.current_trace_context())
        with self._cv:
            gen = self._gen
            gen.subs.append(sub)
            leader = len(gen.stages) + len(gen.subs) == 1
            if not leader and len(gen.stages) + len(gen.subs) >= self.max_docs:
                self._cv.notify_all()  # wake the leader early
        if leader:
            self._lead(gen)
        else:
            gen.done.wait()
        if sub.error is not None:
            raise sub.error
        return sub.applied

    def _lead(self, gen: _Generation) -> None:
        """The (single) flush leader: wait out the batch window for
        co-arriving documents, close the generation, flush it."""
        deadline = time.monotonic() + self.window
        with self._cv:
            while len(gen.stages) + len(gen.subs) < self.max_docs:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cv.wait(left)
            if self._gen is gen:  # close the generation we lead
                self._gen = _Generation()
        self._flush(gen)

    def _flush(self, gen: _Generation) -> None:
        """Close one generation: stage any leader-staged submissions in
        one vectorized pass (host_batch.stage_docs), merge them with any
        submitter-staged stages (the scalar-knob mode — an env-knob flip
        mid-generation can mix the two; both drain here), launch once,
        and release every waiter. A failure reaches every waiter."""
        from . import host_batch

        stages: List[BatchStage] = list(gen.stages)
        try:
            if gen.subs:
                more, results = host_batch.stage_docs(
                    [(s.dev, s.batches) for s in gen.subs]
                )
                trace_of = {}
                n_changes = 0
                for s in gen.subs:
                    r = results.get(id(s.dev))
                    if r is not None:
                        s.applied = r.applied
                        s.error = r.error
                        n_changes += r.applied
                    if s.trace is not None:
                        trace_of.setdefault(id(s.dev), s.trace)
                for st in more:
                    st.trace = trace_of.get(id(st.doc))
                _prof.note("docs", len(gen.subs))
                _prof.note("changes", n_changes)
                stages.extend(more)
            if (
                pipeline_enabled()
                and len(stages) >= self.pipeline_min_docs
            ):
                # wide generation: flush as two half-launches so the
                # second half's pack/linearize runs under the first
                # half's in-flight kernel (drain.overlap_fraction)
                mid = len(stages) // 2
                d1 = dispatch_stages(stages[:mid], self.fallback_ratio)
                try:
                    t0 = time.perf_counter()
                    d2 = dispatch_stages(
                        stages[mid:], self.fallback_ratio
                    )
                    _prof.note("overlap_s", time.perf_counter() - t0)
                except BaseException:
                    collect_stages(d1)
                    raise
                collect_stages(d1)
                collect_stages(d2)
            else:
                resolve_stages(stages, self.fallback_ratio)
        except BaseException as e:
            # every document of the generation sees the failure: its
            # changes were spliced but never resolved, so its caller must
            # not acknowledge the device work
            obs.count("device.batched_error")
            obs.event("device.batched_error", error=str(e)[:200])
            for st in stages:
                if st.error is None:
                    st.error = e
            for s in gen.subs:
                if s.error is None:
                    s.error = e
        finally:
            gen.done.set()
