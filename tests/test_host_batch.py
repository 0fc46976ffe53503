"""Differential suite for the vectorized cross-document host staging
(ops/host_batch.py).

The scalar per-doc ``DeviceDoc.stage_batches`` path is the oracle: for
random interleavings x mixed doc sizes x out-of-order/duplicate
delivery, staging the same deltas through ``host_batch.stage_docs`` (+
the shared packed launch) must leave every document in a bit-identical
state — column-level OpLog equality, identical resolution arrays and
host caches, identical materialized documents including ``at(heads)``
views. Fallback routes (empty logs, non-tail splices) are exercised
and asserted non-vacuous.
"""

import os
import random
import threading

import numpy as np
import pytest

from automerge_tpu import obs
from automerge_tpu.api import AutoDoc
from automerge_tpu.ops import host_batch
from automerge_tpu.ops.batched import CrossDocBatcher, resolve_stages
from automerge_tpu.ops.device_doc import DeviceDoc
from automerge_tpu.ops.oplog import OpLog
from automerge_tpu.types import ActorId, ObjType, ScalarValue

LOG_COLS = (
    "id_key", "obj_key", "elem_key", "action", "prop", "insert",
    "value_tag", "value_int", "width", "expand", "mark_name_idx",
    "elem_ref", "obj_dense", "pred_src", "pred_tgt", "pred_key",
    "obj_table",
)
DEV_COLS = (
    "visible", "winner", "conflicts", "elem_index", "succ_count",
    "inc_count", "counter_val",
)


def assert_identical(vec: DeviceDoc, sca: DeviceDoc, tag=""):
    """Column-level OpLog equality + full DeviceDoc state equality."""
    a, b = vec.log, sca.log
    assert a.n == b.n and a.n_objs == b.n_objs, tag
    assert [x.bytes for x in a.actors] == [x.bytes for x in b.actors], tag
    assert a.props == b.props and a.mark_names == b.mark_names, tag
    assert a.n_miss_elem == b.n_miss_elem, tag
    assert a.n_miss_pred == b.n_miss_pred, tag
    for c in LOG_COLS:
        va, vb = np.asarray(getattr(a, c)), np.asarray(getattr(b, c))
        assert va.shape == vb.shape and np.array_equal(va, vb), (tag, c)
    for row in range(a.n):
        assert a.values[row].tag == b.values[row].tag, (tag, row)
        assert a.values[row].value == b.values[row].value, (tag, row)
    for c in DEV_COLS:
        va = np.asarray(getattr(vec, c))
        vb = np.asarray(getattr(sca, c))
        assert np.array_equal(va, vb), (tag, c)
    for c in ("obj_vis_len", "obj_text_width"):
        assert np.array_equal(vec.res[c], sca.res[c]), (tag, c)
    assert np.array_equal(vec._rows_by_obj, sca._rows_by_obj), tag
    assert np.array_equal(vec._obj_sorted, sca._obj_sorted), tag
    assert sorted(vec._obj_type.items()) == sorted(sca._obj_type.items()), tag
    assert vec.hydrate() == sca.hydrate(), tag


def build_workload(seed, n_docs=5, cycles=4, dup=True, shuffle=True,
                   ballast=0):
    """Mixed-size docs, two editors each (one ranked below / one above
    the base actor), text edits + counters + marks + new objects/props +
    deletes; per-cycle deltas optionally shuffled and re-delivered.
    ``ballast`` adds an untouched archive object so drained deltas stay
    on the dirty-subset (pack-eligible) path."""
    rng = random.Random(seed)
    docs, deltas = [], []
    for i in range(n_docs):
        base = AutoDoc(actor=ActorId(bytes([20]) * 16))
        t = base.put_object("_root", "t", ObjType.TEXT)
        base.splice_text(t, 0, 0, "seed text " * (i + 1))
        base.put("_root", "ctr", ScalarValue("counter", 0))
        if ballast:
            arch = base.put_object("_root", "archive", ObjType.TEXT)
            base.splice_text(arch, 0, 0, "x" * ballast)
        base.commit()
        e1 = base.fork(actor=ActorId(bytes([3 + i]) + bytes(15)))
        e2 = base.fork(actor=ActorId(bytes([190 - i]) + bytes(15)))
        seen = {a.stored.hash for a in base.doc.history}
        cyc = []
        for c in range(cycles):
            for j in range(2 + i):
                e1.splice_text(t, (c + j) % 5, 0, "A")
                e2.splice_text(t, (c + j) % 3, 0, "B")
            e1.increment("_root", "ctr", 1)
            if c == 1:
                e2.mark(t, 1, 4, "em", True)
                e2.put_object("_root", f"obj{i}", ObjType.LIST)
                e1.put("_root", f"key{i}", "v")
            if c == 2:
                e1.delete("_root", f"key{i}")
            e1.commit()
            e2.commit()
            e1.merge(e2)
            e2.merge(e1)
            d = [a.stored for a in e1.doc.history
                 if a.stored.hash not in seen]
            seen.update(x.hash for x in d)
            if shuffle:
                rng.shuffle(d)
            if dup and d and rng.random() < 0.5:
                d = d + rng.sample(d, 1)  # duplicate delivery
            cyc.append(d)
        docs.append(base)
        deltas.append(cyc)
    return docs, deltas


def drive_pair(docs, deltas, cycles):
    """One vectorized and one scalar replica set over the same deltas;
    returns (vec_devs, sca_devs, vectorized_count)."""
    vec = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    sca = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    n_vec = 0
    for c in range(cycles):
        stages, results = host_batch.stage_docs(
            [(vec[i], [deltas[i][c]]) for i in range(len(docs))]
        )
        for r in results.values():
            assert r.error is None, repr(r.error)
            n_vec += bool(r.vectorized)
        if stages:
            resolve_stages(stages)
        for i in range(len(docs)):
            _, st = sca[i].stage_batches([deltas[i][c]])
            if st is not None:
                resolve_stages([st])
        for i in range(len(docs)):
            assert_identical(vec[i], sca[i], (c, i))
    return vec, sca, n_vec


@pytest.mark.parametrize("seed", [1, 7, 42])
def test_differential_random_interleavings(seed):
    docs, deltas = build_workload(seed)
    vec, sca, n_vec = drive_pair(docs, deltas, 4)
    # non-vacuous: the vectorized path actually handled (most) cycles —
    # including cycle 0, where both editors' actors are NEW to the
    # resident log (the monotone rank-remap path)
    assert n_vec >= len(docs) * 3, n_vec
    # historical views agree (element order + clock-masked visibility)
    for i in (0, len(docs) - 1):
        heads = vec[i].current_heads()
        assert vec[i].at(heads).hydrate() == sca[i].at(heads).hydrate()
        assert vec[i].at([]).hydrate() == sca[i].at([]).hydrate()


def test_apply_batches_matches_the_scalar_stage_ready():
    """``apply_batches`` is a generation of one through the vectorized
    staging and the shared route; the scalar ``stage_ready``, called
    directly, is its oracle."""
    docs, deltas = build_workload(5, n_docs=3, cycles=2)
    gen = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    sca = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    n0 = obs.counter_values("host_batch.docs", "").get("", 0)
    for c in range(2):
        for i in range(len(docs)):
            applied = gen[i].apply_batches([deltas[i][c]])
            n, st = sca[i].stage_ready(sca[i]._take_ready(deltas[i][c]))
            assert applied == n
            if st is not None:
                resolve_stages([st])
            assert_identical(gen[i], sca[i], (c, i))
    # non-vacuous: apply_batches staged through the vectorized pass
    assert obs.counter_values("host_batch.docs", "").get("", 0) > n0


def test_out_of_order_delivery_buffers_pending():
    docs, deltas = build_workload(9, n_docs=3, cycles=3, dup=False,
                                  shuffle=False)
    # deliver cycle 1 BEFORE cycle 0: the dependency gap buffers cycle 1
    # in _pending, cycle 0's arrival releases both
    vec = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    sca = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    work = [(vec[i], [deltas[i][1]]) for i in range(3)]
    stages, results = host_batch.stage_docs(work)
    if stages:
        resolve_stages(stages)
    assert all(vec[i].pending_changes() > 0 for i in range(3))
    stages, results = host_batch.stage_docs(
        [(vec[i], [deltas[i][0]]) for i in range(3)]
    )
    for r in results.values():
        assert r.error is None
    if stages:
        resolve_stages(stages)
    for i in range(3):
        sca[i].stage_batches([deltas[i][1]])
        _, st = sca[i].stage_batches([deltas[i][0]])
        if st is not None:
            resolve_stages([st])
        assert vec[i].pending_changes() == sca[i].pending_changes() == 0
        assert_identical(vec[i], sca[i], i)


def test_empty_log_doc_falls_back_and_matches():
    # a device doc opened before any history exists (empty resident log)
    # must route scalar (initial build) and still match
    base = AutoDoc(actor=ActorId(bytes([20]) * 16))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "hello")
    base.commit()
    chs = [a.stored for a in base.doc.history]
    vec = DeviceDoc.resolve(OpLog.from_changes([]))
    sca = DeviceDoc.resolve(OpLog.from_changes([]))
    stages, results = host_batch.stage_docs([(vec, [chs])])
    assert not any(r.vectorized for r in results.values())
    for r in results.values():
        assert r.error is None and r.applied == len(chs)
    if stages:
        resolve_stages(stages)
    _, st = sca.stage_batches([chs])
    if st is not None:
        resolve_stages([st])
    assert_identical(vec, sca)


def test_non_tail_delivery_demotes_to_scalar():
    """A delta whose Lamport ids sit BELOW the resident maximum (a slow
    replica's old edits arriving late) must demote to the scalar splice
    — counted — and still converge bit-identically."""
    base = AutoDoc(actor=ActorId(bytes([20]) * 16))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "base ")
    base.commit()
    slow = base.fork(actor=ActorId(bytes([9]) + bytes(15)))
    slow.splice_text(t, 0, 0, "S")
    slow.commit()
    slow_delta = [a.stored for a in slow.doc.history
                  if a.stored.hash not in
                  {x.stored.hash for x in base.doc.history}]
    fast = base.fork(actor=ActorId(bytes([80]) + bytes(15)))
    for c in range(3):
        fast.splice_text(t, c, 0, "F" * 4)
        fast.commit()
    fast_deltas = [a.stored for a in fast.doc.history
                   if a.stored.hash not in
                   {x.stored.hash for x in base.doc.history}]

    vec = DeviceDoc.resolve(OpLog.from_documents([base]))
    sca = DeviceDoc.resolve(OpLog.from_documents([base]))
    # integrate the fast editor first: resident max Lamport id grows
    stages, _ = host_batch.stage_docs([(vec, [fast_deltas])])
    if stages:
        resolve_stages(stages)
    _, st = sca.stage_batches([fast_deltas])
    if st is not None:
        resolve_stages([st])
    before = obs.counter_values(
        "host_batch.fallback_docs", "reason").get("order", 0)
    # the slow replica's delta: counters below the resident max -> the
    # splice would be mid-array, not a tail append
    stages, results = host_batch.stage_docs([(vec, [slow_delta])])
    for r in results.values():
        assert r.error is None
    if stages:
        resolve_stages(stages)
    after = obs.counter_values(
        "host_batch.fallback_docs", "reason").get("order", 0)
    assert after == before + 1, (before, after)
    _, st = sca.stage_batches([slow_delta])
    if st is not None:
        resolve_stages([st])
    assert_identical(vec, sca)


def test_cross_doc_batcher_leader_staged():
    """Concurrent submitters hand RAW batches to the flush leader, which
    stages every co-arriving document in one vectorized pass before one
    shared launch — results identical to the scalar reference."""
    docs, deltas = build_workload(13, n_docs=4, cycles=2, dup=False,
                                  ballast=400)
    vec = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    sca = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    batcher = CrossDocBatcher(window_ms=200.0, max_docs=4)
    for c in range(2):
        launches0 = obs.counter_values(
            "device.kernel_launches", "path").get("batched", 0)
        applied = {}
        errors = []

        def worker(i, c=c):
            try:
                applied[i] = batcher.apply(vec[i], [deltas[i][c]])
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for th in ts:
            th.start()
        for th in ts:
            th.join(30)
        assert not errors, errors
        assert all(applied[i] > 0 for i in range(4)), applied
        launches1 = obs.counter_values(
            "device.kernel_launches", "path").get("batched", 0)
        # all four co-arriving docs shared ONE packed launch
        assert launches1 - launches0 == 1, (launches0, launches1)
        for i in range(4):
            _, st = sca[i].stage_batches([deltas[i][c]])
            if st is not None:
                resolve_stages([st])
            assert_identical(vec[i], sca[i], (c, i))


def test_duplicate_doc_entries_merge_into_one_staging():
    docs, deltas = build_workload(21, n_docs=2, cycles=2, dup=False)
    vec = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    sca = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    # the same doc twice in one work list: both cycles must merge into
    # ONE staging (a second append would invalidate stage row indices)
    work = [(vec[0], [deltas[0][0]]), (vec[1], [deltas[1][0]]),
            (vec[0], [deltas[0][1]])]
    stages, results = host_batch.stage_docs(work)
    for r in results.values():
        assert r.error is None
    if stages:
        resolve_stages(stages)
    _, st = sca[0].stage_batches([deltas[0][0], deltas[0][1]])
    if st is not None:
        resolve_stages([st])
    _, st = sca[1].stage_batches([deltas[1][0]])
    if st is not None:
        resolve_stages([st])
    assert_identical(vec[0], sca[0], 0)
    assert_identical(vec[1], sca[1], 1)


def test_stage_docs_rejects_historical_views():
    base = AutoDoc(actor=ActorId(bytes([20]) * 16))
    base.put("_root", "x", 1)
    base.commit()
    dev = DeviceDoc.resolve(OpLog.from_documents([base]))
    view = dev.at(dev.current_heads())
    with pytest.raises(ValueError):
        host_batch.stage_docs([(view, [[]])])
