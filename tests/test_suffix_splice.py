"""Differential tests for the suffix splice (``ops/oplog.RowSplice``).

A delta whose op ids land before the resident tail moves only the rows
after its first insertion point. Whatever the shape of the splice, every
OpLog column, pred edge and value must equal ``OpLog.from_changes`` over
the same changes, the compressed column image must decode to the live
columns, and every DeviceDoc array must equal a fresh ``DeviceDoc.resolve``.
Arrays an older reader still holds (a historical view, an in-flight
handle's row ids) keep their values.
"""

import numpy as np
import pytest

from automerge_tpu import obs
from automerge_tpu.api import AutoDoc
from automerge_tpu.obs.metrics import sanitize_metric_name
from automerge_tpu.ops import DeviceDoc, OpLog
from automerge_tpu.ops import compressed as C
from automerge_tpu.ops.oplog import RowSplice, _capacity
from automerge_tpu.types import ActorId, ObjType, ScalarValue

# byte order is rank order: the resident author sorts after every editor,
# so an editor's op concurrent with the author's sorts before it
AUTHOR = ActorId(bytes([200]) * 16)


def actor(i: int) -> ActorId:
    return ActorId(bytes([i]) * 16)


def stored(doc, seen=()):
    return [a.stored for a in doc.doc.history if a.stored.hash not in seen]


def hashes(chs):
    return {c.hash for c in chs}


class Case:
    """Resident changes, the delta appended in one splice, and what the
    splice should report (``mode``, ``moved``, ``p0``; None = not
    checked)."""

    def __init__(self, resident, delta, mode, moved=None, p0=None,
                 fast=None):
        self.resident = resident
        self.delta = delta
        self.mode = mode
        self.moved = moved
        self.p0 = p0
        self.fast = fast


def _text_author(n_chars):
    a = AutoDoc(actor=AUTHOR)
    t = a.put_object("_root", "t", ObjType.TEXT)
    a.splice_text(t, 0, 0, "".join(chr(97 + i % 26) for i in range(n_chars)))
    a.put("_root", "c", ScalarValue("counter", 1))
    a.commit()
    return a, t


def _known_editor(a, t, who):
    """An editor whose first change the author merged: its actor is in
    the resident log, so its later changes need no actor remap."""
    e = a.fork(actor=actor(who))
    e.splice_text(t, 0, 0, "#")
    e.commit()
    a.merge(e)
    return e


def text_before_tail(j):
    a, t = _text_author(300)
    e = _known_editor(a, t, 5)
    a.splice_text(t, a.length(t), 0, "z" * j)
    a.commit()
    e.splice_text(t, 10, 0, "Q")
    e.commit()
    res = stored(a)
    return Case(res, stored(e, hashes(res)), "suffix", moved=j)


def text_tail():
    a, t = _text_author(300)
    e = _known_editor(a, t, 5)
    e.merge(a)
    e.splice_text(t, 7, 1, "tail")
    e.increment("_root", "c", 3)
    e.commit()
    res = stored(a)
    return Case(res, stored(e, hashes(res)), "tail", moved=0)


def text_several_points():
    a, t = _text_author(300)
    e1 = _known_editor(a, t, 5)
    e2 = _known_editor(a, t, 6)
    e1.merge(a)
    a.splice_text(t, a.length(t), 0, "x" * 40)
    a.commit()
    e2.merge(a)
    a.splice_text(t, 3, 0, "y" * 40)
    a.increment("_root", "c", 2)
    a.commit()
    e1.splice_text(t, 50, 2, "one")
    e1.increment("_root", "c", 5)
    e1.commit()
    e2.splice_text(t, 100, 1, "two")
    e2.commit()
    res = stored(a)
    delta = stored(e1, hashes(res)) + stored(e2, hashes(res))
    return Case(res, delta, "suffix")


def text_row_zero():
    """A fresh actor that sorts first, editing from no history at all:
    its ops land before resident row 0, so every row moves."""
    a, t = _text_author(120)
    x = AutoDoc(actor=actor(1))
    x.put("_root", "early", "x")
    x.put("_root", "c", 7)
    x.commit()
    res = stored(a)
    return Case(res, stored(x), "remap", p0=0)


def text_mid_log():
    a, t = _text_author(200)
    e = _known_editor(a, t, 5)
    a.splice_text(t, 20, 3, "m" * 250)
    a.commit()
    e.splice_text(t, 30, 2, "MID")
    e.commit()
    res = stored(a)
    return Case(res, stored(e, hashes(res)), "suffix")


def text_new_actor_before_tail():
    a, t = _text_author(300)
    heads = a.get_heads()
    a.splice_text(t, a.length(t), 0, "w" * 30)
    a.commit()
    e = a.fork_at(heads, actor=actor(9))
    e.splice_text(t, 12, 1, "new")
    sub = e.put_object("_root", "m", ObjType.MAP)
    e.put(sub, "x", 2)
    e.commit()
    res = stored(a)
    return Case(res, stored(e, hashes(res)), "remap", moved=30)


def text_resolves_missing():
    """The resident log lacks a change its later change references (an
    element anchor and a pred): the delta brings it, before the tail."""
    a, t = _text_author(300)
    e = _known_editor(a, t, 5)
    base = stored(a)
    a.splice_text(t, a.length(t), 0, "v" * 20)
    a.commit()
    e.splice_text(t, 40, 0, "Y")
    e.commit()
    d1 = stored(e, hashes(base))
    e.splice_text(t, 41, 0, "Z")
    e.splice_text(t, 40, 1, "")
    e.commit()
    d2 = stored(e, hashes(base) | hashes(d1))
    return Case(stored(a) + d2, d1, "suffix", fast=False)


def text_crosses_bucket():
    a, t = _text_author(40)
    e = _known_editor(a, t, 5)
    a.splice_text(t, a.length(t), 0, "t" * 10)
    a.commit()
    e.splice_text(t, 5, 0, "c" * 30)
    e.commit()
    res = stored(a)
    return Case(res, stored(e, hashes(res)), "suffix", moved=10)


def map_doc():
    """Map keys overwritten concurrently: winners, conflicts and counter
    increments whose rows sit after the insertion point."""
    a = AutoDoc(actor=AUTHOR)
    for i in range(40):
        a.put("_root", f"k{i}", i)
    a.put("_root", "n", ScalarValue("counter", 10))
    a.commit()
    e = a.fork(actor=actor(5))
    e.put("_root", "seen", True)
    e.commit()
    a.merge(e)
    for i in range(0, 40, 3):
        a.put("_root", f"k{i}", f"a{i}")
    a.increment("_root", "n", 4)
    a.delete("_root", "k1")
    a.commit()
    for i in range(0, 40, 2):
        e.put("_root", f"k{i}", f"e{i}")
    e.increment("_root", "n", 9)
    e.delete("_root", "k5")
    e.commit()
    res = stored(a)
    return Case(res, stored(e, hashes(res)), "suffix")


CASES = {
    "tail": text_tail,
    "before_tail_1": lambda: text_before_tail(1),
    "before_tail_5": lambda: text_before_tail(5),
    "before_tail_200": lambda: text_before_tail(200),
    "several_points": text_several_points,
    "row_zero": text_row_zero,
    "mid_log": text_mid_log,
    "new_actor": text_new_actor_before_tail,
    "resolves_missing": text_resolves_missing,
    "crosses_bucket": text_crosses_bucket,
    "map_doc": map_doc,
}

LOG_FIELDS = (
    "id_key", "obj_key", "elem_key", "prop", "elem_ref", "action", "insert",
    "value_tag", "value_int", "width", "expand", "mark_name_idx",
    "obj_dense", "obj_table",
)
DEV_FIELDS = (
    "visible", "winner", "conflicts", "elem_index", "succ_count",
    "inc_count", "counter_val", "_rows_by_obj", "_obj_sorted",
)


def assert_log_matches(log, changes):
    full = OpLog.from_changes(changes, fast=False)
    assert log.n == full.n
    for f in LOG_FIELDS:
        assert np.array_equal(np.asarray(getattr(log, f)),
                              np.asarray(getattr(full, f))), f
    assert log.props == full.props
    assert log.mark_names == full.mark_names
    assert (log.n_miss_elem, log.n_miss_pred) == (full.n_miss_elem,
                                                  full.n_miss_pred)
    edges = sorted(zip(log.pred_src.tolist(), log.pred_tgt.tolist(),
                       log.pred_key.tolist()))
    assert edges == sorted(zip(full.pred_src.tolist(), full.pred_tgt.tolist(),
                               full.pred_key.tolist()))
    for i in range(log.n):
        a, b = log.values[i], full.values[i]
        assert (a.tag, a.value) == (b.tag, b.value), i
    comp = log.compressed(sync=True)
    if comp is not None:
        assert comp.verify_against(log) == []
        fresh = C.CompressedOpColumns().sync(log)
        for name, ent in comp.entries.items():
            if ent is not C._DENSE and fresh.entries[name] is not C._DENSE:
                assert np.array_equal(ent.decode(),
                                      fresh.entries[name].decode()), name


def assert_dev_matches(dev, changes):
    full = DeviceDoc.resolve(OpLog.from_changes(changes))
    m = full.log.n
    assert dev.log.n == m
    for f in DEV_FIELDS:
        assert np.array_equal(np.asarray(getattr(dev, f))[:m],
                              np.asarray(getattr(full, f))[:m]), f


def _splices():
    return (obs.counter_values("oplog.splices", "mode"),
            sum(obs.counter_values("oplog.splice_rows_moved", "").values()))


@pytest.fixture(params=["compressed", "dense"])
def residency(request, monkeypatch):
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED",
                       "1" if request.param == "compressed" else "0")
    return request.param


@pytest.mark.parametrize("name", list(CASES))
def test_oplog_splice_matches_from_changes(name, residency):
    case = CASES[name]()
    log = OpLog.from_changes(case.resident, fast=case.fast)
    log.compressed(sync=True)  # a live image, so its prefix is kept
    modes0, moved0 = _splices()
    info = log.append_changes(case.delta)
    modes1, moved1 = _splices()
    assert info is not None
    assert_log_matches(log, case.resident + case.delta)
    assert {k: v - modes0.get(k, 0) for k, v in modes1.items()
            if v != modes0.get(k, 0)} == {case.mode: 1}
    assert moved1 - moved0 == info.splice.n_moved
    if case.moved is not None:
        assert info.splice.n_moved == case.moved
    if case.p0 is not None:
        assert info.splice.p0 == case.p0
    assert info.tail == (case.mode == "tail")


@pytest.mark.parametrize("name", list(CASES))
def test_device_doc_splice_matches_resolve(name):
    case = CASES[name]()
    dev = DeviceDoc.resolve(OpLog.from_changes(case.resident, fast=case.fast))
    dev.apply_changes(case.delta)
    assert_dev_matches(dev, case.resident + case.delta)
    assert_log_matches(dev.log, case.resident + case.delta)


def test_crossing_a_bucket_grows_every_buffer():
    case = text_crosses_bucket()
    dev = DeviceDoc.resolve(OpLog.from_changes(case.resident))
    n_old = dev.log.n
    dev.apply_changes(case.delta)
    m = dev.log.n
    assert _capacity(n_old) < _capacity(m)
    assert len(dev.log._bufs["id_key"]) == _capacity(m)
    assert len(dev._res_bufs["visible"]) == _capacity(m)


def test_older_readers_keep_their_values():
    """A historical view taken before a non-tail splice, the arrays the
    base held, and an in-flight handle's row ids all read what they read
    before: the splice writes fresh buffers, never rows below n of one an
    older array views."""
    case = text_before_tail(5)
    dev = DeviceDoc.resolve(OpLog.from_changes(case.resident))
    log = dev.log
    heads = dev.current_heads()
    view = dev.at(heads)
    held = {f: getattr(view, f) for f in DEV_FIELDS[:4] + DEV_FIELDS[7:]}
    held.update({"base." + f: getattr(dev, f) for f in DEV_FIELDS})
    held.update({"log." + f: getattr(log, f) for f in LOG_FIELDS})
    held.update({"log.pred_src": log.pred_src, "log.pred_tgt": log.pred_tgt})
    before = {k: np.array(v, copy=True) for k, v in held.items()}
    old_ids = np.array(log.id_key, copy=True)
    rows = np.arange(log.n, dtype=np.int64)

    info = log.append_changes(case.delta)
    assert not info.tail and info.splice.n_moved == 5
    dev._apply_append(info, case.delta)
    for k, v in held.items():
        assert np.array_equal(v, before[k]), k
    # rows taken before the splice, through it, name the same ops
    moved = info.splice.map(rows)
    assert np.array_equal(np.asarray(log.id_key)[moved], old_ids)
    refs = np.array([-3, -2, -1], np.int64)
    assert np.array_equal(info.splice.map(refs), refs)


def test_row_splice_moves_only_the_suffix():
    rs = RowSplice(10, np.array([7, 7, 9], np.int64))
    assert (rs.p0, rs.n_moved, rs.tail) == (7, 3, False)
    assert rs.new_rows.tolist() == [7, 8, 11]
    assert rs.moved.tolist() == [9, 10, 12]
    out = np.empty(13, np.int64)
    rs.into(out, np.arange(10) * 10, np.array([-1, -2, -3]))
    assert out.tolist() == [0, 10, 20, 30, 40, 50, 60, -1, -2, 70, 80, -3, 90]
    assert rs.map(np.array([-1, 6, 7, 9])).tolist() == [-1, 6, 9, 12]
    tail = RowSplice.append(4, 2)
    assert (tail.tail, tail.n_moved, tail.new_rows.tolist()) == (True, 0,
                                                                 [4, 5])


def test_splice_counters_count_rows_moved():
    """A splice 5 rows before the tail of a 10,000-row log moves 5 rows;
    each row-carrying splice counts once under its mode, and both counters
    reach the Prometheus answer."""
    a, t = _text_author(9_995)
    e = _known_editor(a, t, 5)
    a.splice_text(t, a.length(t), 0, "12345")
    a.commit()
    e.splice_text(t, 100, 0, "Q")
    e.commit()
    res = stored(a)
    log = OpLog.from_changes(res)
    assert log.n >= 10_000
    modes0, moved0 = _splices()
    info = log.append_changes(stored(e, hashes(res)))
    modes1, moved1 = _splices()
    assert modes1["suffix"] - modes0.get("suffix", 0) == 1
    assert moved1 - moved0 == info.splice.n_moved <= 5
    e.merge(a)
    e.splice_text(t, e.length(t), 0, "end")
    e.commit()
    log.append_changes(stored(e, log.hashes()))
    modes2, moved2 = _splices()
    assert modes2["tail"] - modes1.get("tail", 0) == 1
    assert moved2 == moved1
    text = obs.render_prometheus()
    for name in ("oplog.splices", "oplog.splice_rows_moved"):
        assert sanitize_metric_name(name) in text, name
