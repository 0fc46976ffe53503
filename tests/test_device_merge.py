"""Device merge kernel vs host document: state equivalence.

The kernel (ops/merge.py) must resolve exactly the state the host op store
reaches by sequential application — same winners, same RGA order, same
counter totals, same conflict sets — for any interleaving of replicas.
Mirrors the reference's merge/conflict integration tests
(reference: rust/automerge/tests/test.rs).
"""

import random

import pytest

from automerge_tpu.api import AutoDoc
from automerge_tpu.ops import DeviceDoc, OpLog
from automerge_tpu.types import ActorId, ObjType, ScalarValue


def actor(i: int) -> ActorId:
    return ActorId(bytes([i]) * 16)


def host_merge(docs):
    """Sequential host merge of all docs into a fresh doc."""
    out = AutoDoc(actor=actor(250))
    for d in docs:
        out.merge(d)
    return out


def assert_equiv(docs):
    host = host_merge(docs)
    dev = DeviceDoc.merge(docs)
    assert dev.hydrate() == host.hydrate()
    return host, dev


def test_single_doc_map():
    d = AutoDoc(actor=actor(1))
    d.put("_root", "a", 1)
    d.put("_root", "b", "x")
    d.put("_root", "a", 2)
    d.commit()
    host, dev = assert_equiv([d])
    assert dev.keys() == ["a", "b"]
    assert dev.get("_root", "a")[0] == ("scalar", ScalarValue("int", 2))


def test_concurrent_map_conflict_winner():
    base = AutoDoc(actor=actor(1))
    base.put("_root", "k", "base")
    base.commit()
    d1 = base.fork(actor=actor(2))
    d2 = base.fork(actor=actor(3))
    d1.put("_root", "k", "one")
    d1.commit()
    d2.put("_root", "k", "two")
    d2.commit()
    host, dev = assert_equiv([d1, d2])
    # conflict: both visible, winner = higher lamport (same ctr, actor 3)
    assert len(dev.get_all("_root", "k")) == 2
    assert dev.get("_root", "k")[0] == ("scalar", ScalarValue("str", "two"))


def test_text_concurrent_splices():
    base = AutoDoc(actor=actor(1))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "hello world")
    base.commit()
    d1 = base.fork(actor=actor(2))
    d2 = base.fork(actor=actor(3))
    d1.splice_text(t, 5, 0, " brave")
    d1.commit()
    d2.splice_text(t, 0, 5, "goodbye")
    d2.commit()
    host, dev = assert_equiv([d1, d2])
    assert dev.text(t) == host.text(t)
    assert dev.length(t) == host.length(t)


def test_list_insert_delete_interleave():
    base = AutoDoc(actor=actor(1))
    lst = base.put_object("_root", "l", ObjType.LIST)
    for i in range(5):
        base.insert(lst, i, i)
    base.commit()
    d1 = base.fork(actor=actor(2))
    d2 = base.fork(actor=actor(3))
    d1.insert(lst, 2, "a")
    d1.delete(lst, 0)
    d1.commit()
    d2.insert(lst, 2, "b")
    d2.delete(lst, 4)
    d2.commit()
    assert_equiv([d1, d2])


def test_counter_concurrent_increments():
    base = AutoDoc(actor=actor(1))
    base.put("_root", "c", ScalarValue("counter", 10))
    base.commit()
    forks = [base.fork(actor=actor(10 + i)) for i in range(4)]
    for i, f in enumerate(forks):
        for _ in range(i + 1):
            f.increment("_root", "c", 2)
        f.commit()
    host, dev = assert_equiv(forks)
    assert dev.get("_root", "c")[0] == ("counter", 10 + 2 * (1 + 2 + 3 + 4))


def test_nested_objects():
    d = AutoDoc(actor=actor(1))
    m = d.put_object("_root", "config", ObjType.MAP)
    d.put(m, "x", 1)
    lst = d.put_object(m, "items", ObjType.LIST)
    d.insert(lst, 0, "i0")
    inner = d.insert_object(lst, 1, ObjType.MAP)
    d.put(inner, "deep", True)
    t = d.put_object("_root", "note", ObjType.TEXT)
    d.splice_text(t, 0, 0, "hi")
    d.commit()
    host, dev = assert_equiv([d])
    assert dev.hydrate() == {
        "config": {"x": 1, "items": ["i0", {"deep": True}]},
        "note": "hi",
    }


def test_delete_map_key():
    d = AutoDoc(actor=actor(1))
    d.put("_root", "gone", 1)
    d.put("_root", "kept", 2)
    d.delete("_root", "gone")
    d.commit()
    host, dev = assert_equiv([d])
    assert dev.keys() == ["kept"]
    assert dev.get("_root", "gone") is None


def test_overwrite_list_element():
    d = AutoDoc(actor=actor(1))
    lst = d.put_object("_root", "l", ObjType.LIST)
    d.insert(lst, 0, "a")
    d.insert(lst, 1, "b")
    d.commit()
    d2 = d.fork(actor=actor(2))
    d2.put(lst, 0, "A")
    d2.commit()
    d.put(lst, 0, "α")
    d.commit()
    assert_equiv([d, d2])


def test_concurrent_inserts_same_position():
    """RGA convergence: same-position inserts order by descending op id."""
    base = AutoDoc(actor=actor(1))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "ab")
    base.commit()
    forks = [base.fork(actor=actor(2 + i)) for i in range(3)]
    for i, f in enumerate(forks):
        f.splice_text(t, 1, 0, f"<{i}>")
        f.commit()
    host, dev = assert_equiv(forks)
    assert dev.text(t) == host.text(t)


@pytest.mark.parametrize("n_forks,n_edits,seed", [(4, 20, 0), (8, 40, 1)])
def test_random_text_fuzz(n_forks, n_edits, seed):
    rng = random.Random(seed)
    base = AutoDoc(actor=actor(1))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "the quick brown fox jumps over the lazy dog")
    base.commit()
    forks = [base.fork(actor=actor(50 + i)) for i in range(n_forks)]
    for fi, f in enumerate(forks):
        for _ in range(n_edits):
            ln = f.length(t)
            if rng.random() < 0.6 or ln == 0:
                pos = rng.randrange(ln + 1)
                f.splice_text(t, pos, 0, rng.choice("abcxyz"))
            else:
                pos = rng.randrange(ln)
                f.splice_text(t, pos, 1, "")
        f.commit()
    host, dev = assert_equiv(forks)
    assert dev.text(t) == host.text(t)


@pytest.mark.parametrize("seed", [0, 7])
def test_random_mixed_fuzz(seed):
    rng = random.Random(seed)
    base = AutoDoc(actor=actor(1))
    lst = base.put_object("_root", "list", ObjType.LIST)
    base.put("_root", "n", ScalarValue("counter", 0))
    for i in range(3):
        base.insert(lst, i, i)
    base.commit()
    forks = [base.fork(actor=actor(60 + i)) for i in range(5)]
    keys = ["a", "b", "c"]
    for f in forks:
        for _ in range(15):
            r = rng.random()
            if r < 0.3:
                f.put("_root", rng.choice(keys), rng.randrange(100))
            elif r < 0.5:
                f.increment("_root", "n", rng.randrange(1, 5))
            elif r < 0.75:
                ln = f.length(lst)
                f.insert(lst, rng.randrange(ln + 1), rng.randrange(100))
            else:
                ln = f.length(lst)
                if ln:
                    f.delete(lst, rng.randrange(ln))
        f.commit()
    assert_equiv(forks)


def test_merge_transitive_chain():
    """Merging partially-merged replicas dedups shared changes by hash."""
    a = AutoDoc(actor=actor(1))
    a.put("_root", "x", 1)
    a.commit()
    b = a.fork(actor=actor(2))
    b.put("_root", "y", 2)
    b.commit()
    c = b.fork(actor=actor(3))
    c.put("_root", "z", 3)
    c.commit()
    log = OpLog.from_documents([a, b, c])
    assert len(log.changes) == 3  # shared history deduped
    assert_equiv([a, b, c])


def test_empty_doc():
    d = AutoDoc(actor=actor(1))
    dev = DeviceDoc.merge([d])
    assert dev.hydrate() == {}


def test_device_get_all_width_aware_text():
    """Integer indexing on TEXT is by character position (host nth parity)."""
    doc = AutoDoc(ActorId(bytes([1]) * 16))
    t = doc.put_object("_root", "t", ObjType.TEXT)
    doc.splice_text(t, 0, 0, "ab")
    doc.splice(t, 1, 0, ["XYZ"])  # "a XYZ b": widths 1,3,1
    doc.commit()
    dd = DeviceDoc.merge([doc])
    assert dd.text(t) == "aXYZb"
    for pos, want in [(0, "a"), (1, "XYZ"), (2, "XYZ"), (3, "XYZ"), (4, "b")]:
        got = dd.get_all(t, pos)
        host = doc.get_all(t, pos)
        assert got and got[-1][0] == ("scalar", ("str", want)), (pos, got)
        assert [v for v, _ in got] == [v for v, _ in host], pos
    assert dd.get_all(t, 5) == []


def test_packed_transport_matches_dict():
    """The byte-minimizing packed transport (slope-RLE runs in, one
    bit-packed vector out — ops/merge.py "packed transport") resolves
    identically to the per-array dict path on a mixed workload."""
    import numpy as np

    from automerge_tpu.ops.merge import conflict_flags, merge_columns

    base = AutoDoc(actor=actor(1))
    t = base.put_object("_root", "text", ObjType.TEXT)
    base.splice_text(t, 0, 0, "packed transport base text")
    base.put("_root", "count", ScalarValue("counter", 5))
    lst = base.put_object("_root", "lst", ObjType.LIST)
    base.insert(lst, 0, 1)
    base.commit()
    forks = [base.fork(actor=actor(10 + i)) for i in range(4)]
    for i, f in enumerate(forks):
        f.splice_text(t, i * 3, 1 if i % 2 else 0, f"[{i}]")
        f.increment("_root", "count", i + 1)
        f.put("_root", "k", i)
        f.insert(lst, 0, 10 + i)
        f.commit()

    log = OpLog.from_documents(forks)
    cols = log.padded_columns()
    r1 = merge_columns(cols, linearize="native", fetch=DeviceDoc.READ_FETCH,
                       n_objs=log.n_objs)
    r2 = merge_columns(cols, fetch=DeviceDoc.READ_FETCH, n_objs=log.n_objs)
    n = log.n
    assert np.array_equal(r1["visible"][:n], r2["visible"][:n])
    assert np.array_equal(r1["winner"][:n], r2["winner"][:n])
    assert np.array_equal(r1["elem_index"][:n], r2["elem_index"][:n])
    # conflicts travels as a flag (merge.conflict_flags)
    assert np.array_equal(
        conflict_flags(r1["conflicts"][:n]), np.asarray(r2["conflicts"][:n])
    )
    for k in ("obj_vis_len", "obj_text_width"):
        m = min(len(r1[k]), len(r2[k]))
        assert np.array_equal(np.asarray(r1[k][:m]), np.asarray(r2[k][:m])), k

    # and the full DeviceDoc read surface agrees with the host merge
    dev = DeviceDoc(log, r2)
    assert dev.hydrate() == host_merge(forks).hydrate()
