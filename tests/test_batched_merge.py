"""Differential tests for the cross-document batched device merge.

A padded multi-document super-batch (ops/batched.py) must materialize
every document BIT-IDENTICALLY to serial per-doc ``apply_changes`` —
same resolution arrays, same reads, same historical views — across
random interleavings, mixed document sizes, out-of-order delivery,
duplicate re-delivery, empty deltas, and the fallback-ratio boundary.
Plus: the group-commit batcher under real threads, and the whale-doc
mesh residency mode degrading cleanly when no multi-device mesh
exists.
"""

import random
import threading

import numpy as np
import pytest

from automerge_tpu import obs
from automerge_tpu.api import AutoDoc
from automerge_tpu.ops import DeviceDoc, OpLog
from automerge_tpu.ops.batched import (
    BatchStage,
    CrossDocBatcher,
    apply_cross_doc,
    plan_stages,
)
from automerge_tpu.types import ActorId, ObjType, ScalarValue


def actor(i: int) -> ActorId:
    return ActorId(bytes([i]) * 16)


def build_base(ballast: int = 300):
    """A doc with a live text + list + counter and an untouched ballast
    object (keeps delta dirty fractions below the per-doc full-reresolve
    cost model, the serve-shaped profile)."""
    base = AutoDoc(actor=actor(1))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "the quick brown fox")
    lst = base.put_object("_root", "l", ObjType.LIST)
    for i in range(5):
        base.insert(lst, i, i * 10)
    base.put("_root", "c", ScalarValue("counter", 5))
    if ballast:
        arch = base.put_object("_root", "archive", ObjType.TEXT)
        base.splice_text(arch, 0, 0, "x" * ballast)
    base.commit()
    return base, t, lst


def edit_fork(f, t, lst, rng, tag):
    ln = f.length(t)
    pos = rng.randrange(0, max(ln, 1))
    if rng.random() < 0.3 and ln > 1:
        f.splice_text(t, min(pos, ln - 1), 1, "")
    else:
        f.splice_text(t, pos, 0, f"<{tag}>")
    r = rng.random()
    if r < 0.3:
        f.increment("_root", "c", rng.randrange(1, 5))
    elif r < 0.6:
        f.put("_root", f"k{rng.randrange(3)}", tag)
    elif f.length(lst):
        if rng.random() < 0.5:
            f.insert(lst, rng.randrange(0, f.length(lst) + 1), tag)
        else:
            f.delete(lst, rng.randrange(0, f.length(lst)))
    f.commit()


def assert_bit_identical(dev, ref, ctx=""):
    assert dev.hydrate() == ref.hydrate(), ctx
    assert sorted(dev.current_heads()) == sorted(ref.current_heads()), ctx
    for a in ("visible", "winner", "conflicts", "elem_index"):
        assert np.array_equal(getattr(dev, a), getattr(ref, a)), (ctx, a)
    n2 = ref.log.n_objs + 2
    assert np.array_equal(
        dev.res["obj_vis_len"][:n2], ref.res["obj_vis_len"][:n2]
    ), ctx
    assert np.array_equal(
        dev.res["obj_text_width"][:n2], ref.res["obj_text_width"][:n2]
    ), ctx


def launch_counts():
    return obs.counter_values("device.kernel_launches", "path")


@pytest.mark.parametrize("seed", [0, 3, 11])
def test_random_interleavings_match_serial_per_doc(seed):
    """N docs of mixed sizes drained over several cycles: the cross-doc
    batch materializes exactly what serial per-doc apply_changes does,
    under shuffled, split, duplicated and dependency-gapped delivery."""
    rng = random.Random(seed)
    n_docs = 4
    docs = []
    for i in range(n_docs):
        # mixed sizes, including one tiny doc with NO ballast (its deltas
        # trip the per-doc full-reresolve fallback inside stage_batches)
        base, t, lst = build_base(ballast=0 if i == 0 else 150 * i)
        chs = [a.stored for a in base.doc.history]
        batched = DeviceDoc.resolve(OpLog.from_changes(chs))
        serial = DeviceDoc.resolve(OpLog.from_changes(chs))
        forks = [base.fork(actor=actor(20 + 4 * i + j)) for j in range(2)]
        docs.append({
            "base": base, "t": t, "lst": lst, "batched": batched,
            "serial": serial, "forks": forks,
            "seen": {c.hash for c in chs},
        })
    for cycle in range(4):
        work = []
        serial_feed = []
        for i, d in enumerate(docs):
            if rng.random() < 0.2:
                work.append((d["batched"], []))  # empty drain for this doc
                serial_feed.append((d["serial"], []))
                continue
            f = d["forks"][rng.randrange(len(d["forks"]))]
            edit_fork(f, d["t"], d["lst"], rng, f"{seed}.{cycle}.{i}")
            delta = [
                a.stored for a in f.doc.history
                if a.stored.hash not in d["seen"]
            ]
            d["seen"].update(c.hash for c in delta)
            rng.shuffle(delta)  # out-of-order: deps may arrive late
            batches = []
            while delta:
                k = rng.randrange(1, len(delta) + 1)
                b = delta[:k]
                delta = delta[k:]
                if b and rng.random() < 0.3:
                    b = b + [b[0]]  # duplicate re-delivery
                batches.append(b)
            work.append((d["batched"], batches))
            serial_feed.append((d["serial"], batches))
            # forks converge through the host doc so later edits merge
            d["base"].apply_changes(
                [a.stored for a in f.doc.history if a.stored.hash is not None]
            )
            for g in d["forks"]:
                g.merge(d["base"])
        apply_cross_doc(work)
        for dev, batches in serial_feed:
            for b in batches:
                dev.apply_changes(b)
        for i, d in enumerate(docs):
            assert d["batched"].pending_changes() == d["serial"].pending_changes()
            assert_bit_identical(
                d["batched"], d["serial"], f"seed {seed} cycle {cycle} doc {i}"
            )
    # historical views ride the same resolution arrays
    for d in docs:
        heads = d["batched"].current_heads()
        assert d["batched"].at(heads).hydrate() == d["serial"].at(heads).hydrate()


def _doc_with_delta(i, ballast=300, edits=1):
    base, t, lst = build_base(ballast=ballast)
    chs = [a.stored for a in base.doc.history]
    f = base.fork(actor=actor(10 + i))
    for j in range(edits):
        f.splice_text(t, (i + j) % max(f.length(t), 1), 0, f"<{i}.{j}>")
    f.commit()
    have = {c.hash for c in chs}
    delta = [a.stored for a in f.doc.history if a.stored.hash not in have]
    return chs, delta


def test_mixed_sizes_share_one_launch():
    """Docs of very different (non-whale) sizes pack into ONE launch."""
    work, serial = [], []
    for i, (ballast, edits) in enumerate([(150, 1), (400, 2), (800, 3)]):
        chs, delta = _doc_with_delta(i, ballast=ballast, edits=edits)
        work.append((DeviceDoc.resolve(OpLog.from_changes(chs)), [delta]))
        s = DeviceDoc.resolve(OpLog.from_changes(chs))
        s.apply_changes(delta)
        serial.append(s)
    before = launch_counts()
    out = apply_cross_doc(work)
    after = launch_counts()
    assert out["batched"] == 3 and out["fallback"] == 0, out
    assert after.get("batched", 0) - before.get("batched", 0) == 1
    assert after.get("per_doc", 0) == before.get("per_doc", 0)
    for (dev, _), s in zip(work, serial):
        assert_bit_identical(dev, s)


def test_empty_deltas_no_launch():
    chs, delta = _doc_with_delta(0)
    dev = DeviceDoc.resolve(OpLog.from_changes(chs))
    before = launch_counts()
    out = apply_cross_doc([(dev, []), (dev, [[]])])
    after = launch_counts()
    assert out == {"applied": 0, "batched": 0, "fallback": 0}
    assert after == before
    # duplicates of already-resident changes are also a no-op
    dev.apply_changes(delta)
    out = apply_cross_doc([(dev, [delta])])
    assert out == {"applied": 0, "batched": 0, "fallback": 0}


def test_fallback_ratio_boundary():
    """The whale rule is STRICT: a doc at exactly ratio x total stays in
    the batch; one row over is peeled (largest first, totals recomputed)."""

    def fake(n):
        return BatchStage(None, np.arange(n), np.arange(1))

    # 20 == 0.5 * (10 + 10 + 20): boundary — stays batched
    batch, whales = plan_stages([fake(10), fake(10), fake(20)], 0.5)
    assert len(batch) == 3 and not whales
    # 21 > 0.5 * 41: peeled; the remaining pair is balanced and stays
    batch, whales = plan_stages([fake(10), fake(10), fake(21)], 0.5)
    assert len(batch) == 2 and len(whales) == 1
    assert whales[0].n_rows == 21
    # ratio >= 1 never peels (a doc cannot exceed its own total)
    batch, whales = plan_stages([fake(1), fake(1000)], 1.0)
    assert len(batch) == 2 and not whales
    # ratio 0 peels everything down to the smallest doc
    batch, whales = plan_stages([fake(3), fake(2), fake(1)], 0.0)
    assert len(batch) == 1 and batch[0].n_rows == 1
    assert [w.n_rows for w in whales] == [3, 2]
    # a single doc is never peeled against itself
    batch, whales = plan_stages([fake(50)], 0.0)
    assert len(batch) == 1 and not whales


def test_whale_falls_back_per_doc_end_to_end():
    """A dominating doc resolves per-doc; results stay bit-identical.
    The whale rule compares DIRTY-SUBSET rows (the kernel work), so the
    whale is a doc whose edited object dwarfs the others' — its ballast
    only keeps it on the subset path."""
    specs = [(150, 1), (150, 1), (2500, 60)]  # the third is the whale
    work, serial = [], []
    for i, (ballast, edits) in enumerate(specs):
        chs, delta = _doc_with_delta(i, ballast=ballast, edits=edits)
        work.append((DeviceDoc.resolve(OpLog.from_changes(chs)), [delta]))
        s = DeviceDoc.resolve(OpLog.from_changes(chs))
        s.apply_changes(delta)
        serial.append(s)
    before = launch_counts()
    out = apply_cross_doc(work, fallback_ratio=0.5)
    after = launch_counts()
    assert out["batched"] == 2 and out["fallback"] == 1, out
    assert after.get("batched", 0) - before.get("batched", 0) == 1
    # the whale's subset re-resolution ran through the per-doc path
    assert after.get("per_doc", 0) - before.get("per_doc", 0) == 1
    for (dev, _), s in zip(work, serial):
        assert_bit_identical(dev, s)


def test_duplicate_doc_in_work_merges_stages():
    """The same DeviceDoc listed twice must merge into one stage — a
    second append would splice the log out from under the first stage's
    row indices (silent corruption, not an exception)."""
    base, t, lst = build_base(ballast=300)
    chs = [a.stored for a in base.doc.history]
    have = {c.hash for c in chs}
    f1 = base.fork(actor=actor(10))
    f1.splice_text(t, 2, 0, "<one>")
    f1.commit()
    d1 = [a.stored for a in f1.doc.history if a.stored.hash not in have]
    f2 = base.fork(actor=actor(11))
    f2.splice_text(t, 0, 0, "<two>")
    f2.put("_root", "k0", "dup")
    f2.commit()
    d2 = [a.stored for a in f2.doc.history if a.stored.hash not in have]
    dev = DeviceDoc.resolve(OpLog.from_changes(chs))
    ref = DeviceDoc.resolve(OpLog.from_changes(chs))
    ref.apply_changes(d1)
    ref.apply_changes(d2)
    out = apply_cross_doc([(dev, [d1]), (dev, [d2])])
    assert out["applied"] == len(d1) + len(d2)
    assert out["batched"] + out["fallback"] <= 1  # ONE stage for the doc
    assert_bit_identical(dev, ref)


def test_stage_batches_contract():
    chs, delta = _doc_with_delta(0)
    dev = DeviceDoc.resolve(OpLog.from_changes(chs))
    # a historical view cannot stage
    view = dev.at(dev.current_heads())
    with pytest.raises(ValueError):
        view.stage_batches([delta])
    # staging appends host-side; the stage carries the dirty subset
    n, stage = dev.stage_batches([delta])
    assert n == len(delta) and stage is not None
    assert stage.doc is dev and len(stage.rows) > 0
    # resolving the stage via the packer completes the apply
    from automerge_tpu.ops.batched import resolve_stages

    resolve_stages([stage])
    ref = DeviceDoc.resolve(OpLog.from_changes(chs))
    ref.apply_changes(delta)
    assert_bit_identical(dev, ref)


def test_cross_doc_batcher_threads():
    """Concurrent workers draining different docs share one launch."""
    n = 3
    work, serial = [], []
    for i in range(n):
        chs, delta = _doc_with_delta(i, ballast=200 + 100 * i)
        work.append((DeviceDoc.resolve(OpLog.from_changes(chs)), [delta]))
        s = DeviceDoc.resolve(OpLog.from_changes(chs))
        s.apply_changes(delta)
        serial.append(s)
    batcher = CrossDocBatcher(window_ms=200.0, max_docs=n)
    before = launch_counts()
    errs = []
    barrier = threading.Barrier(n)

    def worker(dev, batches):
        try:
            barrier.wait()
            batcher.apply(dev, batches)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [
        threading.Thread(target=worker, args=(dev, batches))
        for dev, batches in work
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    after = launch_counts()
    assert not errs, errs
    assert after.get("batched", 0) - before.get("batched", 0) == 1
    for (dev, _), s in zip(work, serial):
        assert_bit_identical(dev, s)


def test_apply_batches_takes_the_batcher_route():
    """apply_batches is a batcher generation of one: the same launch
    and the same arrays as a lone batcher submission."""
    chs, delta = _doc_with_delta(0)
    dev = DeviceDoc.resolve(OpLog.from_changes(chs))
    lone = DeviceDoc.resolve(OpLog.from_changes(chs))
    ref = DeviceDoc.resolve(OpLog.from_changes(chs))
    ref.apply_changes(delta)
    l0 = launch_counts()
    assert dev.apply_batches([delta]) == len(delta)
    l1 = launch_counts()
    assert CrossDocBatcher(window_ms=1.0).apply(lone, [delta]) == len(delta)
    l2 = launch_counts()
    assert l1.get("per_doc", 0) - l0.get("per_doc", 0) == 1
    assert l2.get("per_doc", 0) - l1.get("per_doc", 0) == 1
    assert l2.get("batched", 0) == l0.get("batched", 0)
    assert_bit_identical(dev, lone)
    assert_bit_identical(dev, ref)


# -- whale-doc mesh residency -------------------------------------------------


def _mesh_usable(n: int = 2) -> bool:
    import jax

    return len(jax.devices()) >= n


def test_enable_mesh_degrades_cleanly():
    """Without a multi-device mesh, enable_mesh refuses
    (returns False) and every apply keeps working single-device — the
    graceful skip the acceptance criteria require. On a capable mesh the
    sharded full re-resolution must match the per-doc kernel exactly."""
    chs, delta = _doc_with_delta(0, ballast=0)  # tiny: full reresolve path
    dev = DeviceDoc.resolve(OpLog.from_changes(chs))
    ref = DeviceDoc.resolve(OpLog.from_changes(chs))
    ok = dev.enable_mesh(2, min_rows=0)
    assert ok == _mesh_usable(2)
    dev.apply_changes(delta)
    ref.apply_changes(delta)
    assert_bit_identical(dev, ref)
    if not ok:
        # the refusal was counted with a reason label
        reasons = {
            e["labels"].get("reason")
            for e in obs.snapshot()
            if e["name"] == "device.mesh_unavailable"
        }
        assert reasons, "mesh refusal not observed"


@pytest.mark.skipif(
    not _mesh_usable(2), reason="no multi-device mesh"
)
def test_mesh_full_reresolve_matches_single_device(monkeypatch):
    chs, delta = _doc_with_delta(1, ballast=400, edits=4)
    dev = DeviceDoc.resolve(OpLog.from_changes(chs))
    ref = DeviceDoc.resolve(OpLog.from_changes(chs))
    assert dev.enable_mesh(2, min_rows=0)
    before = launch_counts()
    # the batched staging (the served route on an accelerator) skips the
    # host delta resolution: over the dirty-fraction limit it stages the
    # whole log, and a full stage of a document on a mesh resolves over
    # the mesh
    monkeypatch.setenv("AUTOMERGE_TPU_DIRTY_FRACTION", "0")
    n, stage = dev.stage_batches([delta])
    assert n == len(delta) and stage.full
    assert len(stage.rows) == dev.log.n
    from automerge_tpu.ops.batched import resolve_stages

    assert resolve_stages([stage]) == {"batched": 0, "fallback": 1}
    ref.apply_changes(delta)
    after = launch_counts()
    assert after.get("sharded", 0) > before.get("sharded", 0)
    assert_bit_identical(dev, ref)
