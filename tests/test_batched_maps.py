"""Small map documents' full re-resolutions ride the cross-document launch.

A small map document's change dirties the objects that hold most of its
rows, so the staging half returns a *full* stage (every row, every
object) and the batcher packs it beside the other documents of its
generation. On seeded histories of 2-6 concurrent actors, every change
touching every object, the packed documents end bit-identical to serial
per-document ``apply_changes``, and their winners and conflicts equal
the plain last-writer-wins reference of the benchmark
(``benchmark/kinds/maps.py``). A generation of one document, a whale the
batcher peels, and the two stagers' agreement on a full stage are
pinned too, as are the flush leader's wait for the drains in flight and
the shared launch's program, which follows its shapes alone.
"""

import threading
import time

import numpy as np
import pytest

from automerge_tpu import obs
from automerge_tpu.ops import DeviceDoc, OpLog, batched, host_batch, merge
from automerge_tpu.ops.batched import CrossDocBatcher
from automerge_tpu.storage.change import parse_change
from benchmark.kinds import maps as M
from benchmark.reference import op_id

from .test_batched_merge import assert_bit_identical

SHAPE = {"root_keys": 3, "child_maps": 2, "child_keys": 3, "zipf": 1.0}


def _stored(raw):
    return parse_change(raw)[0]


class MapDoc:
    """One small map document: its base change, ``n_actors`` concurrent
    replicas on the base heads, and the plain reference."""

    def __init__(self, d, seed, n_actors, ops_per_doc=24):
        cfg = dict(SHAPE, ops_per_doc=ops_per_doc)
        self.d = d
        raw, self.ref, _, self.objs = M.base_doc(cfg, [seed, d])
        self.keys, _ = M.shape(cfg)
        self.base = _stored(raw)
        self.rng = np.random.default_rng([seed, d, 1])
        self.enc = M.MapEncoder([M.actor(d, k) for k in range(n_actors + 1)])
        start = ops_per_doc + 1
        self.replicas = [
            {"rank": k + 1, "deps": [self.base.hash], "seq": 1,
             "next": start, "view": {i: self.ref.visible(self.objs[s], key)
                                     for i, (s, key) in enumerate(self.keys)}}
            for k in range(n_actors)]

    def change(self, rep):
        """The replica's next change: one op on every object (a put, or a
        delete where it sees a value), so the change dirties them all."""
        r, rank, ops = self.rng, rep["rank"], []
        for slot in range(len(self.objs)):
            ks = [i for i, (s, _) in enumerate(self.keys) if s == slot]
            k = ks[int(r.integers(len(ks)))]
            key, view = self.keys[k][1], rep["view"]
            if view[k] and r.random() < 0.25:
                ops.append(("del", self.objs[slot], key, view[k]))
                view[k] = []
            else:
                ops.append(("put", self.objs[slot], key, M.value(r), view[k]))
                view[k] = [op_id(rep["next"] + len(ops) - 1, rank)]
        raw = self.enc.change(M.actor(self.d, rank), rep["seq"], rep["next"],
                              rep["deps"], ops)
        for i, op in enumerate(ops):
            self.ref.apply(op_id(rep["next"] + i, rank), op)
        ch = _stored(raw)
        rep["deps"], rep["seq"] = [ch.hash], rep["seq"] + 1
        rep["next"] += len(ops)
        return ch

    def resolved(self):
        return DeviceDoc.resolve(OpLog.from_changes([self.base]))

    def assert_matches_reference(self, dev):
        assert dev.hydrate() == self.ref.materialize()
        obj_text = ["_root"] + [M.id_text(o, self.d) for o in self.objs[1:]]
        want = {(obj_text[self.objs.index(o)], key):
                sorted(M.id_text(e, self.d) for e in cell)
                for (o, key), cell in self.ref.vis.items() if cell}
        assert M.device_visible(dev) == want


def launches():
    return obs.counter_values("device.kernel_launches", "path")


def batch_stages():
    return obs.counter_values("device.batch_stages", "kind")


def apply_generation(batcher, work, stagger=0.0):
    """Every (device doc, changes) pair through the batcher from its own
    thread, all released at once, as shard workers drain documents; the
    ``i``-th submits ``i * stagger`` seconds after the release."""
    barrier = threading.Barrier(len(work))
    errors = []

    def worker(i, dev, chs):
        try:
            barrier.wait()
            time.sleep(i * stagger)
            batcher.apply(dev, [chs])
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=worker, args=(i, *w))
          for i, w in enumerate(work)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errors, errors


@pytest.mark.parametrize("seed", [1, 7, 19])
def test_small_maps_share_one_launch_and_match_the_reference(seed):
    rng = np.random.default_rng(seed)
    docs = [MapDoc(d, seed, int(rng.integers(2, 7))) for d in range(4)]
    packed = [m.resolved() for m in docs]
    serial = [m.resolved() for m in docs]
    batcher = CrossDocBatcher(window_ms=2000.0, max_docs=len(docs))
    windows0 = len([r for r in obs.recorder.snapshot()
                    if r.name == "device.batch_window"])
    for cycle in range(3):
        work = []
        for m in docs:
            k = int(rng.integers(1, len(m.replicas) + 1))
            reps = rng.choice(len(m.replicas), size=k, replace=False)
            work.append([m.change(m.replicas[i]) for i in reps])
        l0, s0 = launches(), batch_stages()
        apply_generation(batcher, list(zip(packed, work)))
        l1, s1 = launches(), batch_stages()
        # one shared launch carried every document's full re-resolution
        assert l1.get("batched", 0) - l0.get("batched", 0) == 1
        assert l1.get("per_doc", 0) == l0.get("per_doc", 0)
        assert s1.get("full", 0) - s0.get("full", 0) == len(docs)
        for m, dev, chs, ref in zip(docs, packed, work, serial):
            ref.apply_changes(chs)
            assert_bit_identical(dev, ref, (seed, cycle, m.d))
            m.assert_matches_reference(dev)
    spans = [r for r in obs.recorder.snapshot()
             if r.name == "device.batch_window"][windows0:]
    assert spans and all(s.fields["reason"] == "full"
                         and s.fields["docs"] == len(docs) for s in spans)


def test_a_lone_document_and_a_peeled_whale_resolve_per_doc():
    # a generation of one small map document: its own per-doc launch
    m = MapDoc(0, 3, 3)
    dev, ref = m.resolved(), m.resolved()
    chs = [m.change(m.replicas[0])]
    l0, s0 = launches(), batch_stages()
    apply_generation(CrossDocBatcher(window_ms=1.0, max_docs=8),
                     [(dev, chs)])
    l1, s1 = launches(), batch_stages()
    assert l1.get("batched", 0) == l0.get("batched", 0)
    assert l1.get("per_doc", 0) - l0.get("per_doc", 0) == 1
    assert s1 == s0
    ref.apply_changes(chs)
    assert_bit_identical(dev, ref)
    m.assert_matches_reference(dev)

    # two small documents and a whale of many times their rows: the
    # whale resolves per-doc, the two share the launch
    docs = [MapDoc(1, 3, 2), MapDoc(2, 3, 2), MapDoc(3, 3, 2, ops_per_doc=400)]
    devs = [d.resolved() for d in docs]
    refs = [d.resolved() for d in docs]
    work = [[d.change(d.replicas[0])] for d in docs]
    l0, s0 = launches(), batch_stages()
    f0 = obs.counter_values("device.batched_fallback", "").get("", 0)
    apply_generation(CrossDocBatcher(window_ms=2000.0, max_docs=3),
                     list(zip(devs, work)))
    l1, s1 = launches(), batch_stages()
    assert l1.get("batched", 0) - l0.get("batched", 0) == 1
    assert l1.get("per_doc", 0) - l0.get("per_doc", 0) == 1
    assert s1.get("full", 0) - s0.get("full", 0) == 2
    assert obs.counter_values(
        "device.batched_fallback", "").get("", 0) - f0 == 1
    for d, dev, ref, chs in zip(docs, devs, refs, work):
        ref.apply_changes(chs)
        assert_bit_identical(dev, ref)
        d.assert_matches_reference(dev)


@pytest.mark.parametrize("seed", [2, 5])
def test_vectorized_and_scalar_stagers_return_the_same_full_stage(seed):
    m = MapDoc(0, seed, 4)
    vec, sca = m.resolved(), m.resolved()
    chs = [m.change(rep) for rep in m.replicas]
    stages, results = host_batch.stage_docs([(vec, [chs])])
    assert results[id(vec)].vectorized and results[id(vec)].error is None
    (a,) = stages
    n, b = sca.stage_batches([chs])
    assert n == len(chs) == results[id(vec)].applied
    assert a.full and b.full
    assert a.doc is vec and b.doc is sca
    assert np.array_equal(a.rows, np.arange(vec.log.n))
    assert np.array_equal(a.rows, b.rows)
    assert np.array_equal(a.dirty, np.arange(vec.log.n_objs))
    assert np.array_equal(a.dirty, b.dirty)


def window_spans(n0):
    return [r for r in obs.recorder.snapshot()
            if r.name == "device.batch_window"][n0:]


@pytest.mark.parametrize("in_flight, fill_wait, batched_launches, reason", [
    (3, 5.0, 1, "full"),      # all three drains in flight join one launch
    (1, 5.0, 0, "full"),      # none other in flight: each closes alone
    (3, 0.05, 0, "timeout"),  # the drains in flight come too late
])
def test_leader_waits_for_the_drains_in_flight(monkeypatch, in_flight,
                                               fill_wait, batched_launches,
                                               reason):
    # drains reach the batcher 0.3 s apart, far past its 1-ms window
    monkeypatch.setattr(batched, "FILL_WAIT_S", fill_wait)
    docs = [MapDoc(d, 11, 2) for d in range(3)]
    devs = [m.resolved() for m in docs]
    refs = [m.resolved() for m in docs]
    work = [[m.change(m.replicas[0])] for m in docs]
    batcher = CrossDocBatcher(window_ms=1.0, max_docs=8)
    batcher.in_flight = lambda: in_flight
    n0 = len(window_spans(0))
    l0 = launches()
    apply_generation(batcher, list(zip(devs, work)), stagger=0.3)
    l1 = launches()
    assert l1.get("batched", 0) - l0.get("batched", 0) == batched_launches
    per_doc = 0 if batched_launches else len(docs)
    assert l1.get("per_doc", 0) - l0.get("per_doc", 0) == per_doc
    assert {s.fields["reason"] for s in window_spans(n0)} == {reason}
    for m, dev, ref, chs in zip(docs, devs, refs, work):
        ref.apply_changes(chs)
        assert_bit_identical(dev, ref)
        m.assert_matches_reference(dev)


def test_shared_launch_program_follows_its_shapes_alone():
    # two generations of the same rows and objects whose columns
    # run-encode differently: one compiled program serves both
    gens = []
    for seed in (4, 9):
        docs = [MapDoc(d, seed, 3) for d in range(2)]
        devs = [m.resolved() for m in docs]
        for m, dev in zip(docs, devs):
            dev.apply_changes([m.change(rep) for rep in m.replicas])
        gens.append([batched.full_stage(dev) for dev in devs])
    cols = [batched._pack(stages)[0] for stages in gens]
    assert len(cols[0]["action"]) == len(cols[1]["action"])
    # each column's runs and slope, as the packed transport would
    # encode them; the shared launch uploads plain columns and ignores them
    runs = [
        {k: None if e is None else (len(e[0]), e[2])
         for k, e in ((k, merge._slope_rle(np.asarray(v, np.int32)))
                      for k, v in c.items())}
        for c in cols
    ]
    assert runs[0] != runs[1], "the run structures agree: vacuous"
    programs0 = len(merge._RESOLUTION_CACHE)
    l0 = launches()
    for stages in gens:
        batched.resolve_stages(stages)
    l1 = launches()
    assert len(merge._RESOLUTION_CACHE) - programs0 <= 1
    assert l1.get("batched", 0) - l0.get("batched", 0) == 2
