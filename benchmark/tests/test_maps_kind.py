"""Deployment kind ``maps`` and traffic writer ``reconnect``
(``maps.reconnect``): the plain last-writer-wins reference against the
program on the encoder's bytes, the cell's generation pinned by hash, and
whole runs of the cell through ``run.main`` on the CPU at a small size,
sound and with each planted fault."""

import copy
import hashlib
from concurrent.futures import ThreadPoolExecutor

import pytest

from benchmark import deploy, faults, run, traffic
from benchmark import reference as R
from benchmark.kinds import maps as M
from benchmark.tests.test_generation_pinned import _changes
from benchmark.tests.test_harness import run_cell

SEED = 3000000007
PINNED = "d5379e29760670c0418d943c19409176f9c34e0167d8b8c3516c1705bb0d5232"


def small_spec():
    """Four documents, one a connection, each with enough requests that
    no connection runs out in a window."""
    spec = copy.deepcopy(run.load_cell(run.ROOT, "maps.reconnect"))
    spec["config"].update(docs=4, ops_per_doc=512)
    spec["traffic"].update(connections=4, replicas_per_doc=96)
    return spec


def test_reference_matches_the_program_on_the_encoders_bytes():
    """Concurrent replicas set and delete the same keys: the program's
    winners, conflicts (``get_all``, ordered by op id), change hashes and
    digest agree with the reference."""
    from automerge_tpu import integrity
    from automerge_tpu.api import AutoDoc

    cfg = dict(root_keys=4, child_maps=3, child_keys=4, zipf=1.0,
               ops_per_doc=200)
    spec = {"replicas_per_doc": 5, "changes_per_replica": 3,
            "ops_per_change": 8, "delete_share": 0.3, "zipf": 1.0}
    raw, ref, order, objs = M.base_doc(cfg, [SEED, 1, 0])
    keys, _ = M.shape(cfg)
    doc = AutoDoc.load(raw)
    assert doc.hydrate() == ref.materialize()
    hashes = [R.change_hash(raw)]
    assert doc.doc.get_heads() == hashes
    chains = traffic.writer("reconnect").doc_chains(
        0, keys, objs, order, copy.deepcopy(ref), hashes[0],
        cfg["ops_per_doc"] + 1, spec, SEED)
    for chain in chains:
        for ch in chain:
            doc.load_incremental(ch.raw)
            for i, op in enumerate(ch.ops):
                ref.apply(R.op_id(ch.start_op + i, ch.rank), op)
            hashes.append(ch.hash)
    assert doc.hydrate() == ref.materialize()
    obj_text = ["_root"] + [M.id_text(o, 0) for o in objs[1:]]
    conflicted = 0
    for slot, key in keys:
        got = doc.get_all(obj_text[slot], key)
        want = ref.visible(objs[slot], key)
        assert [e for _, e in got] == [M.id_text(e, 0) for e in want]
        assert [r[1].to_py() for r, _ in got] == [
            ref.vis[(objs[slot], key)][e] for e in want]
        conflicted += len(want) > 1
    assert conflicted, "no key holds a conflict: the check is vacuous"
    heads = [c[-1].hash for c in chains]
    assert sorted(doc.doc.get_heads()) == sorted(heads)
    assert integrity.doc_digest(doc.doc)["digest"] == R.doc_digest(
        hashes, heads)


def generation_digest() -> str:
    spec = small_spec()
    with ThreadPoolExecutor(max_workers=1) as pool:
        dep = deploy.make(spec["config"], SEED, pool)
        dep.build()
        plan = traffic.make(spec["traffic"], dep, SEED, 3)
    h = hashlib.sha256()
    for doc in dep.docs:
        h.update(doc.base_raw)
    h.update(repr((plan.mode, plan.connections)).encode())
    for cl in plan.clients:
        h.update(repr(cl["conn"]).encode())
        for req in cl["requests"]:
            h.update(b"request")
            _changes(h, req)
    return h.hexdigest()


def test_generation_is_pinned():
    assert generation_digest() == PINNED


@pytest.fixture(scope="module")
def batched():
    """The cross-document batcher on, as on the chip."""
    mp = pytest.MonkeyPatch()
    mp.setenv("AUTOMERGE_TPU_SERVE_BATCHED", "1")
    yield
    mp.undo()


def test_sound_run_is_correct(capsys, batched):
    line = run_cell(capsys, small_spec())
    assert line["correct"] is True, {
        k: c for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert {"rpc_materialize_docs_differing", "rpc_get_all_docs_differing",
            "doc_digest_docs_differing", "device_state_docs_differing",
            "launches_missing_on_batched"} <= set(line["checks"])
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert set(line["metrics"]) == {"ops_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_caught(capsys, batched, fault):
    undo = faults.FAULTS[fault]()
    try:
        line = run_cell(capsys, small_spec())
    finally:
        undo()
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_readers_read_the_batched_counters_and_span():
    stages = ("device.batch_stages", (("kind", "full"),))
    ctx = {"seconds": 2.0, "trace": None, "spans": [
               ("device.batch_window", 0.0, 0.5, {}),
               ("device.batch_window", 0.25, 1.0, {})],
           "counters": {
               stages: 12,
               ("device.kernel_launches", (("path", "batched"),)): 3,
               ("device.kernel_launches", (("path", "per_doc"),)): 4}}
    assert run.reader("batched.doc_share.maps")(ctx) == 75.0
    assert run.reader("batched.docs_per_launch.maps")(ctx) == 4.0
    assert run.reader("batched.window_share.maps")(ctx) == 50.0
    # a program that packs no full stage counts none: nothing to read
    empty = {"seconds": 2.0, "trace": None, "spans": [], "counters": {}}
    for name in ("batched.doc_share.maps", "batched.docs_per_launch.maps",
                 "batched.window_share.maps", "resolve_roofline.maps"):
        assert run.reader(name)(empty) is None


@pytest.mark.parametrize("name", ["staging.host_share", "mirror.drain_ms",
                                  "device.idle_share", "device.wait_share",
                                  "host.gc_share"])
def test_host_layer_readers_read_as_the_fan_in_cells(name):
    ctx = {"seconds": 2.0,
           "trace": {"busy_s": 0.5, "window_s": 2.0, "modules": []},
           "spans": [("device.stage.causal_order", 0.0, 0.5, {}),
                     ("device.wait", 0.25, 1.0, {}),
                     ("host.gc", 1.5, 1.6, {})],
           "counters": {("host.gc_collections", (("generation", "2"),)): 1},
           "hists": {"drain.cycle_seconds": {"n": 4, "sum": 0.2}}}
    got = run.reader(f"{name}.maps")(ctx)
    assert got is not None
    assert got == run.reader(f"{name}.merge")(ctx)
