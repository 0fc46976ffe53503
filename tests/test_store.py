"""Tiered document store: policy units, tier transitions through the
RPC layer, single-flight hydration, salvage cold-opens, and the
end-to-end socket-serving path under residency budgets."""

import json
import os
import socket
import threading
import time

import pytest

from automerge_tpu import obs
from automerge_tpu.rpc import RpcServer
from automerge_tpu.store import (
    TIER_COLD,
    TIER_HOT,
    TIER_WARM,
    DocStats,
    StoreBackpressure,
    StoreBudgets,
    pick_demotions,
)
from automerge_tpu.store.docstore import ColdDocRef


# -- policy units -------------------------------------------------------------


def _stats(*rows):
    return [DocStats(n, t, la, rb) for (n, t, la, rb) in rows]


def test_policy_hot_budget_demotes_lru_first():
    b = StoreBudgets(hot_docs=2, min_idle_s=0.0)
    st = _stats(("a", TIER_HOT, 1.0, 10), ("b", TIER_HOT, 3.0, 10),
                ("c", TIER_HOT, 2.0, 10), ("d", TIER_WARM, 0.5, 10))
    out = pick_demotions(st, b, now=10.0)
    assert [(d.name, d.to, d.reason) for d in out] == [
        ("a", TIER_WARM, "hot_budget")]


def test_policy_warm_bytes_goes_cold_until_under():
    b = StoreBudgets(warm_bytes=25, min_idle_s=0.0)
    st = _stats(("a", TIER_WARM, 1.0, 10), ("b", TIER_WARM, 2.0, 10),
                ("c", TIER_WARM, 3.0, 10))
    out = pick_demotions(st, b, now=10.0)
    assert [(d.name, d.to) for d in out] == [("a", TIER_COLD)]
    assert out[0].reason == "warm_budget"


def test_policy_rss_watermark_demotes_oldest_first():
    b = StoreBudgets(max_rss_bytes=100, min_idle_s=0.0)
    st = _stats(("a", TIER_WARM, 2.0, 30), ("b", TIER_HOT, 1.0, 30))
    out = pick_demotions(st, b, now=10.0, rss_bytes=160)
    # 60 bytes over: both demote, LRU (b) first
    assert [(d.name, d.to, d.reason) for d in out] == [
        ("b", TIER_COLD, "rss"), ("a", TIER_COLD, "rss")]


def test_policy_min_idle_floor_protects_recent_docs():
    b = StoreBudgets(warm_bytes=1, min_idle_s=5.0)
    st = _stats(("fresh", TIER_WARM, 9.0, 100), ("old", TIER_WARM, 1.0, 100))
    out = pick_demotions(st, b, now=10.0)
    assert [d.name for d in out] == ["old"]


def test_policy_idle_age_out_and_coldest_decision_wins():
    b = StoreBudgets(hot_docs=1, warm_bytes=5, idle_cold_s=4.0,
                     min_idle_s=0.0)
    st = _stats(("a", TIER_HOT, 1.0, 10), ("b", TIER_HOT, 8.0, 10))
    out = pick_demotions(st, b, now=10.0)
    by_name = {d.name: d for d in out}
    # a: idle 9s -> cold (idle pass wins over later budget passes)
    assert by_name["a"].to == TIER_COLD and by_name["a"].reason == "idle"
    # b: hot-budget demotion to warm, then warm-bytes takes it cold —
    # the coldest decision survives the merge
    assert by_name["b"].to == TIER_COLD


def test_policy_inactive_budgets_never_demote():
    st = _stats(("a", TIER_HOT, 0.0, 10**9))
    assert pick_demotions(st, StoreBudgets(), now=1e9) == []


# -- metrics removal API (the per-doc gauge hygiene satellite) ---------------


def test_registry_remove_labels_and_gauge_remove():
    from automerge_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.gauge("doc.journal_bytes", doc="a").set(7)
    reg.gauge("doc.journal_bytes", doc="b").set(9)
    reg.counter("doc.journal_bytes", doc="a").inc()  # same name, other type
    assert reg.remove_labels("doc.journal_bytes", {"doc": "a"}) == 2
    left = [e for e in reg.snapshot() if e["name"] == "doc.journal_bytes"]
    assert [e["labels"] for e in left] == [{"doc": "b"}]
    assert reg.gauge_remove("doc.journal_bytes", doc="b") is True
    assert reg.gauge_remove("doc.journal_bytes", doc="b") is False


def test_doc_gauges_removed_on_close(tmp_path):
    from automerge_tpu.api import AutoDoc

    dd = AutoDoc.open(str(tmp_path / "g1"))
    dd.put("_root", "k", 1)
    dd.commit()
    name = dd.obs_name
    assert any(
        e["name"] == "doc.journal_bytes" and e["labels"].get("doc") == name
        for e in obs.snapshot()
    )
    dd.close()
    assert not any(
        e["name"].startswith("doc.") and e["labels"].get("doc") == name
        for e in obs.snapshot()
    )


# -- tier transitions through the RPC layer ----------------------------------


@pytest.fixture
def server(tmp_path):
    s = RpcServer(durable_dir=str(tmp_path / "docs"))
    os.makedirs(s.durable_dir, exist_ok=True)
    yield s
    s.close_durables()


def test_demote_hydrate_round_trip_byte_identical(server):
    s = server
    h = s.openDurable({"name": "rt"})["doc"]
    s.put({"doc": h, "obj": "_root", "prop": "k", "value": 42})
    s.commit({"doc": h})
    save1 = s.save({"doc": h})
    assert s.store.demote("rt", TIER_COLD) == TIER_COLD
    assert isinstance(s._docs[h], ColdDocRef)
    # first access hydrates lazily; contents byte-identical
    assert s.get({"doc": h, "obj": "_root", "prop": "k"}) == 42
    assert s.store.tier("rt") == TIER_WARM
    assert s.save({"doc": h}) == save1


def test_cold_releases_flock_and_memory_footprint(server, tmp_path):
    from automerge_tpu.api import AutoDoc

    s = server
    h = s.openDurable({"name": "fl"})["doc"]
    s.put({"doc": h, "obj": "_root", "prop": "k", "value": 1})
    s.commit({"doc": h})
    s.store.demote("fl", TIER_COLD)
    # the journal flock is released: a second opener succeeds
    other = AutoDoc.open(os.path.join(s.durable_dir, "fl"))
    assert other.get("_root", "k") is not None
    other.close()
    # and the handle placeholder is a few slots, not a document
    assert isinstance(s._docs[h], ColdDocRef)


def test_hot_tier_device_mirror_drops_and_rebuilds(server):
    s = server
    h = s.openDurable({"name": "dev", "device": True})["doc"]
    s.put({"doc": h, "obj": "_root", "prop": "k", "value": 5})
    s.commit({"doc": h})
    assert s.store.tier("dev") == TIER_HOT
    dd = s._docs[h]
    assert dd.device_doc is not None
    assert s.store.demote("dev", TIER_WARM) == TIER_WARM
    assert dd.device_doc is None
    # the device gauges were removed with the mirror
    assert not any(
        e["name"] in ("doc.resident_ops", "doc.device_bytes")
        and e["labels"].get("doc") == "dev"
        for e in obs.snapshot()
    )
    # access promotes back to hot (want_device, no hot budget)
    assert s.get({"doc": h, "obj": "_root", "prop": "k"}) == 5
    assert s.store.tier("dev") == TIER_HOT
    assert s._docs[h].device_doc is not None


def test_failed_promotion_fails_one_request_then_host_serves(
        server, monkeypatch):
    from automerge_tpu import trace

    s = server
    h = s.openDurable({"name": "pf", "device": True})["doc"]
    s.put({"doc": h, "obj": "_root", "prop": "k", "value": 5})
    s.commit({"doc": h})
    s.store.demote("pf", TIER_WARM)
    dd = s._docs[h]

    def boom(self):
        raise RuntimeError("no room in device memory")

    monkeypatch.setattr(type(dd), "build_device_mirror", boom)
    before = trace.counters.get("store.promote_error", 0)
    get = {"id": 1, "method": "get",
           "params": {"doc": h, "obj": "_root", "prop": "k"}}
    assert "no room" in s.handle(get)["error"]["message"]
    # the next requests are served from the host, without a rebuild
    assert s.handle(get)["result"] == s.handle(get)["result"] is not None
    assert trace.counters.get("store.promote_error", 0) == before + 1
    assert s.store.tier("pf") == TIER_WARM and dd.device_doc is None


def test_mutation_on_evicted_instance_is_retriable(server):
    from automerge_tpu.storage.durable import DocumentEvicted

    s = server
    h = s.openDurable({"name": "ev"})["doc"]
    s.put({"doc": h, "obj": "_root", "prop": "k", "value": 1})
    s.commit({"doc": h})
    dd = s._docs[h]
    s.store.demote("ev", TIER_COLD)
    # a caller still holding the evicted instance: reads serve (the
    # op-store is immutable now), mutations refuse retriably instead of
    # silently staging state that would die with the instance
    assert dd.get("_root", "k") is not None
    with pytest.raises(DocumentEvicted):
        dd.put("_root", "k", 2)
    with pytest.raises(DocumentEvicted):
        dd.commit()
    assert DocumentEvicted.retriable is True
    # the RPC envelope surfaces the flag for the client retry loop
    resp = s.handle({"id": 1, "method": "commit", "params": {"doc": h}})
    assert "error" not in resp  # ...because _doc hydrated first
    # but a race that lands on the closed instance maps to retriable
    s.store.demote("ev", TIER_COLD)
    err = s._dispatch(2, "storeDemote", {
        "id": 2, "method": "storeDemote", "params": {"name": "nope"}})
    assert "error" in err  # sanity: dispatch error envelope shape


def test_read_path_refreshes_last_access(server):
    s = server
    h = s.openDurable({"name": "ra"})["doc"]
    s.put({"doc": h, "obj": "_root", "prop": "k", "value": 1})
    s.commit({"doc": h})

    def gauge():
        for e in obs.snapshot():
            if (e["name"] == "doc.last_access_seconds"
                    and e["labels"].get("doc") == "ra"):
                return e["value"]
        return None

    t0 = gauge()
    assert t0 is not None
    dd = s._docs[h]
    la0 = dd.last_access
    time.sleep(0.02)
    # a pure READ must refresh the policy stamp (the satellite:
    # read-hot docs previously looked idle and would have been demoted)
    s.get({"doc": h, "obj": "_root", "prop": "k"})
    assert dd.last_access > la0
    # the scrape-visible gauge refreshes at a bounded cadence, not per
    # request (hot-path cost); with the cadence zeroed it tracks reads
    assert gauge() == pytest.approx(t0)
    dd.TOUCH_EXPORT_INTERVAL_S = 0.0
    time.sleep(0.01)
    s.get({"doc": h, "obj": "_root", "prop": "k"})
    t1 = gauge()
    assert t1 is not None and t1 > t0
    assert dd.last_access == pytest.approx(t1)


def test_single_flight_hydration_opens_exactly_once(server):
    s = server
    h = s.openDurable({"name": "sf"})["doc"]
    s.put({"doc": h, "obj": "_root", "prop": "k", "value": 3})
    s.commit({"doc": h})
    s.store.demote("sf", TIER_COLD)

    opens = []
    orig = s._store_open_cold

    def slow_open(name):
        opens.append(name)
        time.sleep(0.05)
        return orig(name)

    s._store_open_cold = slow_open
    results, errors = [], []

    def reader():
        try:
            results.append(s.get({"doc": h, "obj": "_root", "prop": "k"}))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=reader) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors
    assert results == [3] * 8
    assert opens == ["sf"], "stampede must hydrate exactly once"


def test_hydration_backpressure_is_retriable(server):
    s = server
    for n in ("bp1", "bp2"):
        h = s.openDurable({"name": n})["doc"]
        s.put({"doc": h, "obj": "_root", "prop": "k", "value": 1})
        s.commit({"doc": h})
        s.store.demote(n, TIER_COLD)
    # one hydration slot; make opens slow enough to collide
    s.store._hydrations = threading.Semaphore(1)
    orig = s._store_open_cold

    def slow_open(name):
        time.sleep(0.2)
        return orig(name)

    s._store_open_cold = slow_open
    h1 = s._durable_names["bp1"]
    h2 = s._durable_names["bp2"]
    out = {}

    def read(name, h):
        out[name] = s.handle({
            "id": 1, "method": "get",
            "params": {"doc": h, "obj": "_root", "prop": "k"}})

    t1 = threading.Thread(target=read, args=("bp1", h1))
    t1.start()
    time.sleep(0.05)  # let bp1 take the slot
    read("bp2", h2)
    t1.join()
    assert out["bp1"].get("result") == 1
    err = out["bp2"].get("error")
    assert err is not None and err["type"] == "StoreBackpressure"
    assert err["retriable"] is True
    # and once the slot frees, the same doc hydrates fine
    assert s.get({"doc": h2, "obj": "_root", "prop": "k"}) == 1


def test_cold_open_salvages_damaged_snapshot(server):
    """A cold doc whose snapshot was damaged hydrates through the
    salvage path + journal replay instead of erroring the request."""
    s = server
    h = s.openDurable({"name": "sv"})["doc"]
    s.put({"doc": h, "obj": "_root", "prop": "early", "value": "snap"})
    s.commit({"doc": h})
    s.durableCompact({"doc": h})  # snapshot.am now holds 'early'
    s.put({"doc": h, "obj": "_root", "prop": "late", "value": "tail"})
    s.commit({"doc": h})  # journal tail holds 'late'
    s.store.demote("sv", TIER_COLD)  # tiny journal: closes, no compact
    snap = os.path.join(s.durable_dir, "sv", "snapshot.am")
    assert os.path.exists(snap)
    with open(snap, "ab") as f:
        f.write(b"\x00garbage-chunk-tail\xff" * 8)
    before = obs.legacy_counters.get("load.salvaged_chunks", 0)
    # the serving request succeeds: salvage drops the damage, replays
    # the journal tail on top
    assert s.get({"doc": h, "obj": "_root", "prop": "early"}) == "snap"
    assert s.get({"doc": h, "obj": "_root", "prop": "late"}) == "tail"
    after = obs.legacy_counters.get("load.salvaged_chunks", 0)
    assert after > before, "salvage path did not engage"


def test_budgets_drive_eviction_and_counters(server):
    s = server
    hs = {}
    for i in range(4):
        n = f"bd{i}"
        hs[n] = s.openDurable({"name": n})["doc"]
        s.put({"doc": hs[n], "obj": "_root", "prop": "k", "value": i})
        s.commit({"doc": hs[n]})
    # budgets arrive after the working set exists (the min-idle floor
    # protects in-flight docs; 0.5s keeps re-demotion out of the reads)
    s.store.budgets = StoreBudgets(
        hot_docs=1, warm_bytes=1, min_idle_s=0.5, evict_interval_s=0.0)
    time.sleep(0.6)
    s.store.maybe_evict()
    status = s.storeStatus({})
    assert status["tiers"]["cold"] >= 3, status
    demos = [
        e for e in obs.snapshot()
        if e["name"] == "store.demotions" and e["type"] == "counter"
    ]
    assert demos, "demotion counters never fired"
    assert all(
        set(e["labels"]) == {"from", "to", "reason"} for e in demos)
    # everything stays serveable (hydrate on access)
    for i in range(4):
        assert s.get(
            {"doc": hs[f"bd{i}"], "obj": "_root", "prop": "k"}) == i
    # store.tier gauges reflect the population
    tiers = {
        e["labels"]["tier"]: e["value"]
        for e in obs.snapshot()
        if e["name"] == "store.tier" and e["type"] == "gauge"
    }
    assert sum(tiers.values()) == 4


def test_store_status_and_demote_rpc_surface(server):
    s = server
    s.openDurable({"name": "st1"})
    out = s.handle({"id": 1, "method": "storeStatus",
                    "params": {"docs": True}})["result"]
    assert out["tiers"]["warm"] == 1
    assert "st1" in out["docs"]
    assert out["rssBytes"] > 0
    res = s.handle({"id": 2, "method": "storeDemote",
                    "params": {"name": "st1"}})["result"]
    assert res == {"name": "st1", "tier": "cold"}
    bad = s.handle({"id": 3, "method": "storeDemote",
                    "params": {"name": "missing"}})
    assert "error" in bad


# -- end to end through the socket serving path -------------------------------


def _req(sock, f, rid, method, **params):
    sock.sendall((json.dumps(
        {"id": rid, "method": method, "params": params}) + "\n").encode())
    resp = json.loads(f.readline())
    assert "error" not in resp, resp
    return resp.get("result")


def test_socket_serving_under_budgets_zipfian(tmp_path, monkeypatch):
    """Dozens of docs through the real serve path under a tight budget:
    live population bounded, every doc's contents intact through
    demote/hydrate cycles, no stranded flocks after shutdown."""
    from automerge_tpu.api import AutoDoc
    from automerge_tpu.serve import SocketRpcServer

    monkeypatch.setenv("AUTOMERGE_TPU_STORE_WARM_BYTES", "1")
    monkeypatch.setenv("AUTOMERGE_TPU_STORE_MIN_IDLE", "0.05")
    monkeypatch.setenv("AUTOMERGE_TPU_STORE_EVICT_INTERVAL", "0.1")
    srv = SocketRpcServer(host="127.0.0.1", port=0,
                          durable_dir=str(tmp_path / "zd"))
    srv.start()
    ndocs = 24
    try:
        sock = socket.create_connection(srv.address[:2])
        f = sock.makefile("r")
        rid = 0
        handles = {}
        for i in range(ndocs):
            rid += 1
            handles[i] = _req(sock, f, rid, "openDurable",
                              name=f"z{i:03}")["doc"]
            rid += 1
            _req(sock, f, rid, "put", doc=handles[i], obj="_root",
                 prop="v", value=i)
            rid += 1
            _req(sock, f, rid, "commit", doc=handles[i])
        time.sleep(0.4)  # the sweeper demotes the idle majority
        rid += 1
        st = _req(sock, f, rid, "storeStatus")
        assert st["tiers"]["cold"] > 0, st
        # skewed re-access: doc 0 hammered, the tail touched once
        for i in [0] * 10 + list(range(ndocs)):
            rid += 1
            assert _req(sock, f, rid, "get", doc=handles[i],
                        obj="_root", prop="v") == i
        rid += 1
        _req(sock, f, rid, "shutdown")
        sock.close()
    finally:
        srv.stop()
    # zero stranded flocks: every journal is reopenable
    for i in range(ndocs):
        dd = AutoDoc.open(str(tmp_path / "zd" / f"z{i:03}"))
        assert dd.get("_root", "v") is not None
        dd.close()
