"""Cluster tier: hash ring, journal-shipping replication, failover,
live migration.

Three layers: pure units (ring placement, wire codecs, journal hooks),
in-process leader/follower node pairs over real sockets (replication
convergence, quorum acks, cursor persistence, promotion), and the
router tier end to end (proxying, failover with zero acked-write loss,
migration between shard groups).
"""

import base64
import json
import os
import socket
import tempfile
import threading
import time

import pytest

from automerge_tpu.api import AutoDoc
from automerge_tpu.cluster import (
    ClusterNode,
    ClusterRouter,
    HashRing,
    decode_batch,
    decode_cursor,
    encode_batch,
    encode_cursor,
)
from automerge_tpu.storage.journal import (
    Journal,
    JournalError,
    REC_CHANGE,
    REC_META,
)
from automerge_tpu.types import ActorId


# -- helpers ------------------------------------------------------------------


class Client:
    """Minimal pipelining JSON-RPC socket client."""

    def __init__(self, address):
        self.sock = socket.create_connection(address)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.f = self.sock.makefile("r")
        self.rid = 0

    def call(self, method, allow_error=False, **params):
        self.rid += 1
        self.sock.sendall((json.dumps(
            {"id": self.rid, "method": method, "params": params}
        ) + "\n").encode())
        resp = json.loads(self.f.readline())
        if not allow_error:
            assert "error" not in resp, resp
        return resp if "error" in resp else resp.get("result")

    def close(self):
        self.sock.close()


def addr_of(node):
    return "%s:%d" % node.address


def start_node(tmp, name, **kw):
    d = os.path.join(str(tmp), name)
    node = ClusterNode(
        node_id=name, host="127.0.0.1", port=0, durable_dir=d, **kw
    )
    node.start()
    return node


def wait_until(pred, timeout=10.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# -- units --------------------------------------------------------------------


def test_hashring_stable_balanced_minimal_movement():
    ring = HashRing(["a", "b", "c"], vnodes=64)
    keys = [f"doc{i}" for i in range(3000)]
    before = {k: ring.member_for(k) for k in keys}
    # stability: a rebuilt ring places identically
    again = HashRing(["c", "a", "b"], vnodes=64)
    assert all(again.member_for(k) == v for k, v in before.items())
    # rough balance: no member below a third of the fair share
    counts = {}
    for v in before.values():
        counts[v] = counts.get(v, 0) + 1
    assert min(counts.values()) > len(keys) / 3 / 3
    # removing a member moves ONLY its keys
    ring.remove("b")
    for k in keys:
        if before[k] != "b":
            assert ring.member_for(k) == before[k]
        else:
            assert ring.member_for(k) in ("a", "c")


def test_cursor_and_batch_codecs_roundtrip():
    blob = encode_cursor("node-1/abc123", 991)
    assert decode_cursor(blob) == ("node-1/abc123", 991)
    records = [(REC_CHANGE, b"\x01" * 40), (REC_META, b"name-blob")]
    assert decode_batch(encode_batch(records)) == records
    # damage must raise, never truncate silently: TCP delivered it, so a
    # bad byte is a bug, not a torn write
    wire = bytearray(encode_batch(records))
    wire[10] ^= 0xFF
    with pytest.raises(JournalError):
        decode_batch(bytes(wire))
    assert decode_batch(b"") == []


def test_journal_hooks_fire_with_seqs(tmp_path):
    events = []
    j, _, _ = Journal.open(str(tmp_path / "j.waj"), fsync="always")
    j.on_record = lambda rt, pl, seq: events.append(("rec", rt, seq))
    j.on_synced = lambda seq: events.append(("sync", seq))
    j.append_change(b"abc")
    j.append_change(b"def")
    assert ("rec", REC_CHANGE, 1) in events and ("rec", REC_CHANGE, 2) in events
    assert ("sync", 1) in events and ("sync", 2) in events
    assert j.acked_seq == 2 and j.append_seq == 2
    j.close()


# -- leader/follower replication ---------------------------------------------


def test_replication_quorum_converges_and_promotes(tmp_path):
    fol = start_node(tmp_path, "f1", role="follower")
    led = start_node(tmp_path, "l1", role="leader",
                     replicate_to=[addr_of(fol)], ack_replicas=1)
    try:
        c = Client(led.address)
        d = c.call("openDurable", name="docA")["doc"]
        for i in range(12):
            c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
            c.call("commit", doc=d)
        save_l = c.call("save", doc=d)

        fc = Client(fol.address)
        # follower rejects client mutations
        r = fc.call("create", allow_error=True)
        assert r["error"]["type"] == "NotLeader", r
        # a pipelined run of writes, which the serving layer coalesces,
        # is refused the same way
        hd = fc.call("openDurable", name="docA")["doc"]
        w = AutoDoc(actor=ActorId(bytes([9]) * 16))
        w.put("_root", "x", 1)
        w.commit()
        data = base64.b64encode(w.save()).decode()
        fc.sock.sendall("".join(json.dumps(
            {"id": 900 + i, "method": "applyChanges",
             "params": {"doc": hd, "data": data}}) + "\n"
            for i in range(2)).encode())
        for _ in range(2):
            r = json.loads(fc.f.readline())
            assert r["error"]["type"] == "NotLeader", r
        # the quorum ack means the follower ALREADY holds everything
        st = fc.call("clusterStatus")
        assert st["role"] == "follower"
        cur = st["docs"]["docA"]["cursor"]
        assert cur is not None and cur["lsn"] >= 12
        assert cur["stream"] == led.rpc.hub.stream_id
        # promotion: byte-identical state, serves mutations
        pr = fc.call("clusterPromote")
        assert pr["promoted"] is True
        hf = fc.call("openDurable", name="docA")["doc"]
        assert fc.call("save", doc=hf) == save_l
        fc.call("put", doc=hf, obj="_root", prop="after", value=1)
        fc.call("commit", doc=hf)
        c.close()
        fc.close()
    finally:
        led.stop()
        fol.stop()


def test_replication_cursor_survives_follower_restart(tmp_path):
    fol = start_node(tmp_path, "f1", role="follower")
    fol_addr = addr_of(fol)
    led = start_node(tmp_path, "l1", role="leader",
                     replicate_to=[fol_addr], ack_replicas=1)
    try:
        snapshots = []
        orig_snapshot = led.rpc.hub.snapshot
        led.rpc.hub.snapshot = lambda name: (
            snapshots.append(name) or orig_snapshot(name))

        c = Client(led.address)
        d = c.call("openDurable", name="docA")["doc"]
        for i in range(6):
            c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
            c.call("commit", doc=d)
        first_snapshots = len(snapshots)  # the initial catch-up
        fol.stop()

        fol2 = start_node(tmp_path, "f1", role="follower")
        try:
            led.rpc.hub.remove_follower(fol_addr)
            c.call("clusterReplicateTo", addr=addr_of(fol2))
            for i in range(6, 12):
                c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
                c.call("commit", doc=d)
            fc = Client(fol2.address)
            st = fc.call("clusterStatus")
            cur = st["docs"]["docA"]["cursor"]
            assert cur["lsn"] >= 12
            # the restart resumed from the persisted cursor: the journal
            # tail shipped, no second snapshot
            assert len(snapshots) == first_snapshots, snapshots
            fc.close()
            c.close()
        finally:
            fol2.stop()
    finally:
        led.stop()


def test_ack_gate_times_out_without_followers(tmp_path, monkeypatch):
    monkeypatch.setenv("AUTOMERGE_TPU_CLUSTER_ACK_TIMEOUT", "0.3")
    led = start_node(tmp_path, "l1", role="leader", ack_replicas=1)
    try:
        c = Client(led.address)
        d = c.call("openDurable", name="docA")["doc"]
        c.call("put", doc=d, obj="_root", prop="k", value=1)
        r = c.call("commit", doc=d, allow_error=True)
        # no follower can confirm the write: the ack MUST NOT happen
        assert "error" in r, r
        assert "ReplicationTimeout" in r["error"]["type"], r
        c.close()
    finally:
        led.stop()


# -- cluster-wide trace propagation -------------------------------------------


def test_trace_propagates_router_leader_follower(tmp_path):
    """One traced client write: the router span parents into the client
    context, the leader's request span parents into the ROUTER span, the
    leader's group-commit fsync links the trace, and the follower's
    replicated apply links back — the parent/link chain the flight
    recorder's merged timeline renders (all in-process here, so one
    recorder sees every hop)."""
    from automerge_tpu import obs

    obs.reset_all()
    fol = start_node(tmp_path, "f1", role="follower")
    led = start_node(tmp_path, "l1", role="leader",
                     replicate_to=[addr_of(fol)], ack_replicas=1)
    router = ClusterRouter([[addr_of(led), addr_of(fol)]], heartbeat=5.0)
    router.start()
    try:
        c = Client(router.address)
        d = c.call("openDurable", name="docT")["doc"]
        tid = "e2e-trace-1"

        def traced(method, **params):
            c.rid += 1
            c.sock.sendall((json.dumps(
                {"id": c.rid, "method": method, "params": params,
                 "trace": {"t": tid, "s": 12345}}
            ) + "\n").encode())
            resp = json.loads(c.f.readline())
            assert "error" not in resp, resp
            return resp.get("result")

        traced("put", doc=d, obj="_root", prop="k", value=1)
        traced("commit", doc=d)  # quorum ack: follower holds it durably
        c.close()

        spans = obs.recorder.snapshot()
        in_trace = [r for r in spans if r.trace_id == tid]
        names = {r.name for r in in_trace}
        # router hop: parented into the client's (remote) span id
        router_spans = [r for r in in_trace if r.name == "router.request"]
        assert router_spans and all(
            r.parent_id == 12345 for r in router_spans)
        # leader hop: rpc.request parented into a ROUTER span
        router_ids = {r.span_id for r in router_spans}
        node_reqs = [r for r in in_trace if r.name == "rpc.request"]
        assert node_reqs and any(
            r.parent_id in router_ids for r in node_reqs)
        # the durable write path nests inside the traced request
        assert "journal.append" in names
        # group commit attribution: some fsync links the trace
        fsyncs = [r for r in spans if r.name == "journal.fsync" and r.links]
        assert any(t == tid for r in fsyncs for t, _s in r.links)
        # follower hop: the shipped batch's apply links the client trace
        applies = [r for r in spans if r.name == "repl.apply"]
        assert applies and any(
            t == tid for r in applies if r.links for t, _s in r.links)
        # and the ship span itself carries the link on the leader side
        ships = [r for r in spans if r.name == "cluster.ship_batch"]
        assert any(
            t == tid for r in ships if r.links for t, _s in r.links)
    finally:
        router.stop()
        led.stop()
        fol.stop()


# -- the router tier ----------------------------------------------------------


def test_router_proxies_and_virtualizes_handles(tmp_path):
    n0 = start_node(tmp_path, "n0", role="leader")
    router = ClusterRouter([[addr_of(n0)]], heartbeat=5.0)
    router.start()
    try:
        c = Client(router.address)
        d = c.call("openDurable", name="docA")["doc"]
        for i in range(10):
            c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
        c.call("commit", doc=d)
        assert c.call("length", doc=d, obj="_root") == 10
        assert c.call("get", doc=d, obj="_root", prop="k7") == 7
        # reopening the same name returns the SAME virtual handle
        assert c.call("openDurable", name="docA")["doc"] == d
        # plain (anchor-routed) docs work too
        p = c.call("create")["doc"]
        assert p != d
        c.call("put", doc=p, obj="_root", prop="x", value=1)
        c.call("commit", doc=p)
        info = c.call("clusterInfo")
        assert info["groups"][0]["up"] is True
        c.close()
    finally:
        router.stop()
        n0.stop()


def test_cluster_metrics_merges_nodes_with_labels(tmp_path):
    """clusterMetrics fans out to every node and merges the families
    under node labels; the cluster-metrics CLI scrapes it."""
    from automerge_tpu.cli import main
    from automerge_tpu.obs.metrics import parse_prometheus

    fol = start_node(tmp_path, "f1", role="follower")
    led = start_node(tmp_path, "l1", role="leader",
                     replicate_to=[addr_of(fol)], ack_replicas=1)
    router = ClusterRouter([[addr_of(led), addr_of(fol)]], heartbeat=5.0)
    router.start()
    try:
        c = Client(router.address)
        d = c.call("openDurable", name="docM")["doc"]
        c.call("put", doc=d, obj="_root", prop="x", value=1)
        c.call("commit", doc=d)
        res = c.call("clusterMetrics")
        assert res["format"] == "prometheus" and not res["unreachable"]
        parsed = parse_prometheus(res["body"])
        nodes = {dict(k[1]).get("node") for k in parsed}
        # every sample labeled; router + both nodes present
        assert None not in nodes
        assert nodes >= {"router", addr_of(led), addr_of(fol)}
        # per-doc gauges rode along from the leader
        assert ("doc_journal_bytes",
                (("doc", "docM"), ("node", addr_of(led)))) in parsed
        # one merged family set: a single TYPE line per family
        assert res["body"].count("# TYPE rpc_request_count") <= 1
        c.close()
        # the CLI scrape returns the same body shape
        out = tmp_path / "cm.prom"
        rc = main(["cluster-metrics", "%s:%d" % router.address,
                   "-o", str(out)])
        assert rc == 0
        assert 'node="router"' in out.read_text()
    finally:
        router.stop()
        led.stop()
        fol.stop()


def _kill_node_sockets(node):
    """Simulate abrupt node death for in-process tests: stop listening
    and cut every connection without any flush (the real kill -9 sweep
    lives in scripts/ci/run_cluster)."""
    node._shutdown.set()
    if node._listener is not None:
        node._listener.close()
    with node._conns_lock:
        conns = list(node._conns.values())
    for conn in conns:
        conn.close()


def test_router_failover_zero_acked_loss(tmp_path):
    fol1 = start_node(tmp_path, "n1", role="follower")
    fol2 = start_node(tmp_path, "n2", role="follower")
    led = start_node(
        tmp_path, "n0", role="leader",
        replicate_to=[addr_of(fol1), addr_of(fol2)], ack_replicas=1,
    )
    led_addr = addr_of(led)
    router = ClusterRouter(
        [[led_addr, addr_of(fol1), addr_of(fol2)]],
        heartbeat=0.1, miss_limit=3,
    )
    router.start()
    try:
        c = Client(router.address)
        d = c.call("openDurable", name="docA")["doc"]
        sess = c.call("syncSessionAttach", doc=d, peer="client-x")
        acked = []
        for i in range(10):
            c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
            c.call("commit", doc=d)
            acked.append(i)

        _kill_node_sockets(led)
        # keep writing through the failover: Unavailable is retriable
        i, deadline = 10, time.monotonic() + 30
        while i < 16:
            assert time.monotonic() < deadline, "failover never completed"
            r1 = c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i,
                        allow_error=True)
            if "error" in (r1 or {}):
                time.sleep(0.05)
                continue
            r2 = c.call("commit", doc=d, allow_error=True)
            if "error" in (r2 or {}):
                time.sleep(0.05)
                continue
            acked.append(i)
            i += 1

        info = c.call("clusterInfo")
        assert info["groups"][0]["gen"] >= 1
        assert info["groups"][0]["leader"] != led_addr
        # zero acked-write loss: every acked key is readable
        for i in acked:
            assert c.call("get", doc=d, obj="_root", prop=f"k{i}") == i
        # the attached session re-materializes on the new leader with a
        # bumped epoch (the client side would epoch-handshake, not
        # full-resync)
        sess2 = c.call("syncSessionAttach", doc=d, peer="client-x")
        assert sess2["epoch"] >= 2
        c.close()
    finally:
        router.stop()
        for n in (led, fol1, fol2):
            n.stop()


def test_router_live_migration_between_groups(tmp_path):
    n0 = start_node(tmp_path, "g0", role="leader")
    n1 = start_node(tmp_path, "g1", role="leader")
    router = ClusterRouter([[addr_of(n0)], [addr_of(n1)]], heartbeat=5.0)
    router.start()
    try:
        c = Client(router.address)
        d = c.call("openDurable", name="migdoc")["doc"]
        sess = c.call("syncSessionAttach", doc=d, peer="mig-peer")
        for i in range(20):
            c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
        c.call("commit", doc=d)
        home = HashRing([0, 1]).member_for("migdoc")
        target = 1 - home
        res = c.call("clusterMigrate", name="migdoc", to=target)
        assert res["migrated"] is True
        # reads and writes keep flowing through the same virtual handle
        for i in range(20):
            assert c.call("get", doc=d, obj="_root", prop=f"k{i}") == i
        c.call("put", doc=d, obj="_root", prop="after", value="moved")
        c.call("commit", doc=d)
        assert c.call("get", doc=d, obj="_root", prop="after") == "moved"
        # the attached session moved WITH the doc: the same virtual
        # handle re-attaches on the destination (epoch bumped), instead
        # of routing to the source's freed copy
        stats = c.call("syncSessionStats", session=sess["session"])
        assert stats["epoch"] > sess["epoch"]
        assert c.call("clusterInfo")["overrides"] == {"migdoc": target}
        # the source released its journal flock
        src_dir = os.path.join(
            str(tmp_path), ["g0", "g1"][home], "migdoc")
        dd = AutoDoc.open(src_dir)
        dd.close()
        c.close()
    finally:
        router.stop()
        n0.stop()
        n1.stop()


def test_cold_doc_is_cheap_migration_source(tmp_path):
    """A document demoted to the cold tier migrates as on-disk
    snapshot+tail bytes: no hydration on the source, contents intact on
    the target, ``cluster.migrate_cold_source`` actually fired."""
    from automerge_tpu import obs

    n0 = start_node(tmp_path, "cg0", role="leader")
    n1 = start_node(tmp_path, "cg1", role="leader")
    router = ClusterRouter([[addr_of(n0)], [addr_of(n1)]], heartbeat=5.0)
    router.start()
    try:
        c = Client(router.address)
        d = c.call("openDurable", name="colddoc")["doc"]
        for i in range(12):
            c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
        c.call("commit", doc=d)
        home = HashRing([0, 1]).member_for("colddoc")
        src = [n0, n1][home]
        # demote on the source node: journal closed, op-store dropped
        src.rpc.store.demote("colddoc", "cold", "test")
        assert src.rpc.store.tier("colddoc") == "cold"
        before = obs.legacy_counters.get("cluster.migrate_cold_source", 0)
        res = c.call("clusterMigrate", name="colddoc", to=1 - home)
        assert res["migrated"] is True
        after = obs.legacy_counters.get("cluster.migrate_cold_source", 0)
        # both phases (live read + authoritative re-read under the
        # routing pause) took the cold path: the doc was never hydrated
        # on the source — no residency rebuild happened
        assert after - before >= 2, (before, after)
        # the source released the migrated doc entirely
        assert src.rpc.store.tier("colddoc") is None
        # the doc stayed cold on the source for the whole handoff (no
        # residency rebuild) and the target serves the full contents
        for i in range(12):
            assert c.call("get", doc=d, obj="_root", prop=f"k{i}") == i
        c.call("put", doc=d, obj="_root", prop="after", value="moved")
        c.call("commit", doc=d)
        assert c.call("get", doc=d, obj="_root", prop="after") == "moved"
        c.close()
    finally:
        router.stop()
        n0.stop()
        n1.stop()


def test_follower_replica_hydrates_from_cold_on_apply(tmp_path):
    """Replication keeps flowing to a replica the follower's own store
    demoted to cold: the next shipped batch hydrates it in place and the
    persisted cursor survives the demote/hydrate cycle."""
    fol = start_node(tmp_path, "fcold_f", role="follower")
    led = start_node(tmp_path, "fcold_l", role="leader",
                     replicate_to=[addr_of(fol)])
    try:
        c = Client(led.address)
        d = c.call("openDurable", name="repdoc")["doc"]
        c.call("put", doc=d, obj="_root", prop="a", value=1)
        c.call("commit", doc=d)
        wait_until(
            lambda: fol.rpc.store is not None
            and fol.rpc.store.tier("repdoc") is not None,
            msg="follower opened the replica",
        )
        wait_until(
            lambda: (lambda dd: dd is not None and not getattr(
                dd, "_closed", True) and dd.get("_root", "a") is not None)(
                    fol.rpc._docs.get(
                        fol.rpc._durable_names.get("repdoc"))),
            msg="follower applied the first record",
        )
        fol.rpc.store.demote("repdoc", "cold", "test")
        assert fol.rpc.store.tier("repdoc") == "cold"
        c.call("put", doc=d, obj="_root", prop="b", value=2)
        c.call("commit", doc=d)

        def _fol_has_b():
            h = fol.rpc._durable_names.get("repdoc")
            dd = fol.rpc._docs.get(h)
            if dd is None or getattr(dd, "_closed", False):
                return False
            got = dd.get("_root", "b")
            return got is not None
        wait_until(_fol_has_b, msg="cold follower replica hydrated + applied")
        assert fol.rpc.store.tier("repdoc") == "warm"
        c.close()
    finally:
        led.stop()
        fol.stop()


# -- batched follower apply (host_batch feed point) ---------------------------


def test_follower_apply_batching_feeds_device_mirrors(tmp_path):
    """Shipped records drain through the batched follower path: same-doc
    runs share an ack scope, the repl_apply_batch_size histogram
    observes the drains, and every replica's resident device mirror is
    fed through the vectorized cross-doc staging — mirrors converge to
    the leader's state without a rebuild."""
    from automerge_tpu import obs

    fol = start_node(tmp_path, "fb1", role="follower")
    led = start_node(tmp_path, "lb1", role="leader",
                     replicate_to=[addr_of(fol)], ack_replicas=1)
    try:
        fc = Client(fol.address)
        fh = {}
        for name in ("dA", "dB", "dC"):
            # replicas opened WITH device mirrors on the follower
            # (openDurable is follower-ok)
            fh[name] = fc.call("openDurable", name=name, device=True)["doc"]
        c = Client(led.address)
        for name in ("dA", "dB", "dC"):
            d = c.call("openDurable", name=name)["doc"]
            for i in range(6):
                c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
                c.call("commit", doc=d)
        for name in ("dA", "dB", "dC"):
            doc = fol.rpc._docs[fh[name]]

            def fresh(doc=doc):
                with doc.lock:
                    dev = doc.device_doc
                    if dev is None:
                        return False
                    got = dev.hydrate().get("k5")
                    return got == ("scalar", 5) or got == 5
            # generous deadline: three mirrors drain through shared
            # batched launches behind jit warmup — under CI load the
            # first convergence can take well past the default 10s
            # without anything being wrong
            wait_until(fresh, timeout=60.0,
                       msg=f"device mirror of {name} converged")
        hist = [e for e in obs.snapshot()
                if e["name"] == "cluster.repl_apply_batch_size"]
        assert hist and hist[0]["count"] > 0, hist
        c.close()
        fc.close()
    finally:
        led.stop()
        fol.stop()


def test_follower_apply_serial_knob_restores_old_path(tmp_path, monkeypatch):
    """AUTOMERGE_TPU_REPL_BATCH=0 forces the pre-batching serial path:
    no coalesced drains (mirror stays untouched — the A/B baseline),
    replication itself still converges."""
    monkeypatch.setenv("AUTOMERGE_TPU_REPL_BATCH", "0")
    from automerge_tpu import obs

    before = [e for e in obs.snapshot()
              if e["name"] == "cluster.repl_apply_batch_size"]
    n_before = before[0]["count"] if before else 0
    fol = start_node(tmp_path, "fs1", role="follower")
    led = start_node(tmp_path, "ls1", role="leader",
                     replicate_to=[addr_of(fol)], ack_replicas=1)
    try:
        fc = Client(fol.address)
        fh = fc.call("openDurable", name="dS", device=True)["doc"]
        c = Client(led.address)
        d = c.call("openDurable", name="dS")["doc"]
        for i in range(4):
            c.call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
            c.call("commit", doc=d)
        # quorum acks already guarantee the follower holds the records
        st = fc.call("clusterStatus")
        assert st["docs"]["dS"]["cursor"]["lsn"] >= 4
        doc = fol.rpc._docs[fh]
        with doc.lock:
            # host state converged, the mirror was NOT fed (old behavior)
            assert doc.get("_root", "k3") is not None
            assert doc.device_doc.hydrate() == {}
        after = [e for e in obs.snapshot()
                 if e["name"] == "cluster.repl_apply_batch_size"]
        n_after = after[0]["count"] if after else 0
        assert n_after == n_before, (n_before, n_after)
        c.close()
        fc.close()
    finally:
        led.stop()
        fol.stop()
