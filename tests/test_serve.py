"""The concurrent serving layer: per-document shards, socket transport,
group-commit durability, sync coalescing, backpressure.

Three layers: ShardPool units (ordering/bounding/parallelism), in-process
``SocketRpcServer`` integration over real sockets with concurrent client
threads, and the group-commit durability contract (fsync amortization
plus a crashsim sweep in test_durability.py proving the acked-prefix
guarantee survives batching).
"""

import base64
import json
import socket
import threading
import time

import pytest

from automerge_tpu import obs
from automerge_tpu import trace
from automerge_tpu.api import AutoDoc
from automerge_tpu.serve import QueueFull, ShardPool, SocketRpcServer
from automerge_tpu.types import ActorId


# -- ShardPool units ----------------------------------------------------------


def test_shard_pool_per_key_fifo_and_cross_key_parallel():
    """Items for one key execute in submission order (even across many
    drains); two keys can be in flight on two workers at once."""
    order = {"a": [], "b": []}
    in_flight = set()
    overlap = []
    lock = threading.Lock()
    both_in = threading.Event()

    def execute(key, items):
        with lock:
            in_flight.add(key)
            if len(in_flight) == 2:
                overlap.append(True)
                both_in.set()
        if 0 in items:
            # each key's FIRST batch parks until both keys are in flight
            # (or the 2s timeout proves they never overlap)
            both_in.wait(2)
        order[key].extend(items)
        with lock:
            in_flight.discard(key)

    pool = ShardPool(execute, workers=2, max_queue=64, max_batch=4)
    for i in range(16):
        pool.submit("a", i)
        pool.submit("b", i)
    pool.stop(drain=True)
    assert order["a"] == list(range(16))
    assert order["b"] == list(range(16))
    assert overlap, "two keys never executed concurrently"


def test_shard_pool_backpressure_raises_queue_full():
    blocker = threading.Event()
    started = threading.Event()

    def execute(key, items):
        started.set()
        blocker.wait(10)

    pool = ShardPool(execute, workers=1, max_queue=2, max_batch=1)
    pool.submit("d", 0)
    started.wait(5)  # worker is now stuck holding item 0
    pool.submit("d", 1)
    pool.submit("d", 2)
    with pytest.raises(QueueFull):
        pool.submit("d", 3)
    blocker.set()
    pool.stop(drain=True)


def test_shard_pool_in_flight_counts_busy_and_ready_drains():
    blocker = threading.Event()
    started = threading.Semaphore(0)

    def execute(key, items):
        started.release()
        blocker.wait(10)

    pool = ShardPool(execute, workers=2, max_queue=4, max_batch=1)
    try:
        assert pool.in_flight() == 0
        for key in ("a", "b", "c"):
            pool.submit(key, 0)
        assert started.acquire(timeout=5) and started.acquire(timeout=5)
        # two workers drain "a" and "b"; "c" waits for one of them; a
        # second request of a draining document adds no drain
        pool.submit("a", 1)
        assert pool.in_flight() == 3
    finally:
        blocker.set()
        pool.stop(drain=True)
    assert pool.in_flight() == 0


def test_shard_pool_single_writer_per_key():
    """Even with many workers, one key is never executed by two workers
    at once — the single-writer guarantee documents rely on."""
    active = []
    bad = []
    lock = threading.Lock()

    def execute(key, items):
        with lock:
            if key in active:
                bad.append(key)
            active.append(key)
        time.sleep(0.001)
        with lock:
            active.remove(key)

    pool = ShardPool(execute, workers=8, max_queue=512, max_batch=2)
    for i in range(64):
        pool.submit("hot", i)
        pool.submit(f"cold{i % 4}", i)
    pool.stop(drain=True)
    assert not bad


# -- socket server integration ------------------------------------------------


class Client:
    """Minimal pipelining JSON-RPC socket client for the tests."""

    def __init__(self, address):
        self.sock = socket.create_connection(address)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.f = self.sock.makefile("r")
        self.rid = 0

    def pipeline(self, reqs, allow_errors=False):
        first = self.rid + 1
        lines = []
        for method, params in reqs:
            self.rid += 1
            lines.append(json.dumps(
                {"id": self.rid, "method": method, "params": params}))
        self.sock.sendall(("\n".join(lines) + "\n").encode())
        by = {}
        while len(by) < len(reqs):
            resp = json.loads(self.f.readline())
            if not allow_errors:
                assert "error" not in resp, resp
            by[resp["id"]] = resp
        return [by[first + i] for i in range(len(reqs))]

    def call(self, method, **params):
        resp = self.pipeline([(method, params)])[0]
        return resp.get("result")

    def close(self):
        self.sock.close()


@pytest.fixture
def server(tmp_path):
    srv = SocketRpcServer(
        host="127.0.0.1", port=0, durable_dir=str(tmp_path), workers=4
    )
    srv.start()
    yield srv
    srv.stop()


def test_concurrent_clients_distinct_docs(server):
    """Clients editing different documents run in parallel and none of
    the frames garble or drop."""
    errs = []

    def one(ci):
        try:
            c = Client(server.address)
            d = c.call("create", actor=f"{ci:02x}" * 16)["doc"]
            for k in range(30):
                c.call("put", doc=d, obj="_root", prop=f"k{k}", value=k)
            c.call("commit", doc=d)
            assert c.call("length", doc=d, obj="_root") == 30
            c.close()
        except Exception as e:  # noqa: BLE001 — surface in main thread
            errs.append(f"{ci}: {e}")

    ts = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs


def test_same_doc_requests_keep_arrival_order(server):
    """Pipelined writes to one doc apply in order: the final read sees
    the last write, and a historical read at each commit is consistent."""
    c = Client(server.address)
    d = c.call("create")["doc"]
    reqs = []
    for k in range(50):
        reqs.append(("put", {"doc": d, "obj": "_root", "prop": "x",
                             "value": k}))
    reqs.append(("commit", {"doc": d}))
    reqs.append(("get", {"doc": d, "obj": "_root", "prop": "x"}))
    resps = c.pipeline(reqs)
    assert resps[-1]["result"] == 49
    c.close()


def test_group_commit_amortizes_fsyncs(server):
    """The acceptance gate: >=4 concurrent committers against ONE durable
    doc, journal fsync count strictly below the commit-request count
    (journal.fsync{policy} span counter), and every acked key durable
    after reopening the directory."""
    trace.reset_timers()
    n_clients, n_commits = 4, 8
    errs = []

    def committer(ci):
        try:
            c = Client(server.address)
            d = c.call("openDurable", name="grp")["doc"]
            reqs = []
            for k in range(n_commits):
                reqs.append(("put", {"doc": d, "obj": "_root",
                                     "prop": f"c{ci}_{k}", "value": k}))
                reqs.append(("commit", {"doc": d}))
            c.pipeline(reqs)
            c.close()
        except Exception as e:  # noqa: BLE001
            errs.append(f"{ci}: {e}")

    ts = [threading.Thread(target=committer, args=(i,))
          for i in range(n_clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    total_commit_requests = n_clients * n_commits
    fsyncs = trace.timing_summary().get("journal.fsync", {}).get("n", 0)
    assert 0 < fsyncs < total_commit_requests, (
        f"{fsyncs} fsyncs for {total_commit_requests} commit requests — "
        "group commit did not amortize"
    )
    # the batch-size histogram saw at least one multi-append fsync
    h = obs.registry.histogram("group_commit.batch_size")
    assert h.n > 0 and h.vmax >= 2, (h.n, h.vmax)
    # durability: close via server stop, then reopen and check every key
    server.stop()
    dd = AutoDoc.open(str(server.rpc.durable_dir) + "/grp")
    keys = set(dd.keys())
    missing = [
        f"c{ci}_{k}" for ci in range(n_clients) for k in range(n_commits)
        if f"c{ci}_{k}" not in keys
    ]
    dd.close()
    assert not missing, missing


def test_backpressure_error_surfaces_and_server_survives(tmp_path):
    """A full per-doc queue answers Backpressure immediately; the dropped
    requests are visible in rpc.errors and the server keeps serving."""
    srv = SocketRpcServer(
        host="127.0.0.1", port=0, durable_dir=str(tmp_path),
        workers=1, max_queue=4, max_batch=1,
    )
    srv.start()
    try:
        c = Client(srv.address)
        d = c.call("openDurable", name="bp")["doc"]  # fsync=always: slow
        reqs = []
        for k in range(60):
            reqs.append(("put", {"doc": d, "obj": "_root",
                                 "prop": f"k{k}", "value": k}))
            reqs.append(("commit", {"doc": d}))
        resps = c.pipeline(reqs, allow_errors=True)
        kinds = [
            r["error"]["type"] if "error" in r else "ok" for r in resps
        ]
        assert "Backpressure" in kinds, kinds[:20]
        assert "ok" in kinds
        # nothing else leaked out of the queue bound
        assert set(kinds) <= {"ok", "Backpressure"}, set(kinds)
        # the server still answers new work afterwards
        assert c.call("length", doc=d, obj="_root") >= 1
        c.close()
    finally:
        srv.stop()


def test_merge_across_shards_under_concurrent_edits(server):
    """merge(doc, other) locks both documents (sorted order): racing
    edits to the source never corrupt the merge target."""
    c = Client(server.address)
    a = c.call("create", actor="aa" * 16)["doc"]
    b = c.call("create", actor="bb" * 16)["doc"]
    c.call("put", doc=b, obj="_root", prop="seed", value=1)
    c.call("commit", doc=b)
    errs = []
    stop = threading.Event()

    def editor():
        try:
            c2 = Client(server.address)
            k = 0
            while not stop.is_set():
                c2.call("put", doc=b, obj="_root", prop=f"e{k}", value=k)
                c2.call("commit", doc=b)
                k += 1
            c2.close()
        except Exception as e:  # noqa: BLE001
            errs.append(str(e))

    t = threading.Thread(target=editor)
    t.start()
    try:
        for _ in range(10):
            c.call("merge", doc=a, other=b)
    finally:
        stop.set()
        t.join()
    assert not errs, errs
    assert c.call("get", doc=a, obj="_root", prop="seed") == 1
    c.close()


def test_receive_sync_coalescing_feeds_device_once(server):
    """A pipelined run of receiveSyncMessage frames for one durable
    device doc coalesces the resident-device feed into apply_batches;
    the device log ends exactly in sync with the host history."""
    c = Client(server.address)
    d = c.call("openDurable", name="dev", device=True)["doc"]
    # three peers, each pushing its own changes through the sync protocol
    peers = []
    for i in range(3):
        p = c.call("create", actor=f"{i + 1:02x}" * 16)["doc"]
        for k in range(4):
            c.call("put", doc=p, obj="_root", prop=f"p{i}_{k}", value=k)
        c.call("commit", doc=p)
        sp = c.call("syncStateNew")["sync"]
        sd = c.call("syncStateNew")["sync"]
        peers.append((p, sp, sd))
    trace.reset_counters()
    # drive rounds; each round pipelines every peer's frame so the runs
    # are adjacent in the doc's queue
    for _ in range(10):
        frames = []
        for p, sp, sd in peers:
            m = c.call("generateSyncMessage", doc=p, sync=sp)
            if m is not None:
                frames.append(("receiveSyncMessage",
                               {"doc": d, "sync": sd, "data": m}))
        if not frames:
            break
        c.pipeline(frames)
        for p, sp, sd in peers:
            back = c.call("generateSyncMessage", doc=d, sync=sd)
            if back is not None:
                c.call("receiveSyncMessage", doc=p, sync=sp, data=back)
    # host absorbed every peer's keys
    keys = c.call("keys", doc=d, obj="_root")
    for i in range(3):
        for k in range(4):
            assert f"p{i}_{k}" in keys
    # the resident device doc tracked the host exactly
    dd = server.rpc._docs[d]
    assert dd.device_doc is not None
    assert len(dd.device_doc.log.changes) == len(dd.doc.history)
    assert trace.counters.get("rpc.coalesced", 0) >= 2
    c.close()


def test_served_apply_changes_takes_the_chip_route(server):
    """On the CPU backend as on the chip, a served applyChanges to a
    device-mirrored document joins the cross-document batcher (a
    ``device.batch_window`` span) and its document resolves through the
    packed transport (a ``device.kernel`` span with ``program=packed``)."""
    c = Client(server.address)
    d = c.call("openDurable", name="route", device=True)["doc"]
    w = AutoDoc(actor=ActorId(bytes([4]) * 16))
    changes = []
    for i in range(2):
        w.put("_root", f"k{i}", i)
        w.commit()
        changes.append(base64.b64encode(
            w.get_last_local_change().raw_bytes).decode())
    c.call("applyChanges", doc=d, data=changes[0])
    obs.recorder.clear()
    c.call("applyChanges", doc=d, data=changes[1])
    spans = obs.recorder.snapshot()
    assert [r for r in spans if r.name == "device.batch_window"]
    assert [r for r in spans if r.name == "device.kernel"
            and r.fields.get("program") == "packed"]
    dd = server.rpc._docs[d]
    assert dd.device_doc is not None
    assert dd.device_doc.hydrate() == {"k0": 0, "k1": 1}
    c.close()


@pytest.mark.parametrize("pipelined", [False, True])
def test_failed_device_feed_fails_apply_changes(server, monkeypatch,
                                                pipelined):
    """A device feed that raises fails the applyChanges requests it
    carried (never an ack), counts sync.device_feed_error and drops the
    stale mirror; later writes are served from the host."""
    from automerge_tpu.ops import DeviceDoc
    from automerge_tpu.ops.batched import CrossDocBatcher

    c = Client(server.address)
    d = c.call("openDurable", name="feed", device=True)["doc"]
    w = AutoDoc(actor=ActorId(bytes([3]) * 16))
    changes = []
    for i in range(3):
        w.put("_root", f"k{i}", i)
        w.commit()
        changes.append(base64.b64encode(
            w.get_last_local_change().raw_bytes).decode())

    def boom(self, *args):
        raise RuntimeError("device lost")

    # either route a feed takes: per document, or the cross-doc batcher
    monkeypatch.setattr(DeviceDoc, "apply_batches", boom)
    monkeypatch.setattr(CrossDocBatcher, "apply", boom)
    before = trace.counters.get("sync.device_feed_error", 0)
    n = 2 if pipelined else 1
    resps = c.pipeline([("applyChanges", {"doc": d, "data": ch})
                        for ch in changes[:n]], allow_errors=True)
    assert all("device lost" in r["error"]["message"] for r in resps), resps
    assert trace.counters.get("sync.device_feed_error", 0) == before + 1
    assert server.rpc._docs[d].device_doc is None
    for ch in changes[n:]:
        c.call("applyChanges", doc=d, data=ch)
    assert c.call("keys", doc=d, obj="_root") == ["k0", "k1", "k2"]
    c.close()


def test_hostile_frames_over_socket(server):
    """Garbled JSON, oversized lines and unknown methods answer errors
    over the socket without killing the connection or the server."""
    c = Client(server.address)
    c.call("configure", maxRequestBytes=4096)
    c.sock.sendall(b"this is not json\n")
    resp = json.loads(c.f.readline())
    assert resp["error"]["type"] == "ParseError"
    c.sock.sendall(b"Z" * 10_000 + b"\n")
    resp = json.loads(c.f.readline())
    assert resp["error"]["type"] == "RequestTooLarge"
    assert c.call("create")["doc"] >= 1  # connection still serves
    c.close()


def test_shutdown_request_flushes_and_releases(tmp_path):
    """The shutdown ack means: durable docs flushed, flocks released."""
    srv = SocketRpcServer(host="127.0.0.1", port=0,
                          durable_dir=str(tmp_path), workers=2)
    srv.start()
    c = Client(srv.address)
    d = c.call("openDurable", name="sd")["doc"]
    c.call("put", doc=d, obj="_root", prop="n", value=7)  # no commit
    assert c.call("shutdown") is None
    srv.wait_stopped(10)
    # the pending autocommit tx was flushed and the flock released
    dd = AutoDoc.open(str(tmp_path / "sd"))
    assert dd.hydrate() == {"n": 7}
    dd.close()
    c.close()


def test_unix_socket_transport(tmp_path):
    srv = SocketRpcServer(unix_path=str(tmp_path / "rpc.sock"), workers=2)
    srv.start()
    try:
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.connect(str(tmp_path / "rpc.sock"))
        f = sock.makefile("r")
        sock.sendall(b'{"id":1,"method":"create","params":{}}\n')
        assert json.loads(f.readline())["result"]["doc"] == 1
        sock.close()
    finally:
        srv.stop()
    assert not (tmp_path / "rpc.sock").exists()  # socket file cleaned up


# -- transport-death visibility (stdio satellite) -----------------------------


def test_stdio_transport_death_is_counted():
    """A read or write failure on the stdio loop increments
    rpc.errors{type=transport} instead of dying silently."""
    from automerge_tpu.rpc import RpcServer

    class Exploding:
        def readline(self, limit=None):
            raise OSError("carrier lost")

    trace.reset_counters()
    RpcServer().serve(stdin=Exploding(), stdout=None)
    assert trace.counters.get("rpc.errors", 0) >= 1

    class OkOnce:
        def __init__(self):
            self.lines = ['{"id":1,"method":"create"}\n', ""]

        def readline(self, limit=None):
            return self.lines.pop(0)

    class BrokenOut:
        def write(self, s):
            raise BrokenPipeError("gone")

        def flush(self):
            pass

    before = trace.counters.get("rpc.errors", 0)
    RpcServer().serve(stdin=OkOnce(), stdout=BrokenOut())
    assert trace.counters.get("rpc.errors", 0) > before


# -- sync session coalescing unit --------------------------------------------


def test_session_receive_many_batches_device_feed():
    """receive_many defers per-message device feeds into ONE
    apply_batches call with one batch per message carrying changes."""
    from automerge_tpu.sync import SyncSession

    a = AutoDoc(actor=ActorId(bytes([1]) * 16))
    b = AutoDoc(actor=ActorId(bytes([2]) * 16))
    for i in range(3):
        a.put("_root", f"k{i}", i)
        a.commit()

    class RecordingDev:
        def __init__(self):
            self.batch_calls = []
            self.change_calls = []

        def apply_batches(self, batches):
            self.batch_calls.append([len(x) for x in batches])

        def apply_changes(self, changes):
            self.change_calls.append(len(changes))

    dev = RecordingDev()
    sa = SyncSession(a, epoch=1)
    sb = SyncSession(b, epoch=2, device_doc=dev)
    # run rounds, but deliver a->b frames through receive_many in groups
    pending = []
    for now in range(40):
        fa = sa.poll(now)
        if fa is not None:
            pending.append(fa)
        if len(pending) >= 2 or (fa is None and pending):
            sb.receive_many(list(pending), now)
            pending.clear()
        fb = sb.poll(now)
        if fb is not None:
            sa.receive(fb, now)
        if sa.converged() and sb.converged():
            break
    assert a.get_heads() == b.get_heads()
    # every change reached the device through the batched path only
    assert dev.batch_calls and not dev.change_calls
    total = sum(n for call in dev.batch_calls for n in call)
    assert total == len(b.doc.history)


def test_socket_session_resumes_across_server_restart(tmp_path):
    """The epoch-handshake restart-resume contract over the SOCKET
    transport: a client syncs with a durable server session
    (syncSessionAttach), the server process dies and restarts on the
    same directory, the client reconnects and re-attaches — the bumped
    epoch renegotiates from the persisted shared_heads and the session
    converges again with ZERO full resyncs on either side."""
    from automerge_tpu.sync import SessionConfig, SyncSession

    def drive(client_sess, c, server_session, rounds=60):
        """Pump frames between the in-process client session and the
        server session behind the RPC surface until both converge."""
        for now in range(rounds):
            frame = client_sess.poll(float(now))
            if frame is not None:
                c.call("syncSessionReceive", session=server_session,
                       data=base64.b64encode(frame).decode())
            back = c.call("syncSessionPoll", session=server_session)
            if back is not None:
                client_sess.receive(base64.b64decode(back), float(now))
            stats = c.call("syncSessionStats", session=server_session)
            if client_sess.converged() and stats["converged"]:
                return stats
        raise AssertionError("sessions never converged")

    local = AutoDoc(actor=ActorId(bytes([5]) * 16))
    for i in range(4):
        local.put("_root", f"pre{i}", i)
        local.commit()
    sess = SyncSession(local, epoch=1, config=SessionConfig(timeout=1000.0))

    srv = SocketRpcServer(
        host="127.0.0.1", port=0, durable_dir=str(tmp_path), workers=2
    )
    srv.start()
    c = Client(srv.address)
    d = c.call("openDurable", name="resume")["doc"]
    att = c.call("syncSessionAttach", doc=d, peer="client-A")
    stats = drive(sess, c, att["session"])
    assert stats["resyncs"] == 0 and sess.stats["resyncs"] == 0
    first_epoch = att["epoch"]
    c.close()
    srv.stop()

    # restart on the same directory; the client keeps ITS live session
    srv2 = SocketRpcServer(
        host="127.0.0.1", port=0, durable_dir=str(tmp_path), workers=2
    )
    srv2.start()
    try:
        c2 = Client(srv2.address)
        d2 = c2.call("openDurable", name="resume")["doc"]
        att2 = c2.call("syncSessionAttach", doc=d2, peer="client-A")
        # a new incarnation MUST present a new epoch or the client's dup
        # suppression would eat its frames
        assert att2["epoch"] > first_epoch
        local.put("_root", "post", "after-restart")
        local.commit()
        stats = drive(sess, c2, att2["session"])
        # the epoch handshake renegotiated (a reset happened) but nobody
        # fell back to a FULL resync
        assert stats["resyncs"] == 0, stats
        assert sess.stats["resyncs"] == 0, sess.stats
        assert sess.stats["resets"] >= 1  # the epoch bump was noticed
        assert c2.call("get", doc=d2, obj="_root", prop="post") \
            == "after-restart"
        assert c2.call("get", doc=d2, obj="_root", prop="pre2") == 2
        c2.close()
    finally:
        srv2.stop()
