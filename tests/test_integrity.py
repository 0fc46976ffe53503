"""End-to-end integrity (integrity.py): digest determinism across
residency modes, merge orders and round trips; read-back verification;
scrubber detect-and-repair; chaos bit rot; quarantine plumbing; and the
reference client's ``IntegrityError`` surfacing.
"""

import json
import os
import random
import socket
import threading

import pytest

from automerge_tpu import integrity, obs
from automerge_tpu.api import AutoDoc
from automerge_tpu.rpc import RpcServer
from automerge_tpu.storage.durable import JOURNAL_NAME, SNAPSHOT_NAME
from automerge_tpu.types import ActorId, ObjType


def actor(i):
    return ActorId(bytes([i]) * 16)


def _ctr(name):
    """Total across label sets of one counter (0 when never counted)."""
    return sum(
        e["value"] for e in obs.snapshot()
        if e["type"] == "counter" and e["name"] == name
    )


def _flip_byte(path, frac=0.5):
    data = open(path, "rb").read()
    i = int(len(data) * frac) % max(1, len(data))
    bad = data[:i] + bytes([data[i] ^ 0x40]) + data[i + 1:]
    with open(path, "wb") as f:
        f.write(bad)
    return i


def _build_forks(seed, n_forks=4, edits=5):
    rng = random.Random(seed)
    base = AutoDoc(actor=actor(1))
    t = base.put_object("_root", "text", ObjType.TEXT)
    base.splice_text(t, 0, 0, "seed text")
    base.put("_root", "n", 0)
    base.commit()
    forks = []
    for i in range(n_forks):
        f = base.fork(actor=actor(10 + i))
        for e in range(edits):
            if rng.random() < 0.5:
                pos = rng.randrange(0, f.length(t) + 1)
                f.splice_text(t, pos, 0, f"w{i}.{e} ")
            else:
                f.put("_root", f"k{i}", e * 7 + i)
            f.commit()
        forks.append(f)
    return base, t, forks


# -- digest determinism property suite ----------------------------------------


@pytest.mark.parametrize("seed", [3, 11, 27])
def test_digest_invariant_across_merge_orders(seed):
    base, _t, forks = _build_forks(seed)
    rng = random.Random(seed + 1)
    digests = set()
    for _ in range(4):
        order = list(range(len(forks)))
        rng.shuffle(order)
        m = AutoDoc.load(base.save())
        for i in order:
            m.merge(forks[i])
        digests.add(integrity.doc_digest(m.doc)["digest"])
    assert len(digests) == 1, digests


@pytest.mark.parametrize("seed", [5, 19, 42])
def test_digest_invariant_under_out_of_order_delivery(seed):
    """Any causally-valid interleaving of per-fork change sequences
    (replication reordering across links) lands on the same digest."""
    base, _t, forks = _build_forks(seed)
    have = base.get_heads()
    per_fork = [list(f.get_changes(have)) for f in forks]
    rng = random.Random(seed * 13 + 1)
    digests = set()
    for _ in range(3):
        idx = [0] * len(per_fork)
        m = AutoDoc.load(base.save())
        while True:
            cand = [i for i in range(len(per_fork))
                    if idx[i] < len(per_fork[i])]
            if not cand:
                break
            i = rng.choice(cand)
            m.apply_changes([per_fork[i][idx[i]]])
            idx[i] += 1
        digests.add(integrity.doc_digest(m.doc)["digest"])
    assert len(digests) == 1, digests


def test_digest_invariant_across_residency_modes(monkeypatch):
    """Dense and compressed residency hold the same history, so the
    digest must not move; the column-level oracle (decoded resident
    image == dense image) backs it up."""
    base, _t, forks = _build_forks(7)
    m = AutoDoc.load(base.save())
    for f in forks:
        m.merge(f)
    want = None
    for comp in ("1", "0"):
        monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", comp)
        d = integrity.doc_digest(AutoDoc.load(m.save()).doc)
        if want is None:
            want = d
        assert d == want, (comp, d, want)
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    from automerge_tpu.ops.oplog import OpLog

    log = OpLog.from_documents([m])
    dense = integrity.column_digests(log, source="dense")
    resident = integrity.column_digests(log, source="resident")
    assert dense == resident


def test_digest_save_load_and_demote_hydrate_round_trips(tmp_path):
    srv = RpcServer(durable_dir=str(tmp_path / "docs"))
    try:
        h = srv.openDurable({"name": "rt"})["doc"]
        srv.put({"doc": h, "obj": "_root", "prop": "k", "value": 42})
        srv.commit({"doc": h})
        srv.put({"doc": h, "obj": "_root", "prop": "k2", "value": "x"})
        srv.commit({"doc": h})
        d1 = srv.docDigest({"name": "rt"})
        assert d1["changes"] == 2
        # handle addressing and name addressing agree
        assert srv.docDigest({"doc": h}) == d1
        # save/load round trip
        import base64

        loaded = AutoDoc.load(base64.b64decode(srv.save({"doc": h})))
        assert integrity.doc_digest(loaded.doc)["digest"] == d1["digest"]
        # demote to cold, digest by name hydrates and agrees
        srv.store.demote("rt", "cold")
        assert srv.docDigest({"name": "rt"}) == d1
    finally:
        srv.close_durables()


def test_durable_digest_incremental_matches_full(tmp_path):
    dd = AutoDoc.open(str(tmp_path / "d1"))
    base, _t, forks = _build_forks(9, n_forks=2, edits=3)
    dd.merge(base)
    for f in forks:
        dd.merge(f)
    got = dd.doc_digest()
    assert got == integrity.doc_digest(dd._core)
    dd.close()
    dd2 = AutoDoc.open(str(tmp_path / "d1"))
    assert dd2.doc_digest() == got  # recompute-on-open lands identically
    dd2.close()


def test_docdigest_unknown_name_is_an_error(tmp_path):
    srv = RpcServer(durable_dir=str(tmp_path / "docs"))
    try:
        resp = srv.handle({"id": 1, "method": "docDigest",
                           "params": {"name": "ghost"}})
        assert "error" in resp
    finally:
        srv.close_durables()


# -- read-back verification ----------------------------------------------------


def test_verify_doc_dir_clean_and_first_bad_offset(tmp_path):
    dd = AutoDoc.open(str(tmp_path / "v"))
    dd.put("_root", "k", "v" * 200)
    dd.commit()
    dd.compact()
    dd.close()
    path = str(tmp_path / "v")
    reports = integrity.verify_doc_dir(path)
    assert len(reports) == 2 and all(r.ok for r in reports), reports
    # snapshot bit flip: strict chunk walk reports the damaged frame
    _flip_byte(os.path.join(path, SNAPSHOT_NAME))
    bad = [r for r in integrity.verify_doc_dir(path) if not r.ok]
    assert [r.kind for r in bad] == ["snapshot"]
    assert bad[0].first_bad_offset is not None


def test_verify_journal_detects_mid_file_rot(tmp_path):
    dd = AutoDoc.open(str(tmp_path / "j"))
    for i in range(6):
        dd.put("_root", f"k{i}", "payload-%03d" % i)
        dd.commit()
    dd.close()
    jpath = os.path.join(str(tmp_path / "j"), JOURNAL_NAME)
    r = integrity.verify_journal_bytes(open(jpath, "rb").read())
    assert r.ok and r.units >= 6
    _flip_byte(jpath, frac=0.6)
    r = integrity.verify_journal_bytes(open(jpath, "rb").read())
    assert not r.ok and r.valid_bytes < r.total_bytes
    assert r.first_bad_offset == r.valid_bytes


# -- device-mirror audit --------------------------------------------------------


def test_compressed_verify_against_catches_divergence(monkeypatch):
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    from automerge_tpu.ops.oplog import OpLog

    base, _t, forks = _build_forks(21, n_forks=2)
    m = AutoDoc.load(base.save())
    for f in forks:
        m.merge(f)
    log = OpLog.from_documents([m])
    comp = log.compressed(sync=True)
    assert comp is not None
    assert comp.verify_against(log) == []
    # silently corrupt one dense oracle cell covered by a run entry: the
    # audit must name the diverged column
    import numpy as np

    for name in ("action", "succ_count", "obj_actor"):
        arr = getattr(log, name, None)
        ent = comp.entries.get(name)
        cov = comp.covered.get(name, 0)
        if arr is not None and ent is not None and cov > 0:
            arr = np.asarray(arr)
            old = arr[0]
            arr[0] = old + 1
            try:
                assert name in comp.verify_against(log)
            finally:
                arr[0] = old
            assert comp.verify_against(log) == []
            return
    pytest.skip("no run-coded column to tamper with")


# -- the scrubber ---------------------------------------------------------------


def test_scrubber_repairs_live_doc_bit_rot_with_zero_loss(tmp_path):
    srv = RpcServer(durable_dir=str(tmp_path / "docs"))
    try:
        h = srv.openDurable({"name": "live"})["doc"]
        for i in range(5):
            srv.put({"doc": h, "obj": "_root", "prop": f"k{i}", "value": i})
            srv.commit({"doc": h})
        digest_before = srv.docDigest({"name": "live"})
        path = srv._durable_path("live")
        corrupt0 = _ctr("journal.scrub_corrupt")
        repaired0 = _ctr("journal.scrub_repaired")
        _flip_byte(os.path.join(path, JOURNAL_NAME), frac=0.5)
        summary = srv.scrubNow({})
        assert summary["corrupt"] >= 1 and summary["repaired"] >= 1, summary
        assert _ctr("journal.scrub_corrupt") > corrupt0
        assert _ctr("journal.scrub_repaired") > repaired0
        # zero acked-write loss: in-memory history repaired the disk
        assert all(r.ok for r in integrity.verify_doc_dir(path))
        assert srv.docDigest({"name": "live"}) == digest_before
        for i in range(5):
            assert srv.get(
                {"doc": h, "obj": "_root", "prop": f"k{i}"}) == i
        # a second round finds nothing
        clean0 = _ctr("journal.scrub_clean")
        summary = srv.scrubNow({})
        assert summary["corrupt"] == 0
        assert _ctr("journal.scrub_clean") > clean0
    finally:
        srv.close_durables()


def test_scrubber_detects_cold_doc_rot_and_salvages(tmp_path):
    srv = RpcServer(durable_dir=str(tmp_path / "docs"))
    try:
        h = srv.openDurable({"name": "cold"})["doc"]
        srv.put({"doc": h, "obj": "_root", "prop": "k", "value": "vv"})
        srv.commit({"doc": h})
        srv.store.demote("cold", "cold")
        path = srv._durable_path("cold")
        _flip_byte(os.path.join(path, JOURNAL_NAME), frac=0.7)
        corrupt0 = _ctr("journal.scrub_corrupt")
        summary = srv.scrubNow({})
        assert summary["corrupt"] >= 1, summary
        assert _ctr("journal.scrub_corrupt") > corrupt0
        # unreplicated deployment: salvage is the last resort, and the
        # rewritten files verify clean afterwards
        assert _ctr("journal.scrub_repaired") >= 1
        assert all(r.ok for r in integrity.verify_doc_dir(path))
    finally:
        srv.close_durables()


def test_scrubber_chaos_bitflip_detected_without_disk_damage(
        tmp_path, monkeypatch):
    """FaultyFS BITFLIP corrupts the bytes the scrub READS (the disk
    stays clean): detection fires, and the repair path re-verifies clean
    once the armed fault is spent."""
    monkeypatch.setenv("AUTOMERGE_TPU_CHAOS", "1")
    srv = RpcServer(durable_dir=str(tmp_path / "docs"))
    try:
        h = srv.openDurable({"name": "bf"})["doc"]
        srv.put({"doc": h, "obj": "_root", "prop": "k", "value": 1})
        srv.commit({"doc": h})
        srv.chaosDisk({"name": "bf", "op": "read", "err": "BITFLIP",
                       "count": 1})
        flips0 = obs.counter_values(
            "chaos.injected", "kind").get("disk_read_flip", 0)
        summary = srv.scrubNow({})
        assert summary["corrupt"] >= 1, summary
        assert obs.counter_values("chaos.injected", "kind").get(
            "disk_read_flip", 0) > flips0
        summary = srv.scrubNow({})
        assert summary["corrupt"] == 0, summary
    finally:
        srv.close_durables()


def test_faultyfs_read_bitflip_semantics(tmp_path):
    from automerge_tpu.storage.crashsim import FaultyFS

    p = str(tmp_path / "blob")
    with open(p, "wb") as f:
        f.write(b"A" * 64)
    fs = FaultyFS()
    fs.arm("read", "BITFLIP", count=1)
    flipped = fs.read_bytes(p)
    assert flipped != b"A" * 64
    assert len(flipped) == 64
    assert sum(a != b for a, b in zip(flipped, b"A" * 64)) == 1
    assert fs.read_bytes(p) == b"A" * 64  # armed count spent
    with pytest.raises(ValueError):
        fs.arm("write", "BITFLIP")  # only reads can rot silently
    fs.arm("read", "EIO", count=1)
    with pytest.raises(OSError):
        fs.read_bytes(p)


# -- quarantine plumbing --------------------------------------------------------


def test_hub_quarantine_revokes_the_vote():
    from automerge_tpu.cluster.replication import ReplicationHub

    hub = ReplicationHub("n1", ack_replicas=1)

    class _Link:
        quarantined = False
        durable_lsn = {}

        def stop(self):
            pass

    a, b = _Link(), _Link()
    hub._links["h:1"] = a
    hub._links["h:2"] = b
    assert sorted(hub.follower_addrs()) == ["h:1", "h:2"]
    assert hub.quarantine("h:1") is True
    assert hub.follower_addrs() == ["h:2"]
    assert hub.quarantined_addrs() == ["h:1"]
    assert a.quarantined and not b.quarantined
    assert hub.quarantine("nope") is False
    # gauge reflects the quarantined count
    assert any(
        e["name"] == "cluster.quarantined" and e["value"] == 1
        for e in obs.snapshot()
    )
    hub.close()


# -- gauge hygiene --------------------------------------------------------------


def test_digest_gauge_removed_on_close_and_demotion(tmp_path):
    srv = RpcServer(durable_dir=str(tmp_path / "docs"))

    def gauge(name):
        for e in obs.snapshot():
            if (e["name"] == "doc.digest_changes"
                    and e["labels"].get("doc") == name):
                return e["value"]
        return None

    try:
        h = srv.openDurable({"name": "g1"})["doc"]
        srv.put({"doc": h, "obj": "_root", "prop": "k", "value": 1})
        srv.commit({"doc": h})
        assert gauge("g1") == 1
        srv.store.demote("g1", "cold")
        assert gauge("g1") is None  # cold demotion removed the gauge
        h2 = srv.openDurable({"name": "g2"})["doc"]
        srv.put({"doc": h2, "obj": "_root", "prop": "k", "value": 1})
        srv.commit({"doc": h2})
        assert gauge("g2") == 1
        srv.free({"doc": h2})
        assert gauge("g2") is None  # close removed the gauge
    finally:
        srv.close_durables()


# -- cli: journal-info --verify ------------------------------------------------


def test_cli_journal_info_verify_clean_and_corrupt(tmp_path, capsys):
    from automerge_tpu.cli import main

    d = str(tmp_path / "vd")
    dd = AutoDoc.open(d)
    for i in range(4):
        dd.put("_root", f"k{i}", "x" * 50)
        dd.commit()
    dd.compact()
    dd.put("_root", "tail", 1)
    dd.commit()
    dd.close()
    out = tmp_path / "info.json"
    assert main(["journal-info", d, "--verify", "-o", str(out)]) == 0
    info = json.loads(out.read_text())
    assert {v["kind"] for v in info["verify"]} == {"snapshot", "journal"}
    assert all(v["ok"] for v in info["verify"])
    # a flipped snapshot byte: exit 1, damaged kind + first bad offset
    _flip_byte(os.path.join(d, SNAPSHOT_NAME))
    assert main(["journal-info", d, "--verify", "-o", str(out)]) == 1
    info = json.loads(out.read_text())
    bad = [v for v in info["verify"] if not v["ok"]]
    assert bad and bad[0]["kind"] == "snapshot", info["verify"]
    assert bad[0]["first_bad_offset"] is not None
    assert "corrupt at byte" in capsys.readouterr().err
    # inspection never repairs, and without --verify the deep scan (a
    # full read-back of every byte) stays off
    assert main(["journal-info", d, "-o", str(out)]) == 0
    assert "verify" not in json.loads(out.read_text())


# -- reference client: IntegrityError ------------------------------------------


def _client_mod():
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).parent.parent / "clients" / "python"
            / "amtpu_client.py")
    spec = importlib.util.spec_from_file_location("amtpu_client", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_client_surfaces_integrity_error_without_retry():
    """An IntegrityError is never retried (re-reading damaged bytes
    cannot help) and arrives as its own exception type — even when a
    buggy server marks it retriable."""
    amtpu = _client_mod()
    ls = socket.socket()
    ls.bind(("127.0.0.1", 0))
    ls.listen(8)

    def serve():
        for _ in range(2):
            c, _ = ls.accept()
            f = c.makefile("r")
            req = json.loads(f.readline())
            c.sendall((json.dumps({"id": req["id"], "error": {
                "type": "IntegrityError",
                "message": "digest mismatch",
                # deliberately wrong flag on the second round: the type
                # check must win over the retriable hint
                "retriable": bool(req["params"].get("lie")),
            }}) + "\n").encode())
            c.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    addr = "127.0.0.1:%d" % ls.getsockname()[1]
    c = amtpu.RetryingClient(addr, deadline_s=5, backoff_s=0.01)
    with pytest.raises(amtpu.IntegrityError) as ei:
        c.call("docDigest", name="x")
    assert ei.value.retriable is False
    assert isinstance(ei.value, amtpu.RpcError)
    assert c.last.attempts == 1
    c.close()
    c = amtpu.RetryingClient(addr, deadline_s=5, backoff_s=0.01)
    with pytest.raises(amtpu.IntegrityError):
        c.call("docDigest", name="x", lie=True)
    assert c.last.attempts == 1
    c.close()
    ls.close()
