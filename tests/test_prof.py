"""Drain-cycle performance observatory (automerge_tpu/obs/prof.py):
per-cycle stage attribution, top-K boundedness, occupancy at the pack
site, the perfStatus / profileStart / profileStop RPC surface, the
perf-report CLI (live and offline), and the scripts/ci/perf_gate
trajectory gate."""

import json
import os
import subprocess
import sys
import time

import pytest

from automerge_tpu import obs
from automerge_tpu.api import AutoDoc
from automerge_tpu.obs import prof
from automerge_tpu.rpc import RpcServer
from automerge_tpu.types import ActorId, ObjType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF_GATE = os.path.join(REPO, "scripts", "ci", "perf_gate")


@pytest.fixture(autouse=True)
def _fresh_profiler():
    prof.profiler.reset()
    yield
    prof.profiler.reset()


def _spin(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


# -- report aggregation -------------------------------------------------------


def test_cycle_attributes_stages_and_split():
    with prof.cycle(kind="t") as c:
        with obs.span("device.stage.dedup"):
            _spin(0.002)
        with obs.span("device.apply"):
            with obs.span("device.stage.splice"):
                _spin(0.004)
            _spin(0.001)
        with obs.span("device.kernel"):
            _spin(0.003)
        with obs.span("journal.fsync"):
            _spin(0.002)
    r = c.report
    assert r["stages"]["dedup"] >= 0.002
    assert r["stages"]["splice"] >= 0.004
    assert r["stages"]["kernel"] >= 0.003
    assert r["stages"]["fsync"] >= 0.002
    # apply (host umbrella) counts once: splice stays breakdown-only.
    # Lower bounds are exact (the spins are inside the spans); upper
    # bounds stay loose — a loaded CI box can preempt between clock
    # reads, and the invariant that matters is attributed <= wall.
    assert 0.005 <= r["host_s"] < 0.1
    assert 0.003 <= r["device_s"] < 0.1
    assert 0.002 <= r["fsync_s"] < 0.1
    assert r["attributed_s"] <= r["wall_s"] * 1.01
    assert r["attributed_frac"] > 0.8


def test_nested_device_work_never_double_counts():
    # the per-doc fallback path launches a kernel INSIDE device.apply;
    # the attributed total must stay <= wall and the split must move the
    # nested device time out of the host share
    with prof.cycle(kind="t") as c:
        with obs.span("device.apply"):
            with obs.span("device.kernel"):
                _spin(0.004)
            _spin(0.001)
    r = c.report
    assert r["attributed_s"] <= r["wall_s"] * 1.01
    assert r["stages"]["kernel"] >= 0.004
    assert r["device_s"] >= 0.004  # reassigned to the device side
    assert r["host_s"] < r["device_s"]  # pure host remainder only


def test_cycle_notes_and_occupancy():
    with prof.cycle(kind="t", docs=3) as c:
        prof.note("useful_rows", 75)
        prof.note("padded_rows", 25)
        prof.note("launches")
    r = c.report
    assert r["occupancy"] == 0.75
    assert r["docs"] == 3 and r["launches"] == 1
    s = prof.profiler.status()
    assert s["occupancy"] == 0.75
    assert s["docs_per_launch"] == 3.0


def test_summarize_reports_matches_status():
    reports = []
    for _ in range(3):
        with prof.cycle(kind="t") as c:
            with obs.span("device.kernel"):
                _spin(0.001)
        reports.append(c.report)
    merged = prof.summarize_reports(reports)
    status = prof.profiler.status()
    assert merged["cycles"] == status["cycles"] == 3
    assert merged["stages"].keys() == status["stages"].keys()
    assert merged["attributed_s"] == status["attributed_s"]


def test_disabled_profiler_is_a_noop():
    prof.profiler.enabled = False
    try:
        with prof.cycle(kind="t") as c:
            with obs.span("device.kernel"):
                pass
        assert c.report is None
        assert prof.profiler.cycles == 0
    finally:
        prof.profiler.enabled = True


def test_top_k_table_stays_bounded():
    k = prof.profiler.top_k
    for i in range(50 * k):
        with prof.cycle(kind="t", doc=f"doc{i % (10 * k)}"):
            pass
    assert len(prof.profiler._doc_costs) <= 4 * k
    top = prof.profiler.top_docs()
    assert len(top) <= k
    # the table orders by attributed seconds, descending
    secs = [e["seconds"] for e in top]
    assert secs == sorted(secs, reverse=True)


def test_cycle_doc_wall_does_not_double_count_staging():
    # a serve drain attributes its whole wall to its doc; staging
    # seconds note_doc'd for the SAME doc inside that cycle are part of
    # the wall and must not add on top
    with prof.cycle(kind="t", doc="d1") as c:
        prof.note_doc("d1", 0.001)
        _spin(0.004)
    r = c.report
    assert r["doc_costs"]["d1"] == pytest.approx(r["wall_s"], rel=0.01)


def test_umbrella_opened_before_cycle_clamps_to_cycle_wall():
    # a span entered BEFORE the cycle but exited inside it contributes
    # only its overlap with the cycle, never pre-cycle time
    outer = obs.span("device.apply")
    outer.__enter__()
    _spin(0.01)
    with prof.cycle(kind="t") as c:
        outer.__exit__(None, None, None)
    r = c.report
    assert r["attributed_s"] <= r["wall_s"] * 1.05, r
    assert r["attributed_frac"] <= 1.0
    # the aggregate view clamps too
    assert prof.summarize_reports([r])["attributed_frac"] <= 1.0


def test_device_umbrella_under_host_umbrella_reassigns_split():
    # a live accelerator serve drain: rpc.request (host umbrella) wraps
    # the batched device region — the split must still call it device
    with prof.cycle(kind="t") as c:
        with obs.span("rpc.request"):
            with obs.span("device.batched"):
                with obs.span("device.kernel"):
                    _spin(0.004)
            _spin(0.001)
    r = c.report
    assert r["attributed_s"] <= r["wall_s"] * 1.01
    assert r["device_s"] >= 0.004, r
    assert r["host_s"] < r["device_s"], r
    assert r["stages"]["kernel"] >= 0.004


def test_whale_doc_survives_pruning():
    # space-saving property: a doc that dominates the cost can never be
    # rotated out by a crowd of cheap ones
    prof.profiler._doc_costs["whale"] = 100.0
    for i in range(100 * prof.profiler.top_k):
        with prof.cycle(kind="t", doc=f"cheap{i}"):
            pass
    assert "whale" in dict(
        (e["doc"], e["seconds"]) for e in prof.profiler.top_docs()
    )


# -- real drains through the device layer ------------------------------------


def _mkdoc(i, ballast=300):
    base = AutoDoc(actor=ActorId(bytes([1]) * 16))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "live text ")
    arch = base.put_object("_root", "a", ObjType.TEXT)
    base.splice_text(arch, 0, 0, "x" * ballast)
    base.commit()
    chs = [a.stored for a in base.doc.history]
    f = base.fork(actor=ActorId(bytes([10 + i]) * 16))
    f.splice_text(t, i % 5, 0, f"<{i}>")
    f.commit()
    have = {c.hash for c in chs}
    delta = [a.stored for a in f.doc.history if a.stored.hash not in have]
    return chs, delta


def _cross_doc_work(n, seed=0):
    from automerge_tpu.ops import DeviceDoc, OpLog

    return [
        (DeviceDoc.resolve(OpLog.from_changes(chs)), [delta])
        for chs, delta in (_mkdoc(seed + i) for i in range(n))
    ]


def test_batched_drain_cycle_report():
    from automerge_tpu.ops.batched import apply_cross_doc

    apply_cross_doc(_cross_doc_work(3))  # warm the jit caches
    work = _cross_doc_work(3, seed=3)
    prof.profiler.reset()
    with prof.cycle(kind="t") as c:
        apply_cross_doc(work)
    r = c.report
    # the acceptance contract: >=90% of the drain wall clock lands in
    # named stages, occupancy comes from the pack site, one launch
    assert r["attributed_frac"] >= 0.9, r
    assert r["launches"] == 1 and r["docs"] == 3
    assert r["useful_rows"] > 0 and r["occupancy"] is not None
    assert 0 < r["occupancy"] <= 1.0
    for stage in ("pack", "h2d", "kernel", "readback", "scatter"):
        assert r["stages"].get(stage, 0) > 0, (stage, r["stages"])
    # the host staging half attributes through the vectorized cross-doc
    # stages (host_pack/host_splice) — or through the scalar splice
    # stage for a document the fast path does not admit
    assert (
        r["stages"].get("host_splice", 0) > 0
        or r["stages"].get("splice", 0) > 0
    ), r["stages"]
    # the pack site's counters fired alongside
    rows = obs.counter_values("device.batch_rows", "").get("", 0)
    pad = obs.counter_values("device.batch_padding_rows", "").get("", 0)
    assert rows > 0 and rows / (rows + pad) == pytest.approx(
        r["occupancy"], abs=0.2
    )
    # per-doc attribution reached the top-K table
    assert prof.profiler.top_docs()


def test_cycle_report_lands_in_flight_ring():
    from automerge_tpu.ops.batched import apply_cross_doc

    with prof.cycle(kind="t"):
        apply_cross_doc(_cross_doc_work(2, seed=6))
    evs = [
        {"name": n, "fields": f}
        for _t, n, f in obs.flight.events
        if n == "drain.cycle_report"
    ]
    assert evs
    merged = prof.summarize_flight_events(evs)
    assert merged["cycles"] >= 1
    assert merged["stages"].get("kernel", {}).get("seconds", 0) > 0
    assert merged["attributed_frac"] > 0


# -- RPC surface --------------------------------------------------------------


def test_perf_status_rpc():
    rpc = RpcServer()
    with prof.cycle(kind="t"):
        with obs.span("device.kernel"):
            _spin(0.001)
    resp = rpc.handle({"id": 1, "method": "perfStatus", "params": {}})
    assert "error" not in resp, resp
    s = resp["result"]
    assert s["cycles"] >= 1
    assert "host_pct" in s and "device_pct" in s and "stages" in s
    assert "drain_cycle_seconds" in s and "queue_wait_seconds" in s
    json.dumps(s)  # the whole status must be JSON-serializable


def test_profile_start_stop_rpc_clean_degrade(tmp_path):
    rpc = RpcServer()
    # stop with nothing active: a clean {"ok": false}, not an error
    resp = rpc.handle({"id": 1, "method": "profileStop", "params": {}})
    assert "error" not in resp and resp["result"]["ok"] is False
    d = str(tmp_path / "jaxprof")
    start = rpc.handle(
        {"id": 2, "method": "profileStart", "params": {"dir": d}}
    )["result"]
    if not start["ok"]:
        # the clean-degrade contract on boxes without a profiler backend
        assert "reason" in start
        return
    # a second start while active degrades, never raises
    again = rpc.handle(
        {"id": 3, "method": "profileStart", "params": {}}
    )["result"]
    assert again["ok"] is False
    # the launch's spans mirror into the capture while it is active
    from automerge_tpu.ops.batched import apply_cross_doc

    apply_cross_doc(_cross_doc_work(2, seed=9))
    stop = rpc.handle(
        {"id": 4, "method": "profileStop", "params": {}}
    )["result"]
    assert stop["ok"] is True and stop["dir"] == d
    # the capture produced an xplane/trace artifact under the dir
    found = [
        os.path.join(r, fn) for r, _d, fs in os.walk(d) for fn in fs
    ]
    assert found, "profiler capture produced no artifacts"


def test_readback_umbrella_counts_its_wait_and_copy_once():
    # device.readback holds device.wait (blocked on the device) and
    # device.d2h (the copy): both are breakdown rows, the umbrella alone
    # adds to the split, so attributed time never exceeds the wall
    with prof.cycle(kind="t") as c:
        with obs.span("device.kernel"):
            _spin(0.001)
        with obs.span("device.readback"):
            with obs.span("device.wait"):
                _spin(0.004)
            with obs.span("device.d2h"):
                _spin(0.002)
    r = c.report
    assert r["stages"]["wait"] >= 0.004 and r["stages"]["d2h"] >= 0.002
    assert r["stages"]["readback"] >= r["stages"]["wait"] + r["stages"]["d2h"]
    assert r["attributed_s"] <= r["wall_s"] * 1.01
    assert r["device_s"] < r["stages"]["readback"] + r["stages"]["kernel"] + 0.001
    # under a host umbrella the whole readback moves to the device side
    with prof.cycle(kind="t") as c:
        with obs.span("device.apply"):
            with obs.span("device.readback"):
                with obs.span("device.wait"):
                    _spin(0.004)
    r = c.report
    assert r["attributed_s"] <= r["wall_s"] * 1.01
    assert r["device_s"] >= 0.004 and r["host_s"] < r["device_s"]
    text = prof.render_text(prof.profiler.status())
    assert "blocked on device:" in text
    assert "kernel_dispatch" in text and "device_wait" in text


# -- perf-report CLI ----------------------------------------------------------


def test_perf_report_live_server(tmp_path, capsys):
    """Live mode: serve drains are real profiler cycles, and
    ``perf-report --connect`` renders them from the perfStatus RPC."""
    import socket as socketmod

    from automerge_tpu.cli import main as cli_main
    from automerge_tpu.serve import SocketRpcServer

    srv = SocketRpcServer(host="127.0.0.1", port=0,
                          durable_dir=str(tmp_path / "dur"))
    os.makedirs(str(tmp_path / "dur"), exist_ok=True)
    srv.start()
    host, port = srv.address
    try:
        sock = socketmod.create_connection((host, port))
        f = sock.makefile("r")
        rid = [0]

        def call(method, **params):
            rid[0] += 1
            sock.sendall((json.dumps(
                {"id": rid[0], "method": method, "params": params}
            ) + "\n").encode())
            resp = json.loads(f.readline())
            assert "error" not in resp, resp
            return resp["result"]

        d = call("openDurable", name="livedoc", fsync="never")["doc"]
        for i in range(6):
            call("put", doc=d, obj="_root", prop=f"k{i}", value=i)
            call("commit", doc=d)
        out_path = tmp_path / "live.json"
        rc = cli_main(["perf-report", "--connect", f"{host}:{port}",
                       "--format", "json", "-o", str(out_path)])
        assert rc == 0
        rep = json.loads(out_path.read_text())
        # every drain of the shard pool was a profiler cycle, anchored
        # to the real serve path, with the doc named in the top table
        assert rep["cycles"] >= 1
        assert any(e["doc"] == "livedoc" for e in rep["top_docs"])
        assert rep["drain_cycle_seconds"]["p50"] > 0
        text_path = tmp_path / "live.txt"
        rc = cli_main(["perf-report", "--connect", f"{host}:{port}",
                       "-o", str(text_path)])
        assert rc == 0
        assert "drain cycles:" in text_path.read_text()
        sock.close()
    finally:
        srv.stop()
    # drain.cycle_seconds / drain.docs recorded at the drain loop
    assert obs.registry.histogram("drain.cycle_seconds").n >= 1
    assert obs.registry.histogram("drain.docs").n >= 1


def test_perf_report_offline_from_flight_dump(tmp_path, capsys):
    from automerge_tpu.cli import main as cli_main
    from automerge_tpu.ops.batched import apply_cross_doc

    with prof.cycle(kind="t"):
        apply_cross_doc(_cross_doc_work(2, seed=12))
    dump = obs.flight.dump(str(tmp_path / "flight-test-1-1.json"))
    out_path = tmp_path / "report.txt"
    rc = cli_main(["perf-report", dump, "-o", str(out_path)])
    assert rc == 0
    text = out_path.read_text()
    assert "drain cycles:" in text and "attributed" in text
    assert "split: host" in text and "device" in text
    rc = cli_main(["perf-report", dump, "--format", "json",
                   "-o", str(tmp_path / "report.json")])
    assert rc == 0
    rep = json.loads((tmp_path / "report.json").read_text())
    assert rep["cycles"] >= 1 and rep["source"] == "flight"


def test_perf_report_no_input_errors(tmp_path, capsys):
    from automerge_tpu.cli import main as cli_main

    assert cli_main(["perf-report"]) == 1


# -- scripts/ci/perf_gate -----------------------------------------------------


def _bench_json(scale=1.0, host=None, config=None):
    d = {
        "metric": "x", "value": 1.0,
        "git_commit": "deadbeef",
        "config": dict(config or {"BENCH_REPS": 1}),
        "configs": {
            "micro": {
                "map_10000": {
                    "put_ops_per_sec": 700000.0 * scale,
                    "apply_ops_per_sec": 130000.0 * scale,
                    "save_ms": 22.0 / scale,
                    "load_ms": 50.0 / scale,
                },
                "map_1000": {"put_ops_per_sec": 500000.0 * scale},
                "range_10000": {"iter_elems_per_sec": 1.2e6 * scale},
            },
        },
    }
    if host is not None:
        d["host"] = host
    return d


def _run_gate(tmp_path, cur, baseline, extra_env=None):
    traj = tmp_path / "traj"
    traj.mkdir(exist_ok=True)
    (traj / "BENCH_r01.json").write_text(json.dumps(baseline))
    cur_path = tmp_path / "cur.json"
    cur_path.write_text(json.dumps(cur))
    out = tmp_path / "out"
    env = dict(
        os.environ,
        PERF_GATE_JSON=str(cur_path),
        PERF_GATE_DIR=str(traj),
        PERF_GATE_OUT=str(out),
        **(extra_env or {}),
    )
    p = subprocess.run(
        [sys.executable, PERF_GATE], env=env,
        capture_output=True, text=True, timeout=120,
    )
    return p, out


def test_perf_gate_passes_and_self_tests(tmp_path):
    fp = {"cpu_count": 8, "machine": "x"}
    p, out = _run_gate(
        tmp_path, _bench_json(1.0, host=fp), _bench_json(1.0, host=fp)
    )
    assert p.returncode == 0, p.stdout + p.stderr
    assert "PASS" in p.stdout
    assert "self-test ok" in p.stdout
    # the next trajectory artifact was emitted with the round bumped
    assert (out / "BENCH_r02.json").exists(), p.stdout


def test_perf_gate_fails_on_real_regression(tmp_path):
    # a 3x across-the-board slowdown sits far past the 0.5 floor
    p, _ = _run_gate(tmp_path, _bench_json(1 / 3.0), _bench_json(1.0))
    assert p.returncode == 1, p.stdout + p.stderr
    assert "REGRESSION" in p.stdout + p.stderr


def test_perf_gate_noise_tolerance(tmp_path):
    # 30% slower is noise under the default 0.5 relative floor
    p, _ = _run_gate(tmp_path, _bench_json(0.7), _bench_json(1.0))
    assert p.returncode == 0, p.stdout + p.stderr


def test_perf_gate_self_test_survives_big_improvement(tmp_path):
    # a genuine 3x speedup must PASS — the self-test injects from the
    # baseline, so an improved current run cannot absorb the injection
    p, _ = _run_gate(tmp_path, _bench_json(3.0), _bench_json(1.0))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "self-test ok" in p.stdout, p.stdout


def test_perf_gate_refuses_cross_host_comparison(tmp_path):
    p, out = _run_gate(
        tmp_path,
        _bench_json(0.01, host={"cpu_count": 8, "machine": "a"}),
        _bench_json(1.0, host={"cpu_count": 64, "machine": "b"}),
    )
    # a 100x "regression" against another box: refused, not failed
    assert p.returncode == 0, p.stdout + p.stderr
    assert "SKIPPED" in p.stdout
    assert (out / "BENCH_r02.json").exists()


def test_perf_gate_unfingerprinted_baseline_warns_or_refuses(tmp_path):
    # pre-fingerprint baseline: compares with a loud warning by
    # default, refuses under PERF_GATE_REQUIRE_FINGERPRINT=1
    cur = _bench_json(1.0, host={"cpu_count": 8, "machine": "x"})
    p, _ = _run_gate(tmp_path, cur, _bench_json(1.0))
    assert p.returncode == 0 and "WARNING" in p.stdout, p.stdout
    p, _ = _run_gate(
        tmp_path, cur, _bench_json(1.0),
        extra_env={"PERF_GATE_REQUIRE_FINGERPRINT": "1"},
    )
    assert p.returncode == 0 and "SKIPPED" in p.stdout, p.stdout


def test_perf_gate_size_gated_metrics_skip_on_mismatch(tmp_path):
    base = _bench_json(1.0, config={"BENCH_REPLAY_EDITS": 259778})
    base["configs"]["replay"] = {"ops_per_sec": 1e9}  # huge-box number
    cur = _bench_json(1.0, config={"BENCH_REPLAY_EDITS": 20000})
    cur["configs"]["replay"] = {"ops_per_sec": 1e5}
    p, _ = _run_gate(tmp_path, cur, base)
    # sizes differ -> replay is not comparable; micro still gates; pass
    assert p.returncode == 0, p.stdout + p.stderr
    assert "replay" not in p.stdout


def test_perf_gate_salvages_truncated_tail(tmp_path):
    # a driver wrapper with parsed=null and only a truncated tail of the
    # bench line (the shape one round-5 record had): its micro guards
    # must still be recovered
    import importlib.util
    from importlib.machinery import SourceFileLoader

    loader = SourceFileLoader("perf_gate_mod", PERF_GATE)
    spec = importlib.util.spec_from_loader("perf_gate_mod", loader)
    pg = importlib.util.module_from_spec(spec)
    loader.exec_module(pg)
    tail = (
        '9, "kernel_chain": 4, "transport_bytes_in": 5571088}, "rga": '
        '{"actors": 1000, "ops": 1003000, "ops_per_sec": 10483886.0}, '
        '"micro": {"map_100": {"put_ops_per_sec": 160038.9, "save_ms": '
        '1.98, "load_ms": 1.9, "apply_ops_per_sec": 120097.5}, '
        '"map_10000": {"put_ops_per_sec": 704343.1, "save_ms": 22.31, '
        '"load_ms": 49.54, "apply_ops_per_sec": 138955.8}, "range_10000": '
        '{"iter_elems_per_sec": 1243899.0}}}}\n'
    )
    rec = tmp_path / "BENCH_r05.json"
    rec.write_text(json.dumps({
        "n": 5, "cmd": "python bench.py", "rc": 0, "tail": tail,
        "parsed": None,
    }))
    point = pg.load_point(str(rec))
    assert point is not None and point.get("salvaged") is True
    micro = point["configs"]["micro"]["map_10000"]
    assert micro["put_ops_per_sec"] > 0 and micro["save_ms"] > 0
