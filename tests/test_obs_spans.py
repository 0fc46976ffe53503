"""Spans with known intervals, garbage-collection spans, span mirroring into
a ``jax.profiler`` capture, the serve drain's per-request queue spans, the
device wait inside a readback, and the names of the jitted programs."""

import ast
import gc
import glob
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from automerge_tpu import obs
from automerge_tpu.api import AutoDoc
from automerge_tpu.types import ActorId, ObjType


def _spans(name):
    return [r for r in obs.recorder.snapshot() if r.name == name]


# -- record_span ---------------------------------------------------------------


def test_record_span_nesting_and_parents():
    obs.reset_all()
    t0 = time.perf_counter()
    top = obs.record_span("rs.top", t0, t0 + 0.002, k=1)
    with obs.span("rs.outer") as outer:
        inner = obs.record_span("rs.inner", t0, t0 + 0.005)
        obs.record_span("rs.leaf", t0 + 0.001, t0 + 0.003, parent=inner)
    by = {r.name: r for r in obs.recorder.snapshot()}
    assert by["rs.top"].parent_id is None and by["rs.top"].span_id == top
    assert by["rs.top"].fields == {"k": 1}
    # no parent given: the calling context's active span, as obs.span
    assert by["rs.inner"].parent_id == outer.span_id
    assert by["rs.leaf"].parent_id == inner
    # the interval is the one given, on the ring's clock
    assert by["rs.inner"].start == pytest.approx(t0 - obs._ORIGIN)
    assert by["rs.inner"].duration == pytest.approx(0.005)
    assert by["rs.leaf"].start - by["rs.inner"].start == pytest.approx(0.001)
    # accounted like any span: timings and the histogram
    assert obs.timing_summary()["rs.leaf"]["n"] == 1
    assert obs.registry.histogram("rs.inner").n == 1


# -- garbage collection ---------------------------------------------------------


def _gc_counted(gen):
    return obs.counter_values("host.gc_collections", "generation").get(
        str(gen), 0)


def test_forced_collection_lands_as_a_host_gc_span():
    obs.reset_all()
    before = _gc_counted(2)
    with obs.span("gc.around") as around:
        gc.collect()
    spans = _spans("host.gc")
    assert spans, "a forced collection left no host.gc span"
    s = spans[-1]
    assert s.fields["generation"] == 2
    assert {"collected", "uncollectable"} <= set(s.fields)
    assert s.parent_id == around.span_id  # the span it interrupted
    assert s.thread_id == threading.get_ident()
    outer = _spans("gc.around")[-1]
    assert outer.start <= s.start and s.duration <= outer.duration
    assert _gc_counted(2) >= before + 1


@pytest.mark.parametrize("lock", ["recorder", "registry"])
def test_collection_under_an_obs_lock_does_not_deadlock(lock):
    """The hook runs wherever an allocation triggers it, also while the
    thread holds the recorder's or the registry's lock: it must take
    neither, and the collection must still reach the ring."""
    obs.reset_all()
    held = obs.recorder._lock if lock == "recorder" else obs.registry.lock
    done = threading.Event()

    def body():
        with held:
            gc.collect()
        with obs.span("after.gc"):
            pass
        done.set()

    t = threading.Thread(target=body, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and done.is_set(), "deadlocked in the gc hook"
    assert any(r.fields["generation"] == 2 for r in _spans("host.gc"))


def test_collections_are_counted_without_a_span_exit():
    obs.reset_all()
    before = _gc_counted(2)
    gc.collect()
    gc.collect()
    # a snapshot alone folds what the hook queued
    assert _gc_counted(2) == before + 2
    assert len(_spans("host.gc")) >= 2


# -- mirroring into a jax.profiler capture ------------------------------------


def test_spans_are_not_annotated_without_a_capture():
    import jax  # noqa: F401 — the check is live once JAX is imported

    assert obs._annotation() is None


def test_span_lands_in_the_capture_host_plane_on_the_ring_clock(tmp_path):
    import jax
    from jax.profiler import ProfileData

    obs.reset_all()
    jax.profiler.start_trace(str(tmp_path))
    try:
        marker = jax.profiler.TraceAnnotation("test.marker")
        m0 = time.perf_counter()
        marker.__enter__()
        time.sleep(0.02)
        with obs.span("test.mirrored"):
            time.sleep(0.005)
        marker.__exit__(None, None, None)
    finally:
        jax.profiler.stop_trace()
    assert obs._annotation() is None  # the capture is over
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert path
    found = {}
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("test.marker", "test.mirrored"):
                    found[e.name] = (plane.name, e.start_ns, e.duration_ns)
    assert set(found) == {"test.marker", "test.mirrored"}, found
    assert found["test.mirrored"][0].startswith("/host")
    rec = _spans("test.mirrored")[-1]
    trace_off = (found["test.mirrored"][1] - found["test.marker"][1]) / 1e9
    ring_off = rec.start - (m0 - obs._ORIGIN)
    assert abs(trace_off - ring_off) < 1e-3, (trace_off, ring_off)
    assert found["test.mirrored"][2] / 1e9 == pytest.approx(
        rec.duration, abs=1e-3)


# -- serve drains ---------------------------------------------------------------


def test_live_drain_records_one_queued_span_per_request(tmp_path):
    from automerge_tpu.serve import SocketRpcServer

    os.makedirs(str(tmp_path / "dur"), exist_ok=True)
    srv = SocketRpcServer(host="127.0.0.1", port=0, workers=1,
                          durable_dir=str(tmp_path / "dur"))
    srv.start()
    try:
        sock = socket.create_connection(srv.address)
        f = sock.makefile("r")

        def call(rid, method, **params):
            sock.sendall((json.dumps({"id": rid, "method": method,
                                      "params": params}) + "\n").encode())
            resp = json.loads(f.readline())
            assert "error" not in resp, resp
            return resp["result"]

        d = call(1, "openDurable", name="queued-doc", fsync="never")["doc"]
        obs.reset_all()
        # one burst: the drains take the puts in batches
        n = 24
        sock.sendall("".join(json.dumps({
            "id": 100 + i, "method": "put",
            "params": {"doc": d, "obj": "_root", "prop": f"k{i}",
                       "value": i}}) + "\n" for i in range(n)).encode())
        got = [json.loads(f.readline()) for _ in range(n)]
        assert all("error" not in r for r in got)
        call(200, "commit", doc=d)
        sock.close()
    finally:
        srv.stop()
    drains = {r.span_id: r for r in _spans("serve.drain")
              if r.fields["doc"] == "queued-doc"}
    queued = [r for r in _spans("serve.queued")
              if r.parent_id in drains]
    assert sorted(r.fields["id"] for r in queued) == list(
        range(100, 100 + n)) + [200]
    for sid, dr in drains.items():
        mine = sorted((r for r in queued if r.parent_id == sid),
                      key=lambda r: r.fields["position"])
        assert dr.fields["requests"] == len(mine)
        assert [r.fields["position"] for r in mine] == list(range(len(mine)))
        assert "changes" in dr.fields
        for r in mine:
            # from the request's submit to the dequeue: ends before the
            # drain span begins and never runs backwards
            assert r.duration >= 0.0
            assert r.start + r.duration <= dr.start + 1e-6
    assert {r.fields["method"] for r in queued} == {"put", "commit"}
    assert sum(d.fields["changes"] for d in drains.values()) == 1


# -- the device wait inside a readback -----------------------------------------


def _device_doc_log():
    from automerge_tpu.ops import OpLog

    base = AutoDoc(actor=ActorId(bytes([3]) * 16))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "spans on the device path")
    base.commit()
    return OpLog.from_changes([a.stored for a in base.doc.history])


@pytest.mark.parametrize("transport,program", [
    ("dict", ("scatter", "dense")),
    ("packed", ("packed",)),
])
def test_device_wait_nests_inside_device_readback(transport, program):
    from automerge_tpu.ops import DeviceDoc
    from automerge_tpu.ops.merge import merge_columns

    log = _device_doc_log()
    obs.reset_all()
    if transport == "packed":
        DeviceDoc.resolve(log)
    else:
        # the dict path: an explicit linearize pin
        merge_columns(log.columns(), linearize="native",
                      fetch=DeviceDoc.READ_FETCH, n_objs=log.n_objs,
                      n_props=len(log.props))
    by_id = {r.span_id: r for r in obs.recorder.snapshot()}
    waits, copies = _spans("device.wait"), _spans("device.d2h")
    assert waits and copies
    for r in waits + copies:
        assert by_id[r.parent_id].name == "device.readback"
    for w, c in zip(waits, copies):
        assert w.parent_id == c.parent_id
        assert w.start + w.duration <= c.start + 1e-6  # wait, then copy
    kernels = _spans("device.kernel")
    assert kernels and all(k.fields["program"] in program for k in kernels)
    assert all(k.fields["rows"] > 0 for k in kernels)


# -- program names ------------------------------------------------------------


def test_no_program_in_merge_is_jitted_as_f():
    from automerge_tpu.ops import merge as M

    tree = ast.parse(open(M.__file__).read())
    funcs = {n.name: n for n in ast.walk(tree)
             if isinstance(n, ast.FunctionDef)}
    assert "f" not in funcs
    jitted = [
        n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
        and any(ast.unparse(d) in ("jax.jit", "jit")
                for d in n.decorator_list)
    ]
    assert {n.name for n in jitted} == set(M.RESOLUTION_PROGRAMS)


def test_resolution_programs_carry_the_resolution_names():
    from automerge_tpu.ops import merge as M
    from automerge_tpu.ops.oplog import pad_columns

    log = _device_doc_log()
    cols = pad_columns(log.columns(), log.n_objs)
    P = len(cols["action"])
    fetch = M.RESOLVE_FETCH
    resolution = {
        M.merge_kernel, M.merge_kernel_core,
        M.merge_kernel_condensed(32, 16),
        M.scatter_kernel_core(log.n_objs, len(log.props)),
        M.geometry_kernel(M.resolution_geom(P, log.n_objs), fetch),
        M.geometry_kernel(M.resolution_geom(
            P, log.n_objs, full=True), fetch + ("elem_index",)),
        M.geometry_kernel(M.resolution_geom(
            P, log.n_objs, len(log.props)), fetch),
        M._packed_fn(fetch, M._obj_cap(log.n_objs, P)),
        M._packed_fn(fetch + ("elem_index",), M._obj_cap(log.n_objs, P)),
    }
    assert {fn.__name__ for fn in resolution} == set(M.RESOLUTION_PROGRAMS)
    # the name reaches the XLA module a profile shows
    text = M.merge_kernel_core.lower(
        {k: np.asarray(v) for k, v in cols.items()}).as_text()
    assert "jit_merge_kernel_core" in text
