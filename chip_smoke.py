#!/usr/bin/env python3
"""Bring-up smoke of the served merge path on one TPU chip.

One process holds the chip. It starts JAX once, then, in the same
process, the socket server that ``python -m automerge_tpu.rpc --socket``
builds; client threads talk to it over the socket with
``clients/python/amtpu_client.py``. No child process touches JAX.

* Phase A, served: 16 durable text documents opened with device mirrors,
  each loaded with its own generated edit-trace-length document (259,778
  edits), then 4 clients (2 editors per document) push keystroke changes,
  pipelined, as ``applyChanges`` and as sync messages. Checked against
  the host ``Document`` given the same changes: text and materialized
  values through the RPC, ``docDigest``, and each mirror's
  device-resolved reads.
* Phase B, fan-in: 1024 replicas of the edit-trace base, 16 edits each,
  resolved on the chip (``DeviceDoc.resolve`` -> ``merge_columns``),
  against the host ``Document`` that applies every replica's change.
* ``--chips 4`` runs only the whale-document mesh: one ~4.2M-op text
  document (16 edit-trace sessions) through ``sharded_merge_columns`` over
  4 chips, the same document resolved on one chip, and the host
  reference.

Everything is generated from ``--seed``. Earlier stdout lines are one
JSON object each; the last line is the contract line. Exits non-zero,
without that line, when JAX finds no TPU or any check fails.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
EDIT_TRACE_EDITS = 259_778  # the length of Automerge's edit-trace
# phase A: documents, clients, rounds, keystroke changes per document per
# round; phase B: replicas and edits each; --chips 4: edit-trace sessions
DOCS, CLIENTS, ROUNDS, KEYS = 16, 4, 4, 3
REPLICAS, REPLICA_EDITS = 1024, 16
MESH_SESSIONS = 16

# counters that say a device failure was caught and degraded; any of
# them non-zero fails the smoke
DEGRADE_COUNTERS = (
    ("sync.device_feed_error", None),
    ("device.batched_error", None),
    ("store.promote_error", None),
    ("device.mesh_unavailable", ("reason", "error")),
)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


_T0 = time.perf_counter()


def note(msg: str) -> None:
    """Progress on stderr, so a slow or cut run shows where it was."""
    print(f"[chip_smoke +{time.perf_counter() - _T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)


# -- instrumentation ----------------------------------------------------------


class CompileMeter:
    """Counts XLA compiles (persistent-cache hits included) and their
    seconds through jax.monitoring."""

    def __init__(self):
        import jax

        self.n = self.s = self.hits = self.misses = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.s += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snap(self) -> dict:
        return {"compiles": self.n, "compile_s": self.s,
                "cache_hits": self.hits, "cache_misses": self.misses}


def counters() -> dict:
    """{(name, ((label, value), ...)): value} for every counter."""
    from automerge_tpu import obs

    return {
        (e["name"], tuple(sorted(e["labels"].items()))): e["value"]
        for e in obs.snapshot()
        if e["type"] == "counter"
    }


def spans() -> dict:
    from automerge_tpu import obs

    return obs.timing_summary()


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def phase_report(name, t0, c0, s0, m0, meter) -> dict:
    """The per-phase line: wall and compile time, kernel launches by
    path, host-side stages, degrade counters, peak device memory."""
    import jax

    wall = time.perf_counter() - t0
    dc = delta(c0, counters())
    s1 = spans()
    ds = {
        k: {"s": v["s"] - s0.get(k, {}).get("s", 0.0),
            "n": v["n"] - s0.get(k, {}).get("n", 0)}
        for k, v in s1.items()
        if v["n"] != s0.get(k, {}).get("n", 0)
    }
    launches = {}
    for (cname, labels), v in dc.items():
        if cname == "device.kernel_launches":
            launches[dict(labels).get("path", "")] = v
    degrade = {}
    for cname, lab in DEGRADE_COUNTERS:
        degrade[cname if lab is None else f"{cname}{{{lab[0]}={lab[1]}}}"] = sum(
            v for (n, labels), v in dc.items()
            if n == cname and (lab is None or dict(labels).get(lab[0]) == lab[1])
        )
    m1 = meter.snap()
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {
        "phase": name,
        "wall_s": wall,
        "compile": {k: m1[k] - m0[k] for k in m1},
        "kernel_launches": launches,
        "host_stages": {k: ds[k] for k in ("merge.host", "host.linearize")
                        if k in ds},
        "device_stages": {k: v for k, v in ds.items()
                          if k.startswith(("device.", "parallel."))},
        "degrade": degrade,
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    }


def check_report(rep: dict) -> None:
    check(sum(rep["kernel_launches"].values()) > 0,
          f"{rep['phase']}: no device kernel launch")
    check(not any(rep["degrade"].values()),
          f"{rep['phase']}: device failures were counted: {rep['degrade']}")
    check("merge.host" not in rep["host_stages"],
          f"{rep['phase']}: merge_columns ran on the host engine")


# -- phase A: the served path ----------------------------------------------------


def _b64(b: bytes) -> str:
    return base64.b64encode(b).decode("ascii")


def _pipeline(c, calls, what, retried):
    """Pipeline ``calls`` and return their results. Calls answered with a
    retriable error (a shedding server's ``Overloaded``, ``Backpressure``)
    are sent again after the server's ``retryAfterMs``, as the reference
    client's retry contract says; any other error fails the smoke.
    ``retried`` (a one-element list) counts the re-sent calls."""
    results = [None] * len(calls)
    todo = list(range(len(calls)))
    deadline = time.monotonic() + 600
    while todo:
        again, wait = [], 0.05
        for i, r in zip(todo, c.pipeline([calls[i] for i in todo])):
            err = r.get("error")
            if err is None:
                results[i] = r.get("result")
            elif err.get("retriable") and time.monotonic() < deadline:
                again.append(i)
                wait = max(wait, err.get("retryAfterMs", 50) / 1000.0)
            else:
                raise SmokeFailure(f"{what}: {err}")
        retried[0] += len(again)
        if again:
            time.sleep(min(wait, 5.0))
        todo = again
    return results


def _client_session(ci, addr, docs, handles, n_docs, rounds, keys, seed,
                    made, errors, retried):
    """One client: a local replica of each document it edits, keystroke
    changes pushed as applyChanges (even rounds) and sync messages (odd
    rounds), every flight pipelined across its documents."""
    try:
        import numpy as np

        from amtpu_client import RetryingClient
        from automerge_tpu.api import AutoDoc
        from automerge_tpu.sync.protocol import Message, SyncState
        from automerge_tpu.types import ActorId

        rng = np.random.default_rng(seed * 7919 + ci)
        mine = [d for d in range(n_docs) if d % 2 == ci % 2]
        local = {
            d: AutoDoc.load(docs[d]["data"],
                            actor=ActorId(bytes([0x40 + ci]) * 16))
            for d in mine
        }
        cur = {d: int(rng.integers(0, local[d].length(docs[d]["text"]) + 1))
               for d in mine}
        cstate = {d: SyncState() for d in mine}
        with RetryingClient(addr, deadline_s=600) as c:
            sstate = dict(zip(mine, [r["sync"] for r in _pipeline(
                c, [("syncStateNew", {})] * len(mine), "syncStateNew",
                retried)]))
            for r in range(rounds):
                fresh = {d: [] for d in mine}
                for d in mine:
                    doc, text = local[d], docs[d]["text"]
                    for _ in range(keys):
                        n = doc.length(text)
                        pos = min(cur[d], n)
                        if pos and rng.random() < 0.3:
                            doc.splice_text(text, pos - 1, 1, "")
                            cur[d] = pos - 1
                        else:
                            ch = chr(97 + int(rng.integers(0, 26)))
                            doc.splice_text(text, pos, 0, ch)
                            cur[d] = pos + 1
                        doc.commit()
                        fresh[d].append(doc.get_last_local_change())
                    made[d].extend(fresh[d])
                if r % 2 == 0:
                    _pipeline(c, [
                        ("applyChanges", {"doc": handles[d],
                                          "data": _b64(ch.raw_bytes)})
                        for d in mine for ch in fresh[d]
                    ], "applyChanges", retried)
                    note(f"client {ci} round {r}: applyChanges")
                    continue
                for _ in range(16):  # sync until both sides are quiet
                    sends = []
                    for d in mine:
                        m = local[d].generate_sync_message(cstate[d])
                        if m is not None:
                            sends.append(("receiveSyncMessage", {
                                "doc": handles[d], "sync": sstate[d],
                                "data": _b64(m.encode())}))
                    if sends:
                        _pipeline(c, sends, "receiveSyncMessage", retried)
                    replies = _pipeline(c, [
                        ("generateSyncMessage",
                         {"doc": handles[d], "sync": sstate[d]})
                        for d in mine
                    ], "generateSyncMessage", retried)
                    for d, m in zip(mine, replies):
                        if m is not None:
                            local[d].receive_sync_message(
                                cstate[d],
                                Message.decode(base64.b64decode(m)))
                    if not sends and all(m is None for m in replies):
                        break
                note(f"client {ci} round {r}: sync")
    except BaseException as e:  # noqa: BLE001 — reported by the main thread
        errors.append(f"client {ci}: {type(e).__name__}: {e}")


def phase_served(seed, n_docs, n_edits, n_clients, rounds, keys, durable_dir):
    """Phase A. Returns the comparison summary; raises SmokeFailure."""
    sys.path.insert(0, os.path.join(HERE, "clients", "python"))
    from amtpu_client import RetryingClient
    from automerge_tpu import bench as W
    from automerge_tpu import integrity
    from automerge_tpu.api import AutoDoc
    from automerge_tpu.serve import SocketRpcServer
    from automerge_tpu.types import ActorId, ObjType

    t0 = time.perf_counter()
    docs = []
    for d in range(n_docs):
        doc = AutoDoc(actor=ActorId(bytes([1]) * 16))
        text = doc.put_object("_root", "text", ObjType.TEXT)
        doc.splice_text_many(
            text, W.synth_edit_trace(n_edits, seed=seed * 1000 + d))
        doc.commit()
        docs.append({"name": f"smoke-{d}", "data": doc.save(), "text": text})
    t_gen = time.perf_counter() - t0
    note(f"served: generated {n_docs} documents")

    srv = SocketRpcServer(host="127.0.0.1", port=0, durable_dir=durable_dir)
    srv.start()
    try:
        addr = "%s:%d" % srv.address
        retried = [0]
        t0 = time.perf_counter()
        with RetryingClient(addr, deadline_s=600) as c:
            handles = [r["doc"] for r in _pipeline(c, [
                ("openDurable", {"name": d["name"], "device": True})
                for d in docs
            ], "openDurable", retried)]
            _pipeline(c, [
                ("applyChanges", {"doc": h, "data": _b64(d["data"])})
                for h, d in zip(handles, docs)
            ], "load", retried)
        t_load = time.perf_counter() - t0
        note("served: loaded")

        t0 = time.perf_counter()
        made = {d: [] for d in range(n_docs)}
        errors: list = []
        threads = [
            threading.Thread(target=_client_session, args=(
                ci, addr, docs, handles, n_docs, rounds, keys, seed, made,
                errors, retried))
            for ci in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_traffic = time.perf_counter() - t0
        check(not errors, "; ".join(errors))
        note("served: traffic done")

        t0 = time.perf_counter()
        n_changes = sum(len(v) for v in made.values())
        with RetryingClient(addr, deadline_s=600) as c:
            for d, (h, doc) in enumerate(zip(handles, docs)):
                ref = AutoDoc.load(doc["data"])
                ref.apply_changes(made[d])
                text, values = ref.text(doc["text"]), ref.hydrate()
                got_text, got_values, got_digest = _pipeline(c, [
                    ("text", {"doc": h, "obj": doc["text"]}),
                    ("materialize", {"doc": h}),
                    ("docDigest", {"name": doc["name"]}),
                ], "read back", retried)
                check(got_text == text, f"{doc['name']}: RPC text differs")
                check(got_values == values,
                      f"{doc['name']}: RPC materialized values differ")
                want = integrity.doc_digest(ref.doc)
                check(got_digest["digest"] == want["digest"]
                      and got_digest["changes"] == want["changes"],
                      f"{doc['name']}: docDigest differs")
                dd = srv.rpc._docs[h]
                with dd.lock:
                    dev = dd.device_doc
                    check(dev is not None, f"{doc['name']}: mirror dropped")
                    check(dev.hydrate() == values,
                          f"{doc['name']}: device values differ")
        t_check = time.perf_counter() - t0
        note("served: checked")
    finally:
        srv.stop()
        srv.wait_stopped(30)
    return {
        "docs": n_docs, "edits_per_doc": n_edits, "clients": n_clients,
        "client_changes": n_changes, "retried_requests": retried[0],
        "generate_s": t_gen, "load_s": t_load,
        "traffic_s": t_traffic, "check_s": t_check,
        "compared": ["rpc_text", "rpc_materialize", "docDigest",
                     "device_values"],
        "equal": True,
    }


# -- phase B: fan-in ------------------------------------------------------------


def phase_fanin(seed, n_edits, n_replicas, replica_edits):
    from automerge_tpu import bench as W
    from automerge_tpu.api import AutoDoc
    from automerge_tpu.ops import DeviceDoc, OpLog

    t0 = time.perf_counter()
    trace = W.synth_edit_trace(n_edits, seed=seed)
    base = W.build_base(trace, n_edits)
    replicas = W.synth_fanin(base, trace, n_replicas, replica_edits, n_edits)
    changes = list(base.changes) + replicas
    t_gen = time.perf_counter() - t0
    note(f"fanin: generated {n_replicas} replicas")

    t0 = time.perf_counter()
    log = OpLog.from_changes(changes)
    dev = DeviceDoc.resolve(log)
    t_merge = time.perf_counter() - t0

    t0 = time.perf_counter()
    ref = AutoDoc.load(base.doc.save())
    ref.apply_changes(replicas)
    check(dev.hydrate() == ref.hydrate(),
          "fan-in: device values differ from the host Document")
    t_check = time.perf_counter() - t0
    return {"replicas": n_replicas, "edits_per_replica": replica_edits,
            "ops": int(log.n), "generate_s": t_gen, "merge_s": t_merge,
            "check_s": t_check, "compared": ["values"],
            "equal": True}


# -- --chips 4: the whale-document mesh --------------------------------------------


RES_KEYS = ("visible", "winner", "conflicts", "elem_index",
            "obj_vis_len", "obj_text_width")


def phase_mesh(seed, n_edits, sessions, n_chips):
    import jax
    import numpy as np

    from automerge_tpu import bench as W
    from automerge_tpu.api import AutoDoc
    from automerge_tpu.ops import DeviceDoc, OpLog
    from automerge_tpu.ops.merge import merge_columns
    from automerge_tpu.parallel import default_mesh, sharded_merge_columns
    from automerge_tpu.types import ActorId, ObjType

    t0 = time.perf_counter()
    doc = AutoDoc(actor=ActorId(bytes([1]) * 16))
    text = doc.put_object("_root", "text", ObjType.TEXT)
    # the sessions' edits in one bulk ingest: a splice_text_many call
    # costs time in the text's length, so one call per session grows
    # quadratically (418 s on the 4-chip host at 16 sessions)
    edits = []
    for s in range(sessions):
        edits += W.synth_edit_trace(n_edits, seed=seed * 100 + s)
    doc.splice_text_many(text, edits)
    doc.commit()
    want = doc.text(text)
    log = OpLog.from_changes([a.stored for a in doc.doc.history])
    cols = log.padded_columns()
    t_gen = time.perf_counter() - t0
    note(f"mesh: generated {log.n} ops")

    kw = dict(n_objs=log.n_objs, n_props=len(log.props))
    t0 = time.perf_counter()
    res_mesh = sharded_merge_columns(cols, default_mesh(n_chips), **kw)
    t_mesh = time.perf_counter() - t0
    mesh_bytes = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in jax.local_devices()[:n_chips]]

    t0 = time.perf_counter()
    res_one = merge_columns(log.columns(), fetch=DeviceDoc.READ_FETCH, **kw)
    t_one = time.perf_counter() - t0

    n = log.n
    for k in RES_KEYS:
        a, b = np.asarray(res_mesh[k]), np.asarray(res_one[k])
        m = n if k not in ("obj_vis_len", "obj_text_width") else log.n_objs + 2
        if k == "conflicts":
            # the packed transport returns a conflicted flag, not the
            # count (merge_columns: readers compare > 1)
            a, b = a > 1, b > 1
        check(np.array_equal(a[:m], b[:m]),
              f"mesh: {k} differs between {n_chips} chips and one")
    for name, res in (("mesh", res_mesh), ("one chip", res_one)):
        check(DeviceDoc(log, res).hydrate() == {"text": want},
              f"mesh: {name} values differ from the host Document")
    return {"chips": n_chips, "sessions": sessions, "ops": int(n),
            "rows_padded": int(len(cols["action"])), "generate_s": t_gen,
            "mesh_s": t_mesh, "one_chip_s": t_one,
            "peak_bytes_per_device_after_mesh": mesh_bytes,
            "compared": list(RES_KEYS) + ["values_vs_host"], "equal": True}


# -- entry ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the whale-document mesh path")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "automerge_tpu")):
        print("chip_smoke: the automerge_tpu package is not next to "
              "chip_smoke.py; run it from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    import jax

    from automerge_tpu import compile_cache, native

    cache_dir = compile_cache.enable()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 2
    meter = CompileMeter()
    lib = native.load()
    if lib is None:
        print("chip_smoke: the native library did not load", file=sys.stderr)
        return 1
    emit({"setup": {"device_kind": devs[0].device_kind,
                    "devices": len(devs), "jax": jax.__version__,
                    "compile_cache": cache_dir,
                    "native_library": os.path.basename(lib._name)}})

    def run(name, fn, *a):
        t0, c0, s0, m0 = time.perf_counter(), counters(), spans(), meter.snap()
        result = fn(*a)
        rep = phase_report(name, t0, c0, s0, m0, meter)
        rep["result"] = result
        emit(rep)
        check_report(rep)

    try:
        if args.chips == 4:
            run("mesh", phase_mesh, args.seed, EDIT_TRACE_EDITS,
                MESH_SESSIONS, 4)
        else:
            with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
                run("served", phase_served, args.seed, DOCS,
                    EDIT_TRACE_EDITS, CLIENTS, ROUNDS, KEYS, tmp)
            run("fanin", phase_fanin, args.seed, EDIT_TRACE_EDITS,
                REPLICAS, REPLICA_EDITS)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    # the contract line, keys in the documented order
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
