"""Traffic writer ``reconnect``: replicas of a local-first app's map
documents come back online and push the changes they made offline."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

from benchmark import deploy, loadgen
from benchmark import synth as S
from benchmark.deploy import rng
from benchmark.kinds.maps import MapEncoder, actor, value, zipf_keys
from benchmark.reference import op_id
from benchmark.traffic import Plan, chain_changes

# the shard workers the server drains with (its AUTOMERGE_TPU_SERVE_WORKERS,
# default 8): a shared launch holds at most one document a worker
WORKERS = int(os.environ.get("AUTOMERGE_TPU_SERVE_WORKERS", 8))
# set-up repeats its ramp until one compiles and loads nothing, at most
# this many times
WARM_RAMPS = 4


def doc_chains(d, keys, objs, order, ref, base_hash, start_op, t, seed):
    """The chains of document ``d``'s ``replicas_per_doc`` replicas, each
    ``changes_per_replica`` changes of ``ops_per_change`` ops on the base
    heads. Each op is on a key drawn Zipf(``zipf``) over the document's
    hot-key ``order``: a delete (``delete_share``) or a put of an int or a
    string. A replica sees the base state and its own ops; a delete of a
    key it sees deleted is a put."""
    W = t["replicas_per_doc"]
    enc = MapEncoder([actor(d, k) for k in range(W + 1)])
    out = []
    for w in range(W):
        rank, r = w + 1, rng(seed, 6, d, w)
        view = {k: ref.visible(objs[slot], key)
                for k, (slot, key) in enumerate(keys)}

        def make(start):
            ops = []
            for k in zipf_keys(r, order, t["ops_per_change"], t["zipf"]):
                slot, key = keys[k]
                if view[k] and r.random() < t["delete_share"]:
                    ops.append(("del", objs[slot], key, view[k]))
                    view[k] = []
                else:
                    ops.append(("put", objs[slot], key, value(r), view[k]))
                    view[k] = [op_id(start + len(ops) - 1, rank)]
            return ops

        chain = S.Chain(actor(d, rank), [base_hash], start_op)
        out.append(chain_changes(chain, enc, d, rank, make,
                                 t["changes_per_replica"]))
    return out


def plan(t, dep, seed, seconds):
    """Closed loop: ``replicas_per_doc`` replicas of every document return
    at once, each with one chain (``doc_chains``) that starts on the
    document's base heads, so the replicas are concurrent. One request is
    one replica's whole chain. ``connections`` clients, one request in
    flight on each; client ``c`` owns the documents ``d`` with ``d %
    connections == c``, its requests in an order drawn from the seed, so
    the requests in flight always target distinct documents. The chains
    are made in the deployment's process pool."""
    p = Plan()
    p.mode = "closed"
    p.seconds = seconds
    C = p.connections = t["connections"]
    futs = [dep.pool.submit(doc_chains, d, dep.keys, dep.objs[d],
                            dep.order[d], doc.ref, doc.base_hash,
                            dep.base_max_op + 1, t, seed)
            for d, doc in enumerate(dep.docs)]
    per_conn = [[] for _ in range(C)]
    for d, f in enumerate(futs):
        per_conn[d % C] += f.result()
    for c, reqs in enumerate(per_conn):
        order = rng(seed, 7, c).permutation(len(reqs))
        p.clients.append({"conn": c, "requests": [reqs[i] for i in order]})
    return p


def warm(client, dep, plan, cfg, meter):
    """Resolve in set-up every shared launch the window can make. A
    generation holds one document for each drain in flight, at most one a
    shard worker, and its program follows the number of its documents and
    their bucketed rows. So for each width ``g`` up to the workers (or the
    connections, if fewer), ``g`` connections of a load generator process,
    as in the window (``drive``), each push the window's requests of one
    document to a fresh copy of it: the ``g`` copies grow in step through
    every row bucket that ``g`` documents reach in a window. The first
    ramp over the widths sends for twice the window's seconds; the later
    ones send each width as many requests a connection as the first did,
    until one ramp compiles and loads no program, at most ``WARM_RAMPS``.
    The copies are freed. Returns the requests sent."""
    G = min(plan.connections, WORKERS)
    reqs = {d: [] for d in range(G)}
    for cl in plan.clients:
        for req in cl["requests"]:
            if req[0].doc in reqs:
                reqs[req[0].doc].append(req)
    depth = {}
    n = 0

    def programs():
        snap = meter.snap()
        return snap["compiles"] + snap["cache_hits"]

    for ramp in range(WARM_RAMPS):
        seen = programs()
        for g in range(1, G + 1):
            docs = dep.docs[:g]
            opened = client.pipeline([
                ("openDurable", {"name": f"{doc.name}-warm-{ramp}-{g}",
                                 "device": cfg["device"],
                                 "fsync": cfg["fsync"]})
                for doc in docs])
            handles = [r["doc"] for r in opened]
            client.pipeline([("applyChanges",
                              {"doc": h, "data": deploy.b64(doc.base_raw)})
                             for h, doc in zip(handles, docs)])
            k = depth.get(g)
            sent = drive({
                "addr": list(client.sock.getpeername()),
                "seconds": 2.0 * plan.seconds if k is None else 3600.0,
                "grace": 60.0, "mode": "closed", "connections": g,
                "seed": 0, "clients": [{"conn": d, "requests": [
                    {"due": None, "line": _line(handles[d], req)}
                    for req in reqs[d][:k]]} for d in range(g)]})
            depth.setdefault(g, max(sent))
            n += sum(sent)
            client.pipeline([("free", {"doc": h}) for h in handles])
        new = programs() - seen
        print(f"[warm] ramp {ramp}: widths 1-{G}, {new} programs compiled "
              "or loaded", file=sys.stderr, flush=True)
        if not new:
            break
    return n


def drive(job) -> list:
    """Run ``job`` to its end in a load generator process of its own, as
    the window's load comes (``benchmark/loadgen.py``), so that the
    server's drains meet as they do in the window. Returns the requests
    sent on each connection."""
    with tempfile.TemporaryDirectory(prefix="amtpu_warm_") as work:
        path = os.path.join(work, "job.json")
        with open(path, "w") as f:
            json.dump(job, f)
        child = subprocess.Popen(
            [sys.executable, loadgen.__file__, path,
             os.path.join(work, "out.json")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        try:
            if child.stdout.readline().strip() != "ready":
                raise RuntimeError("the load generator did not start")
            child.stdin.write(f"{time.monotonic()!r}\n")
            child.stdin.close()
            if child.wait() != 0:
                raise RuntimeError(
                    f"the load generator exited {child.returncode}")
            with open(os.path.join(work, "out.json")) as f:
                recs = json.load(f)["records"]
            return [sum(1 for r in recs if r[0] == c)
                    for c in range(len(job["clients"]))]
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()


def _line(handle, req) -> str:
    return json.dumps({"method": "applyChanges", "params": {
        "doc": handle, "data": deploy.b64(b"".join(ch.raw for ch in req))}})
