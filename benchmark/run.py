#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``benchmark/configs/<config>.json``), its
traffic mix (``benchmark/traffic/<traffic>.json``) and its metrics (one
reader each, ``benchmark/metrics/<metric>.py``) are found by the names in
``BENCHMARK.json``; the configuration's deployment kind
(``benchmark/kinds/<kind>.py``) and the mix's traffic writer
(``benchmark/writers/<writer>.py``) by the names in those files. A name
with no file ends the run with exit code 2 before JAX starts.

This process holds the chip. It starts JAX with the persistent compile
cache at ``.jax_cache/`` in the checkout, fails without a TPU, makes the
deployment's documents and the traffic's changes from ``--seed``, serves
them with ``SocketRpcServer`` in-process, loads and warms up, then opens
the window. The load comes from ``benchmark/loadgen.py`` in a child
process that never imports JAX. After the window it reads the device's
peak memory, checks the served state against the reference, and prints
earlier lines (one JSON object each) and, last, the result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ProcessPoolExecutor  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import deploy, devtrace, load, traffic, wire  # noqa: E402

GRACE_S = 60.0
# set when the window opens: a planted fault (benchmark/faults.py) that
# must hit the timed path waits for it
WINDOW_OPEN = threading.Event()
# counters that say a device failure was caught and degraded (as
# chip_smoke.py checks them): any of them above 0 fails the run
DEGRADE_COUNTERS = (
    ("sync.device_feed_error", None),
    ("device.batched_error", None),
    ("store.promote_error", None),
    ("device.mesh_unavailable", ("reason", "error")),
)


class Breach(Exception):
    """The run could not be measured: it prints no result."""


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:.1f}s] {msg}",
          file=sys.stderr, flush=True)


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


# -- discovery by name ---------------------------------------------------------


def load_cell(root: str, name: str) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics, as
    ``BENCHMARK.json`` names them."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in moved
                              else [])]
    return {"bench": bench, "cell": cell, "config": cfg, "traffic": mix,
            "end_to_end": e2e, "per_layer": layer}


def reader(name: str):
    """The ``read(ctx)`` function of metric ``name``."""
    return load(os.path.join(HERE, "metrics"), name).read


# -- what the program counts ---------------------------------------------------


class CompileMeter:
    """XLA compiles and persistent-cache reads, through jax.monitoring
    (copied from chip_smoke.py)."""

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
    from automerge_tpu import obs

    return {(e["name"], tuple(sorted(e["labels"].items()))): e["value"]
            for e in obs.snapshot() if e["type"] == "counter"}


def histograms() -> dict:
    """{name: {"n", "sum", "buckets"}} over every label set."""
    from automerge_tpu import obs

    out = {}
    with obs.registry.lock:
        for (name, typ), fam in obs.registry._families.items():
            if typ != "histogram":
                continue
            h = out.setdefault(name, {"n": 0, "sum": 0.0, "buckets": {}})
            for child in fam.children.values():
                h["n"] += child.n
                h["sum"] += child.total
                for i, c in child.buckets.items():
                    h["buckets"][i] = h["buckets"].get(i, 0) + c
    return out


def hist_delta(a: dict, b: dict) -> dict:
    out = {}
    for name, hb in b.items():
        ha = a.get(name, {"n": 0, "sum": 0.0, "buckets": {}})
        out[name] = {
            "n": hb["n"] - ha["n"], "sum": hb["sum"] - ha["sum"],
            "buckets": {i: c - ha["buckets"].get(i, 0)
                        for i, c in hb["buckets"].items()
                        if c - ha["buckets"].get(i, 0)},
        }
    return out


def count_delta(a: dict, b: dict) -> dict:
    return {k: v - a.get(k, 0) for k, v in b.items() if v != a.get(k, 0)}


def labelled(delta: dict, name: str, label: str) -> dict:
    out = {}
    for (n, labels), v in delta.items():
        if n == name:
            k = dict(labels).get(label, "")
            out[k] = out.get(k, 0) + v
    return out


# -- the run -------------------------------------------------------------------


def open_docs(client, dep, cfg):
    got = client.pipeline([
        ("openDurable", {"name": d.name, "device": cfg["device"],
                         "fsync": cfg["fsync"]}) for d in dep.docs])
    for d, r in zip(dep.docs, got):
        d.handle = r["doc"]
    client.pipeline([("applyChanges", {"doc": d.handle,
                                       "data": deploy.b64(d.base_raw)})
                     for d in dep.docs])


def joined(reqs) -> str:
    """The changes of ``reqs`` (requests, each a list of changes) as one
    ``applyChanges`` payload."""
    return deploy.b64(b"".join(ch.raw for req in reqs for ch in req))


def request_line(dep, req) -> str:
    return json.dumps({"method": "applyChanges", "params": {
        "doc": dep.docs[req[0].doc].handle, "data": joined([req])}})


def send_warm(client, dep, plan):
    """The warm-up changes, one pipeline per document so that drains see
    many changes at once, as in the window; then into the reference."""
    by_doc = {}
    for ch in plan.warm:
        by_doc.setdefault(ch.doc, []).append(ch)
    calls = [("applyChanges", {"doc": dep.docs[d].handle,
                               "data": deploy.b64(ch.raw)})
             for d, chs in by_doc.items() for ch in chs]
    client.pipeline(calls)
    for ch in plan.warm:
        apply_ref(dep, ch)


def warm_growth(client, dep, plan, cfg, meter):
    """Replay the states the window can reach on scratch copies of the
    documents it grows, so that the window finds every program it runs
    compiled (or in the persistent cache). ``plan.growth`` gives, for each
    document, the window's requests in the order they reach it and a
    stride. A first copy takes them ``stride`` at a time, one
    ``applyChanges`` each. Where one of its steps made the program compile
    or load anything, a second copy takes that step's requests one at a
    time, so that each state within it is resolved once too. The shapes
    of the program grow with the document and never come back, so a step
    that compiled nothing passed no new shape. The copies are freed
    afterwards. Returns the resolutions."""
    n = 0

    def programs():
        snap = meter.snap()
        return snap["compiles"] + snap["cache_hits"]

    for d, reqs, every in plan.growth:
        doc = dep.docs[d]
        warm = [[ch for ch in plan.warm if ch.doc == d]]
        copies = []

        def copy():
            h = client.call("openDurable",
                            name=f"{doc.name}-growth-{len(copies)}",
                            device=cfg["device"], fsync=cfg["fsync"])["doc"]
            client.call("applyChanges", doc=h, data=deploy.b64(doc.base_raw))
            client.call("applyChanges", doc=h, data=joined(warm))
            copies.append(h)
            return h

        a, b, at_b = copy(), None, 0
        for s in range(0, len(reqs), every):
            e = min(s + every, len(reqs))
            seen = programs()
            client.call("applyChanges", doc=a, data=joined(reqs[s:e]))
            n += 1
            if programs() == seen or e - s == 1:
                continue
            b = b or copy()
            if at_b < s:
                client.call("applyChanges", doc=b, data=joined(reqs[at_b:s]))
                n += 1
            for k in range(s, e - 1):
                client.call("applyChanges", doc=b, data=joined(reqs[k:k + 1]))
                n += 1
            at_b = e - 1
        for h in copies:
            client.call("free", doc=h)
    return n


def apply_acked(dep, clients, by_client):
    """Every acknowledged change whose dependencies the server holds into
    the reference, in each client's order. A change acknowledged after
    one of its writer's earlier changes was given up waits in the server
    for the missing one, so it is not in the served state either."""
    for cl, recs in zip(clients, by_client):
        for req, rec in zip(cl["requests"], recs):
            if rec is None or rec[4] is None or rec[6] is not None:
                continue
            for ch in req:
                if all(d in dep.docs[ch.doc].held for d in ch.deps):
                    apply_ref(dep, ch)


def apply_ref(dep, ch):
    doc = dep.docs[ch.doc]
    dep.apply(doc, ch.rank, ch.start_op, ch.ops)
    doc.applied(ch.hash, ch.deps)


def check(client, srv, dep, plan, out) -> dict:
    """Every acknowledged change into the reference, then each document
    compared by its deployment kind's ``compare``. Returns {check:
    documents that differ}."""
    apply_acked(dep, plan.clients, out["by_client"])
    bad = {}
    for d in dep.docs:
        for k, v in dep.compare(client, srv, d).items():
            bad[k] = bad.get(k, 0) + v
    return bad


def loadgen(work, name, addr, plan, clients, seconds, dep, seed):
    """Start the load generator on ``clients`` for a window of
    ``seconds``; it connects and waits for ``start``."""
    job = {"addr": addr, "seconds": seconds, "grace": GRACE_S,
           "mode": plan.mode, "connections": plan.connections,
           "seed": seed % (1 << 63),
           "clients": [{"conn": cl["conn"], "requests": [
               {"due": cl["due"][k] if "due" in cl else None,
                "line": request_line(dep, req)}
               for k, req in enumerate(cl["requests"])]} for cl in clients]}
    path = os.path.join(work, name + ".job.json")
    with open(path, "w") as f:
        json.dump(job, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), path,
         os.path.join(work, name + ".out.json")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    if child.stdout.readline().strip() != "ready":
        raise Breach("the load generator did not start")
    return child


def start(child, t0):
    """Open the generator's window at ``t0`` (monotonic clock)."""
    child.stdin.write(f"{t0!r}\n")
    child.stdin.close()


def by_client(clients, result):
    """Records as [client][index] (None where a request never came due)."""
    out = [[None] * len(cl["requests"]) for cl in clients]
    for rec in result["records"]:
        out[rec[0]][rec[1]] = rec
    return out


def run(args, require_tpu=True, spec=None) -> int:
    spec = spec or load_cell(ROOT, args.workload)
    cell, cfg, mix = spec["cell"], spec["config"], spec["traffic"]
    if not os.path.isdir(os.path.join(ROOT, "automerge_tpu")):
        print("bench: the automerge_tpu package is not in this checkout",
              file=sys.stderr)
        return 2
    if require_tpu:
        # a fixed path inside the checkout: only the first run compiles
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                               ".jax_cache")
    os.environ["AUTOMERGE_TPU_SPAN_BUFFER"] = str(1 << 18)
    try:
        readers = {m["name"]: reader(m["name"]) for m in (
            spec["per_layer"] if args.trace else spec["end_to_end"])}
        deployment = deploy.kind(cfg["kind"])
        writer = traffic.writer(mix["writer"])
    except FileNotFoundError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    # the reference's replay of each base document runs in JAX-free
    # workers while this process starts JAX
    pool = ProcessPoolExecutor(max_workers=min(8, os.cpu_count() or 1),
                               mp_context=multiprocessing.get_context("spawn"))
    try:
        dep = deployment.Deployment(cfg, args.seed, pool)
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        devs = jax.devices()
        if require_tpu and devs[0].platform != "tpu":
            print(f"bench: no TPU: JAX found {len(devs)} {devs[0].platform} "
                  "device(s)", file=sys.stderr)
            return 3
        if len(devs) < cell["chips"]:
            print(f"bench: the cell needs {cell['chips']} chips, JAX found "
                  f"{len(devs)}", file=sys.stderr)
            return 3
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        kind = devs[0].device_kind
        if require_tpu and kind not in peaks:
            print(f"bench: no peaks for device kind {kind!r}", file=sys.stderr)
            return 3
        meter = CompileMeter()
        from automerge_tpu import compile_cache

        compile_cache.enable()
        dep.build()
        plan = writer.plan(mix, dep, args.seed, args.seconds)
    finally:
        pool.shutdown()
    log(f"generated {len(dep.docs)} documents, {len(plan.warm)} warm-up "
        f"changes and {sum(len(c['requests']) for c in plan.clients)} "
        "traffic requests")
    return window(args, spec, dep, plan, readers, meter, devs, peaks,
                  getattr(writer, "warm", warm_growth))


def window(args, spec, dep, plan, readers, meter, devs, peaks, warm) -> int:
    """Serve the documents, warm up (``warm``: the writer's own, or
    ``warm_growth``), open the window, then read the metrics and compare
    the served state; prints the result line."""
    import jax

    cell, cfg, mix = spec["cell"], spec["config"], spec["traffic"]
    WINDOW_OPEN.clear()
    durable = tempfile.mkdtemp(prefix="amtpu_bench_")
    work = tempfile.mkdtemp(prefix="amtpu_bench_run_")
    from automerge_tpu.serve import SocketRpcServer

    srv = SocketRpcServer(host="127.0.0.1", port=0, durable_dir=durable)
    srv.start()
    child = None
    try:
        addr = list(srv.address)
        client = wire.Client(addr)
        open_docs(client, dep, cfg)
        log("documents open and loaded")
        send_warm(client, dep, plan)
        log("warm-up sent")
        n = warm(client, dep, plan, cfg, meter)
        if n:
            log(f"growth warmed in {n} steps")

        trace_dir = os.path.join(work, "trace")
        if args.trace:
            jax.profiler.start_trace(trace_dir)
        child = loadgen(work, "window", addr, plan, plan.clients,
                        args.seconds, dep, args.seed)
        t0 = time.monotonic() + 0.2
        WINDOW_OPEN.set()
        start(child, t0)
        out_path = os.path.join(work, "window.out.json")
        c0, h0, m0 = counters(), histograms(), meter.snap()
        # name any program compiled inside the window on stderr
        jax.config.update("jax_log_compiles", True)
        setup_s = t0 - T_START
        time.sleep(max(0.0, t0 - time.monotonic()))
        ann = (jax.profiler.TraceAnnotation(devtrace.WINDOW) if args.trace
               else None)
        if ann is not None:
            ann.__enter__()
        p0 = time.perf_counter()
        time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        p1 = time.perf_counter()
        if ann is not None:
            ann.__exit__(None, None, None)
        c1, h1, m1 = counters(), histograms(), meter.snap()
        jax.config.update("jax_log_compiles", False)
        from automerge_tpu import obs

        spans = [(r.name, r.start + obs._ORIGIN - p0,
                  r.start + obs._ORIGIN + r.duration - p0, r.fields)
                 for r in obs.recorder.snapshot()
                 if r.start + obs._ORIGIN + r.duration > p0
                 and r.start + obs._ORIGIN < p1]
        if args.trace:
            jax.profiler.stop_trace()
        child.wait(timeout=args.seconds + GRACE_S + 60)
        if child.returncode != 0:
            raise Breach(f"the load generator exited {child.returncode}")
        with open(out_path) as f:
            result = json.load(f)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:cell["chips"]])
        c_all = count_delta({}, c1)
        win_c = count_delta(c0, c1)
        run_line = {
            "run": {
                "kernel_launches": labelled(win_c, "device.kernel_launches",
                                            "path"),
                "degrade": {
                    n if lab is None else f"{n}{{{lab[0]}={lab[1]}}}": sum(
                        v for (cn, ls), v in c_all.items() if cn == n and (
                            lab is None or dict(ls).get(lab[0]) == lab[1]))
                    for n, lab in DEGRADE_COUNTERS},
                "host_engine_merges": obs.timing_summary().get(
                    "merge.host", {}).get("n", 0),
                "window_compiles": {k: m1[k] - m0[k] for k in m1},
                "setup_compiles": m0,
            }}
        recs = result["records"]
        late = result["late_s"]
        emit({"load": {
            "requests_due": len(recs),
            "answered": sum(1 for r in recs if r[4] is not None),
            "retried": sum(r[5] for r in recs),
            "abandoned": sum(1 for r in recs if r[6] == "abandoned"),
            "errors": sum(1 for r in recs
                          if r[6] not in (None, "abandoned")),
            "generator_late_ms": {
                "p50": 1e3 * late[len(late) // 2] if late else None,
                "p99": 1e3 * late[int(len(late) * 0.99)] if late else None,
                "max": 1e3 * late[-1] if late else None},
            "exhausted_clients": len(result["exhausted"])}})
        emit(run_line)
        rl = run_line["run"]
        # the rules of every run, compared like the served state: a
        # breach makes the run not correct. A program compiled, or read
        # from the persistent cache, inside the window is one that set-up
        # did not warm (jax_log_compiles names it on stderr)
        wc = rl["window_compiles"]
        rules = {
            "programs_compiled_in_window": wc["compiles"] + wc["cache_hits"],
            "clients_out_of_changes": len(result["exhausted"]),
            "launches_missing_on_" + mix["expect_path"]: int(
                rl["kernel_launches"].get(mix["expect_path"], 0) <= 0),
            "degraded_device_failures": sum(rl["degrade"].values()),
            "host_engine_merges": rl["host_engine_merges"],
        }
        ops = [[sum(len(ch.ops) for ch in req) for req in cl["requests"]]
               for cl in plan.clients]
        ctx = {
            "cell": cell["name"], "seconds": args.seconds, "setup_s": setup_s,
            "records": [r + [ops[r[0]][r[1]]] for r in recs],
            "grace_s": GRACE_S, "spans": spans, "counters": win_c,
            "hists": hist_delta(h0, h1), "peaks": peaks.get(devs[0].device_kind),
            "trace": None, "window_perf": (p0, p1),
        }
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": cell["chips"], "memory_peak_bytes": int(peak)}
        breakdown = None
        if args.trace:
            red = devtrace.reduce(devtrace.events_from_file(trace_dir))
            ctx["trace"] = red
            mods = {}
            for n, _, d in red["modules"]:
                m = mods.setdefault(n, [0, 0.0])
                m[0] += 1
                m[1] += d
            # the roofline's module match is by name (``jit_f``): the
            # launches it counts set beside the resolution spans say
            # whether other programs share the name
            emit({"trace": {"busy_s": red["busy_s"],
                            "window_s": red["window_s"],
                            "modules": mods,
                            "kernel_spans": sum(
                                1 for n, s, _, _ in spans
                                if n == "device.kernel"
                                and 0.0 <= s < args.seconds)}})
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {
                "device_ops": [[n, s] for n, s in red["ops"][:10]],
                "idle_gaps": [[devtrace.open_span(
                    [(n, s, e) for n, s, e, _ in spans], at, d), d]
                    for at, d in red["gaps"][:10]],
            }
        metrics = {}
        units = {m["name"]: m["unit"]
                 for m in spec["end_to_end"] + spec["per_layer"]}
        for name, fn in readers.items():
            v = fn(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}

        result["by_client"] = by_client(plan.clients, result)
        bad = check(client, srv, dep, plan, result)
        unanswered = sum(1 for r in recs if r[4] is None and r[6] is None)
        checks = {f"{k}_docs_differing": {"value": v, "limit": 0}
                  for k, v in bad.items()}
        checks["unanswered_requests"] = {"value": unanswered, "limit": 0}
        for k, v in rules.items():
            checks[k] = {"value": v, "limit": 0}
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        client.close()
    except Breach as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        srv.stop()
        srv.wait_stopped(60)
        shutil.rmtree(durable, ignore_errors=True)
        shutil.rmtree(work, ignore_errors=True)
    for k, c in checks.items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": len(recs),
            "failed": sum(1 for r in recs if r[6] != "abandoned" and (
                r[4] is None or r[6] is not None)),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None, require_tpu=True, spec=None) -> int:
    """``require_tpu`` and ``spec`` are for the tests under
    ``benchmark/tests``, which drive a run on the CPU at a small size."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if spec is None and not os.path.isfile(os.path.join(ROOT, "BENCHMARK.json")):
        print("bench: no BENCHMARK.json at the checkout's root", file=sys.stderr)
        return 2
    return run(args, require_tpu=require_tpu, spec=spec)


if __name__ == "__main__":
    sys.exit(main())
