#!/usr/bin/env python
"""Benchmark driver: the BASELINE.md configs on real hardware.

Primary metric (BASELINE.json): ops/sec merged on the edit-trace N-replica
fan-in through the full device path (columnar extraction + batched merge
kernel + readback), vs the sequential-apply baseline. The baseline divisor
is the FASTER of (a) the measured native C++ sequential apply on this host
(automerge_tpu/bench.py seq_apply_baseline — the reference's
apply_changes loop shape, automerge.rs:1258-1280, natively compiled) and
(b) the pinned Rust estimate documented in BASELINE.md — i.e. the
conservative choice.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": "ops/s", "vs_baseline": ...,
   "configs": {replay, fanin, mapcounter, rga, sync}}

Env knobs: BENCH_BASE_EDITS, BENCH_REPLICAS, BENCH_FORK_EDITS,
BENCH_REPLAY_EDITS, BENCH_MC_ACTORS, BENCH_MC_INCS, BENCH_RGA_ACTORS,
BENCH_RGA_OPS, BENCH_SYNC_OPS, BENCH_HOST_CAP, BENCH_VERBOSE.
"""

import json
import os
import sys
import time

# Pinned Rust-reference throughput estimates (ops/s) — see BASELINE.md
# "Pinned baseline" for the reasoning. No Rust toolchain exists in this
# image; the measured native C++ sequential apply below is the primary
# baseline and these pins act as a floor so vs_baseline can never benefit
# from a slow native build.
RUST_PIN_REPLAY = 500_000.0   # local transaction replay (edit-trace bench)
RUST_PIN_APPLY = 250_000.0    # remote apply_changes (per-op seek/insert)


# every knob resolved through env_int / env_flag lands here, so the
# output JSON carries the exact configuration that produced it — the
# BENCH_r0*.json trajectory stays self-describing across PRs
RESOLVED_CONFIG = {}

BENCH_SCHEMA_VERSION = 2


def env_int(name, default):
    v = int(os.environ.get(name, default))
    RESOLVED_CONFIG[name] = v
    return v


def env_flag(name, default=""):
    v = os.environ.get(name, default)
    RESOLVED_CONFIG[name] = v
    return v


def git_commit():
    """The repo HEAD this bench ran against (None outside a checkout)."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        )
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None


def host_fingerprint():
    """Which box produced these numbers. scripts/ci/perf_gate refuses to
    compare trajectory points whose fingerprints differ — a number from
    a different host is a different experiment, not a regression."""
    import platform

    fp = {
        "cpu_count": os.cpu_count(),
        "machine": platform.machine(),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    try:
        import jax

        fp["jax_backend"] = jax.default_backend()
        fp["jax_device_count"] = jax.device_count()
    except Exception:
        fp["jax_backend"] = None
        fp["jax_device_count"] = 0
    return fp


def main():
    # Benchmark hygiene (what pytest-benchmark and criterion do): cyclic-GC
    # pauses are runtime noise, not framework cost — the store's bulk builds
    # allocate ~1M objects and a generational collection walking them lands
    # at an arbitrary later point, skewing whichever phase it lands in.
    import gc

    gc.disable()
    import resource as _resource

    import numpy as np

    from automerge_tpu import bench as W
    from automerge_tpu.api import AutoDoc
    from automerge_tpu.core.document import Document
    from automerge_tpu.ops import DeviceDoc, OpLog
    from automerge_tpu.ops.merge import merge_columns
    from automerge_tpu.sync import SyncState
    from automerge_tpu.types import ActorId

    verbose = env_flag("BENCH_VERBOSE")
    reps = env_int("BENCH_REPS", 3)  # best-of-N, one knob for every config
    results = {}

    # per-config wall clock: elapsed seconds between consecutive marks,
    # summed to a total at the end — the additive number perf_gate tracks
    # so a config that quietly doubles its setup cost is caught even when
    # its headline throughput metric holds steady
    wall_s = {}
    _wall_prev = [time.perf_counter()]

    def wall_mark(config):
        now = time.perf_counter()
        wall_s[config] = round(now - _wall_prev[0], 3)
        _wall_prev[0] = now

    def note(msg):
        if verbose:
            print(msg, file=sys.stderr, flush=True)

    trace = W.synth_edit_trace()

    # ---- config 1: full-trace replay through the host transaction layer ----
    n_replay = env_int("BENCH_REPLAY_EDITS", len(trace))
    doc = AutoDoc(actor=ActorId(bytes([7]) * 16))
    from automerge_tpu.types import ObjType

    tobj = doc.put_object("_root", "text", ObjType.TEXT)
    t0 = time.perf_counter()
    n_ops = W.apply_edits(doc, tobj, trace[:n_replay])
    doc.commit()
    t_replay = time.perf_counter() - t0
    # bulk-ingest variant: the same edits through splice_text_many (the
    # whole replay loop runs in the native edit session)
    doc_b = AutoDoc(actor=ActorId(bytes([8]) * 16))
    tobj_b = doc_b.put_object("_root", "text", ObjType.TEXT)
    t0 = time.perf_counter()
    n_b = doc_b.splice_text_many(tobj_b, trace[:n_replay])
    doc_b.commit()
    t_batch = time.perf_counter() - t0
    results["replay"] = {
        "edits": n_replay,
        "ops": n_ops,
        "seconds": round(t_replay, 3),
        "ops_per_sec": round(n_ops / t_replay, 1),
        "vs_baseline": round(n_ops / t_replay / RUST_PIN_REPLAY, 4),
        "batch_ops_per_sec": round(n_b / t_batch, 1),
        "batch_vs_baseline": round(n_b / t_batch / RUST_PIN_REPLAY, 4),
    }
    if env_flag("BENCH_PHASES"):
        # the reference edit-trace binary's phase report
        # (rust/edit-trace/src/main.rs:23-55): save / load / fork_at / text
        t0 = time.perf_counter()
        saved = doc_b.save()
        t_save = time.perf_counter() - t0
        t0 = time.perf_counter()
        loaded = AutoDoc.load(saved)
        t_load = time.perf_counter() - t0
        heads = doc_b.get_heads()
        t0 = time.perf_counter()
        forked = doc_b.fork_at(heads)
        t_fork = time.perf_counter() - t0
        t0 = time.perf_counter()
        txt = loaded.text(tobj_b)
        t_text = time.perf_counter() - t0
        assert forked.get_heads() == heads
        results["replay"]["phases_ms"] = {
            "save": round(t_save * 1000, 1),
            "load": round(t_load * 1000, 1),
            "fork_at": round(t_fork * 1000, 1),
            "text": round(t_text * 1000, 1),
            "save_bytes": len(saved),
            "text_len": len(txt),
        }
    note(f"replay: {results['replay']}")
    wall_mark("replay")
    del doc, doc_b

    # ---- config 2: N-way fan-in merge (primary) ----------------------------
    # BASELINE.json sizes: forks of the FULL 259,778-edit trace document
    base_edits = env_int("BENCH_BASE_EDITS", len(trace))
    n_replicas = env_int("BENCH_REPLICAS", 1024)
    fork_edits = env_int("BENCH_FORK_EDITS", 250)
    t0 = time.perf_counter()
    base = W.build_base(trace, base_edits)
    t_base = time.perf_counter() - t0
    t0 = time.perf_counter()
    replica_changes = W.synth_fanin(base, trace, n_replicas, fork_edits, base_edits)
    changes = list(base.changes) + replica_changes
    t_synth = time.perf_counter() - t0
    note(f"fanin build: base {t_base:.1f}s, synth {t_synth:.1f}s")

    # device path: extraction + kernel + native linearization + readback
    def device_merge_timed(chs, reps, rep_times=None):
        """Warm up (jit compile + page-in), then min-of-reps end to end.
        ``rep_times`` (a list, if given) collects every rep's e2e seconds
        so configs can report their spread."""
        log = OpLog.from_changes(chs)
        kw = dict(
            fetch=DeviceDoc.READ_FETCH, n_objs=log.n_objs,
            n_props=len(log.props),
        )
        res = merge_columns(log.columns(), **kw)
        best = (float("inf"), float("inf"))
        for _ in range(reps):
            # release the previous rep's arrays BEFORE reallocating: the
            # tuned allocator (native._tune_allocator) then reuses the
            # same resident pages and identical reps agree within a few
            # percent (the r4 "3-60s" spread was refaulting the working
            # set while the old copy was still live)
            log = res = None
            t0 = time.perf_counter()
            log = OpLog.from_changes(chs)
            t_ex = time.perf_counter() - t0
            t0 = time.perf_counter()
            res = merge_columns(log.columns(), **kw)
            t_mg = time.perf_counter() - t0
            if rep_times is not None:
                rep_times.append(t_ex + t_mg)
            if t_ex + t_mg < sum(best):
                best = (t_ex, t_mg)
        return log, res, best

    log, res, (t_extract, t_merge) = device_merge_timed(
        changes, reps
    )
    t_device = t_extract + t_merge
    n = log.n

    # baseline 1: native sequential apply (measured)
    t_native, native_text = W.seq_apply_baseline(
        changes, base.text_obj, reps=reps
    )
    native_rate = n / t_native

    # convergence check: device == native sequential
    dev = DeviceDoc(log, res)
    dev_text = dev.text(base.text_exid)
    assert dev_text == native_text, "device/native merge divergence"

    # baseline 2: the framework's own host python apply (rate from a slice)
    host_cap = env_int("BENCH_HOST_CAP", 60_000)
    host = Document(ActorId(bytes([9]) * 16))
    t0 = time.perf_counter()
    applied_ops = 0
    for ch in changes:
        host.apply_changes([ch])
        applied_ops += len(ch.ops)
        if applied_ops >= host_cap:
            break
    host.ops  # noqa: B018 — applies defer; materialize the view
    t_host = time.perf_counter() - t0
    host_rate = applied_ops / t_host

    baseline_rate = max(native_rate, RUST_PIN_APPLY)
    dev_rate = n / t_device

    # kernel-only, device-timed: inputs resident on device, outputs left on
    # device, transport excluded. Bytes each way are recorded alongside so
    # the e2e gap is attributable. A failure here fails the run.
    kernel = {}
    if env_flag("BENCH_KERNEL", "1") != "0":
        import jax
        import jax.numpy as jnp

        from automerge_tpu.ops.merge import (
            encode_transport, merge_kernel, merge_kernel_core,
            scatter_geometry_ok, scatter_kernel_core,
        )

        cols_np = log.padded_columns(include_aorder=True)
        cols_dev = jax.block_until_ready(
            {k: jnp.asarray(v) for k, v in cols_np.items()}
        )
        # completion is forced by reading ONE scalar back; that read's
        # latency is measured separately and subtracted, and M chained
        # kernel launches amortize the residual.
        M = env_int("BENCH_KERNEL_CHAIN", 4)

        def _sync(o):
            return float(np.asarray(o["obj_vis_len"][0]))

        def time_kernel(fn, host_work=None):
            """Warm + rtt-probe + best-of-reps of M chained launches;
            ``host_work`` (if given) runs between dispatch and sync each
            launch — the host-overlap the production pipeline uses."""
            out = fn(cols_dev)  # compile + warm
            _sync(out)
            t0 = time.perf_counter()
            _sync(out)
            rtt = time.perf_counter() - t0
            t_best = float("inf")
            for _ in range(reps + 1):
                t0 = time.perf_counter()
                for _ in range(M):
                    out = fn(cols_dev)  # async dispatch
                    if host_work is not None:
                        host_work()
                _sync(out)
                dt = max(time.perf_counter() - t0 - rtt, 1e-9) / M
                t_best = min(t_best, dt)
            return t_best, rtt

        have_scatter = scatter_geometry_ok(
            len(cols_np["action"]), log.n_objs, len(log.props)
        )
        # all-device document ordering: the chain-condensed kernel
        # (runs found by scans, doubling only over the run tables)
        # replaces the plain pointer-doubling ranking when the run count
        # fits a bucket meaningfully below the row space
        from automerge_tpu.ops.merge import (
            condensed_caps, merge_kernel_condensed,
        )

        rcap, obj_cap = condensed_caps(log)
        if rcap <= len(cols_np["action"]):
            full_fn = merge_kernel_condensed(rcap, obj_cap)
            kernel["condensed_runs"] = int(log.condensed_run_count())
        else:
            full_fn = merge_kernel
        variants = [("full", full_fn), ("core", merge_kernel_core)]
        if have_scatter:
            variants.append(
                ("scatter", scatter_kernel_core(log.n_objs, len(log.props)))
            )
        for name, fn in variants:
            t_best, rtt = time_kernel(fn)
            kernel[f"t_kernel_{name}_s"] = round(t_best, 4)
            kernel[f"kernel_{name}_ops_per_sec"] = round(n / t_best, 1)
            # per-variant: each variant's timing subtracts its own probe
            kernel[f"sync_rtt_{name}_s"] = round(rtt, 4)
        kernel["kernel_chain"] = M
        _, arrays = encode_transport(cols_np)
        kernel["transport_bytes_in"] = int(
            sum(a.nbytes for a in arrays.values())
        )
        # "pipeline": what production actually runs — the resolution
        # kernel on device OVERLAPPED with the host preorder ranking
        # (ops/merge.py host_linearize supplies elem_index). This number
        # INCLUDES document ordering, unlike the scatter/core variants,
        # and is the reported kernel number.
        from automerge_tpu.ops.oplog import host_linearize

        pipe_fn = variants[-1][1] if have_scatter else merge_kernel_core
        t_best, rtt = time_kernel(
            pipe_fn, host_work=lambda: host_linearize(cols_np)
        )
        kernel["t_kernel_pipeline_s"] = round(t_best, 4)
        kernel["kernel_pipeline_ops_per_sec"] = round(n / t_best, 1)
        kernel["sync_rtt_pipeline_s"] = round(rtt, 4)
        # headline kernel number = the pipeline (resolution + ordering).
        # The scatter/core variants above isolate the device resolution
        # phase; "full" is the all-device path whose ranking gathers are
        # the known-weak spot (BASELINE.md).
        best_core = kernel["kernel_pipeline_ops_per_sec"]
        kernel["kernel_ops_per_sec"] = best_core
        kernel["kernel_vs_baseline"] = round(best_core / baseline_rate, 3)
        note(f"fanin kernel-only: {kernel}")

    results["fanin"] = {
        **kernel,
        "replicas": n_replicas,
        "ops": n,
        "t_extract_s": round(t_extract, 3),
        "t_merge_s": round(t_merge, 3),
        "p50_merge_latency_s": round(t_device, 3),
        "ops_per_sec": round(dev_rate, 1),
        "native_seq_apply_ops_per_sec": round(native_rate, 1),
        "host_python_ops_per_sec": round(host_rate, 1),
        "baseline_ops_per_sec": round(baseline_rate, 1),
        # vs the measured decode+apply model (conservative: the model is
        # faster than the Rust reference — no B-tree, no index upkeep)
        "vs_baseline": round(dev_rate / baseline_rate, 3),
        # vs the pinned Rust apply_changes estimate (BASELINE.md) — the
        # divisor BASELINE.json's >=50x target is phrased against
        "vs_pin": round(dev_rate / RUST_PIN_APPLY, 3),
    }
    note(f"fanin: {results['fanin']}")
    wall_mark("fanin")

    # ---- config 2b: incremental device merge (persistent DeviceDoc) --------
    # K small deltas (one live replica typing against a large resident doc)
    # applied through the incremental append + dirty-set re-resolution path;
    # the divisor is the from-scratch extract+resolve at the SAME final
    # state. p50 per-delta latency is the headline (the first delta pays the
    # new-actor rank remap; the median is the steady state the sync path
    # sees). Device-phase spans (trace.time) are exported as phases_s.
    from automerge_tpu import obs
    from automerge_tpu import trace as T

    def _latency_percentiles(hist_name, latencies):
        """Feed raw per-iteration latencies into the named obs histogram
        and report its log-bucket-derived p50/p95/p99 (what a scraper of
        the Prometheus exposition would compute)."""
        h = obs.registry.histogram(hist_name)
        for x in latencies:
            h.observe(x)
        return {
            "latency_p50_s": round(h.percentile(0.50), 6),
            "latency_p95_s": round(h.percentile(0.95), 6),
            "latency_p99_s": round(h.percentile(0.99), 6),
        }

    inc_k = env_int("BENCH_INC_DELTAS", 16)
    inc_ops = env_int("BENCH_INC_OPS", 250)
    inc = {}
    try:
        deltas = W.synth_delta_chain(base, trace, inc_k, inc_ops, base_edits)
        resident_changes = list(base.changes)
        final_changes = resident_changes + [c for b in deltas for c in b]
        _, _, (t_fex, t_fmg) = device_merge_timed(final_changes, reps)
        t_scratch = t_fex + t_fmg
        dev = DeviceDoc.resolve(OpLog.from_changes(resident_changes))
        # clean per-config phase attribution WITHOUT losing the whole-run
        # totals the top-level trace_timings reports: stash + merge back
        saved_timings = {k: list(v) for k, v in T.timings.items()}
        T.reset_timers()
        lats = []
        for b in deltas:
            t0 = time.perf_counter()
            dev.apply_changes(b)
            lats.append(time.perf_counter() - t0)
        full = DeviceDoc.resolve(OpLog.from_changes(final_changes))
        assert dev.text(base.text_exid) == full.text(base.text_exid), (
            "incremental/full divergence"
        )
        lat = sorted(lats)
        p50 = lat[len(lat) // 2]
        delta_ops = sum(len(c.ops) for b in deltas for c in b) / max(
            len(deltas), 1
        )
        inc = {
            "deltas": len(deltas),
            "ops_per_delta": int(delta_ops),
            "resident_ops": dev.log.n,
            "p50_delta_latency_s": round(p50, 5),
            "max_delta_latency_s": round(lat[-1], 5),
            **_latency_percentiles("bench.incremental.delta_latency", lats),
            "delta_ops_per_sec": round(delta_ops / p50, 1),
            "from_scratch_s": round(t_scratch, 4),
            "speedup_vs_rebuild": round(t_scratch / p50, 2),
            "phases_s": {
                k: v["s"] for k, v in T.timing_summary().items()
            },
            "counters": {
                k: v
                for k, v in T.counters.items()
                if k.startswith(("oplog.", "device.", "extract."))
            },
        }
        for k, v in T.timings.items():
            s = saved_timings.setdefault(k, [0.0, 0])
            s[0] += v[0]
            s[1] += v[1]
        T.timings.clear()
        T.timings.update(saved_timings)
        del dev, full, deltas, final_changes
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        inc = {"incremental_error": repr(e)[:500]}
        print(f"incremental config failed:\n{tb}", file=sys.stderr, flush=True)
    results["incremental"] = inc
    note(f"incremental: {results['incremental']}")
    wall_mark("incremental")

    # ---- config 3: Map+Counter commutative merge ---------------------------
    # BASELINE.json size: 10k actors x 1k increments = ~10M ops
    mc_actors = env_int("BENCH_MC_ACTORS", 10_000)
    mc_incs = env_int("BENCH_MC_INCS", 1_000)
    cdoc, keys = W.build_counter_base(64)
    t0 = time.perf_counter()
    mc_changes, mc_expected = W.synth_mapcounter(cdoc, keys, mc_actors, mc_incs)
    t_synth = time.perf_counter() - t0
    all_mc = [a.stored for a in cdoc.doc.history] + mc_changes
    mc_reps = []
    mlog, mres, (t_mc_ex, t_mc_mg) = device_merge_timed(
        all_mc, reps, rep_times=mc_reps
    )
    t_mc = t_mc_ex + t_mc_mg
    mdev = DeviceDoc(mlog, mres)
    # exact-total verification: every increment is +1
    for k in keys[:4]:
        got = mdev.get("_root", k)
        assert got[0] == ("counter", mc_expected.get(k, 0)), (k, got)
    mc_rate = mlog.n / t_mc
    results["mapcounter"] = {
        "actors": mc_actors,
        "ops": mlog.n,
        "t_synth_s": round(t_synth, 2),
        "t_extract_s": round(t_mc_ex, 3),
        "t_merge_s": round(t_mc_mg, 3),
        "p50_merge_latency_s": round(t_mc, 3),
        # per-rep spread: identical calls should agree (VERDICT r4 flagged
        # 3-60s swings; the allocator tuning in native.load targets this)
        "rep_seconds": [round(t, 3) for t in mc_reps],
        "rep_spread": round(max(mc_reps) / min(mc_reps), 2) if mc_reps else None,
        "ops_per_sec": round(mc_rate, 1),
        "vs_baseline": round(mc_rate / RUST_PIN_APPLY, 3),
    }
    note(f"mapcounter: {results['mapcounter']}")
    wall_mark("mapcounter")
    del mlog, mres, mdev, mc_changes, all_mc

    # ---- config 4: RGA stress ---------------------------------------------
    # >=1M interleaved ops on one shared sequence (1k actors x 1k ops)
    rga_actors = env_int("BENCH_RGA_ACTORS", 1_000)
    rga_ops = env_int("BENCH_RGA_OPS", 1_000)
    rbase = W.build_base(trace, 3_000)
    rga_changes = W.synth_rga(rbase, rga_actors, rga_ops)
    all_rga = list(rbase.changes) + rga_changes
    rlog, rres, (t_rga_ex, t_rga_mg) = device_merge_timed(
        all_rga, reps
    )
    t_rga = t_rga_ex + t_rga_mg
    t_rn, rn_text = W.seq_apply_baseline(
        all_rga, rbase.text_obj, reps=reps
    )
    rdev = DeviceDoc(rlog, rres)
    assert rdev.text(rbase.text_exid) == rn_text, "rga device/native divergence"
    rga_baseline = max(rlog.n / t_rn, RUST_PIN_APPLY)
    rga_rate = rlog.n / t_rga
    results["rga"] = {
        "actors": rga_actors,
        "ops": rlog.n,
        "p50_merge_latency_s": round(t_rga, 3),
        "ops_per_sec": round(rga_rate, 1),
        "native_seq_apply_ops_per_sec": round(rlog.n / t_rn, 1),
        "vs_baseline": round(rga_rate / rga_baseline, 3),
        "vs_pin": round(rga_rate / RUST_PIN_APPLY, 3),
    }
    note(f"rga: {results['rga']}")
    wall_mark("rga")
    del rlog, rres, rdev, rga_changes, all_rga

    # ---- config 5: sync catch-up ------------------------------------------
    # BASELINE.json size: 1M-op divergence
    sync_ops = env_int("BENCH_SYNC_OPS", 1_000_000)
    sbase = W.build_base(trace, 2_000)
    n_sync_replicas = max(sync_ops // 2_000, 1)
    sync_changes = W.synth_fanin(sbase, trace, n_sync_replicas, 2_000, 2_000)
    base_save = sbase.doc.save()
    ahead = AutoDoc.load(base_save)
    ahead.apply_changes(sync_changes)
    n_synced = sum(len(c.ops) for c in sync_changes)
    ahead_text = ahead.text(sbase.text_exid)

    def sync_once():
        """One full catch-up of a fresh behind replica; returns
        (seconds, rounds, phase dict). Phases: generate (bloom build,
        have/need, change selection, transport encode) and receive
        (transport decode, causal merge) per side, plus the caught-up
        read that materializes the replica."""
        behind = AutoDoc.load(base_save)
        s1, s2 = SyncState(), SyncState()
        ph = {"gen_ahead": 0.0, "gen_behind": 0.0,
              "recv_behind": 0.0, "recv_ahead": 0.0, "read": 0.0}
        round_lats = []
        t0 = time.perf_counter()
        rounds = 0
        while True:
            t = r0 = time.perf_counter()
            m1 = ahead.generate_sync_message(s1)
            ph["gen_ahead"] += time.perf_counter() - t
            t = time.perf_counter()
            m2 = behind.generate_sync_message(s2)
            ph["gen_behind"] += time.perf_counter() - t
            if m1 is None and m2 is None:
                break
            if m1 is not None:
                t = time.perf_counter()
                behind.receive_sync_message(s2, m1)
                ph["recv_behind"] += time.perf_counter() - t
            if m2 is not None:
                t = time.perf_counter()
                ahead.receive_sync_message(s1, m2)
                ph["recv_ahead"] += time.perf_counter() - t
            rounds += 1
            round_lats.append(time.perf_counter() - r0)
            if rounds > 100:
                raise RuntimeError("sync did not converge")
        # one read inside the timed region: op-store materialization is
        # lazy, so catch-up isn't "done" until the replica is readable
        t = time.perf_counter()
        behind_text = behind.text(sbase.text_exid)
        ph["read"] = time.perf_counter() - t
        dt = time.perf_counter() - t0
        assert behind.get_heads() == ahead.get_heads()
        assert behind_text == ahead_text
        return dt, rounds, ph, round_lats

    # best-of-reps like every other config (a fresh replica per rep);
    # per-round latencies from EVERY rep feed the histogram (the spread
    # is the signal — best-of hides the tail)
    all_round_lats = []
    t_sync, rounds, phases, rl = sync_once()
    all_round_lats.extend(rl)
    for _ in range(reps - 1):
        dt, r, p, rl = sync_once()
        all_round_lats.extend(rl)
        if dt < t_sync:
            t_sync, rounds, phases = dt, r, p
    sync_rate = n_synced / t_sync
    results["sync"] = {
        "divergence_ops": n_synced,
        "rounds": rounds,
        "seconds": round(t_sync, 3),
        "phases_s": {k: round(v, 3) for k, v in phases.items()},
        **_latency_percentiles("bench.sync.round_latency", all_round_lats),
        "ops_per_sec": round(sync_rate, 1),
        "vs_baseline": round(sync_rate / RUST_PIN_APPLY, 4),
    }
    note(f"sync: {results['sync']}")
    wall_mark("sync")

    # ---- micro-bench guard: map put/save/load/apply + range iteration ------
    # (reference: rust/automerge/benches/map.rs:48-263, benches/range.rs —
    # the per-op paths the macro configs cannot isolate; regressions here
    # show up as per-op time even when the batched merge path is healthy)
    micro = {}
    micro_max = env_int("BENCH_MICRO_MAX", 10_000)
    for n_keys in (100, 1_000, 10_000):
        if n_keys > micro_max:
            continue
        t_put = t_save = t_load = t_apply = float("inf")
        for _ in range(max(reps, 1)):
            mdoc = AutoDoc(actor=ActorId(bytes([11]) * 16))
            t0 = time.perf_counter()
            for i in range(n_keys):
                mdoc.put("_root", f"k{i:06}", i)
            mdoc.commit()
            t_put = min(t_put, time.perf_counter() - t0)
            t0 = time.perf_counter()
            saved = mdoc.save()
            t_save = min(t_save, time.perf_counter() - t0)
            t0 = time.perf_counter()
            loaded = AutoDoc.load(saved)
            loaded.keys()  # materialization is lazy; end at readable
            t_load = min(t_load, time.perf_counter() - t0)
            changes = b"".join(
                a.stored.raw_bytes for a in mdoc.doc.history
            )
            rcv = AutoDoc(actor=ActorId(bytes([12]) * 16))
            t0 = time.perf_counter()
            rcv.load_incremental(changes)
            rcv.keys()
            t_apply = min(t_apply, time.perf_counter() - t0)
        micro[f"map_{n_keys}"] = {
            "put_ops_per_sec": round(n_keys / t_put, 1),
            "save_ms": round(t_save * 1000, 2),
            "load_ms": round(t_load * 1000, 2),
            "apply_ops_per_sec": round(n_keys / t_apply, 1),
        }
    # range iteration (benches/range.rs)
    n_range = min(10_000, micro_max)
    rdoc = AutoDoc(actor=ActorId(bytes([13]) * 16))
    lst = rdoc.put_object("_root", "l", ObjType.LIST)
    for i in range(n_range):
        rdoc.insert(lst, i, i)
    rdoc.commit()
    t_range = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        total = sum(1 for _ in rdoc.list_items(lst))
        t_range = min(t_range, time.perf_counter() - t0)
        assert total == n_range
    micro[f"range_{n_range}"] = {
        "iter_elems_per_sec": round(n_range / t_range, 1),
    }
    results["micro"] = micro
    note(f"micro: {micro}")
    wall_mark("micro")

    # ---- config: durable write path (journal + compaction + recovery) ------
    # N commits through a DurableDocument: journal append overhead per
    # commit, compaction count at the default thresholds, and — the
    # recovery-time headline — a reopen that replays snapshot + journal.
    # Counters/timings (journal.append/fsync, compact.*,
    # journal.replayed_records) surface in the JSON for observability.
    import shutil
    import tempfile

    dur = {}
    n_dur = env_int("BENCH_DURABLE_COMMITS", 2000)
    dur_fsync = env_flag("BENCH_DURABLE_FSYNC", "interval")
    tmpd = tempfile.mkdtemp(prefix="amtpu_bench_durable_")
    try:
        dd = AutoDoc.open(
            os.path.join(tmpd, "doc"), fsync=dur_fsync,
            actor=ActorId(bytes([14]) * 16),
        )
        commit_lats = []
        t0 = time.perf_counter()
        for i in range(n_dur):
            c0 = time.perf_counter()
            dd.put("_root", f"k{i % 512:04}", i)
            dd.commit()
            commit_lats.append(time.perf_counter() - c0)
        t_commits = time.perf_counter() - t0
        dd.close()
        compactions = T.counters.get("compact.runs", 0)
        tj = T.timing_summary()
        pre_replayed = T.counters.get("journal.replayed_records", 0)
        t0 = time.perf_counter()
        dd2 = AutoDoc.open(os.path.join(tmpd, "doc"))
        t_reopen = time.perf_counter() - t0
        replayed = T.counters.get("journal.replayed_records", 0) - pre_replayed
        n_history = len(dd2.doc.history)
        dd2.close()
        dur = {
            "commits": n_dur,
            "fsync": dur_fsync,
            "commits_per_sec": round(n_dur / t_commits, 1),
            **_latency_percentiles("bench.durable.commit_latency", commit_lats),
            "journal_append_s": tj.get("journal.append", {}).get("s", 0.0),
            "journal_fsync_s": tj.get("journal.fsync", {}).get("s", 0.0),
            "compactions": compactions,
            "reopen_s": round(t_reopen, 4),
            "replayed_records": replayed,
            "history_after_reopen": n_history,
        }
        assert replayed < n_dur or compactions == 0, dur  # replay is bounded
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        dur = {"durable_error": repr(e)[:500]}
        print(f"durable config failed:\n{tb}", file=sys.stderr, flush=True)
    finally:
        shutil.rmtree(tmpd, ignore_errors=True)
    results["durable"] = dur
    note(f"durable: {results['durable']}")
    wall_mark("durable")

    # ---- config: concurrent serving (socket transport + doc shards) --------
    # The serving-layer headline: N concurrent socket clients pipeline a
    # mixed ingestion workload (applyChanges blobs + put/commit + sync
    # rounds, durable docs, fsync=always) against `rpc --socket`, vs the
    # SAME per-client workload request/response through the serial stdio
    # frontend. Both servers are real subprocesses (their own GIL, as
    # deployed). The structural win: the stdio loop pays one fsync per
    # durable ack, the concurrent server drains each pipelined flight
    # into ONE group-commit fsync and runs distinct docs' fsyncs in
    # parallel. Serial and concurrent reps interleave in tight pairs and
    # the reported speedup is the best PAIRED ratio — on shared
    # infrastructure the fsync/CPU regime drifts minute to minute, and a
    # pair measured in the same window is the honest comparison.
    # Client-observed per-ack latencies feed an obs histogram so
    # p50/p95/p99 are log-bucket-derived like every other config.
    serve_cfg = {}
    try:
        if env_flag("BENCH_SERVE", "1") != "0":
            import base64
            import re
            import shutil
            import socket as socketmod
            import subprocess
            import tempfile
            import threading

            n_clients = env_int("BENCH_SERVE_CLIENTS", 4)
            n_sv_ops = env_int("BENCH_SERVE_OPS", 48)
            sv_flight = env_int("BENCH_SERVE_PIPELINE", 16)
            sv_reps = env_int("BENCH_SERVE_REPS", max(reps, 2))
            sub_env = dict(os.environ, JAX_PLATFORMS="cpu")

            def build_blobs(ci, tag):
                """Pre-encoded single-commit change chunks — the replica-
                push ingestion stream a sync server absorbs."""
                seed = (hash(tag) & 0x7F) | 1
                src = AutoDoc(actor=ActorId(
                    bytes([seed]) + bytes([101 + ci]) * 15))
                for i in range(n_sv_ops):
                    src.put("_root", f"c{ci}_{i:04}", i)
                    src.commit()
                return [
                    base64.b64encode(a.stored.raw_bytes).decode()
                    for a in src.doc.history
                ]

            def client_workload(pipeline, ci, blobs, lats=None):
                """One client's mixed flights; returns its request count.
                ``lats`` collects the send->ack latency of every response
                in the pipelined flights."""
                nreq = 0

                def c(reqs):
                    nonlocal nreq
                    nreq += len(reqs)
                    return pipeline(reqs, lats)

                dname = f"b{ci}_{abs(hash(blobs[0])) % 10**9}"
                d = c([("openDurable", {"name": dname})])[0]["doc"]
                p = c([("create", {})])[0]["doc"]
                s1 = c([("syncStateNew", {})])[0]["sync"]
                s2 = c([("syncStateNew", {})])[0]["sync"]
                for lo in range(0, n_sv_ops, sv_flight):
                    fl = [
                        ("applyChanges", {"doc": d, "data": blobs[i]})
                        for i in range(lo, min(lo + sv_flight, n_sv_ops))
                    ]
                    fl.append(("put", {"doc": d, "obj": "_root",
                                       "prop": f"p{lo}", "value": lo}))
                    fl.append(("commit", {"doc": d}))
                    c(fl)
                    m1 = c([("generateSyncMessage",
                             {"doc": d, "sync": s1})])[0]
                    if m1 is not None:
                        c([("receiveSyncMessage",
                            {"doc": p, "sync": s2, "data": m1})])
                    m2 = c([("generateSyncMessage",
                             {"doc": p, "sync": s2})])[0]
                    if m2 is not None:
                        c([("receiveSyncMessage",
                            {"doc": d, "sync": s1, "data": m2})])
                c([("free", {"doc": d})])
                return nreq

            def socket_pipeline(sock, f, rid):
                def pipeline(reqs, lats=None):
                    first = rid[0] + 1
                    lines = []
                    for m, p in reqs:
                        rid[0] += 1
                        lines.append(json.dumps(
                            {"id": rid[0], "method": m, "params": p}))
                    t0 = time.perf_counter()
                    sock.sendall(("\n".join(lines) + "\n").encode())
                    by = {}
                    while len(by) < len(reqs):
                        resp = json.loads(f.readline())
                        if lats is not None:
                            by_now = time.perf_counter()
                            lats.append(by_now - t0)
                        assert "error" not in resp, resp
                        by[resp["id"]] = resp.get("result")
                    return [by[first + i] for i in range(len(reqs))]
                return pipeline

            # -- the two server subprocesses, started and warmed once ----
            tmp_ser = tempfile.mkdtemp(prefix="amtpu_bench_serve_ser_")
            tmp_conc = tempfile.mkdtemp(prefix="amtpu_bench_serve_conc_")
            ser_proc = conc_proc = None

            srid = [0]

            def serial_request(method, params):
                srid[0] += 1
                ser_proc.stdin.write(json.dumps(
                    {"id": srid[0], "method": method, "params": params}
                ) + "\n")
                ser_proc.stdin.flush()
                resp = json.loads(ser_proc.stdout.readline())
                assert "error" not in resp, resp
                return resp.get("result")

            def serial_sync_pipeline(reqs, lats=None):
                # the stdio embedder protocol: one request, one response
                return [serial_request(m, p) for m, p in reqs]

            def conc_client(ci, blobs, counts, lat_sink, barrier):
                sock = socketmod.create_connection(("127.0.0.1", conc_port))
                sock.setsockopt(socketmod.IPPROTO_TCP,
                                socketmod.TCP_NODELAY, 1)
                f = sock.makefile("r")
                barrier.wait()
                counts[ci] = client_workload(
                    socket_pipeline(sock, f, [0]), ci, blobs, lat_sink)
                sock.close()

            def conc_rep(tag):
                all_blobs = [build_blobs(ci, tag) for ci in range(n_clients)]
                counts = [0] * n_clients
                lat_sinks = [[] for _ in range(n_clients)]
                barrier = threading.Barrier(n_clients + 1)
                ts = [
                    threading.Thread(target=conc_client, args=(
                        ci, all_blobs[ci], counts, lat_sinks[ci], barrier))
                    for ci in range(n_clients)
                ]
                for t in ts:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in ts:
                    t.join()
                dt = time.perf_counter() - t0
                return sum(counts), dt, [x for ls in lat_sinks for x in ls]

            def serial_rep(tag):
                all_blobs = [build_blobs(ci, tag) for ci in range(n_clients)]
                t0 = time.perf_counter()
                n_req = sum(
                    client_workload(serial_sync_pipeline, ci, all_blobs[ci])
                    for ci in range(n_clients)
                )
                return n_req, time.perf_counter() - t0

            try:
                ser_proc = subprocess.Popen(
                    [sys.executable, "-m", "automerge_tpu.rpc",
                     "--durable", tmp_ser],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, env=sub_env,
                )
                conc_proc = subprocess.Popen(
                    [sys.executable, "-m", "automerge_tpu.rpc",
                     "--socket", "127.0.0.1:0", "--durable", tmp_conc],
                    stderr=subprocess.PIPE, text=True, env=sub_env,
                )
                conc_port = int(re.search(
                    r"(\d+)\)", conc_proc.stderr.readline()).group(1))
                # keep draining stderr: a chatty server must not block on
                # a full pipe mid-measurement
                threading.Thread(
                    target=lambda: [None for _ in conc_proc.stderr],
                    daemon=True,
                ).start()

                # warmup both paths (jit/codecs/page-in), untimed
                serial_rep("warm_s")
                conc_rep("warm_c")

                pairs = []
                all_lats = []
                total_req = None
                for rep in range(sv_reps):
                    sn, st = serial_rep(f"s{rep}")
                    cn, ct, lats = conc_rep(f"c{rep}")
                    assert sn == cn, (sn, cn)
                    total_req = cn
                    all_lats.extend(lats)
                    pairs.append((round(sn / st, 1), round(cn / ct, 1)))
                serial_request("shutdown", {})
                ser_proc.stdin.close()
                ser_proc.wait(timeout=60)
                sock = socketmod.create_connection(
                    ("127.0.0.1", conc_port))
                sock.sendall(b'{"id":1,"method":"shutdown"}\n')
                sock.makefile("r").readline()
                sock.close()
                conc_proc.wait(timeout=60)
            finally:
                # a failure mid-config must not leak server processes
                # (their journal flocks) or the temp state directories
                for p_ in (ser_proc, conc_proc):
                    if p_ is not None and p_.poll() is None:
                        p_.kill()
                        p_.wait(timeout=10)
                shutil.rmtree(tmp_ser, ignore_errors=True)
                shutil.rmtree(tmp_conc, ignore_errors=True)

            best_pair = max(pairs, key=lambda p: p[1] / p[0])
            serve_cfg = {
                "clients": n_clients,
                "ops_per_client": n_sv_ops,
                "pipeline_depth": sv_flight,
                "requests": total_req,
                "rep_pairs_rps": [
                    {"serial_stdio": s, "concurrent": c} for s, c in pairs
                ],
                "serial_stdio_requests_per_sec": best_pair[0],
                "requests_per_sec": best_pair[1],
                "speedup_vs_serial": round(best_pair[1] / best_pair[0], 2),
                **_latency_percentiles("bench.serve.request_latency",
                                       all_lats),
            }
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        serve_cfg = {"serve_error": repr(e)[:500]}
        print(f"serve config failed:\n{tb}", file=sys.stderr, flush=True)
    results["serve"] = serve_cfg
    note(f"serve: {results['serve']}")
    wall_mark("serve")

    # ---- config: serve scrub A/B (integrity scrub overhead) ----------------
    # The SAME concurrent socket workload against two fresh servers in
    # tight interleaved pairs: integrity scrub ON at an aggressive
    # cadence (a round every 0.1s, ~150x hotter than the production
    # default) vs AUTOMERGE_TPU_SCRUB=0. The exported goodput_ratio
    # (best paired on/off rps) is the scrub's measured tax on serving
    # goodput; the acceptance floor (>= 0.95 in run_bench_smoke, and a
    # tracked perf_gate metric) enforces the "off the ack path" design —
    # a scrub that grabs doc locks greedily or verifies synchronously
    # lands well under it.
    try:
        if (env_flag("BENCH_SERVE", "1") != "0"
                and env_flag("BENCH_SERVE_SCRUB", "1") != "0"
                and "requests_per_sec" in serve_cfg):
            scrub_reps = env_int("BENCH_SERVE_SCRUB_REPS", sv_reps)
            tmp_on = tempfile.mkdtemp(prefix="amtpu_bench_scrub_on_")
            tmp_off = tempfile.mkdtemp(prefix="amtpu_bench_scrub_off_")
            on_proc = off_proc = None

            def spawn_scrub(tmp, scrub_env):
                proc = subprocess.Popen(
                    [sys.executable, "-m", "automerge_tpu.rpc",
                     "--socket", "127.0.0.1:0", "--durable", tmp],
                    stderr=subprocess.PIPE, text=True,
                    env=dict(sub_env, **scrub_env))
                port = int(re.search(r"(\d+)\)",
                                     proc.stderr.readline()).group(1))
                threading.Thread(target=lambda: [None for _ in proc.stderr],
                                 daemon=True).start()
                return proc, port

            def scrub_rep(port, tag):
                all_blobs = [build_blobs(ci, tag) for ci in range(n_clients)]
                counts = [0] * n_clients
                barrier = threading.Barrier(n_clients + 1)

                def go(ci):
                    sock = socketmod.create_connection(("127.0.0.1", port))
                    sock.setsockopt(socketmod.IPPROTO_TCP,
                                    socketmod.TCP_NODELAY, 1)
                    f = sock.makefile("r")
                    barrier.wait()
                    counts[ci] = client_workload(
                        socket_pipeline(sock, f, [0]), ci, all_blobs[ci])
                    sock.close()

                ts = [threading.Thread(target=go, args=(ci,))
                      for ci in range(n_clients)]
                for t in ts:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in ts:
                    t.join()
                return sum(counts), time.perf_counter() - t0

            try:
                on_proc, on_port = spawn_scrub(tmp_on, {
                    "AUTOMERGE_TPU_SCRUB": "1",
                    "AUTOMERGE_TPU_SCRUB_INTERVAL": "0.1",
                    "AUTOMERGE_TPU_SCRUB_SAMPLE": "64",
                })
                off_proc, off_port = spawn_scrub(
                    tmp_off, {"AUTOMERGE_TPU_SCRUB": "0"})
                scrub_rep(on_port, "warm_on")
                scrub_rep(off_port, "warm_off")
                ratios = []
                for rep in range(scrub_reps):
                    on_n, on_t = scrub_rep(on_port, f"on{rep}")
                    off_n, off_t = scrub_rep(off_port, f"off{rep}")
                    assert on_n == off_n, (on_n, off_n)
                    ratios.append((on_n / on_t) / (off_n / off_t))
                for port in (on_port, off_port):
                    sock = socketmod.create_connection(("127.0.0.1", port))
                    sock.sendall(b'{"id":1,"method":"shutdown"}\n')
                    sock.makefile("r").readline()
                    sock.close()
                on_proc.wait(timeout=60)
                off_proc.wait(timeout=60)
            finally:
                for p_ in (on_proc, off_proc):
                    if p_ is not None and p_.poll() is None:
                        p_.kill()
                        p_.wait(timeout=10)
                shutil.rmtree(tmp_on, ignore_errors=True)
                shutil.rmtree(tmp_off, ignore_errors=True)
            serve_cfg["scrub"] = {
                "reps": scrub_reps,
                "scrub_interval_s": 0.1,
                "rep_goodput_ratios": [round(r, 3) for r in ratios],
                "goodput_ratio": round(max(ratios), 3),
            }
            note(f"serve scrub A/B: {serve_cfg['scrub']}")
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        print(f"serve scrub config failed:\n{traceback.format_exc()}",
              file=sys.stderr, flush=True)
        serve_cfg["scrub_error"] = repr(e)[:500]

    # ---- config: serve_batched (cross-document batched device merge) -------
    # N resident documents drain one coalesced delta each per cycle — the
    # multi-document work a ShardPool drain hands the device layer. Two
    # modes through the SAME stage/pack/launch machinery (ops/batched.py):
    # per_doc = one packed launch per document (max_docs_per_launch=1, the
    # old dispatch discipline), batched = every document in ONE launch per
    # drain cycle. Kernel launches are counted via the
    # device.kernel_launches{path=batched} counter and asserted to drop
    # from O(docs) to O(1) per cycle; both modes' final documents are
    # checked identical. Each document carries an untouched "archive"
    # ballast object so the drained deltas stay on the dirty-subset path
    # (the serve-shaped workload: big resident history, edits concentrated
    # in the live object).
    sb_cfg = {}
    try:
        if env_flag("BENCH_SERVE_BATCHED", "1") != "0":
            from automerge_tpu.obs import prof
            from automerge_tpu.ops.batched import apply_cross_doc

            sb_docs = env_int("BENCH_SB_DOCS", 32)
            sb_cycles = env_int("BENCH_SB_CYCLES", 8)
            sb_ops = env_int("BENCH_SB_OPS", 40)
            sb_ballast = env_int("BENCH_SB_BALLAST", 4000)

            def sb_launches():
                return obs.counter_values("device.kernel_launches", "path")

            def sb_input_bytes():
                """(kernel input bytes, dense-equivalent bytes) — the
                run-native staging's counters; input = what device_put
                actually moved and the expand+resolve jit consumed."""
                return (
                    obs.counter_values(
                        "device.kernel_input_bytes", "").get("", 0),
                    obs.counter_values(
                        "device.kernel_input_dense_bytes", "").get("", 0),
                )

            def sb_workload(tag):
                """Per doc: (base changes, [delta per cycle]) — one
                editing replica typing into the live object each cycle."""
                wl = []
                for i in range(sb_docs):
                    base = AutoDoc(actor=ActorId(bytes([21]) * 16))
                    live = base.put_object("_root", "live", ObjType.TEXT)
                    base.splice_text(live, 0, 0, "live seed text ")
                    arch = base.put_object("_root", "archive", ObjType.TEXT)
                    base.splice_text(arch, 0, 0, "x" * sb_ballast)
                    base.commit()
                    chs = [a.stored for a in base.doc.history]
                    ed = base.fork(actor=ActorId(
                        bytes([31 + (tag & 1)]) + bytes([i % 250]) + bytes(14)))
                    seen = {c.hash for c in chs}
                    cycles = []
                    for c in range(sb_cycles):
                        ln = ed.length(live)
                        for j in range(sb_ops):
                            ed.splice_text(
                                live, (i + c * sb_ops + j) % max(ln + j, 1),
                                0, "ab"[j % 2],
                            )
                        ed.commit()
                        delta = [
                            a.stored for a in ed.doc.history
                            if a.stored.hash not in seen
                        ]
                        seen.update(ch.hash for ch in delta)
                        cycles.append(delta)
                    wl.append((chs, cycles))
                return wl

            def sb_run(wl, max_per_launch, reports=None, pipeline=None):
                """``reports`` (a list, if given) collects one profiler
                cycle report per drain cycle — the observatory's
                attribution for exactly these drains. ``pipeline``
                forces the drain pipeline on/off (None = env default);
                the per-doc baseline runs with it off so its timing
                keeps the serial per-doc-launch semantics."""
                devs = [
                    DeviceDoc.resolve(OpLog.from_changes(chs))
                    for chs, _ in wl
                ]
                l0 = sb_launches()
                b0 = sb_input_bytes()
                t0 = time.perf_counter()
                for c in range(sb_cycles):
                    with prof.cycle(kind="bench_drain") as cyc:
                        apply_cross_doc(
                            [(devs[i], [wl[i][1][c]])
                             for i in range(sb_docs)],
                            max_docs_per_launch=max_per_launch,
                            pipeline=pipeline,
                        )
                    if reports is not None and cyc.report is not None:
                        reports.append(cyc.report)
                dt = time.perf_counter() - t0
                l1 = sb_launches()
                b1 = sb_input_bytes()
                dl = {
                    k: l1.get(k, 0) - l0.get(k, 0)
                    for k in set(l0) | set(l1)
                    if l1.get(k, 0) != l0.get(k, 0)
                }
                bts = (b1[0] - b0[0], b1[1] - b0[1])
                return devs, dt, dl, bts

            wl = sb_workload(0)
            delta_ops = sum(
                len(c.ops) for _, cycles in wl for b in cycles for c in b
            )
            sb_half = max(sb_docs // 2, 1)
            # warm all three mode shapes (jit compile per capacity bucket)
            sb_run(sb_workload(1), 1, pipeline=False)
            sb_run(sb_workload(1), None)
            sb_run(sb_workload(1), sb_half, pipeline=True)
            t_per = t_bat = t_pipe = float("inf")
            cycle_reports = []
            pipe_reports = []
            rn_bytes = (0, 0)
            for _ in range(max(reps, 1)):
                devs_p, dt_p, l_per, _ = sb_run(wl, 1, pipeline=False)
                devs_b, dt_b, l_bat, bts = sb_run(
                    wl, None, reports=cycle_reports
                )
                # pipelined mode: two half-drain launches per cycle so
                # chunk 2's host staging runs under chunk 1's kernel
                devs_pl, dt_pl, l_pipe, _ = sb_run(
                    wl, sb_half, reports=pipe_reports, pipeline=True
                )
                t_per = min(t_per, dt_p)
                t_bat = min(t_bat, dt_b)
                t_pipe = min(t_pipe, dt_pl)
                rn_bytes = (rn_bytes[0] + bts[0], rn_bytes[1] + bts[1])
            # the observatory's view of the batched drains: >=90% of the
            # measured drain wall clock attributed to named stages, with
            # the host/device split and the pack-site occupancy figure
            cycle_report = prof.summarize_reports(cycle_reports)
            pipe_report = prof.summarize_reports(pipe_reports)
            # all modes must materialize identical documents
            for i in (0, sb_docs // 2, sb_docs - 1):
                assert devs_p[i].hydrate() == devs_b[i].hydrate(), i
                assert devs_pl[i].hydrate() == devs_b[i].hydrate(), i
            sb_cfg = {
                "docs": sb_docs,
                "cycles": sb_cycles,
                "ops_per_delta": sb_ops,
                "delta_ops_total": delta_ops,
                "resident_ops": int(devs_b[0].log.n),
                "per_doc_seconds": round(t_per, 4),
                "per_doc_ops_per_sec": round(delta_ops / t_per, 1),
                "per_doc_launches": l_per,
                "batched_seconds": round(t_bat, 4),
                "batched_ops_per_sec": round(delta_ops / t_bat, 1),
                "batched_launches": l_bat,
                "launches_per_drain_per_doc": round(
                    l_per.get("batched", 0) / sb_cycles, 2
                ),
                "launches_per_drain_batched": round(
                    l_bat.get("batched", 0) / sb_cycles, 2
                ),
                "uplift_vs_per_doc": round(t_per / t_bat, 2),
                "occupancy": cycle_report["occupancy"],
                "cycle_report": cycle_report,
                # run-native staging: what the batched drains actually
                # shipped to (and computed on) the device vs the dense
                # image those rows would have been
                "run_native": {
                    "kernel_input_bytes": int(rn_bytes[0]),
                    "kernel_input_dense_bytes": int(rn_bytes[1]),
                    "input_compress_ratio": round(
                        rn_bytes[1] / rn_bytes[0], 2
                    ) if rn_bytes[0] else 0.0,
                },
                # the double-buffered drain: two half-launches per
                # cycle, second half's host staging under the first
                # half's in-flight kernel
                "pipeline": {
                    "seconds": round(t_pipe, 4),
                    "ops_per_sec": round(delta_ops / t_pipe, 1),
                    "launches_per_drain": round(
                        l_pipe.get("batched", 0) / sb_cycles, 2
                    ),
                    "overlap_s": pipe_report.get("overlap_s", 0.0),
                    "overlap_fraction": pipe_report.get(
                        "overlap_fraction", 0.0
                    ),
                    "uplift_vs_per_doc": round(t_per / t_pipe, 2),
                    "vs_single_launch": round(t_bat / t_pipe, 2),
                },
            }
            del devs_p, devs_b, devs_pl, wl
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        sb_cfg = {"serve_batched_error": repr(e)[:500]}
        print(f"serve_batched config failed:\n{tb}", file=sys.stderr,
              flush=True)
    results["serve_batched"] = sb_cfg
    note(f"serve_batched: {results['serve_batched']}")
    wall_mark("serve_batched")

    # ---- config: cluster (replicated serving + leader failover) ------------
    # Three node subprocesses (leader + 2 followers, quorum acks) behind
    # an in-process router. The workload commits through the router while
    # the leader is kill -9'd BENCH_CLUSTER_FAILOVERS times; each cycle
    # measures the client-observed failover latency (first failed ack ->
    # first successful ack on the promoted leader) and the killed node
    # rejoins as a follower before the next cycle. Reported: replicated
    # commit throughput under quorum acks plus failover-latency
    # p50/p95/p99 from the same log-bucketed histograms as every other
    # config.
    cluster_cfg = {}
    try:
        if env_flag("BENCH_CLUSTER", "1") != "0":
            import re
            import shutil
            import socket as socketmod
            import subprocess
            import tempfile
            import threading

            from automerge_tpu.cluster import ClusterRouter

            n_failovers = env_int("BENCH_CLUSTER_FAILOVERS", 3)
            n_warm = env_int("BENCH_CLUSTER_OPS", 30)
            hb = float(env_flag("BENCH_CLUSTER_HEARTBEAT", "0.25"))
            tmp_cluster = tempfile.mkdtemp(prefix="amtpu_bench_cluster_")
            sub_env = dict(
                os.environ, JAX_PLATFORMS="cpu",
                AUTOMERGE_TPU_CLUSTER_HEARTBEAT=str(hb),
            )
            procs = {}

            def spawn_node(i, extra):
                d = os.path.join(tmp_cluster, f"n{i}")
                os.makedirs(d, exist_ok=True)
                p = subprocess.Popen(
                    [sys.executable, "-m", "automerge_tpu.rpc",
                     "--socket", "127.0.0.1:0", "--durable", d,
                     "--node-id", f"n{i}"] + extra,
                    stderr=subprocess.PIPE, text=True, env=sub_env,
                )
                addr = "127.0.0.1:" + re.search(
                    r"(\d+)\)", p.stderr.readline()).group(1)
                threading.Thread(
                    target=lambda: [None for _ in p.stderr],
                    daemon=True).start()
                procs[addr] = p
                return addr

            a1 = spawn_node(1, ["--follow", "pending", "--ack-replicas", "1"])
            a2 = spawn_node(2, ["--follow", "pending", "--ack-replicas", "1"])
            a0 = spawn_node(0, ["--replicate-to", a1, "--replicate-to", a2,
                                "--ack-replicas", "1"])
            router = ClusterRouter([[a0, a1, a2]], heartbeat=hb,
                                   miss_limit=2)
            router.start()

            # the reference retry client (clients/python): capped-backoff
            # retry on retriable errors with a per-call deadline budget —
            # its blocked-seconds accounting IS the client-observed
            # failover latency, so the bench stops hand-rolling the loop
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "clients", "python"))
            from amtpu_client import RetryingClient

            try:
                c = RetryingClient(router.address, deadline_s=60,
                                   backoff_s=0.02, max_backoff_s=0.2)
                d = c.call("openDurable", name="bench")["doc"]
                # throughput under quorum acks, failure-free
                t0 = time.perf_counter()
                for i in range(n_warm):
                    c.call("put", doc=d, obj="_root", prop=f"w{i}", value=i)
                    c.call("commit", doc=d)
                t_quorum = time.perf_counter() - t0

                fo_lats = []
                k = 0
                for cycle in range(n_failovers):
                    leader = next(
                        g["leader"] for g in c.call("clusterInfo")["groups"])
                    procs[leader].kill()  # SIGKILL: the real thing
                    procs[leader].wait()
                    # first acked write after the kill IS the
                    # client-observed failover latency: wall time covers
                    # both failure modes — requests frozen inside the
                    # router while it promotes, and retriable errors the
                    # retry loop rides out (c.last.blocked_s)
                    t_fail = time.perf_counter()
                    c.call("put", doc=d, obj="_root", prop=f"f{k}", value=k)
                    c.call("commit", doc=d)
                    fo_lats.append(time.perf_counter() - t_fail)
                    k += 1
                    # a fresh node rejoins the group as a follower so
                    # every cycle keeps a full quorum pool
                    new_leader = next(
                        g["leader"] for g in c.call("clusterInfo")["groups"])
                    rejoin = spawn_node(
                        10 + cycle, ["--follow", new_leader,
                                     "--ack-replicas", "1"])
                    c.call("clusterJoin", group=0, addr=rejoin)
                # every acked key must be readable (zero acked-write loss)
                for i in range(n_warm):
                    got = c.call("get", doc=d, obj="_root", prop=f"w{i}")
                    assert got == i, (i, got)
                for i in range(k):
                    got = c.call("get", doc=d, obj="_root", prop=f"f{i}")
                    assert got == i, (i, got)
                c.close()
            finally:
                router.stop()
                for p_ in procs.values():
                    if p_.poll() is None:
                        p_.kill()
                        p_.wait(timeout=10)
                shutil.rmtree(tmp_cluster, ignore_errors=True)

            cluster_cfg = {
                "nodes": 3,
                "ack_replicas": 1,
                "failovers": n_failovers,
                "quorum_commits_per_sec": round(n_warm / t_quorum, 1),
                "failover_latencies_s": [round(x, 3) for x in fo_lats],
                **{
                    k.replace("latency", "failover_latency"): v
                    for k, v in _latency_percentiles(
                        "bench.cluster.failover_latency", fo_lats
                    ).items()
                },
            }
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        cluster_cfg = {"cluster_error": repr(e)[:500]}
        print(f"cluster config failed:\n{tb}", file=sys.stderr, flush=True)
    results["cluster"] = cluster_cfg
    note(f"cluster: {results['cluster']}")
    wall_mark("cluster")

    # ---- config: tiered (bounded-memory residency at many-doc scale) -------
    # N durable documents created and Zipfian-accessed through the REAL
    # socket serve path against two servers: one with the tiered store's
    # budgets configured (bounded residency: idle docs demote warm ->
    # cold, cold docs hydrate on access), one with the store unbounded
    # (the old behavior: every doc ever opened stays fully materialized,
    # run at a reduced doc count because every live journal holds an fd).
    # Asserted here: the store server's RSS stays under the configured
    # watermark while serving every doc, demotions/hydrations actually
    # fired, and a demote -> hydrate round trip returns byte-identical
    # contents. Reported: RSS vs the unbounded server's linear
    # projection, cold-open (hydration) latency percentiles from the
    # server's own store.hydrate histogram, and access throughput.
    tiered_cfg = {}
    try:
        if env_flag("BENCH_TIERED", "1") != "0":
            import re
            import resource
            import shutil
            import socket as socketmod
            import subprocess
            import tempfile
            import threading

            td_docs = env_int("BENCH_TD_DOCS", 100_000)
            td_accesses = env_int("BENCH_TD_ACCESSES",
                                  min(td_docs, 20_000))
            td_flight = env_int("BENCH_TD_PIPELINE", 64)
            td_headroom = env_int("BENCH_TD_RSS_HEADROOM", 256 << 20)

            # the unbounded baseline holds one journal fd per live doc:
            # cap it under the fd limit (raised as far as allowed), then
            # project linearly to td_docs
            soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
            try:
                resource.setrlimit(
                    resource.RLIMIT_NOFILE,
                    (min(hard, 1 << 16) if hard > 0 else 1 << 16, hard))
                soft = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
            except (ValueError, OSError):
                pass
            td_base_docs = env_int(
                "BENCH_TD_BASELINE_DOCS",
                max(64, min(td_docs, 2000, soft - 128)))

            def proc_rss(pid):
                with open(f"/proc/{pid}/statm") as f:
                    return int(f.read().split()[1]) * os.sysconf(
                        "SC_PAGE_SIZE")

            def spawn(tag, extra_env):
                tmp = tempfile.mkdtemp(prefix=f"amtpu_bench_td_{tag}_")
                p = subprocess.Popen(
                    [sys.executable, "-m", "automerge_tpu.rpc",
                     "--socket", "127.0.0.1:0", "--durable", tmp],
                    stderr=subprocess.PIPE, text=True,
                    env=dict(os.environ, JAX_PLATFORMS="cpu", **extra_env),
                )
                port = int(re.search(
                    r"(\d+)\)", p.stderr.readline()).group(1))
                threading.Thread(
                    target=lambda: [None for _ in p.stderr],
                    daemon=True).start()
                sock = socketmod.create_connection(("127.0.0.1", port))
                sock.setsockopt(socketmod.IPPROTO_TCP,
                                socketmod.TCP_NODELAY, 1)
                return p, tmp, sock, sock.makefile("r")

            def flights(sock, f, reqs, lats=None):
                """Pipelined request flights; returns results by order."""
                out_ = []
                for lo in range(0, len(reqs), td_flight):
                    chunk = reqs[lo:lo + td_flight]
                    lines = [
                        json.dumps({"id": lo + i, "method": m, "params": pp})
                        for i, (m, pp) in enumerate(chunk)
                    ]
                    t0 = time.perf_counter()
                    sock.sendall(("\n".join(lines) + "\n").encode())
                    by = {}
                    while len(by) < len(chunk):
                        resp = json.loads(f.readline())
                        err = resp.get("error")
                        if err is not None:
                            if err.get("retriable"):
                                # backpressure/hydration contention: the
                                # client owns the retry
                                m, pp = chunk[resp["id"] - lo]
                                time.sleep(0.01)
                                sock.sendall((json.dumps(
                                    {"id": resp["id"], "method": m,
                                     "params": pp}) + "\n").encode())
                                continue
                            raise AssertionError(resp)
                        if lats is not None:
                            lats.append(time.perf_counter() - t0)
                        by[resp["id"]] = resp.get("result")
                    out_.extend(by[lo + i] for i in range(len(chunk)))
                return out_

            # residency, not durability, is under test: fsync="never"
            # keeps the populate phase from being an fsync benchmark
            # (demote/hydrate correctness is unaffected — the journal
            # bytes are written either way)
            td_fsync = env_flag("BENCH_TD_FSYNC", "never")

            def populate(sock, f, n, tag):
                handles = []
                step = max(1, td_flight // 4)
                for lo in range(0, n, step):
                    batch = range(lo, min(lo + step, n))
                    hs = [
                        r["doc"] for r in flights(sock, f, [
                            ("openDurable",
                             {"name": f"t{i:06}", "fsync": td_fsync})
                            for i in batch
                        ])
                    ]
                    handles.extend(hs)
                    reqs = []
                    for i, h in zip(batch, hs):
                        reqs.append(("put", {"doc": h, "obj": "_root",
                                             "prop": "v", "value": i}))
                        reqs.append(("commit", {"doc": h}))
                    flights(sock, f, reqs)
                return handles

            store_env = {
                "AUTOMERGE_TPU_STORE_WARM_BYTES": str(
                    env_int("BENCH_TD_WARM_BYTES", 4 << 20)),
                "AUTOMERGE_TPU_STORE_EVICT_INTERVAL": "0.2",
                "AUTOMERGE_TPU_STORE_MIN_IDLE": "0.05",
            }
            sp = st = ss = sf = None
            up = ut = us = uf = None
            try:
                sp, st, ss, sf = spawn("store", store_env)
                up, ut, us, uf = spawn("unbounded", {})
                rss_store_0 = proc_rss(sp.pid)
                rss_unb_0 = proc_rss(up.pid)
                rss_budget = rss_store_0 + td_headroom
                # tell the store its hard watermark (config accepts env
                # only at construction, so restart-free: the warm-bytes
                # budget is the active bound; the watermark is asserted
                # on the measured outcome below)

                t0 = time.perf_counter()
                store_handles = populate(ss, sf, td_docs, "s")
                t_pop = time.perf_counter() - t0
                populate(us, uf, td_base_docs, "u")

                rss_store_1 = proc_rss(sp.pid)
                rss_unb_1 = proc_rss(up.pid)
                per_doc = (rss_unb_1 - rss_unb_0) / max(1, td_base_docs)
                rss_linear = rss_unb_0 + per_doc * td_docs

                # Zipfian access phase against the store server
                rng = np.random.default_rng(7)
                draws = rng.zipf(1.3, size=4 * td_accesses)
                draws = draws[draws <= td_docs][:td_accesses]
                while len(draws) < td_accesses:
                    extra = rng.zipf(1.3, size=td_accesses)
                    draws = np.concatenate(
                        [draws, extra[extra <= td_docs]])[:td_accesses]
                lats = []
                t0 = time.perf_counter()
                reqs = [
                    ("get", {"doc": store_handles[int(r) - 1],
                             "obj": "_root", "prop": "v"})
                    for r in draws
                ]
                vals = flights(ss, sf, reqs, lats)
                t_access = time.perf_counter() - t0
                for r, v in zip(draws, vals):
                    assert v == int(r) - 1, (int(r) - 1, v)
                rss_store_2 = proc_rss(sp.pid)

                # demote -> hydrate round trip must be byte-identical
                probe = store_handles[0]
                save_a = flights(ss, sf, [("save", {"doc": probe})])[0]
                flights(ss, sf, [("storeDemote", {"name": "t000000"})])
                save_b = flights(ss, sf, [("save", {"doc": probe})])[0]
                roundtrip_ok = save_a == save_b

                # the server's own accounting: tiers, demotions, hydrate
                # latency histogram
                snap = flights(ss, sf, [("metrics", {"format": "json"})])[0]
                entries = snap["metrics"]
                demotions = sum(
                    e["value"] for e in entries
                    if e["name"] == "store.demotions"
                    and e["type"] == "counter"
                )
                hyd = [
                    e for e in entries
                    if e["name"] == "store.hydrate"
                    and e["type"] == "histogram"
                ]
                hydrations = sum(e["count"] for e in hyd)
                tiers = {
                    e["labels"]["tier"]: e["value"]
                    for e in entries
                    if e["name"] == "store.tier" and e["type"] == "gauge"
                }

                rss_peak = max(rss_store_1, rss_store_2)
                assert rss_peak <= rss_budget, (
                    f"store RSS {rss_peak} exceeded budget {rss_budget}")
                # > 1: at least one POLICY demotion beyond the explicit
                # round-trip storeDemote below — a run where the budget
                # never bites is vacuous
                assert demotions > 1, "no policy demotions fired"
                assert hydrations > 0, "no cold opens fired (vacuous run)"
                assert roundtrip_ok, "demote->hydrate changed the bytes"

                for sock_, f_ in ((ss, sf), (us, uf)):
                    flights(sock_, f_, [("shutdown", {})])
                sp.wait(timeout=60)
                up.wait(timeout=60)
            finally:
                for p_ in (sp, up):
                    if p_ is not None and p_.poll() is None:
                        p_.kill()
                        p_.wait(timeout=10)
                for d_ in (st, ut):
                    if d_ is not None:
                        shutil.rmtree(d_, ignore_errors=True)

            tiered_cfg = {
                "docs": td_docs,
                "accesses": td_accesses,
                "baseline_docs": td_base_docs,
                "populate_seconds": round(t_pop, 3),
                "populate_docs_per_sec": round(td_docs / t_pop, 1),
                "access_seconds": round(t_access, 3),
                "accesses_per_sec": round(td_accesses / t_access, 1),
                "rss_budget_bytes": rss_budget,
                "rss_store_bytes": rss_peak,
                "rss_under_budget": True,
                "rss_unbounded_baseline_bytes": rss_unb_1,
                "rss_linear_projection_bytes": int(rss_linear),
                "bytes_per_resident_doc": int(per_doc),
                "tiers": tiers,
                "demotions": int(demotions),
                "hydrations": int(hydrations),
                "roundtrip_identical": roundtrip_ok,
                **{
                    k.replace("latency", "cold_open_latency"): round(v, 6)
                    for k, v in (
                        ("latency_p50_s", hyd[0]["p50"] if hyd else 0.0),
                        ("latency_p95_s", hyd[0]["p95"] if hyd else 0.0),
                        ("latency_p99_s", hyd[0]["p99"] if hyd else 0.0),
                    )
                },
                **_latency_percentiles("bench.tiered.access_latency", lats),
            }
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        tiered_cfg = {"tiered_error": repr(e)[:500]}
        print(f"tiered config failed:\n{tb}", file=sys.stderr, flush=True)
    results["tiered"] = tiered_cfg
    note(f"tiered: {results['tiered']}")
    wall_mark("tiered")

    # ---- config: compressed (compute-on-compressed resident columns) -------
    # The same synthetic text+counter workload drained through the
    # cross-doc batched path TWICE in one process: compressed residency
    # (AUTOMERGE_TPU_COMPRESSED=1, the default) vs dense (=0, the
    # fallback/oracle mode). Asserted inside the config: bit-identical
    # materialized documents and op columns across modes. Reported: true
    # resident column bytes per doc and h2d bytes per drain under each
    # mode (the device.h2d_bytes counter the staging sites feed), their
    # ratios, and resident-docs-per-GiB — the "5-10x more resident docs
    # per chip" claim as a measured number.
    comp_cfg = {}
    try:
        if env_flag("BENCH_COMPRESSED", "1") != "0":
            from automerge_tpu.ops.batched import apply_cross_doc
            from automerge_tpu.types import ObjType as _OT
            from automerge_tpu.types import ScalarValue

            cp_docs = env_int("BENCH_CP_DOCS", 8)
            cp_cycles = env_int("BENCH_CP_CYCLES", 6)
            cp_ops = env_int("BENCH_CP_OPS", 40)

            wl = []
            for i in range(cp_docs):
                cbase = AutoDoc(actor=ActorId(bytes([41]) * 16))
                live = cbase.put_object("_root", "live", _OT.TEXT)
                cbase.splice_text(live, 0, 0, f"seed text for doc {i} ")
                cbase.put("_root", "ctr", ScalarValue("counter", 0))
                cbase.commit()
                chs = [a.stored for a in cbase.doc.history]
                ed = cbase.fork(actor=ActorId(
                    bytes([51]) + bytes([i % 250]) + bytes(14)))
                seen = {c.hash for c in chs}
                cyc = []
                for c in range(cp_cycles):
                    ln = ed.length(live)
                    for j in range(cp_ops):
                        ed.splice_text(
                            live, (i + c * cp_ops + j) % max(ln + j, 1),
                            0, "ab"[j % 2],
                        )
                    ed.increment("_root", "ctr", 1)
                    ed.commit()
                    delta = [
                        a.stored for a in ed.doc.history
                        if a.stored.hash not in seen
                    ]
                    seen.update(ch.hash for ch in delta)
                    cyc.append(delta)
                wl.append((chs, cyc))

            def cp_run(mode, work):
                prev = os.environ.get("AUTOMERGE_TPU_COMPRESSED")
                os.environ["AUTOMERGE_TPU_COMPRESSED"] = mode
                try:
                    devs = [
                        DeviceDoc.resolve(OpLog.from_changes(chs))
                        for chs, _ in work
                    ]
                    h0 = obs.counter_values(
                        "device.h2d_bytes", "").get("", 0)
                    t0 = time.perf_counter()
                    for c in range(cp_cycles):
                        apply_cross_doc(
                            [(devs[i], [work[i][1][c]])
                             for i in range(len(work))]
                        )
                    dt = time.perf_counter() - t0
                    h1 = obs.counter_values(
                        "device.h2d_bytes", "").get("", 0)
                    col = sum(d.log.resident_column_nbytes() for d in devs)
                    res = sum(d.resident_nbytes() for d in devs)
                    return devs, h1 - h0, col, res, dt
                finally:
                    if prev is None:
                        os.environ.pop("AUTOMERGE_TPU_COMPRESSED", None)
                    else:
                        os.environ["AUTOMERGE_TPU_COMPRESSED"] = prev

            # warm both mode shapes (jit compile + page-in) on a
            # throwaway prefix so the reported seconds compare staging,
            # not first-launch compile
            warm = wl[: max(cp_docs // 2, 1)]
            cp_run("1", warm)
            cp_run("0", warm)
            devs_c, h2d_c, col_c, res_c, t_c = cp_run("1", wl)
            devs_d, h2d_d, col_d, res_d, t_d = cp_run("0", wl)
            # bit-identical materialized documents AND op columns
            for i in (0, cp_docs // 2, cp_docs - 1):
                assert devs_c[i].hydrate() == devs_d[i].hydrate(), i
                for colname in ("id_key", "action", "elem_ref",
                                "obj_dense", "value_int"):
                    assert np.array_equal(
                        np.asarray(getattr(devs_c[i].log, colname)),
                        np.asarray(getattr(devs_d[i].log, colname)),
                    ), (i, colname)
            gib = 1 << 30
            per_doc_c = max(res_c // cp_docs, 1)
            per_doc_d = max(res_d // cp_docs, 1)
            comp_cfg = {
                "docs": cp_docs,
                "cycles": cp_cycles,
                "ops_per_delta": cp_ops,
                "resident_ops": int(devs_c[0].log.n),
                "identical_docs": True,
                "resident_column_bytes_per_doc": col_c // cp_docs,
                "resident_column_bytes_per_doc_dense": col_d // cp_docs,
                "resident_compress_ratio": round(col_d / max(col_c, 1), 2),
                "device_bytes_per_doc": int(per_doc_c),
                "device_bytes_per_doc_dense": int(per_doc_d),
                "h2d_bytes_per_drain": h2d_c // cp_cycles,
                "h2d_bytes_per_drain_dense": h2d_d // cp_cycles,
                "h2d_compress_ratio": round(h2d_d / max(h2d_c, 1), 2),
                "resident_docs_per_gib": int(gib // per_doc_c),
                "resident_docs_per_gib_dense": int(gib // per_doc_d),
                "seconds_compressed": round(t_c, 4),
                "seconds_dense": round(t_d, 4),
            }
            del devs_c, devs_d, wl
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        comp_cfg = {"compressed_error": repr(e)[:500]}
        print(f"compressed config failed:\n{tb}", file=sys.stderr,
              flush=True)
    results["compressed"] = comp_cfg
    note(f"compressed: {results['compressed']}")
    wall_mark("compressed")

    # ---- config: overload (admission control + deadline propagation) -------
    # Drive a concurrent durable server far past its saturation point
    # with per-request deadlines and measure GOODPUT: responses that
    # succeed within their own deadline. Clients are the reference
    # cooperating kind — an AIMD in-flight window that halves on
    # Overloaded/DeadlineExceeded and grows on success — so the
    # admission layer's shed answers act as the congestion signal that
    # parks the system at its efficient operating point. The SAME drive
    # against an admission-disabled control server shows the classic
    # overload collapse: no shed signal, queues to the configured
    # bound, every response late. Also verified in-config: zero
    # acked-write loss (every acked put covered by an acked commit is
    # present at readback) and zero deadlocked clients. Each phase
    # writes fresh documents: doc/journal growth across phases would
    # otherwise confound capacity vs overdrive service times.
    ol_cfg = {}
    try:
        if env_flag("BENCH_OVERLOAD", "1") != "0":
            import re
            import shutil
            import socket as socketmod
            import subprocess
            import tempfile
            import threading

            ol_docs = env_int("BENCH_OL_DOCS", 3)
            # capacity is measured at a healthy queue depth (waits well
            # inside every shed band); overdrive offers OVERDRIVE x
            # that in-flight demand per client
            ol_cap_window = env_int("BENCH_OL_CAP_WINDOW", 16)
            ol_overdrive = env_int("BENCH_OL_OVERDRIVE", 12)
            ol_window = ol_cap_window * ol_overdrive
            ol_cap_ops = env_int("BENCH_OL_CAP_OPS", 3000)
            ol_ops = env_int("BENCH_OL_OPS", 4800)  # overdrive reqs/client
            ol_deadline_ms = env_int("BENCH_OL_DEADLINE_MS", 200)
            ol_env = dict(
                os.environ,
                JAX_PLATFORMS="cpu",
                # deep queues: the point is admission/deadline shedding,
                # not the per-doc QueueFull backstop masking it (same
                # depth for control and treatment — only admission
                # differs between the two servers)
                AUTOMERGE_TPU_SERVE_QUEUE_DEPTH="8192",
                # the operator contract: admission target wait tracks
                # the latency SLO. Proportional shedding then settles
                # the admitted queue near band center (2-4x target),
                # comfortably inside the client deadline.
                AUTOMERGE_TPU_ADMISSION_TARGET_WAIT_S=str(
                    ol_deadline_ms / 8.0 / 1000.0),
                # resample the load score often enough that a window
                # burst cannot slip past a stale-low cached score
                AUTOMERGE_TPU_ADMISSION_SAMPLE_S="0.01",
            )

            def ol_spawn(tmpdir, admission):
                env = dict(ol_env, AUTOMERGE_TPU_ADMISSION=admission)
                proc = subprocess.Popen(
                    [sys.executable, "-m", "automerge_tpu.rpc",
                     "--socket", "127.0.0.1:0", "--durable", tmpdir],
                    stderr=subprocess.PIPE, text=True, env=env,
                )
                port = int(re.search(
                    r"(\d+)\)", proc.stderr.readline()).group(1))
                threading.Thread(
                    target=lambda: [None for _ in proc.stderr],
                    daemon=True,
                ).start()
                return proc, port

            def ol_ask(sock, f, method, params):
                """Serial control-path request, retried through shed
                windows (openDurable is rank-1 and can itself be shed
                under full overload — a real client retries it)."""
                for _ in range(400):
                    sock.sendall((json.dumps(
                        {"id": 0, "method": method, "params": params})
                        + "\n").encode())
                    while True:
                        resp = json.loads(f.readline())
                        if resp.get("id") == 0:
                            break
                    if "error" not in resp:
                        return resp
                    time.sleep(0.025)
                return resp

            def ol_shutdown(proc, port):
                sock = socketmod.create_connection(("127.0.0.1", port))
                sock.sendall(b'{"id":1,"method":"shutdown"}\n')
                sock.makefile("r").readline()
                sock.close()
                proc.wait(timeout=60)

            def ol_server_stats(port):
                """Overload counters off the live server (metrics RPC):
                shed per class, deadline expiries per stage, brownout
                transitions, the queue-wait histogram."""
                sock = socketmod.create_connection(("127.0.0.1", port))
                f = sock.makefile("r")
                sock.sendall(
                    b'{"id":1,"method":"metrics",'
                    b'"params":{"format":"json"}}\n')
                snap = json.loads(f.readline())["result"]["metrics"]
                sock.close()
                out = {"shed": {}, "deadline_expired": {},
                       "brownout_transitions": {}}
                for it in snap:
                    name, labels = it.get("name"), it.get("labels", {})
                    if name == "serve.shed":
                        out["shed"][labels.get("class")] = it["value"]
                    elif name == "serve.deadline_expired":
                        out["deadline_expired"][
                            labels.get("stage")] = it["value"]
                    elif name == "cluster.brownout_transitions":
                        out["brownout_transitions"][
                            labels.get("to")] = it["value"]
                    elif name == "serve.load_score":
                        out["load_score"] = round(it["value"], 3)
                    elif name == "serve.queue_wait":
                        out["queue_wait_p95_s"] = round(
                            it.get("p95", 0.0), 6)
                return out

            class _OlStats:
                __slots__ = ("goodput", "late", "shed", "other",
                             "lats", "acked_keys", "done")

                def __init__(self):
                    self.goodput = 0  # success within its own deadline
                    self.late = 0  # success past the deadline
                    self.shed = 0  # DeadlineExceeded/Overloaded/Backpressure
                    self.other = 0
                    self.lats = []  # accepted-request latencies
                    self.acked_keys = []  # put keys covered by acked commit
                    self.done = False

            _SHED_TYPES = {"DeadlineExceeded", "Overloaded", "Backpressure"}

            def ol_client(port, doc_name, tag, n_ops, deadline_ms, window,
                          stats):
                """One driver: pipelined requests under an AIMD
                in-flight window (halve on shed, grow on success),
                7 puts then a commit, each stamped with its own deadline
                when ``deadline_ms`` is set. Ends with an undeadlined
                flush commit so every acked put is commit-covered for
                the readback audit."""
                sock = socketmod.create_connection(("127.0.0.1", port))
                sock.setsockopt(socketmod.IPPROTO_TCP,
                                socketmod.TCP_NODELAY, 1)
                sock.settimeout(120.0)
                f = sock.makefile("r")
                r = ol_ask(sock, f, "openDurable", {"name": doc_name})
                dh = r["result"]["doc"]
                sent = {}  # id -> (t_send, kind, key)
                acked_puts = {}  # id -> key (awaiting a covering commit)
                nid = [0]
                cwnd = [16.0]
                last_cut = [0.0]

                def send_one(i):
                    nid[0] += 1
                    if i % 8 == 7:
                        req = {"id": nid[0], "method": "commit",
                               "params": {"doc": dh}}
                        kind, key = "commit", None
                    else:
                        key = f"{tag}_{i:06}"
                        req = {"id": nid[0], "method": "put",
                               "params": {"doc": dh, "obj": "_root",
                                          "prop": key, "value": i}}
                        kind = "put"
                    if deadline_ms:
                        req["deadlineMs"] = deadline_ms
                    sent[nid[0]] = (time.perf_counter(), kind, key)
                    sock.sendall((json.dumps(req) + "\n").encode())

                def read_one():
                    resp = json.loads(f.readline())
                    rid = resp.get("id")
                    t0, kind, key = sent.pop(rid)
                    lat = time.perf_counter() - t0
                    if "error" in resp:
                        etype = resp["error"].get("type")
                        if etype in _SHED_TYPES:
                            stats.shed += 1
                            nw = time.perf_counter()
                            if nw - last_cut[0] > 0.1:
                                cwnd[0] = max(8.0, cwnd[0] * 0.6)
                                last_cut[0] = nw
                        else:
                            stats.other += 1
                        return
                    cwnd[0] = min(float(window), cwnd[0] + 0.5)
                    stats.lats.append(lat)
                    if deadline_ms and lat > deadline_ms / 1000.0:
                        stats.late += 1
                    else:
                        stats.goodput += 1
                    if kind == "put":
                        acked_puts[rid] = key
                    else:  # an acked commit covers every earlier ack
                        for pid in [p for p in acked_puts if p < rid]:
                            stats.acked_keys.append(acked_puts.pop(pid))

                i = 0
                while i < n_ops or sent:
                    while i < n_ops and len(sent) < min(window,
                                                        int(cwnd[0])):
                        send_one(i)
                        i += 1
                    if sent:
                        read_one()
                # flush: one undeadlined commit (retried through shed
                # windows) so surviving acked puts are commit-covered
                resp = ol_ask(sock, f, "commit", {"doc": dh})
                if "error" not in resp:
                    stats.acked_keys.extend(acked_puts.values())
                    acked_puts.clear()
                sock.close()
                stats.done = True

            def ol_drive(port, phase, n_ops, deadline_ms, window):
                """One phase: one client thread per doc against a
                phase-specific document set; returns (stats list, wall
                seconds, all joined)."""
                stats = []
                ts = []
                barrier = threading.Barrier(ol_docs + 1)

                def run(st, dname, tag):
                    barrier.wait()
                    ol_client(port, dname, tag, n_ops, deadline_ms,
                              window, st)

                for d in range(ol_docs):
                    st = _OlStats()
                    stats.append(st)
                    ts.append(threading.Thread(
                        target=run,
                        args=(st, f"{phase}{d}", f"{phase}_d{d}"),
                        daemon=True))
                for t in ts:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in ts:
                    t.join(timeout=300.0)
                dt = time.perf_counter() - t0
                return stats, dt, all(st.done for st in stats)

            def ol_readback(port, phase):
                """{doc name: set of present keys} straight off the
                server — the acked-write-loss audit's ground truth."""
                sock = socketmod.create_connection(("127.0.0.1", port))
                f = sock.makefile("r")
                present = {}
                for d in range(ol_docs):
                    name = f"{phase}{d}"
                    r = ol_ask(sock, f, "openDurable", {"name": name})
                    dh = r["result"]["doc"]
                    r = ol_ask(sock, f, "keys", {"doc": dh,
                                                 "obj": "_root"})
                    present[name] = set(r["result"])
                sock.close()
                return present

            def ol_phase_summary(stats, dt):
                offered = sum(
                    st.goodput + st.late + st.shed + st.other
                    for st in stats)
                goodput = sum(st.goodput for st in stats)
                shed = sum(st.shed for st in stats)
                return {
                    "offered": offered,
                    "goodput": goodput,
                    "goodput_rps": round(goodput / dt, 1),
                    "late": sum(st.late for st in stats),
                    "shed": shed,
                    "shed_rate": round(shed / max(offered, 1), 4),
                    "errors_other": sum(st.other for st in stats),
                    "seconds": round(dt, 3),
                }

            tmp_ctl = tempfile.mkdtemp(prefix="amtpu_bench_ol_ctl_")
            tmp_un = tempfile.mkdtemp(prefix="amtpu_bench_ol_un_")
            ctl_proc = un_proc = None
            try:
                # -- controlled server: capacity, then overdrive --------
                ctl_proc, ctl_port = ol_spawn(tmp_ctl, "1")
                ol_drive(ctl_port, "wm", 256, 0, ol_cap_window)
                cap_stats, cap_dt, cap_ok = ol_drive(
                    ctl_port, "cap", ol_cap_ops, 0, ol_cap_window)
                capacity_rps = sum(
                    st.goodput for st in cap_stats) / cap_dt
                od_stats, od_dt, od_ok = ol_drive(
                    ctl_port, "od", ol_ops, ol_deadline_ms, ol_window)
                # zero acked-write loss: every put acked AND covered by
                # an acked commit must be present at readback
                acked = {f"od{d}": set() for d in range(ol_docs)}
                for st in od_stats:
                    for k in st.acked_keys:
                        acked[f"od{k.split('_d', 1)[1].split('_', 1)[0]}"
                              ].add(k)
                present = ol_readback(ctl_port, "od")
                lost = {
                    d: sorted(acked[d] - present[d])[:5]
                    for d in acked if acked[d] - present[d]
                }
                server_stats = ol_server_stats(ctl_port)
                ol_shutdown(ctl_proc, ctl_port)

                # -- control server: same overdrive, admission off ------
                un_proc, un_port = ol_spawn(tmp_un, "0")
                ol_drive(un_port, "wm", 256, 0, ol_cap_window)
                un_od_stats, un_od_dt, un_ok = ol_drive(
                    un_port, "od", ol_ops, ol_deadline_ms, ol_window)
                ol_shutdown(un_proc, un_port)
            finally:
                for p_ in (ctl_proc, un_proc):
                    if p_ is not None and p_.poll() is None:
                        p_.kill()
                        p_.wait(timeout=10)
                shutil.rmtree(tmp_ctl, ignore_errors=True)
                shutil.rmtree(tmp_un, ignore_errors=True)

            od = ol_phase_summary(od_stats, od_dt)
            un = ol_phase_summary(un_od_stats, un_od_dt)
            ol_cfg = {
                "docs": ol_docs,
                "overdrive": ol_overdrive,
                "ops_per_client": ol_ops,
                "window": ol_window,
                "cap_window": ol_cap_window,
                "deadline_ms": ol_deadline_ms,
                "capacity_rps": round(capacity_rps, 1),
                **od,
                "goodput_ratio": round(
                    od["goodput_rps"] / max(capacity_rps, 1e-9), 3),
                "acked_write_loss": sum(len(v) for v in lost.values()),
                "lost_sample": lost,
                "deadlocked": not (cap_ok and od_ok and un_ok),
                "server": server_stats,
                **_latency_percentiles(
                    "bench.overload.accepted_latency",
                    [x for st in od_stats for x in st.lats]),
                "control": {
                    **un,
                    "goodput_ratio": round(
                        un["goodput_rps"] / max(capacity_rps, 1e-9), 3),
                    **_latency_percentiles(
                        "bench.overload.control_latency",
                        [x for st in un_od_stats for x in st.lats]),
                },
            }
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        ol_cfg = {"overload_error": repr(e)[:500]}
        print(f"overload config failed:\n{tb}", file=sys.stderr, flush=True)
    results["overload"] = ol_cfg
    note(f"overload: {results['overload']}")
    wall_mark("overload")

    # ---- config: persistence (run-coded snapshot codec vs legacy chunk) ----
    # One column format from disk to device. A: cold-open latency of the
    # SAME document persisted as a run-coded ARSN image (the default
    # writer) vs the legacy chunk codec (AUTOMERGE_TPU_RUNSNAP=0) —
    # percentiles over repeated from-disk opens, plus hydrate-to-first-
    # read (open + first value read) for each codec. The zero-re-encode
    # contract rides along: a device-mirror build after a run-coded open
    # must not advance oplog.hydrate_reencode, and the chunk path MUST
    # (non-vacuous counter). B: compaction write amplification — the
    # cost-gated compactor (compact_cost_ratio: defer while the journal
    # tail is cheaper than the image rewrite) vs full-rewrite-at-every-
    # threshold, in snapshot bytes written per committed op.
    ps_cfg = {}
    try:
        if env_flag("BENCH_PERSISTENCE", "1") != "0":
            import shutil
            import tempfile

            from automerge_tpu.storage.durable import SNAPSHOT_NAME

            ps_ops = env_int("BENCH_PS_OPS", 100_000)
            ps_opens = env_int("BENCH_PS_OPENS", 12)
            ps_commits = env_int("BENCH_PS_COMMITS", 600)
            ps_every = env_int("BENCH_PS_COMPACT_EVERY", 48)
            ps_ratio = float(env_flag("BENCH_PS_COST_RATIO", "4.0"))

            ps_dir = tempfile.mkdtemp(prefix="amtpu_bench_ps_")
            try:
                run_path = os.path.join(ps_dir, "run")
                chunk_path = os.path.join(ps_dir, "chunk")
                dd = AutoDoc.open(
                    run_path, fsync="never",
                    actor=ActorId(bytes([21]) * 16),
                )
                tob = dd.put_object("_root", "text", ObjType.TEXT)
                dd.put("_root", "probe", 1)
                dd.commit()
                edits = trace[:ps_ops]
                step = max(1, min(2000, max(1, len(edits) // 64)))
                for lo in range(0, len(edits), step):
                    W.apply_edits(dd, tob, edits[lo:lo + step])
                    dd.commit()
                dd.compact()
                heads_a = sorted(dd.get_heads())
                dd.close()

                # the SAME document re-persisted through the legacy chunk
                # writer: copy the doc dir, rewrite its snapshot with the
                # run-coded writer disabled
                shutil.copytree(run_path, chunk_path)
                prior = os.environ.get("AUTOMERGE_TPU_RUNSNAP")
                os.environ["AUTOMERGE_TPU_RUNSNAP"] = "0"
                try:
                    d2 = AutoDoc.open(chunk_path, fsync="never")
                    assert d2.compact(), "legacy snapshot rewrite refused"
                    heads_b = sorted(d2.get_heads())
                    d2.close()
                finally:
                    if prior is None:
                        os.environ.pop("AUTOMERGE_TPU_RUNSNAP", None)
                    else:
                        os.environ["AUTOMERGE_TPU_RUNSNAP"] = prior

                def cold_open_stats(path, hist_name):
                    """Repeated from-disk opens of a fully-compacted doc:
                    per-open latency, open+first-read, the re-encode
                    counter across one device-mirror build, and the
                    hydrate byte counters by codec label."""
                    lats = []
                    first_read = None
                    re0 = T.counters.get("oplog.hydrate_reencode", 0)
                    hb0 = dict(obs.counter_values(
                        "store.hydrate_bytes", "codec"))
                    for i in range(ps_opens):
                        t0 = time.perf_counter()
                        d_ = AutoDoc.open(path, fsync="never")
                        t_open = time.perf_counter() - t0
                        v = d_.get("_root", "probe")
                        t_read = time.perf_counter() - t0
                        assert v is not None, v
                        lats.append(t_open)
                        if first_read is None:
                            first_read = t_read
                            # cold -> hot: the device mirror must source
                            # the retained run image (legacy: re-extract,
                            # which the counter charges)
                            d_.build_device_mirror()
                        d_.close()
                    hb1 = dict(obs.counter_values(
                        "store.hydrate_bytes", "codec"))
                    return {
                        "snapshot_bytes": os.path.getsize(
                            os.path.join(path, SNAPSHOT_NAME)),
                        "hydrate_to_first_read_s": round(first_read, 4),
                        "hydrate_reencode": T.counters.get(
                            "oplog.hydrate_reencode", 0) - re0,
                        "hydrate_bytes": {
                            k: hb1.get(k, 0) - hb0.get(k, 0)
                            for k in hb1
                            if hb1.get(k, 0) != hb0.get(k, 0)
                        },
                        **_latency_percentiles(hist_name, lats),
                    }

                rs = cold_open_stats(
                    run_path, "bench.persistence.cold_open_runsnap")
                cs = cold_open_stats(
                    chunk_path, "bench.persistence.cold_open_chunk")

                def write_amp(tag, cost_ratio):
                    """ps_commits small commits against aggressive
                    compaction thresholds; the bytes the compactor
                    rewrote per committed op is the write-amp figure."""
                    b0 = T.counters.get("compact.bytes_written", 0)
                    r0 = T.counters.get("compact.runs", 0)
                    d_ = AutoDoc.open(
                        os.path.join(ps_dir, f"wa_{tag}"), fsync="never",
                        compact_max_records=ps_every,
                        compact_max_bytes=1 << 30,
                        compact_cost_ratio=cost_ratio,
                        actor=ActorId(bytes([22]) * 16),
                    )
                    pay = "v" * 160
                    t0 = time.perf_counter()
                    for i in range(ps_commits):
                        d_.put("_root", f"k{i % 256:04}", f"{pay}{i}")
                        d_.commit()
                    dt = time.perf_counter() - t0
                    d_.close()
                    written = T.counters.get(
                        "compact.bytes_written", 0) - b0
                    return {
                        "cost_ratio": cost_ratio,
                        "compactions": T.counters.get(
                            "compact.runs", 0) - r0,
                        "snapshot_bytes_written": written,
                        "bytes_per_op": round(written / ps_commits, 1),
                        "commits_per_sec": round(ps_commits / dt, 1),
                    }

                wa_full = write_amp("full", 0.0)
                wa_gated = write_amp("gated", ps_ratio)

                ps_cfg = {
                    "edits": len(edits),
                    "opens": ps_opens,
                    "commits": ps_commits,
                    "heads_identical": heads_a == heads_b,
                    "runsnap": rs,
                    "chunk": cs,
                    "cold_open_p50_speedup": round(
                        cs["latency_p50_s"] / max(rs["latency_p50_s"],
                                                  1e-9), 2),
                    "cold_open_p99_speedup": round(
                        cs["latency_p99_s"] / max(rs["latency_p99_s"],
                                                  1e-9), 2),
                    "full_rewrite": wa_full,
                    "cost_gated": wa_gated,
                    "write_amp_reduction": round(
                        wa_full["bytes_per_op"]
                        / max(wa_gated["bytes_per_op"], 1e-9), 2),
                }
            finally:
                shutil.rmtree(ps_dir, ignore_errors=True)
    except Exception as e:  # noqa: BLE001 — degrade, record, continue
        import traceback

        tb = traceback.format_exc()
        ps_cfg = {"persistence_error": repr(e)[:500]}
        print(f"persistence config failed:\n{tb}", file=sys.stderr,
              flush=True)
    results["persistence"] = ps_cfg
    note(f"persistence: {results['persistence']}")
    wall_mark("persistence")
    wall_s["total"] = round(sum(wall_s.values()), 3)

    out = {
        "metric": "edit_trace_fanin_merge_ops_per_sec",
        "value": results["fanin"]["ops_per_sec"],
        "unit": "ops/s",
        "vs_baseline": results["fanin"]["vs_baseline"],
        # provenance: which code produced these numbers, under exactly
        # which resolved knobs, on which box — the JSON is
        # self-describing across PRs and perf_gate can refuse to compare
        # points from different hosts
        "git_commit": git_commit(),
        "host": host_fingerprint(),
        "schema_version": BENCH_SCHEMA_VERSION,
        "config": dict(sorted(RESOLVED_CONFIG.items())),
        # memory trajectory alongside throughput: this process's peak
        # RSS over the whole run (ru_maxrss is KiB on Linux) — the
        # number the tiered-store work is accountable to across PRs
        "max_rss_bytes": _resource.getrusage(
            _resource.RUSAGE_SELF).ru_maxrss * 1024,
        "configs": results,
        # per-config wall clock + total: the additive cost view — a
        # config whose setup quietly doubles shows up here even when its
        # headline throughput number holds
        "wall_s": wall_s,
        # cumulative device-phase attribution across the whole run
        # (trace.time spans: device.extract / h2d / kernel / readback /
        # materialize, merge.host)
        "trace_timings": T.timing_summary(),
        # every kernel dispatch over the whole run, by dispatch path
        # (per_doc / batched / sharded — the device.kernel_launches
        # counter each dispatch site increments)
        "kernel_launches": obs.counter_values(
            "device.kernel_launches", "path"
        ),
        # run-native demotions over the whole run: which columns shipped
        # dense anyway and why (ratio = run table degenerate past the
        # gate, dtype = not int32/bool, short = below the run-encode
        # floor) — the per-column view of the ratio-gate dense fallback
        "run_native_fallback": {
            "by_reason": obs.counter_values(
                "device.run_native_fallback", "reason"
            ),
            "by_column": obs.counter_values(
                "device.run_native_fallback", "column"
            ),
        },
        # pack-site occupancy across every batched launch of the run:
        # useful rows / (useful + padded) from the device.batch_rows /
        # device.batch_padding_rows counters (None = nothing packed)
        "batch_occupancy": (
            lambda u, p: round(u / (u + p), 4) if (u + p) else None
        )(
            obs.counter_values("device.batch_rows", "").get("", 0),
            obs.counter_values("device.batch_padding_rows", "").get("", 0),
        ),
        # per-change-hash extraction-cache efficacy across the whole run:
        # the observatory names extract as a dominant host stage, and
        # this is the knob that decides how much of it is re-decode
        # (hits/misses from extract.change_cache_hit/miss; None = the
        # cache was never consulted)
        "extract_cache": (
            lambda h, ms: {
                "hits": h,
                "misses": ms,
                "cache_hit_ratio": (
                    round(h / (h + ms), 4) if (h + ms) else None
                ),
            }
        )(
            obs.counter_values("extract.change_cache_hit", "").get("", 0),
            obs.counter_values("extract.change_cache_miss", "").get("", 0),
        ),
        # span-ring health: how much of the run the flight recorder /
        # Perfetto export can still see (dropped > 0 means the ring
        # wrapped and the phase trace is a suffix, not the whole run)
        "span_buffer": {
            "recorded": len(obs.recorder),
            "dropped": obs.counter_values(
                "obs.spans_dropped", "").get("", 0),
        },
        # tail attribution: per-phase latency distributions from the span
        # histograms (log-bucketed; "what is p99 merge latency")
        "phase_percentiles": {
            e["name"] + "".join(
                "{%s=%s}" % (k, v) for k, v in sorted(e["labels"].items())
            ): {k: round(e[k], 6) for k in ("p50", "p95", "p99")}
            for e in obs.snapshot()
            if e["type"] == "histogram"
            and e["name"].startswith(("device.", "merge.", "journal.",
                                      "sync.", "compact.", "rpc.",
                                      "group_commit."))
        },
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
