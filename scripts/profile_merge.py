#!/usr/bin/env python
"""Profile the fan-in merge half: log.columns() prep + native merge engine."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from automerge_tpu import bench as W
from automerge_tpu import native
from automerge_tpu.ops import DeviceDoc, OpLog
from automerge_tpu.ops.merge import merge_columns

trace = W.synth_edit_trace()
base_edits = int(os.environ.get("BENCH_BASE_EDITS", 259_778))
n_replicas = int(os.environ.get("BENCH_REPLICAS", 1024))
fork_edits = int(os.environ.get("BENCH_FORK_EDITS", 250))
t0 = time.perf_counter()
base = W.build_base(trace, base_edits)
print(f"base build: {time.perf_counter()-t0:.2f}s", file=sys.stderr)
t0 = time.perf_counter()
replica_changes = W.synth_fanin(base, trace, n_replicas, fork_edits, base_edits)
changes = list(base.changes) + replica_changes
print(f"synth: {time.perf_counter()-t0:.2f}s", file=sys.stderr)

log = OpLog.from_changes(changes)
kw = dict(fetch=DeviceDoc.READ_FETCH, n_objs=log.n_objs, n_props=len(log.props))
merge_columns(log.columns(), **kw)  # warm

for _ in range(4):
    log = OpLog.from_changes(changes)
    t0 = time.perf_counter()
    cols = log.columns()
    t_cols = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = native.merge_cols(cols, log.n_objs, want_elem_index=True)
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter()
    merge_columns(log.columns(), **kw)
    t_full = time.perf_counter() - t0
    print(
        f"columns() {t_cols*1e3:.1f}ms  native.merge_cols {t_native*1e3:.1f}ms"
        f"  merge_columns e2e {t_full*1e3:.1f}ms",
        file=sys.stderr,
    )

if os.environ.get("PROFILE", "0") != "0":
    import cProfile
    import pstats

    log = OpLog.from_changes(changes)
    pr = cProfile.Profile()
    pr.enable()
    merge_columns(log.columns(), **kw)
    pr.disable()
    pstats.Stats(pr, stream=sys.stderr).sort_stats("cumulative").print_stats(25)
