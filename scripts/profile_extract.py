#!/usr/bin/env python
"""Profile OpLog.from_changes on the fan-in workload (the round-4 target)."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import cProfile
import pstats

from automerge_tpu import bench as W
from automerge_tpu.ops import OpLog

trace = W.synth_edit_trace()
base_edits = int(os.environ.get("BENCH_BASE_EDITS", 120_000))
n_replicas = int(os.environ.get("BENCH_REPLICAS", 1024))
fork_edits = int(os.environ.get("BENCH_FORK_EDITS", 250))
t0 = time.perf_counter()
base = W.build_base(trace, base_edits)
print(f"base build: {time.perf_counter()-t0:.2f}s", file=sys.stderr)
t0 = time.perf_counter()
replica_changes = W.synth_fanin(base, trace, n_replicas, fork_edits, base_edits)
changes = list(base.changes) + replica_changes
print(f"synth: {time.perf_counter()-t0:.2f}s", file=sys.stderr)

# warm
log = OpLog.from_changes(changes)
print(f"n={log.n}", file=sys.stderr)

for _ in range(3):
    t0 = time.perf_counter()
    log = OpLog.from_changes(changes)
    print(f"from_changes: {time.perf_counter()-t0:.4f}s", file=sys.stderr)

if os.environ.get("PROFILE", "1") != "0":
    pr = cProfile.Profile()
    pr.enable()
    log = OpLog.from_changes(changes)
    pr.disable()
    stats = pstats.Stats(pr, stream=sys.stderr)
    stats.sort_stats("cumulative").print_stats(30)
