"""The batched causal-resolution merge kernel (the north star).

Replaces the reference's sequential per-op seek/insert loop
(reference: rust/automerge/src/automerge.rs:1258-1280, op_tree.rs:212-239)
with one jit-compiled pass over the whole op log:

  1. succ resolution     — pred references (pre-resolved to row indices by
                           the host columnizer) scatter-added into per-op
                           succ / increment counters (batched ``add_succ``,
                           op_set.rs:194-203).
  2. visibility          — op visible iff it has no non-increment successor
                           (counters) / no successor at all (everything
                           else); deletes, increments and marks are never
                           visible (types.rs:712-744).
  3. per-key winners     — lexsort by (obj, key, row) + segmented reductions
                           give the winning op and conflict count for every
                           map prop and list-element run (vectorized
                           ``TopOps``, iter/top_ops.rs:44-103). Rows are in
                           Lamport order, so "max row" is "max Lamport".
  4. RGA linearization   — insert ops form a forest (parent = reference
                           element, siblings ordered by descending Lamport
                           id, query/insert.rs); document order is its
                           preorder traversal, computed with pointer-doubling
                           successor threading + Wyllie list ranking: two
                           O(log n)-step gather loops instead of a pointer
                           walk.

Everything is int32 with static power-of-two shapes: no 64-bit emulation on
TPU, one compiled kernel per capacity bucket, and the hot work is sorts,
gathers and segmented reductions — shapes XLA maps well onto the VPU.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .oplog import (
    ELEM_HEAD, PAD_ACTION, PAD_FILLS, TAG_COUNTER, _capacity, _next_pow2,
)

_DELETE = 3
_INCREMENT = 5
_MARK = 7
_PUT = 1

# plain int (weakly-typed in jax): a module-level jnp scalar would start
# the default backend and compile a kernel at IMPORT time
NONE32 = -1

# the names every resolution program is jitted under (its XLA module is
# ``jit_<name>``): ``merge_kernel`` ranks element order on the device,
# ``merge_kernel_core`` leaves it to the host. No other program is
# jitted here
RESOLUTION_PROGRAMS = ("merge_kernel", "merge_kernel_core")


def _ceil_log2(n: int) -> int:
    return max(1, int(n - 1).bit_length())


def _program(name: str, fn):
    """``jax.jit(fn)`` as the program ``name``: a profile then names its
    XLA module ``jit_<name>``, not after a local helper's name."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _stage(name: str):
    """Trace a kernel stage under ``jax.named_scope(name)``: every XLA op
    it emits carries the stage in its metadata (``op_name``), so a
    profile splits the device time by stage. Metadata only: the
    optimized program is unchanged."""

    def wrap(fn):
        @functools.wraps(fn)
        def staged(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return staged

    return wrap


@contextlib.contextmanager
def readback(outputs, rows: int, name: str = "device.readback"):
    """``with readback(out, rows): host = np.asarray(out)`` — the
    blocking half of a launch, as a ``name`` span around two children:
    ``device.wait``, the host blocked until ``outputs`` are ready (the
    device time that the launch's ``device.kernel`` span, an enqueue,
    does not cover), then ``device.d2h``, the copy the body makes. The
    copy blocked on the same outputs before; nothing new waits."""
    with obs.span(name, rows=rows):
        with obs.span("device.wait", rows=rows):
            jax.block_until_ready(outputs)
        with obs.span("device.d2h", rows=rows):
            yield


@_stage("succ_resolution")
def succ_resolution(c):
    """Phase 1: pred scatter -> per-op succ/inc counters (batched add_succ).

    The bandwidth-heavy phase; parallel/sharding.py shards the pred stream
    across a device mesh and psums these partial counters. Three 1-D
    scatter-adds: one (P, 3) scatter carrying all three took the TPU
    compiler ~10 s at 64k+ rows, each 1-D one ~0.1 s.

    ``covered`` gates each pred edge by its source op's clock coverage: a
    successor outside the read clock does not overwrite (the vectorized
    ``Clock::covers`` test on the succ side of ``visible_at``,
    reference: types.rs:712-744, clock.rs:71-77).
    """
    P = c["action"].shape[0]
    action = c["action"]
    tgt = c["pred_tgt"]
    src = c["pred_src"]
    hit = (tgt >= 0) & c["covered"][src]
    src_is_inc = action[src] == _INCREMENT
    tgt_c = jnp.where(hit, tgt, 0)

    def acc(v):
        return jnp.zeros(P, jnp.int32).at[tgt_c].add(v)

    return (
        acc(jnp.where(hit & ~src_is_inc, 1, 0)),
        acc(jnp.where(hit & src_is_inc, 1, 0)),
        acc(jnp.where(hit & src_is_inc, c["value_i32"][src], 0)),
    )


def visibility(c, succ_count, inc_count):
    """Phase 2: the visibility rule (types.rs:712-744), shared by the
    single-device kernel and the sharded path (parallel/sharding.py).

    ``covered`` masks ops outside the read clock (all-true for current
    state)."""
    action = c["action"]
    valid = action != PAD_ACTION
    never = (action == _DELETE) | (action == _INCREMENT) | (action == _MARK)
    is_counter = (action == _PUT) & (c["value_tag"] == TAG_COUNTER)
    # counter puts survive increment successors (types.rs:712-720)
    return (
        valid
        & c["covered"]
        & ~never
        & jnp.where(is_counter, succ_count == 0, (succ_count + inc_count) == 0)
    )


@_stage("resolve_state")
def resolve_state(c, succ_count, inc_count, counter_inc, obj_cap=None):
    """Phases 2-4: visibility, per-key winners, RGA linearization.

    Returns a dict of device arrays (all int32/bool, per-row unless noted):
      visible      — op currently visible
      counter_inc  — summed increment payloads landing on this op
      winner       — row of the winning visible op of this row's key group
                     (-1 if none visible)
      conflicts    — number of visible ops in this row's key group
      elem_index   — document-order position of this insert op among its
                     object's elements (-1 for non-inserts)
      obj_vis_len  — per dense-object visible element count   [indexed by
      obj_text_width — per dense-object visible text width     obj_dense]
      succ_count / inc_count — successor bookkeeping (patches/debug)
    """
    P = c["action"].shape[0]
    rows = jnp.arange(P, dtype=jnp.int32)
    action = c["action"]
    valid = action != PAD_ACTION
    insert = c["insert"]
    elem_ref = c["elem_ref"]
    obj_dense = c["obj_dense"]

    # --- 2. visibility -----------------------------------------------------
    # RGA linearization below deliberately ignores ``covered`` so element
    # order — which depends only on the insert forest — is identical across
    # historical views of one log.
    visible = visibility(c, succ_count, inc_count)

    # --- 3. per-key winners ------------------------------------------------
    is_map = c["prop"] >= 0
    # an insert op heads its own element run; updates/deletes name the run
    # they target via their (row-resolved) elem reference
    run_key = jnp.where(insert, rows, elem_ref)
    g_obj = jnp.where(valid, obj_dense, jnp.int32(P))
    g_kind = is_map.astype(jnp.int32)
    g_key = jnp.where(is_map, c["prop"], run_key)
    # the three group keys pack into ONE int32 when the object table is
    # small (obj_cap is static): a single-key sort moves half the data of
    # the 3-key + payload variant. Every sort here ends in the row id, so
    # its keys are unique and it need not be stable: a stable sort took
    # the TPU compiler ~2.5x as long (21 s vs 7 s at 262k rows).
    key_bits = _ceil_log2(P + 5)
    if obj_cap is not None and ((2 * (obj_cap + 2)) << key_bits) < (1 << 31):
        # invalid rows take the sentinel obj_cap+1 (> every valid obj_dense)
        g_obj_p = jnp.where(valid, obj_dense, jnp.int32(min(P, obj_cap + 1)))
        packed = (
            ((g_obj_p * 2 + g_kind) << key_bits)
            | (g_key + 4)  # run_key sentinels reach -3; offset keeps it positive
        )
        packed_s, sort_idx = jax.lax.sort((packed, rows), num_keys=2)
        newseg = jnp.concatenate(
            [jnp.array([True]), packed_s[1:] != packed_s[:-1]]
        )
    else:
        # one multi-key sort pass (lexsort would run one full sort per key)
        g_obj_s, g_kind_s, g_key_s, sort_idx = jax.lax.sort(
            (g_obj, g_kind, g_key, rows), num_keys=4
        )
        newseg = jnp.concatenate(
            [
                jnp.array([True]),
                (g_obj_s[1:] != g_obj_s[:-1])
                | (g_kind_s[1:] != g_kind_s[:-1])
                | (g_key_s[1:] != g_key_s[:-1]),
            ]
        )
    seg = (jnp.cumsum(newseg) - 1).astype(jnp.int32)
    vis_s = visible[sort_idx]
    cand = jnp.where(vis_s, jnp.arange(P, dtype=jnp.int32), NONE32)
    win_pos = jax.ops.segment_max(cand, seg, num_segments=P)
    seg_vis = jax.ops.segment_sum(vis_s.astype(jnp.int32), seg, num_segments=P)
    win_row = jnp.where(win_pos >= 0, sort_idx[jnp.clip(win_pos, 0, P - 1)], NONE32)
    seg_of_row = jnp.zeros(P, jnp.int32).at[sort_idx].set(seg)
    winner = win_row[seg_of_row]
    conflicts = seg_vis[seg_of_row]

    # --- 4. RGA linearization ---------------------------------------------
    # the shared sibling-forest builder (node space: [0,P) element nodes,
    # [P,2P+2) object roots, sentinel terminates every chain)
    is_elem, parent_row, first_child, next_sib = forest(c)

    core = {
        "visible": visible,
        "counter_inc": counter_inc,
        "winner": winner,
        "conflicts": conflicts,
        "succ_count": succ_count,
        "inc_count": inc_count,
        "first_child": first_child,
        "next_sib": next_sib,
        "parent_row": parent_row,
        "is_elem": is_elem,
    }

    # --- per-object stats (order-independent) ------------------------------
    elem_vis = is_elem & (winner >= 0)
    obj_idx = jnp.where(valid, obj_dense, jnp.int32(P + 1))
    core["obj_vis_len"] = jax.ops.segment_sum(
        elem_vis.astype(jnp.int32), obj_idx, num_segments=P + 2
    )
    w_width = jnp.where(elem_vis, c["width"][jnp.clip(winner, 0, P - 1)], 0)
    core["obj_text_width"] = jax.ops.segment_sum(
        w_width, obj_idx, num_segments=P + 2
    )
    return core


@_stage("device_linearize")
def device_linearize(c, core):
    """Document-order element indices computed fully on device.

    Pointer-doubling + Wyllie ranking: O(log n) passes of gathers. On TPU
    the ranking pass gathers along the (near-random) document-order chain,
    which the hardware handles far worse than the host's sequential walk —
    so the default pipeline uses the native preorder walk
    (native am_preorder_index) and this path serves the pure-device /
    multi-chip dry-run flow.
    """
    P = c["action"].shape[0]
    rows = jnp.arange(P, dtype=jnp.int32)
    # the doubling loops run in *element* space [0, P) + sentinel P: element
    # nodes are the only chain participants, so arrays (and the random
    # gathers, the expensive part on TPU) are half the full node space
    E = P + 1
    SE = jnp.int32(P)
    first_child = core["first_child"]  # node space (roots included)
    next_sib_e = jnp.concatenate([core["next_sib"][:P], jnp.array([-1], jnp.int32)])
    fc_e = jnp.concatenate([jnp.minimum(first_child[:P], SE + 1), jnp.array([-1], jnp.int32)])
    fc_e = jnp.where(fc_e > SE, NONE32, fc_e)  # child refs are always < P
    parent_row = core["parent_row"]
    is_elem = core["is_elem"]
    elem_ref = c["elem_ref"]

    # A(i): next sibling of i, else of nearest ancestor (threaded successor),
    # resolved by pointer doubling over the parent chain. Parents that are
    # object roots terminate the climb (ans = END).
    parent_e = jnp.concatenate(
        [
            jnp.where(is_elem & (elem_ref >= 0), elem_ref, SE),
            jnp.array([P], jnp.int32),
        ]
    ).astype(jnp.int32)
    is_elem_e = jnp.concatenate([is_elem, jnp.array([False])])
    has_sib = next_sib_e != NONE32
    done = has_sib | ~is_elem_e | (parent_e == SE)
    ans = jnp.where(has_sib & is_elem_e, next_sib_e, NONE32)
    jump = parent_e

    def _thread(_, st):
        ans, done, jump = st
        take = (~done) & done[jump]
        ans = jnp.where(take, ans[jump], ans)
        done = done | take
        jump = jump[jump]
        return ans, done, jump

    ans, done, jump = jax.lax.fori_loop(
        0, _ceil_log2(E) + 1, _thread, (ans, done, jump)
    )

    # preorder successor: first child, else A(i); Wyllie ranking gives the
    # distance to the chain end, hence the document-order index
    succ_e = jnp.where(fc_e != NONE32, fc_e, ans)
    nxt = jnp.where(succ_e < 0, SE, succ_e)
    nxt = nxt.at[SE].set(SE)
    dist = jnp.where(jnp.arange(E, dtype=jnp.int32) == SE, 0, 1).astype(jnp.int32)

    def _rank(_, st):
        dist, nxt = st
        return dist + dist[nxt], nxt[nxt]

    dist, nxt = jax.lax.fori_loop(0, _ceil_log2(E) + 1, _rank, (dist, nxt))
    # chain start per row: the root's first child (an element node)
    start = first_child[P + c["obj_dense"]]
    start_c = jnp.clip(start, 0, P - 1)
    return jnp.where(
        is_elem & (start >= 0), dist[start_c] - dist[rows], NONE32
    )


@jax.jit
def merge_kernel(c):
    """Single-device merge, everything on device (incl. linearization)."""
    core = resolve_state(c, *succ_resolution(c))
    core["elem_index"] = device_linearize(c, core)
    return core


@jax.jit
def merge_kernel_core(c):
    """Device merge without document-order ranking (the hybrid pipeline:
    the native preorder walk supplies elem_index on host)."""
    return resolve_state(c, *succ_resolution(c))


@_stage("device_linearize")
def device_linearize_condensed(c, core, rcap: int, obj_cap: int = None):
    """All-device document order via CHAIN CONDENSATION.

    The plain pointer-doubling ranking (device_linearize) pays two
    O(log N)-step loops of random gathers over the full row space — the
    known-weak all-device phase. This version collapses the preorder
    list into RUNS first: in actor-concatenated element order
    (``c["aorder"]``, host-supplied layout permutation), a typing chain
    is a CONTIGUOUS stretch of slots where each op is its predecessor's
    first child (the structure native/condense.cpp exploits host-side;
    reference locality: query/insert.rs:11-160). Runs are found with
    cumsum + segmented scans, and the two doubling loops (sibling-climb
    threading + Wyllie ranking) run over ``rcap``-sized run tables.

    Every full-width data movement is expressed as a SCATTER along the
    permutation (unique indices) rather than a gather — random gathers
    cost ~10x more than scatters on this hardware — leaving one small
    per-object gather in the whole pass. The caller guarantees the true
    run count fits ``rcap`` (OpLog counts runs host-side and picks the
    bucket).
    """
    P = c["action"].shape[0]
    i32 = jnp.int32
    ks = jnp.arange(P, dtype=i32)
    is_elem = core["is_elem"]
    er = c["elem_ref"]
    first_child = core["first_child"]
    next_sib = core["next_sib"][:P]
    seq = c["aorder"]  # compact slot k -> element row (pad sentinel = P)

    # first-child continuation, scatter-style: each element row p marks
    # ITS first child as a continuation (unique targets)
    fc_elem = first_child[:P]  # first child of element row p (node space<P)
    mark = is_elem & (fc_elem >= 0)
    is_cont = (
        jnp.zeros(P + 1, jnp.bool_)
        .at[jnp.where(mark, jnp.clip(fc_elem, 0, P - 1), P)]
        .set(True)[:P]
    )

    valid = seq < P
    seqc = jnp.clip(seq, 0, P - 1)
    # row -> compact slot (junk writes land in the spare slot)
    kpos = (
        jnp.full(P + 1, 0, i32)
        .at[jnp.where(valid, seqc, P)]
        .set(ks)[:P]
    )
    # per-slot facts: scatter each row's packed data to its slot
    row_pack = jnp.stack(
        [
            er,
            next_sib,
            is_cont.astype(i32) * 2 + (next_sib != NONE32).astype(i32),
        ],
        axis=1,
    )
    slot_tgt = jnp.where(is_elem, kpos, P)
    g = (
        jnp.zeros((P + 1, 3), i32)
        .at[slot_tgt]
        .set(row_pack)[:P]
    )
    er_k = g[:, 0]
    sib_k = g[:, 1]
    cont_bit = g[:, 2]

    # run segmentation: slot k continues its run iff it is a first-child
    # continuation AND its parent is the previous compact slot's row
    prev_row = jnp.concatenate([jnp.full(1, P, i32), seq[:-1]])
    cont_k = valid & (cont_bit >= 2) & (er_k == prev_row)
    brk = valid & ~cont_k
    run_of_k = jnp.cumsum(brk.astype(i32)) - 1

    # segmented scan carrying the run-start position and the "last
    # sibling-bearing member so far" answer (one scan, no gathers)
    flag_k = valid & ((cont_bit & 1) == 1)
    val_k = jnp.where(flag_k, sib_k, NONE32)

    def _seg_last(x, y):
        xv, xf, xs, xb = x
        yv, yf, ys, yb = y
        v = jnp.where(yb, yv, jnp.where(yf, yv, xv))
        f = jnp.where(yb, yf, xf | yf)
        s = jnp.where(yb, ys, xs)
        return (v, f, s, xb | yb)

    ans_k, ansf_k, start_k, _ = jax.lax.associative_scan(
        _seg_last, (val_k, flag_k, ks, brk)
    )
    off_k = ks - start_k

    # run tables (rcap capacity; host guarantees run count <= rcap).
    # Runs are CONTIGUOUS compact stretches: lengths from start diffs.
    rix = jnp.arange(rcap, dtype=i32)
    rsafe = jnp.clip(run_of_k, 0, rcap - 1)
    run_cnt = jnp.sum(brk.astype(i32))
    live_r = rix < run_cnt
    n_elems = jnp.sum(valid.astype(i32))
    run_start = (
        jnp.full(rcap + 1, 0, i32)
        .at[jnp.where(brk, rsafe, rcap)]
        .set(ks)[:rcap]
    )
    run_end = jnp.where(
        rix + 1 < run_cnt,
        jnp.concatenate([run_start[1:], jnp.zeros(1, i32)]),
        n_elems,
    )
    run_len = jnp.where(live_r, run_end - run_start, 0)

    # condensed sibling-climb: each run asks "A at my head's parent" —
    # answered within the parent's run prefix when a flagged member
    # exists, else inherited from THAT run's own climb (all rcap-sized)
    head_row = seq[jnp.clip(run_start, 0, P - 1)]
    par_head = jnp.where(live_r, er[jnp.clip(head_row, 0, P - 1)], NONE32)
    par_is_elem = par_head >= 0  # object-root parents (<0) end the climb
    pk = kpos[jnp.clip(par_head, 0, P - 1)]
    a_at_p = ans_k[pk]
    f_at_p = ansf_k[pk]
    prun = jnp.clip(run_of_k[pk], 0, rcap - 1)
    done_r = (~par_is_elem) | f_at_p
    ans_r = jnp.where(par_is_elem & f_at_p, a_at_p, NONE32)
    jump_r = jnp.where(par_is_elem, prun, rix)

    # static unroll: a flat HLO graph — fori_loop pays ~1ms/iteration of
    # launch overhead on this backend, dwarfing the tiny rcap-sized gathers
    for _ in range(_ceil_log2(rcap) + 1):
        take = (~done_r) & done_r[jump_r]
        ans_r = jnp.where(take, ans_r[jump_r], ans_r)
        done_r = done_r | take
        jump_r = jump_r[jump_r]

    # run successor: the tail's first child (a later run's head), else the
    # tail's climb answer (within-run prefix, else the run climb)
    tail_k = jnp.clip(run_start + run_len - 1, 0, P - 1)
    tail_row = seq[tail_k]
    fc_tail = first_child[jnp.clip(tail_row, 0, P - 1)]
    a_tail = jnp.where(ansf_k[tail_k], ans_k[tail_k], ans_r)
    nxt_row = jnp.where(live_r, jnp.where(fc_tail >= 0, fc_tail, a_tail), NONE32)
    succ_run = jnp.where(
        nxt_row >= 0,
        jnp.clip(run_of_k[kpos[jnp.clip(nxt_row, 0, P - 1)]], 0, rcap - 1),
        jnp.int32(rcap),
    )

    # Wyllie over runs, weights = run lengths; sentinel slot rcap = END
    dist_r = jnp.concatenate([jnp.where(live_r, run_len, 0), jnp.zeros(1, i32)])
    nxt_r = jnp.concatenate([succ_run, jnp.full(1, rcap, i32)])

    for _ in range(_ceil_log2(rcap) + 1):  # static unroll (see climb)
        dist_r = dist_r + dist_r[nxt_r]
        nxt_r = nxt_r[nxt_r]

    # broadcast each run's dist to its slots: scatter to head slots (rcap
    # writes), then carry-from-boundary with a segmented scan — no table
    # gather with full-width indices
    dist_at_head = (
        jnp.zeros(P + 1, i32)
        .at[jnp.where(live_r, jnp.clip(run_start, 0, P), P)]
        .set(dist_r[:rcap])[:P]
    )

    def _seg_carry(x, y):
        xv, xb = x
        yv, yb = y
        return (jnp.where(yb, yv, xv), xb | yb)

    dist_k, _ = jax.lax.associative_scan(_seg_carry, (dist_at_head, brk))

    # nodes from v (inclusive) to END: run dist minus offset; scatter the
    # per-slot value back to rows, then rank = T(object start) - T(v)
    t_slot = dist_k - off_k
    t_row = (
        jnp.zeros(P + 1, i32)
        .at[jnp.where(valid, seqc, P)]
        .set(t_slot)[:P]
    )
    if obj_cap is not None:
        # small static object table: T(start) per object via two tiny
        # gathers + ONE full-width table lookup
        roots = first_child[P : P + obj_cap + 2]
        t_start_obj = jnp.where(
            roots >= 0, t_row[jnp.clip(roots, 0, P - 1)], NONE32
        )
        t_start = t_start_obj[jnp.clip(c["obj_dense"], 0, obj_cap + 1)]
        return jnp.where(is_elem & (t_start >= 0), t_start - t_row, NONE32)
    start = first_child[P + c["obj_dense"]]
    startc = jnp.clip(start, 0, P - 1)
    return jnp.where(
        is_elem & (start >= 0), t_row[startc] - t_row, NONE32
    )


def condensed_caps(log) -> tuple:
    """(rcap, obj_cap) buckets for merge_kernel_condensed — routed through
    oplog._capacity, the ONE growth/bucket policy (shared with pad_columns
    and the packed transport) so a growing document reuses the compiled
    kernel for every size inside a bucket instead of retracing per row
    count."""
    rcap = _capacity(max(log.condensed_run_count(), 1), 32)
    obj_cap = _capacity(max(log.n_objs, 1), 16)
    return rcap, obj_cap


@functools.lru_cache(maxsize=None)
def merge_kernel_condensed(rcap: int, obj_cap: int = None):
    """jit'd all-device merge whose linearization condenses chains into at
    most ``rcap`` runs (one compiled kernel per (rcap, obj_cap) bucket).
    A static ``obj_cap`` also arms resolve_state's packed single-key
    winner sort."""

    def kernel(c):
        core = resolve_state(c, *succ_resolution(c), obj_cap=obj_cap)
        core["elem_index"] = device_linearize_condensed(c, core, rcap, obj_cap)
        return core

    return _program("merge_kernel", kernel)


# -- scatter-based resolution -------------------------------------------------
#
# The sort-free winner formulation (a sequence run's group id is its
# run-head row; map groups index a dense obj x prop table) measured ~1.45x
# faster than the sort-based resolve_state on a v5e at the 1024-replica
# fan-in (32.5ms vs 47ms for 376k ops), with bit-identical outputs. It
# needs static group-table geometry (n_objs, n_props from the OpLog), so
# callers that have it get this kernel and the sort path remains both the
# fallback and the geometry-free default. Same gate as the native host
# engine and the sharded path: the dense table must stay O(P)-ish.


def scatter_geom_key(n_objs: int, n_props: int):
    """Pow2-bucketed (n_objs2, n_props) geometry: a growing document must
    reuse compiled kernels (one per capacity bucket, like obj_cap/P), and a
    larger group table changes nothing — the gid mapping stays injective
    and every output is per-row or fixed-size."""
    return (_next_pow2(max(n_objs + 2, 16)), _next_pow2(max(n_props, 1)))


def scatter_geometry_ok(P: int, n_objs: int, n_props: int) -> bool:
    # evaluated on the BUCKETED geometry (scatter_geom_key) so the gate
    # bounds the actual compiled table, not the pre-bucket request
    n_objs2, np_eff = scatter_geom_key(n_objs, n_props)
    return n_objs2 * np_eff <= 8 * P + 65536


def forest(c):
    """Sibling forest (parent / first_child / next_sib), shared by the
    scatter kernel and the sharded path (parallel/sharding.py).

    first_child is a scatter-max (children order is descending row =
    descending Lamport, query/insert.rs); next_sib adjacency keeps one
    sort — a few percent of the merge."""
    P = c["action"].shape[0]
    rows = jnp.arange(P, dtype=jnp.int32)
    valid = c["action"] != PAD_ACTION
    insert = c["insert"]
    elem_ref = c["elem_ref"]
    obj_dense = c["obj_dense"]
    N = 2 * P + 3
    S = jnp.int32(N - 1)
    is_elem = insert & valid
    parent_row = jnp.where(
        is_elem,
        jnp.where(
            elem_ref == ELEM_HEAD,
            P + obj_dense,
            jnp.where(elem_ref >= 0, elem_ref, S),
        ),
        S,
    ).astype(jnp.int32)
    first_child = (
        jnp.full(N, NONE32, jnp.int32)
        .at[jnp.where(is_elem, parent_row, N - 1)]
        .max(jnp.where(is_elem, rows, NONE32))
    )
    sib_parent = jnp.where(is_elem, parent_row, jnp.int32(N))
    sp_s, neg_rows = jax.lax.sort((sib_parent, -rows), num_keys=2)
    sib_idx = -neg_rows
    nxt_same = jnp.concatenate([sp_s[1:] == sp_s[:-1], jnp.array([False])])
    nxt_row = jnp.concatenate([sib_idx[1:], jnp.array([-1], jnp.int32)])
    in_range = sp_s < N
    next_sib = (
        jnp.full(N, NONE32, jnp.int32)
        .at[jnp.where(in_range, sib_idx, N - 1)]
        .set(jnp.where(nxt_same & in_range, nxt_row, NONE32))
    )
    return is_elem, parent_row, first_child, next_sib


@_stage("resolve_state_scatter")
def resolve_state_scatter(c, succ_count, inc_count, counter_inc,
                          n_objs2: int, n_props: int):
    """Sort-free resolve_state: same output dict, winners via scatter-max/
    scatter-add over dense group ids."""
    P = c["action"].shape[0]
    G = P + 2 * n_objs2 + n_objs2 * n_props + 1
    rows = jnp.arange(P, dtype=jnp.int32)
    action = c["action"]
    valid = action != PAD_ACTION
    insert = c["insert"]
    elem_ref = c["elem_ref"]
    obj_dense = c["obj_dense"]
    prop = c["prop"]
    visible = visibility(c, succ_count, inc_count)

    run = jnp.where(insert, rows, elem_ref)
    seq_gid = jnp.where(
        run >= 0,
        run,
        P + obj_dense * 2 + jnp.where(elem_ref == ELEM_HEAD, 0, 1),
    )
    map_gid = P + 2 * n_objs2 + obj_dense * n_props + prop
    gid = jnp.where(prop >= 0, map_gid, seq_gid)
    gid = jnp.where(valid, gid, G - 1).astype(jnp.int32)
    win = (
        jnp.full(G, NONE32, jnp.int32)
        .at[gid]
        .max(jnp.where(visible, rows, NONE32))
    )
    cnt = jnp.zeros(G, jnp.int32).at[gid].add(visible.astype(jnp.int32))
    winner = jnp.where(valid, win[gid], NONE32)
    conflicts = jnp.where(valid, cnt[gid], 0)

    is_elem, parent_row, first_child, next_sib = forest(c)
    core = {
        "visible": visible,
        "counter_inc": counter_inc,
        "winner": winner,
        "conflicts": conflicts,
        "succ_count": succ_count,
        "inc_count": inc_count,
        "first_child": first_child,
        "next_sib": next_sib,
        "parent_row": parent_row,
        "is_elem": is_elem,
    }
    elem_vis = is_elem & (winner >= 0)
    obj_idx = jnp.where(valid, obj_dense, jnp.int32(P + 1))
    core["obj_vis_len"] = (
        jnp.zeros(P + 2, jnp.int32).at[obj_idx].add(elem_vis.astype(jnp.int32))
    )
    w_width = jnp.where(elem_vis, c["width"][jnp.clip(winner, 0, P - 1)], 0)
    core["obj_text_width"] = jnp.zeros(P + 2, jnp.int32).at[obj_idx].add(w_width)
    return core


_scatter_core_cache = {}


def scatter_kernel_core(n_objs: int, n_props: int):
    """Jitted geometry-specialized scatter-resolution kernel (no ranking)."""
    key = scatter_geom_key(n_objs, n_props)
    fn = _scatter_core_cache.get(key)
    if fn is None:
        n_objs2, np_eff = key

        def core(c):
            return resolve_state_scatter(
                c, *succ_resolution(c), n_objs2=n_objs2, n_props=np_eff
            )

        fn = _scatter_core_cache[key] = _program("merge_kernel_core", core)
    return fn


# -- packed transport ---------------------------------------------------------
#
# A document's own launch moves three arrays:
#   in : the padded columns plain, written once each into two int32 stacks
#        (plain_transport): (6, P) per-row columns, action/insert/
#        value_tag/covered bit-packed into one flags word, and (2, Q) pred
#        columns, so a program follows the shapes alone. Plain, because
#        run-encoding them (encode_transport, the mesh path's transport)
#        costs the host many passes over the rows to save a copy that
#        PCIe makes in a fraction of that time
#   out: one flat int32 vector, the requested per-row outputs concatenated;
#        boolean outputs bit-packed 32/word; per-object stats truncated to
#        a bucketed object capacity on device
# Linearization (elem_index) is computed HOST-side by host_linearize from
# the stacks it uploads, overlapped with the device kernel — element
# order depends only on the insert forest, so it needs neither the merge
# results nor any extra transfer. device_linearize remains for the
# pure-device flow (multi-chip dry run, no native core).


def conflict_flags(counts) -> np.ndarray:
    """``DeviceDoc.conflicts``' one encoding: 2 where a key has more
    than one visible op, else 1. The packed transport reads back only
    that flag, bit-packed; every path that resolves rows for a
    ``DeviceDoc`` (packed, the plain launch, the host delta resolution,
    the mesh) stores its exact visible-op counts through here, so a
    document compares exactly with its oracle whichever path resolved
    it. Readers compare ``> 1``. The dict path of ``merge_columns``
    still returns the exact count."""
    return np.where(np.asarray(counts) > 1, np.int32(2), np.int32(1))


_F_ACTION = 15
_F_INSERT = 1 << 4
_F_TAG_SHIFT = 5
_F_COVERED = 1 << 9


_OBJ_STATS = ("obj_vis_len", "obj_text_width")
# boolean / flag outputs travel as 32-bit bitmasks (1/32 the bytes)
_BIT_OUTPUTS = {"visible": None, "conflicts": 1}  # name -> "flag if > thresh"
# node-space outputs: [0,P) elements + [P,2P+2) object roots + sentinel
_NODE_OUTPUTS = ("first_child", "next_sib")

_P_ORDER = ("flags", "prop", "elem_ref", "obj_dense", "value_i32", "width")


_Q_ORDER = ("pred_src", "pred_tgt")


def _flags_column(cols) -> np.ndarray:
    return (
        cols["action"].astype(np.int32)
        | (cols["insert"].astype(np.int32) << 4)
        | (cols["value_tag"].astype(np.int32) << _F_TAG_SHIFT)
        | (cols["covered"].astype(np.int32) << 9)
    )


def _unflag(cols):
    """Replace ``cols``' flags word by the four columns it packs (numpy
    or traced arrays alike)."""
    flags = cols.pop("flags")
    cols["action"] = flags & _F_ACTION
    cols["insert"] = (flags & _F_INSERT) != 0
    cols["value_tag"] = (flags >> _F_TAG_SHIFT) & 15
    cols["covered"] = (flags & _F_COVERED) != 0
    return cols


def _slope_rle(x: np.ndarray):
    """Slope-RLE one column: x[i] == w[run(i)] + slope*i, or None.

    Slope candidates: 0, 1 and the modal first-difference — the latter
    catches the stride-N patterns Lamport row order produces when N
    replicas' same-counter ops interleave (elem_ref then steps by N).
    Returns (w, cum, slope) int32 arrays, or None when the column doesn't
    compress below n/8 runs (caller ships it as a plain column).
    """
    n = len(x)
    if n == 0:
        return None
    x64 = x.astype(np.int64)
    cands = [0, 1]
    if n > 2:
        d = np.diff(x64[: min(n, 1 << 16)])
        vals, counts = np.unique(d, return_counts=True)
        mode = int(vals[np.argmax(counts)])
        if mode not in cands and abs(mode) < (1 << 20):
            cands.append(mode)
    best = None
    idx = np.arange(n, dtype=np.int64)
    for s in cands:
        y = x64 - s * idx
        b = np.flatnonzero(y[1:] != y[:-1]) + 1
        if best is None or len(b) < len(best[2]):
            best = (s, y, b)
    s, y, b = best
    if len(b) + 1 > max(n // 8, 15):
        return None
    starts = np.concatenate([[0], b])
    w = y[starts]
    if w.size and (w.min() < -(1 << 31) or w.max() >= (1 << 31)):
        return None
    cum = np.concatenate([b, [n]])
    return w.astype(np.int32), cum.astype(np.int32), s


_SCAN_BLOCK = 1024


def _expand_runs(W, C, S, n):
    """Decode a stack of K ``_slope_rle`` run tables on device: (K, R)
    run values ``W`` and cumulative ends ``C`` (padded with ``C == n``),
    per-table slopes ``S`` (None: every slope is 0) -> (K, n) int32 rows,
    row i of table k being ``W[k, r] + S[k] * i`` for the run r that
    holds i.

    Each run's step from the run before is written at the run's first
    row, and a prefix sum over the rows carries it forward: one scatter
    of K*R entries and one scan, with no search over the runs and no
    gather over the rows. The steps may wrap in int32; the wrapped sum
    is still the exact value. Padded entries start past the last row,
    so they either drop or land in the rows cut off at the end.

    The sum runs in blocks of ``_SCAN_BLOCK`` rows, then over the
    blocks' totals: the TPU compiler takes seconds to compile one scan
    along a row of 262k, and a fraction of that for the two short
    ones."""
    K, R = W.shape
    nb = -(-n // _SCAN_BLOCK)
    zero = jnp.zeros((K, 1), jnp.int32)
    starts = jnp.concatenate([zero, C[:, :-1]], axis=1)
    # padded entries would all start at n: give each its own index past
    # the end, so the scatter's indices stay unique
    starts = jnp.where(starts < n, starts, n + jnp.arange(R, dtype=jnp.int32))
    steps = W - jnp.concatenate([zero, W[:, :-1]], axis=1)
    k = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None], (K, R))
    heads = jnp.zeros((K, nb, _SCAN_BLOCK), jnp.int32).at[
        k, starts // _SCAN_BLOCK, starts % _SCAN_BLOCK
    ].add(steps, mode="drop", indices_are_sorted=True, unique_indices=True)
    inner = jax.lax.cumsum(heads, axis=2)
    total = inner[:, :, -1]
    before = jax.lax.cumsum(total, axis=1) - total
    rows = (inner + before[:, :, None]).reshape(K, -1)[:, :n]
    if S is not None:
        rows = rows + S[:, None] * jnp.arange(n, dtype=jnp.int32)
    return rows


def _note_h2d(actual: int, dense: int) -> None:
    """Byte accounting every H2D site shares: the counter pair the
    perf-report ratio line reads, plus the cycle profiler notes."""
    from ..obs import prof as _prof

    obs.count("device.h2d_bytes", n=actual)
    obs.count("device.h2d_dense_bytes", n=dense)
    _prof.note("h2d_bytes", actual)
    _prof.note("h2d_dense_bytes", dense)


def _resolution_body(geom):
    """The resolution body for one geometry key: ``("core", obj_cap)`` =
    resolve_state (its group sort packs into one int32 key when the
    bucketed object count allows — a 3-key sort took the TPU compiler
    ~45 s at 32k rows, a 1-key one ~15 s), ``("full", obj_cap)`` = the
    same plus on-device linearization, ``("scatter", n_objs2, n_props)``
    = the bucketed geometry of the scatter-max winner kernel."""
    if geom[0] == "scatter":
        return lambda c: resolve_state_scatter(
            c, *succ_resolution(c), n_objs2=geom[1], n_props=geom[2]
        )

    def body(c):
        core = resolve_state(c, *succ_resolution(c), obj_cap=geom[1])
        if geom[0] == "full":
            core["elem_index"] = device_linearize(c, core)
        return core

    return body


# the outputs the resolution launches return unless a caller asks for
# more: what DeviceDoc reads and the batched scatter consume
RESOLVE_FETCH = (
    "visible", "winner", "conflicts", "obj_vis_len", "obj_text_width",
)

_RESOLUTION_CACHE = {}


def geometry_kernel(geom, fetch=RESOLVE_FETCH):
    """The jit'd resolution kernel over plain columns for one geometry
    (``_resolution_body``), one compiled program per (geom, fetch). It
    returns only the ``fetch`` outputs, so XLA drops what nobody reads:
    returning all of them kept the sibling-forest sort alive and took
    the TPU compiler ~29 s at 262k rows, against ~2 s without it."""
    key = (geom, fetch)
    fn = _RESOLUTION_CACHE.get(key)
    if fn is None:
        core = _resolution_body(geom)

        def resolve(c):
            out = core(c)
            return {k: out[k] for k in fetch}

        name = "merge_kernel" if geom[0] == "full" else "merge_kernel_core"
        fn = _RESOLUTION_CACHE[key] = _program(name, resolve)
    return fn


def _obj_cap(n_objs, P: int) -> int:
    """Bucketed per-object table size (stats truncation, sort-key packing)."""
    return min(_capacity((n_objs or P) + 2, 16), P + 2)


def resolution_geom(P: int, n_objs=None, n_props=None, full=False):
    """The geometry key ``prepare_resolution`` compiles against."""
    if full:
        return ("full", _obj_cap(n_objs, P))
    if (
        n_objs is not None
        and n_props is not None
        and scatter_geometry_ok(P, n_objs, n_props)
    ):
        return ("scatter", *scatter_geom_key(n_objs, n_props))
    return ("core", _obj_cap(n_objs, P))


def prepare_resolution(cols_np, n_objs=None, n_props=None, full=False,
                       fetch=RESOLVE_FETCH):
    """Upload bucket-padded dict columns plain for one resolution launch
    (a ``device.h2d`` span) and return a zero-arg dispatch closure.

    The closure enqueues the launch inside a ``device.kernel`` span and
    returns without waiting: the span times the dispatch, and the
    device's time shows in the ``device.wait`` span of the ``readback``
    that collects it. Its ``program`` field says which body ran:
    ``scatter`` (the scatter-max winner kernel, where the geometry gate
    allows) or ``dense`` (the sort-based core; ``full=True`` pins it
    with on-chip linearization). The launch returns the ``fetch``
    outputs only. Plain columns make the program follow the columns'
    shapes alone, so a set-up that met a launch's shapes has compiled
    its program."""
    cols_np = {k: np.asarray(v) for k, v in cols_np.items()}
    P = len(cols_np["action"])
    geom = resolution_geom(P, n_objs, n_props, full)
    nbytes = sum(v.nbytes for v in cols_np.values())
    with obs.span("device.h2d", rows=P, bytes=nbytes):
        cols = {k: jnp.asarray(v) for k, v in cols_np.items()}
    _note_h2d(nbytes, nbytes)
    fn = geometry_kernel(geom, tuple(fetch))
    program = "scatter" if geom[0] == "scatter" else "dense"

    def dispatch():
        with obs.span("device.kernel", rows=P, program=program):
            return fn(cols)

    return dispatch


def plain_transport(cols, n_objs: int) -> dict:
    """The per-document launch's input: ``cols`` (raw or already padded)
    padded to their buckets with ``pad_columns``' fills, ``obj_dense``
    with ``n_objs``, each written once into an int32 stack: ``P``, (6, P)
    in ``_P_ORDER``, and ``Q``, (2, Q) in ``_Q_ORDER``."""
    fills = dict(PAD_FILLS, obj_dense=n_objs)
    fills["flags"] = int(_flags_column(
        {k: np.asarray(v) for k, v in fills.items()}))
    cols = {k: np.asarray(v) for k, v in cols.items()}
    cols["flags"] = _flags_column(cols)
    stacks = {}
    for name, order, n in (("P", _P_ORDER, len(cols["action"])),
                           ("Q", _Q_ORDER, len(cols["pred_src"]))):
        out = stacks[name] = np.empty((len(order), _capacity(n)), np.int32)
        for i, k in enumerate(order):
            out[i, :n] = cols[k]
            out[i, n:] = fills[k]
    return stacks


def encode_transport(cols) -> tuple:
    """Choose per column between slope-RLE runs and plain transfer: the
    mesh path's transport (parallel/sharding.py).

    The op columns are extremely runny in real workloads (typing runs give
    ``elem_ref[i] = i-1`` or stride-N interleaves, long spans share one
    object/action/width), so most of the input compresses to a few KB.
    Runs are decoded on device by ``_expand_runs``, one call per stack.

    Returns (static_key, arrays) where ``static_key`` identifies the jit
    variant (which columns are plain) and ``arrays`` is the input pytree.
    """
    p_sources = dict(cols, flags=_flags_column(cols))
    groups = {
        "P": {k: p_sources[k].astype(np.int32) for k in _P_ORDER},
        "Q": {k: cols[k].astype(np.int32) for k in _Q_ORDER},
    }
    arrays = {}
    plain_names = []
    for gname, group in groups.items():
        length = len(next(iter(group.values())))
        encs = {}
        for k, x in group.items():
            e = _slope_rle(x)
            if e is None:
                plain_names.append(k)
            else:
                encs[k] = e
        if encs:
            r_cap = _next_pow2(max(max(len(w) for w, _, _ in encs.values()), 16))
            names = tuple(encs)
            W = np.zeros((len(encs), r_cap), np.int32)
            C = np.full((len(encs), r_cap), np.int32(length), np.int32)
            S = np.empty(len(encs), np.int32)
            for i, k in enumerate(names):
                w, cum, s = encs[k]
                W[i, : len(w)] = w
                C[i, : len(cum)] = cum
                S[i] = s
            arrays[f"w{gname}"] = W
            arrays[f"c{gname}"] = C
            arrays[f"s{gname}"] = S
        plain = [k for k in group if k not in encs]
        if plain:
            arrays[f"plain{gname}"] = np.stack([group[k] for k in plain])
    run_namesP = tuple(k for k in groups["P"] if k not in plain_names)
    run_namesQ = tuple(k for k in groups["Q"] if k not in plain_names)
    plainP = tuple(k for k in groups["P"] if k in plain_names)
    plainQ = tuple(k for k in groups["Q"] if k in plain_names)
    return (run_namesP, plainP, run_namesQ, plainQ), arrays


def count_transport(static_key) -> None:
    """Count one packed-transport launch's columns: those decoded from
    runs on the device (``device.runs_expanded{path=packed}``) and each
    shipped plain (``oplog.compress_fallback{column,reason=transport}``)."""
    run_namesP, plainP, run_namesQ, plainQ = static_key
    obs.count("device.runs_expanded", n=len(run_namesP) + len(run_namesQ),
              labels={"path": "packed"})
    for k in plainP + plainQ:
        obs.count("oplog.compress_fallback",
                  labels={"column": k, "reason": "transport"})


@_stage("transport_unpack")
def _unpack_transport(static_key, arrays, P, Q):
    run_namesP, plainP, run_namesQ, plainQ = static_key
    cols = {}
    for gname, run_names, plain_names, n in (
        ("P", run_namesP, plainP, P),
        ("Q", run_namesQ, plainQ, Q),
    ):
        if run_names:
            runs = _expand_runs(
                arrays[f"w{gname}"], arrays[f"c{gname}"],
                arrays[f"s{gname}"], n,
            )
            for i, k in enumerate(run_names):
                cols[k] = runs[i]
        for i, k in enumerate(plain_names):
            cols[k] = arrays[f"plain{gname}"][i]
    return _unflag(cols)


@_stage("transport_unpack")
def _unstack(arrays):
    """``plain_transport``'s stacks back into the kernel's columns."""
    cols = dict(zip(_P_ORDER, arrays["P"]))
    cols.update(zip(_Q_ORDER, arrays["Q"]))
    return _unflag(cols)


def _bitpack(v):
    """(P,) bool -> (P/32,) int32 bitmask (P is a multiple of 16)."""
    P = v.shape[0]
    pad = (-P) % 32
    b = jnp.pad(v.astype(jnp.uint32), (0, pad)).reshape(-1, 32)
    words = (b << jnp.arange(32, dtype=jnp.uint32)).sum(axis=1).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _bitunpack(words, P):
    bits = np.unpackbits(
        np.asarray(words, np.int32).view(np.uint8), bitorder="little"
    )
    return bits[:P].astype(bool)


@_stage("emit")
def _emit(core, fetch, obj_cap):
    """Concatenate the requested outputs into one int32 transfer vector."""
    outs = []
    for k in fetch:
        v = core[k]
        if k in _BIT_OUTPUTS:
            thresh = _BIT_OUTPUTS[k]
            flag = v if thresh is None else v > thresh
            outs.append(_bitpack(flag))
            continue
        v = v.astype(jnp.int32)
        if k in _OBJ_STATS:
            v = v[:obj_cap]
        outs.append(v.reshape(-1))
    return jnp.concatenate(outs)


def _packed_fn(fetch, obj_cap, scatter_geom=None):
    """The per-document resolution program: ``plain_transport``'s two
    stacks in, one flat int32 vector out."""
    def packed(arrays):
        c = _unstack(arrays)
        if scatter_geom is not None:
            core = resolve_state_scatter(
                c, *succ_resolution(c),
                n_objs2=scatter_geom[0], n_props=scatter_geom[1],
            )
        else:
            core = resolve_state(c, *succ_resolution(c), obj_cap=obj_cap)
        if "elem_index" in fetch:
            core["elem_index"] = device_linearize(c, core)
        return _emit(core, fetch, obj_cap)

    name = "merge_kernel" if "elem_index" in fetch else "merge_kernel_core"
    return _program(name, packed)


from .oplog import host_linearize  # noqa: F401  (moved: jax-free)


_packed_cache = {}


def _split_flat(flat, fetch, P, obj_cap):
    out = {}
    pos = 0
    words = (P + 31) // 32
    for k in fetch:
        if k in _BIT_OUTPUTS:
            v = _bitunpack(flat[pos : pos + words], P)
            pos += words
            if k == "conflicts":
                # the bit says "more than one visible op": a count of 2
                v = conflict_flags(v * 2)
        else:
            if k in _OBJ_STATS:
                size = obj_cap
            elif k in _NODE_OUTPUTS:
                size = 2 * P + 3
            else:
                size = P
            v = flat[pos : pos + size]
            pos += size
            if k == "is_elem":
                v = v.astype(bool)
        out[k] = v
    return out


def _packed_merge(cols_np, fetch, n_objs, n_props, pad_obj):
    from .. import native

    P = _capacity(len(cols_np["action"]))
    Q = _capacity(len(cols_np["pred_src"]))
    obj_cap = _obj_cap(n_objs, P)
    fetch = tuple(fetch)
    scatter_geom = (
        scatter_geom_key(n_objs, n_props)
        if n_objs is not None
        and n_props is not None
        and scatter_geometry_ok(P, n_objs, n_props)
        else None
    )

    # element order never needs the device (host_linearize): computing it
    # host-side while the kernel runs removes the two pointer-doubling
    # gather loops (the kernel's dominant cost) AND 4 B/op of readback
    # keep elem_index on device when it is the ONLY fetch (an explicitly
    # forced packed transport should exercise the device); otherwise rank
    # it host-side overlapped with the kernel
    host_elem = (
        "elem_index" in fetch and len(fetch) > 1 and native.preorder_available()
    )
    dev_fetch = (
        tuple(k for k in fetch if k != "elem_index") if host_elem else fetch
    )

    key = (dev_fetch, obj_cap, P, Q, scatter_geom)
    fn = _packed_cache.get(key)
    if fn is None:
        fn = _packed_cache[key] = _packed_fn(dev_fetch, obj_cap, scatter_geom)
    nbytes = 4 * (len(_P_ORDER) * P + len(_Q_ORDER) * Q)
    with obs.span("device.h2d", rows=P, bytes=nbytes):
        arrays = plain_transport(cols_np, pad_obj)
        arrays_dev = {k: jnp.asarray(v) for k, v in arrays.items()}
    _note_h2d(nbytes, nbytes)
    with obs.span("device.kernel", rows=P, program="packed"):
        flat_dev = fn(arrays_dev)  # async dispatch
    # ranked from the rows the device got, decoded as it decodes them
    elem_index = (
        host_linearize(_unflag(dict(zip(_P_ORDER, arrays["P"]))))
        if host_elem else None
    )
    with readback(flat_dev, P):
        flat = np.asarray(flat_dev)
    with obs.span("device.materialize", rows=P):
        out = _split_flat(flat, dev_fetch, P, obj_cap)
    if host_elem:
        out["elem_index"] = elem_index
    return out


ALL_OUTPUTS = (
    "visible", "counter_inc", "winner", "conflicts", "succ_count",
    "inc_count", "first_child", "next_sib", "parent_row", "is_elem",
    "obj_vis_len", "obj_text_width", "elem_index",
)


def merge_columns(cols_np, linearize: str = "auto", fetch=None, n_objs=None,
                  n_props=None):
    """Host entry: numpy columns in, numpy resolution out.

    ``linearize``: "device" (all on chip), "native" (C++ preorder walk),
    or "auto" (native when available — the ranking pass's random gathers
    are a poor fit for TPU, see device_linearize).

    ``fetch`` selects which output arrays are brought back to the host
    (default: all); read paths request only what they consume.
    ``n_objs`` (when given) truncates the per-object stats to the live
    object count before transfer. ``n_props`` (with ``n_objs``) supplies
    the static group-table geometry that selects the faster sort-free
    scatter resolution (resolve_state_scatter) on the device paths;
    without it the sort-based kernel runs.

    Transport, chosen from the arguments alone and the same on every
    backend: the packed path (two plain stacks in, one array out — see
    "packed transport" above) whenever ``fetch`` is given and
    ``linearize`` is left on "auto"; the dict path otherwise
    (``fetch=None`` or an explicit ``linearize`` pin), which stays the
    reference. The packed path returns ``conflicts`` as
    ``conflict_flags`` (1 or 2), the dict path the exact visible-op
    count.
    """
    from .. import native

    # pure-linearization calls never need a device at all (element order is
    # a host computation); shortcut before anything touches the jax backend.
    # An explicit linearize="device" pin (the pure-device/dry-run flow)
    # still runs on chip.
    if (
        fetch is not None
        and set(fetch) == {"elem_index"}
        and linearize in ("auto", "native")
        and native.preorder_available()
    ):
        return {"elem_index": host_linearize(cols_np)}

    # the jit kernels need bucket-padded shapes; callers may hand over the
    # raw (unpadded) columns dict (idempotent for padded input)
    from .oplog import pad_columns

    n_objs_eff = (
        n_objs
        if n_objs is not None
        else (
            int(np.asarray(cols_np["obj_dense"]).max()) + 1
            if len(cols_np["action"])
            else 1
        )
    )
    if fetch is not None and linearize == "auto":
        # pads as it stacks the columns for the upload
        return _packed_merge(cols_np, fetch, n_objs, n_props, n_objs_eff)
    cols_np = pad_columns(cols_np, n_objs_eff)

    if linearize == "auto":
        linearize = "native" if native.preorder_available() else "device"
    need = set(fetch) if fetch is not None else set(ALL_OUTPUTS)

    def pull(out, keys):
        host = {}
        with readback(out, len(cols_np["action"])):
            for k in keys:
                v = out[k]
                if k in ("obj_vis_len", "obj_text_width") and n_objs is not None:
                    v = v[: n_objs + 2]
                host[k] = np.asarray(v)
        return host

    if linearize == "native":
        # the plain upload happens here, outside the kernel span; the
        # closure dispatches the geometry's kernel
        dispatch = prepare_resolution(
            cols_np, n_objs, n_props,
            fetch=tuple(
                k for k in ALL_OUTPUTS if k in need and k != "elem_index"
            ),
        )
        out = dispatch()
        host = pull(out, need - {"elem_index"})
        if "elem_index" in need:
            # ranked from the host-resident columns — zero device traffic
            host["elem_index"] = host_linearize(cols_np)
        return host
    dispatch = prepare_resolution(
        cols_np, n_objs_eff, full=True,
        fetch=tuple(k for k in ALL_OUTPUTS if k in need),
    )
    return pull(dispatch(), need)
