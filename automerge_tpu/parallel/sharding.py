"""Multi-chip merge: shard the op-log merge over a jax.sharding.Mesh.

The reference's "distribution" is logical (actors + the sync protocol,
reference: rust/automerge/src/sync.rs); its compute is single-threaded. On
TPU every phase of the merge scales across chips:

  1. succ resolution — the pred stream is split across the mesh, every
     device scatter-adds its slice into full-size counter arrays, one
     ``psum`` over ICI combines them (the collective analogue of the
     reference's per-op ``add_succ``, op_set.rs:194-203).
  2. visibility — elementwise, replicated (cheaper than communicating it).
  3. per-key winners — NO sort: a sequence run's group id is the run-head
     insert row itself and map groups index a dense (obj x prop) table, so
     each device scatter-max/adds its ROW SLICE into group-id arrays and
     one ``pmax``/``psum`` pair merges them. This is what makes the
     resolution phase itself shard (round-2 sharded only the pred
     scatter); the sort-based formulation (ops/merge.py resolve_state)
     remains the fallback when the map-group table would be too large.
  4. RGA linearization — the sibling forest builds with scatters (first
     child = max-row child; next sibling = each child pointing its
     predecessor, derived from one replicated sort kept for adjacency);
     the pointer-doubling threading + Wyllie ranking loops — the dominant
     cost on a single chip — run SHARDED: each device advances its node
     slice and an ``all_gather`` re-replicates state between doubling
     steps (O(log n) steps, compute per step P/n).

Scaling model (How-to-Scale style): phases 1+3 are scatter-bound with
per-device cost (Q+P)/n plus P-sized all-reduces; phase 4 is
gather-latency-bound with per-device cost (P log P)/n plus log P
all-gathers. All collectives ride the mesh axis (ICI on real chips).

The packed transport (ops/merge.py encode_transport) runs through this
path too: runs are decoded on device inside the shard_map body, so the
host ships a few KB per column, not columns.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..obs import prof as _prof
from ..ops.merge import (
    NONE32,
    _ceil_log2,
    _unpack_transport,
    encode_transport,
    forest as _forest,
    resolve_state,
    succ_resolution,
    visibility,
)
from ..ops.oplog import ELEM_HEAD, PAD_ACTION

AXIS = "shard"


def default_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` available devices."""
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devs)} "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N for a "
                "virtual CPU mesh)"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (AXIS,))


# column -> partition spec: the pred stream splits along the mesh axis, op
# columns are replicated (single source of truth for in_specs + device_put).
# Row WORK is sharded by slicing inside the body, so replicated columns do
# not serialize the resolution phases.
COLUMN_SPECS = {
    "action": P(),
    "insert": P(),
    "prop": P(),
    "elem_ref": P(),
    "obj_dense": P(),
    "value_tag": P(),
    "value_i32": P(),
    "width": P(),
    "covered": P(),
    "pred_src": P(AXIS),
    "pred_tgt": P(AXIS),
}
# (the "aorder" column is opt-in for the single-device condensed kernel
# only — OpLog.columns() excludes it by default, so the sharded specs
# never see it; its own condensation is chain-based)

def _sharded_winners(c, visible, Pl, n_objs2, n_props, G):
    """Scatter-based per-key winners, row-sliced per device.

    Group-id space: [0,P) seq runs (run-head row), then per-object
    HEAD/missing sentinel groups, then the dense (obj x prop) map table,
    then one trash slot for pad rows. Winner = pmax of per-shard
    scatter-max of visible global rows; conflicts = psum of counts.
    """
    Ptot = c["action"].shape[0]
    i0 = jax.lax.axis_index(AXIS) * Pl

    def sl(x):
        return jax.lax.dynamic_slice_in_dim(x, i0, Pl)

    rows_l = i0 + jnp.arange(Pl, dtype=jnp.int32)
    action_l = sl(c["action"])
    valid_l = action_l != PAD_ACTION
    insert_l = sl(c["insert"])
    elem_l = sl(c["elem_ref"])
    obj_l = sl(c["obj_dense"])
    prop_l = sl(c["prop"])
    vis_l = sl(visible)

    run_l = jnp.where(insert_l, rows_l, elem_l)
    seq_gid = jnp.where(
        run_l >= 0,
        run_l,
        Ptot + obj_l * 2 + jnp.where(elem_l == ELEM_HEAD, 0, 1),
    )
    map_gid = Ptot + 2 * n_objs2 + obj_l * n_props + prop_l
    gid = jnp.where(prop_l >= 0, map_gid, seq_gid)
    gid = jnp.where(valid_l, gid, G - 1).astype(jnp.int32)

    win = (
        jnp.full(G, NONE32, jnp.int32)
        .at[gid]
        .max(jnp.where(vis_l, rows_l, NONE32))
    )
    cnt = jnp.zeros(G, jnp.int32).at[gid].add(vis_l.astype(jnp.int32))
    win = jax.lax.pmax(win, AXIS)
    cnt = jax.lax.psum(cnt, AXIS)

    winner_l = jnp.where(valid_l, win[gid], NONE32)
    conflicts_l = jnp.where(valid_l, cnt[gid], 0)
    winner = jax.lax.all_gather(winner_l, AXIS, tiled=True)
    conflicts = jax.lax.all_gather(conflicts_l, AXIS, tiled=True)

    # per-object stats from the local slice (obj arrays sized P+2 to match
    # resolve_state's layout)
    is_elem_l = insert_l & valid_l
    elem_vis_l = is_elem_l & (winner_l >= 0)
    w_width_l = jnp.where(
        elem_vis_l, c["width"][jnp.clip(winner_l, 0, Ptot - 1)], 0
    )
    obj_idx_l = jnp.where(valid_l, obj_l, jnp.int32(Ptot + 1))
    obj_vis_len = jax.lax.psum(
        jnp.zeros(Ptot + 2, jnp.int32)
        .at[obj_idx_l]
        .add(elem_vis_l.astype(jnp.int32)),
        AXIS,
    )
    obj_text_width = jax.lax.psum(
        jnp.zeros(Ptot + 2, jnp.int32).at[obj_idx_l].add(w_width_l), AXIS
    )
    return winner, conflicts, obj_vis_len, obj_text_width


def _sharded_linearize(c, is_elem, parent_row, first_child, next_sib, Pl):
    """Document-order ranking with SHARDED doubling steps.

    Same algorithm as ops/merge.py device_linearize (threaded successors by
    pointer doubling + Wyllie list ranking) but each device advances only
    its slice of the state arrays per step and an all_gather re-replicates
    them — per-step compute drops to P/n gathers, comms is O(P) per step
    over the mesh axis.
    """
    Ptot = c["action"].shape[0]
    E = Ptot + 1
    SE = jnp.int32(Ptot)
    elem_ref = c["elem_ref"]
    next_sib_e = jnp.concatenate([next_sib[:Ptot], jnp.array([-1], jnp.int32)])
    fc_e = jnp.concatenate(
        [jnp.minimum(first_child[:Ptot], SE + 1), jnp.array([-1], jnp.int32)]
    )
    fc_e = jnp.where(fc_e > SE, NONE32, fc_e)
    parent_e = jnp.concatenate(
        [
            jnp.where(is_elem & (elem_ref >= 0), elem_ref, SE),
            jnp.array([Ptot], jnp.int32),
        ]
    ).astype(jnp.int32)
    is_elem_e = jnp.concatenate([is_elem, jnp.array([False])])
    has_sib = next_sib_e != NONE32
    done = has_sib | ~is_elem_e | (parent_e == SE)
    ans = jnp.where(has_sib & is_elem_e, next_sib_e, NONE32)
    jump = parent_e

    # element-space slices: E = P + 1, so row-slice length Pl would leave
    # the sentinel uncovered (n*Pl = P < E). Element space gets its own
    # slice length El = Pl + 1; arrays pad to n*El and padding entries are
    # fixed points of both loops (done=True / dist=0, nxt=SE), so covering
    # them is harmless.
    n_sh = Ptot // Pl
    El = Pl + 1
    Epad = n_sh * El
    i0 = jax.lax.axis_index(AXIS) * El

    def pad_e(x, fill):
        return jnp.concatenate([x, jnp.full(Epad - E, fill, x.dtype)])

    def sl(x):
        return jax.lax.dynamic_slice_in_dim(x, i0, El)

    def regather(x_l):
        return jax.lax.all_gather(x_l, AXIS, tiled=True)

    # thread: resolve next-sibling-of-nearest-ancestor by doubling
    ansP, doneP, jumpP = pad_e(ans, NONE32), pad_e(done, True), pad_e(jump, SE)

    def _thread(_, st):
        ansF, doneF, jumpF = st
        a_l, d_l, j_l = sl(ansF), sl(doneF), sl(jumpF)
        take = (~d_l) & doneF[j_l]
        a_l = jnp.where(take, ansF[j_l], a_l)
        d_l = d_l | take
        j_l = jumpF[j_l]
        return regather(a_l), regather(d_l), regather(j_l)

    ansP, doneP, jumpP = jax.lax.fori_loop(
        0, _ceil_log2(E) + 1, _thread, (ansP, doneP, jumpP)
    )
    ans = ansP[:E]

    succ_e = jnp.where(fc_e != NONE32, fc_e, ans)
    nxt = jnp.where(succ_e < 0, SE, succ_e)
    nxt = nxt.at[SE].set(SE)
    dist = jnp.where(jnp.arange(E, dtype=jnp.int32) == SE, 0, 1).astype(jnp.int32)
    distP, nxtP = pad_e(dist, 0), pad_e(nxt, SE)

    def _rank(_, st):
        dF, nF = st
        d_l, n_l = sl(dF), sl(nF)
        d_l = d_l + dF[n_l]
        n_l = nF[n_l]
        return regather(d_l), regather(n_l)

    distP, nxtP = jax.lax.fori_loop(0, _ceil_log2(E) + 1, _rank, (distP, nxtP))
    dist = distP[:E]
    rows = jnp.arange(Ptot, dtype=jnp.int32)
    start = first_child[Ptot + c["obj_dense"]]
    start_c = jnp.clip(start, 0, Ptot - 1)
    return jnp.where(
        is_elem & (start >= 0), dist[start_c] - dist[rows], NONE32
    )


def _sharded_linearize_condensed(c, cond, Pl, Rl):
    """Document-order ranking over the chain-CONDENSED graph.

    The host collapses first-child chains (native/condense.cpp) to R
    chains; preorder is chain-to-chain (a non-first child is always a
    chain head), so both iterative phases — the ancestor climb and the
    Wyllie ranking — run over R-sized arrays. Per doubling step each
    device advances its R/n slice and all_gathers O(R), not O(P): the
    collective volume follows the CONDENSED problem size (VERDICT r3
    item 7). Expansion back to element ranks is elementwise on P/n
    slices with ONE final P-sized all_gather.
    """
    Ptot = c["action"].shape[0]
    R2 = cond["tail_ans"].shape[0]
    SC = jnp.int32(R2 - 1)  # sentinel chain slot (len 0, self-loop)
    i0 = jax.lax.axis_index(AXIS) * Rl

    def slr(x):
        return jax.lax.dynamic_slice_in_dim(x, i0, Rl)

    def regather(x_l):
        return jax.lax.all_gather(x_l, AXIS, tiled=True)

    cpar = cond["cpar"]
    centry = cond["centry"]
    tail_ans = cond["tail_ans"]
    # climb: first non-missing centry along the cpar chain, starting at
    # the chain itself; chains whose parent is a root terminate with NONE
    done0 = (centry != NONE32) | (cpar == NONE32)
    ans0 = jnp.where(centry != NONE32, centry, NONE32)
    jump0 = jnp.where(cpar == NONE32, jnp.arange(R2, dtype=jnp.int32), cpar)

    def _climb(_, st):
        ansF, doneF, jumpF = st
        a_l, d_l, j_l = slr(ansF), slr(doneF), slr(jumpF)
        take = (~d_l) & doneF[j_l]
        a_l = jnp.where(take, ansF[j_l], a_l)
        d_l = d_l | take
        j_l = jumpF[j_l]
        return regather(a_l), regather(d_l), regather(j_l)

    ans, _, _ = jax.lax.fori_loop(
        0, _ceil_log2(R2) + 1, _climb, (ans0, done0, jump0)
    )
    # A(tail): the within-chain answer wins; else the resolved climb
    a_elem = jnp.where(tail_ans != NONE32, tail_ans, ans)
    # condensed successor: A targets are always chain heads
    cnxt = jnp.where(
        a_elem >= 0, cond["chain_id"][jnp.clip(a_elem, 0, Ptot - 1)], SC
    ).astype(jnp.int32)
    cnxt = cnxt.at[SC].set(SC)
    cdist = cond["clen"].astype(jnp.int32)

    def _rank(_, st):
        dF, nF = st
        d_l, n_l = slr(dF), slr(nF)
        d_l = d_l + dF[n_l]
        n_l = nF[n_l]
        return regather(d_l), regather(n_l)

    cdist, cnxt = jax.lax.fori_loop(
        0, _ceil_log2(R2) + 1, _rank, (cdist, cnxt)
    )

    # expansion: element rank from (chain rank, in-chain offset)
    ip = jax.lax.axis_index(AXIS) * Pl

    def slp(x):
        return jax.lax.dynamic_slice_in_dim(x, ip, Pl)

    cid_l = slp(cond["chain_id"])
    off_l = slp(cond["offset"])
    obj_l = slp(c["obj_dense"])
    is_elem_l = slp(c["insert"]) & (slp(c["action"]) != PAD_ACTION)
    start_l = cond["start_chain"][obj_l]
    dist_l = cdist[jnp.clip(cid_l, 0, R2 - 1)] - off_l
    dstart_l = cdist[jnp.clip(start_l, 0, R2 - 1)]
    rank_l = jnp.where(
        is_elem_l & (cid_l >= 0) & (start_l >= 0), dstart_l - dist_l, NONE32
    )
    return jax.lax.all_gather(rank_l, AXIS, tiled=True)


def _sharded_merge(c, Pl, n_objs2, n_props, G, use_scatter, cond=None, Rl=0):
    """shard_map body: every phase sharded (see module docstring)."""
    partial_counts = succ_resolution(c)
    succ_count, inc_count, counter_inc = (
        jax.lax.psum(x, AXIS) for x in partial_counts
    )
    if use_scatter:
        visible = visibility(c, succ_count, inc_count)
        winner, conflicts, obj_vis_len, obj_text_width = _sharded_winners(
            c, visible, Pl, n_objs2, n_props, G
        )
        is_elem, parent_row, first_child, next_sib = _forest(c)
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
            "obj_vis_len": obj_vis_len,
            "obj_text_width": obj_text_width,
        }
    else:
        # map-group table too large for the dense gid space: replicated
        # sort-based resolution (the round-2 shape), sharded scatter only
        core = resolve_state(c, succ_count, inc_count, counter_inc)
        is_elem = core["is_elem"]
        parent_row = core["parent_row"]
        first_child = core["first_child"]
        next_sib = core["next_sib"]
    if cond is not None:
        core["elem_index"] = _sharded_linearize_condensed(c, cond, Pl, Rl)
    else:
        core["elem_index"] = _sharded_linearize(
            c, is_elem, parent_row, first_child, next_sib, Pl
        )
    return core


@lru_cache(maxsize=None)
def _make_sharded_fn(
    mesh: Mesh, Ptot: int, n_objs2: int, n_props: int, packed_key,
    R2: int = 0,
):
    n = mesh.devices.size
    Pl = Ptot // n
    n_props_eff = max(n_props, 1)
    G = Ptot + 2 * n_objs2 + n_objs2 * n_props_eff + 1
    use_scatter = n_objs2 * n_props_eff <= 8 * Ptot + 65536
    if not use_scatter:
        G = Ptot + 1  # unused
    Rl = R2 // n
    cond_specs = (
        {
            "chain_id": P(), "offset": P(), "tail_ans": P(), "cpar": P(),
            "centry": P(), "clen": P(), "start_chain": P(),
        }
        if R2
        else None
    )

    if packed_key is None:

        def body(cols, *cond_arg):
            return _sharded_merge(
                cols, Pl=Pl, n_objs2=n_objs2, n_props=n_props_eff, G=G,
                use_scatter=use_scatter,
                cond=cond_arg[0] if cond_arg else None, Rl=Rl,
            )

        # check_vma=False: outputs pass through all_gather, whose
        # replication the vma checker cannot infer statically (values ARE
        # identical across shards — asserted by the CPU-mesh equality tests)
        in_specs = (
            (dict(COLUMN_SPECS), cond_specs)
            if R2
            else (dict(COLUMN_SPECS),)
        )
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False,
        )
        return jax.jit(fn)

    # packed transport: runs decoded on device inside the body; the pred
    # stream is sliced per shard from the expanded columns
    def packed_body(arrays, *cond_arg):
        cols = _unpack_transport(packed_key[0], arrays, Ptot, packed_key[1])
        q = packed_key[1]
        ql = q // n
        qi = jax.lax.axis_index(AXIS) * ql
        c = dict(cols)
        c["pred_src"] = jax.lax.dynamic_slice_in_dim(cols["pred_src"], qi, ql)
        c["pred_tgt"] = jax.lax.dynamic_slice_in_dim(cols["pred_tgt"], qi, ql)
        return _sharded_merge(
            c, Pl=Pl, n_objs2=n_objs2, n_props=n_props_eff, G=G,
            use_scatter=use_scatter,
            cond=cond_arg[0] if cond_arg else None, Rl=Rl,
        )

    in_specs = (P(), cond_specs) if R2 else (P(),)
    fn = jax.shard_map(
        packed_body, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def make_sharded_merge(mesh: Mesh, n_objs2: int = None, n_props: int = None):
    """Build a jitted N-chip merge for ``mesh`` (dict-transport variant).

    Kept for callers that prepare padded columns themselves. Without real
    ``n_objs2``/``n_props`` geometry the conservative defaults route map
    groups through the sort-based fallback (a dense table sized from
    guesses would silently collapse distinct map keys into one group).
    """
    n = mesh.devices.size

    def run(cols):
        P_ = cols["action"].shape[0]
        if P_ % n:
            raise ValueError(
                f"row capacity {P_} must divide evenly over {n} devices"
            )
        no2 = n_objs2 if n_objs2 is not None else P_ + 2
        np_ = n_props if n_props is not None else P_
        return _make_sharded_fn(mesh, P_, no2, np_, None)(cols)

    return run


def _pad_to_multiple(a: np.ndarray, m: int, fill) -> np.ndarray:
    r = (-len(a)) % m
    if r == 0:
        return a
    return np.concatenate([a, np.full(r, fill, dtype=a.dtype)])


def _next_pow2(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def condense_host(cols_np, n_objs2: int, n_shards: int):
    """Host chain condensation feeding the o(P)-collective linearization.

    Builds the sibling forest with one lexsort (ops/oplog.py host_forest)
    and collapses first-child chains natively (native/condense.cpp);
    returns (R2, cond arrays) with chain arrays padded to a pow2 bucket
    R2 > R that divides over ``n_shards``, the last slot reserved as the
    list-end sentinel. Raises NativeUnavailable when the native core is
    absent (callers fall back to the replicated doubling).
    """
    from .. import native
    from ..ops.oplog import host_forest

    insert, parent_row, first_child, next_sib = host_forest(cols_np)
    Ptot = len(insert)
    R, cond = native.chain_condense(
        first_child, next_sib, parent_row, insert, Ptot, n_objs2
    )
    # strictly > R so the last slot is free for the sentinel, and a
    # multiple of n_shards so the per-device slices tile exactly
    R2 = max(_next_pow2(R + 1), 2)
    R2 = -(-R2 // n_shards) * n_shards
    out = {
        "chain_id": np.ascontiguousarray(cond["chain_id"], np.int32),
        "offset": np.ascontiguousarray(cond["offset"], np.int32),
        "tail_ans": _pad_exact(cond["tail_ans"], R2, -1),
        "cpar": _pad_exact(cond["cpar"], R2, -1),
        "centry": _pad_exact(cond["centry"], R2, -1),
        "clen": _pad_exact(cond["len"], R2, 0),
        "start_chain": np.ascontiguousarray(cond["start_chain"], np.int32),
    }
    return R2, out


def _pad_exact(a: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full(size, fill, np.int32)
    out[: len(a)] = a
    return out


def sharded_merge_columns(
    cols_np, mesh: Optional[Mesh] = None, n_objs: Optional[int] = None,
    n_props: Optional[int] = None, transport: str = "dict",
):
    """Host entry: numpy columns in, numpy resolution out, over ``mesh``.

    Arrays are placed with explicit per-column shardings on the mesh's own
    devices — never the process-default backend, which may be a different
    (or unusable) client than the mesh was built over.

    ``n_objs``/``n_props`` (the live object/prop counts, from OpLog) size
    the dense map-group table; absent, conservative defaults route map
    groups through the sort-based fallback. ``transport="packed"`` ships
    slope-RLE runs and decodes on device (the thin-link path).
    """
    mesh = mesh or default_mesh()
    n = mesh.devices.size
    cols_np = dict(cols_np)
    cols_np["pred_src"] = _pad_to_multiple(cols_np["pred_src"], n, 0)
    cols_np["pred_tgt"] = _pad_to_multiple(cols_np["pred_tgt"], n, -1)
    Ptot = len(cols_np["action"])
    if Ptot % n:
        raise ValueError(
            f"row capacity {Ptot} must divide evenly over {n} devices "
            "(padded_columns capacities are powers of two / 8k multiples)"
        )
    n_objs2 = (n_objs + 2) if n_objs is not None else Ptot + 2
    np_eff = n_props if n_props is not None else Ptot

    # chain-condensed linearization (o(P) collectives per doubling step);
    # the replicated full-size doubling remains the no-native fallback
    from .. import native as _native

    R2 = 0
    cond_np = None
    try:
        with obs.span("parallel.condense", rows=Ptot):
            R2, cond_np = condense_host(cols_np, n_objs2, n)
    except _native.NativeUnavailable:
        pass

    def put_cond():
        return {
            k: jax.device_put(v, NamedSharding(mesh, P()))
            for k, v in cond_np.items()
        }

    obs.count("device.kernel_launches", labels={"path": "sharded"})
    _prof.note("launches")
    if transport == "packed":
        static_key, arrays = encode_transport(cols_np)
        fn = _make_sharded_fn(
            mesh, Ptot, n_objs2, np_eff,
            (static_key, len(cols_np["pred_src"])), R2,
        )
        with obs.span("parallel.h2d", rows=Ptot):
            arrs = {
                k: jax.device_put(v, NamedSharding(mesh, P()))
                for k, v in arrays.items()
            }
            cond = put_cond() if R2 else None
        with obs.span("parallel.kernel", rows=Ptot, devices=n), \
                _prof.annotate("amtpu.sharded_launch"):
            out = fn(arrs, cond) if R2 else fn(arrs)
    else:
        with obs.span("parallel.h2d", rows=Ptot):
            cols = {
                k: jax.device_put(v, NamedSharding(mesh, COLUMN_SPECS[k]))
                for k, v in cols_np.items()
            }
            cond = put_cond() if R2 else None
        fn = _make_sharded_fn(mesh, Ptot, n_objs2, np_eff, None, R2)
        with obs.span("parallel.kernel", rows=Ptot, devices=n), \
                _prof.annotate("amtpu.sharded_launch"):
            out = fn(cols, cond) if R2 else fn(cols)
    with obs.span("parallel.readback", rows=Ptot):
        return {k: np.asarray(v) for k, v in out.items()}
