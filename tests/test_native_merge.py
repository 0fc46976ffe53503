"""Native host merge engine vs the jax kernel: bit-exact equivalence.

merge_cols.cpp is the host oracle of ops/merge.py merge_columns, which
always runs the device kernel. Every output array must
match the jit kernel exactly on every workload shape, including historical
(covered-mask) views; mirrors the reference requirement that all apply
paths converge to one op set (reference: rust/automerge/tests/test.rs
merge scenarios).
"""

import numpy as np
import pytest

from automerge_tpu import bench as W
from automerge_tpu import native
from automerge_tpu.api import AutoDoc
from automerge_tpu.ops import DeviceDoc, OpLog
from automerge_tpu.ops.merge import ALL_OUTPUTS, merge_columns
from automerge_tpu.types import ActorId, ObjType, ScalarValue

pytestmark = pytest.mark.skipif(
    not (native.available() and native.merge_available()),
    reason="native merge engine not available",
)


def actor(i: int) -> ActorId:
    return ActorId(bytes([i]) * 16)


def _rich_changes():
    """Maps, nested objects, text, counters, deletes, marks, conflicts."""
    base = AutoDoc(actor=actor(1))
    base.put("_root", "n", ScalarValue("counter", 5))
    text = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(text, 0, 0, "hello world")
    lst = base.put_object("_root", "l", ObjType.LIST)
    for i in range(5):
        base.insert(lst, i, i)
    base.commit()
    d1 = base.fork(actor=actor(2))
    d2 = base.fork(actor=actor(3))
    d1.increment("_root", "n", 3)
    d1.splice_text(text, 0, 5, "goodbye")
    d1.put("_root", "k", "one")
    d1.mark(text, 0, 4, "bold", True)
    d1.commit()
    d2.increment("_root", "n", -1)
    d2.delete(lst, 2)
    d2.insert(lst, 0, "x")
    d2.put("_root", "k", "two")
    d2.commit()
    docs = [d1, d2]
    out = []
    for d in docs:
        out.extend(a.stored for a in d.doc.history)
    return out


_WORKLOAD_CACHE = {}


def _workload(name):
    """Built lazily inside tests — collection must not touch the native
    encoders (the module skipif has to fire first on lib-less hosts)."""
    if name in _WORKLOAD_CACHE:
        return _WORKLOAD_CACHE[name]
    if name == "rich":
        changes = _rich_changes()
    elif name == "mapcounter":
        cdoc, keys = W.build_counter_base(6)
        mc, _ = W.synth_mapcounter(cdoc, keys, 12, 8)
        changes = [a.stored for a in cdoc.doc.history] + mc
    else:
        trace = W.synth_edit_trace(4000)
        base = W.build_base(trace, 1500)
        if name == "fanin":
            changes = list(base.changes) + W.synth_fanin(base, trace, 12, 40, 1500)
        else:
            changes = list(base.changes) + W.synth_rga(base, 15, 25)
    _WORKLOAD_CACHE[name] = changes
    return changes


def _assert_same(jx, nv, name, keys=ALL_OUTPUTS):
    for k in keys:
        a, b = np.asarray(jx[k]), np.asarray(nv[k])
        m = min(len(a), len(b))  # obj stats may differ in padded tail length
        assert np.array_equal(a[:m], b[:m]), (name, k)


@pytest.mark.parametrize("name", ["fanin", "rga", "mapcounter", "rich"])
def test_engine_equivalence(name):
    log = OpLog.from_changes(_workload(name))
    cols = log.padded_columns()
    jx = merge_columns(cols, linearize="device", fetch=ALL_OUTPUTS, n_objs=log.n_objs)
    nv = native.merge_cols(cols, log.n_objs)
    _assert_same(jx, nv, name)


def test_engine_equivalence_historical():
    """Covered-mask (clock-gated) views must match too."""
    changes = _rich_changes()
    log = OpLog.from_changes(changes)
    # cover only the first half of the log's ops (a plausible clock cut:
    # covered is per-row; the kernel must gate visibility identically)
    covered = np.zeros(log.n, np.bool_)
    covered[: log.n // 2] = True
    cols = log.padded_columns(covered=covered)
    jx = merge_columns(cols, linearize="device", fetch=ALL_OUTPUTS, n_objs=log.n_objs)
    nv = native.merge_cols(cols, log.n_objs)
    _assert_same(jx, nv, "historical")


def test_native_engine_reads_match_the_kernel():
    """merge_columns runs the kernel and never the host engine (no
    ``merge.host`` span), and the host engine's outputs give the same
    document reads."""
    from automerge_tpu import obs

    def host_merges():
        return obs.timing_summary().get("merge.host", {}).get("n", 0)

    changes = _rich_changes()
    log = OpLog.from_changes(changes)

    n0 = host_merges()
    res_jax = merge_columns(
        log.padded_columns(), fetch=DeviceDoc.READ_FETCH, n_objs=log.n_objs
    )
    assert host_merges() == n0
    assert set(res_jax) == set(DeviceDoc.READ_FETCH)
    nat = native.merge_cols(log.padded_columns(), log.n_objs)
    res_nat = {k: nat[k] for k in DeviceDoc.READ_FETCH}
    d1 = DeviceDoc(log, res_jax)
    d2 = DeviceDoc(OpLog.from_changes(changes), res_nat)
    assert d1.hydrate() == d2.hydrate()


def test_map_hash_fallback():
    """Sparse (many objects x many disjoint props, few ops) exceeds the
    dense (obj x prop) table budget and exercises the hash group path."""
    doc = AutoDoc(actor=actor(9))
    for i in range(300):
        o = doc.put_object("_root", f"o{i}", ObjType.MAP)
        doc.put(o, f"p{i}a", i)
        doc.put(o, f"p{i}b", -i)
    doc.commit()
    changes = [a.stored for a in doc.doc.history]
    log = OpLog.from_changes(changes)
    cols = log.padded_columns()
    jx = merge_columns(cols, linearize="device", fetch=ALL_OUTPUTS, n_objs=log.n_objs)
    nv = native.merge_cols(cols, log.n_objs)
    _assert_same(jx, nv, "hash-fallback")


@pytest.mark.parametrize("name", ["fanin", "rga", "mapcounter", "rich"])
def test_scatter_kernel_matches_sort_kernel(name):
    """The sort-free scatter resolution (geometry-specialized) must match
    the sort-based kernel bit-for-bit on every workload shape."""
    import jax.numpy as jnp

    from automerge_tpu.ops.merge import (
        merge_kernel_core, scatter_geometry_ok, scatter_kernel_core,
    )

    log = OpLog.from_changes(_workload(name))
    cols_np = log.padded_columns()
    assert scatter_geometry_ok(
        len(cols_np["action"]), log.n_objs, len(log.props)
    )
    cols = {k: jnp.asarray(v) for k, v in cols_np.items()}
    o1 = merge_kernel_core(cols)
    o2 = scatter_kernel_core(log.n_objs, len(log.props))(cols)
    for k in (
        "visible", "winner", "conflicts", "succ_count", "inc_count",
        "counter_inc", "is_elem", "parent_row", "first_child", "next_sib",
        "obj_vis_len", "obj_text_width",
    ):
        a, b = np.asarray(o1[k]), np.asarray(o2[k])
        assert a.shape == b.shape, (name, k, a.shape, b.shape)
        assert np.array_equal(a, b), (name, k)


def test_join_rows_fuzz_and_key_zero():
    """The extraction join (interpolation + memo) against the numpy oracle,
    including the key-0 case the memo's empty marker must not alias
    (review regression) and memo-sized repetitive streams."""
    rng = np.random.default_rng(5)
    for trial in range(120):
        n = int(rng.integers(1, 3000))
        if trial % 3 == 0:
            s = np.sort(rng.integers(0, 1 << 40, n).astype(np.int64))
        elif trial % 3 == 1:  # clustered: adversarial for interpolation
            s = np.sort(
                np.concatenate(
                    [rng.integers(0, 64, n // 2 + 1),
                     rng.integers(1 << 39, (1 << 39) + 64, n // 2 + 1)]
                ).astype(np.int64)
            )[:n]
        else:  # duplicate-heavy
            s = np.sort(rng.integers(0, 40, n).astype(np.int64))
        q = np.concatenate(
            [rng.choice(s, min(n, 40)), rng.integers(-(1 << 41), 1 << 41, 40)]
        ).astype(np.int64)
        got = native.join_rows(s, q, -7)
        pos = np.searchsorted(s, q)
        posc = np.clip(pos, 0, n - 1)
        want = np.where(s[posc] == q, posc, -7).astype(np.int32)
        assert np.array_equal(got, want), trial
    # key 0, large repetitive stream (memo active): absent then present
    s0 = np.sort(rng.integers(1, 1 << 40, 100_000).astype(np.int64))
    q0 = np.zeros(80_000, np.int64)
    assert (native.join_rows(s0, q0, -1) == -1).all()
    s1 = np.unique(np.concatenate([[0], s0]))
    assert (native.join_rows(s1, q0, -1) == 0).all()


def test_join_rows_int64_min_key():
    """INT64_MIN (the memo's empty marker) as a query key must search, not
    false-hit a pristine slot (review regression); memo active via total
    query count regardless of the thread split."""
    rng = np.random.default_rng(9)
    s = np.sort(rng.integers(1, 1 << 40, 50_000).astype(np.int64))
    q = np.full(150_000, np.iinfo(np.int64).min, np.int64)
    assert (native.join_rows(s, q, -3) == -3).all()
