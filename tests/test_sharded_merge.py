"""Sharded merge over a virtual 8-device CPU mesh matches the host result.

conftest.py forces JAX_PLATFORMS=cpu with 8 virtual devices, so this runs
the real shard_map/psum path without TPU hardware.
"""

import jax
import pytest

from automerge_tpu.api import AutoDoc
from automerge_tpu.ops import DeviceDoc, OpLog
from automerge_tpu.parallel import default_mesh, sharded_merge_columns
from automerge_tpu.types import ActorId, ObjType, ScalarValue


def actor(i):
    return ActorId(bytes([i]) * 16)


@pytest.mark.parametrize("n_devices", [2, 8])
def test_sharded_matches_single_device(n_devices):
    assert len(jax.devices()) >= n_devices
    base = AutoDoc(actor=actor(1))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "shared base text")
    base.put("_root", "count", ScalarValue("counter", 0))
    base.commit()
    forks = [base.fork(actor=actor(10 + i)) for i in range(4)]
    for i, f in enumerate(forks):
        f.splice_text(t, i, 2, f"[{i}]")
        f.increment("_root", "count", i + 1)
        f.commit()

    log = OpLog.from_documents(forks)
    mesh = default_mesh(n_devices)
    res = sharded_merge_columns(log.padded_columns(), mesh)
    dev_sharded = DeviceDoc(log, res)
    dev_single = DeviceDoc.resolve(log)
    assert dev_sharded.hydrate() == dev_single.hydrate()
    host = AutoDoc(actor=actor(99))
    for f in forks:
        host.merge(f)
    assert dev_sharded.hydrate() == host.hydrate()


def _single_device_res(log, covered=None):
    """Oracle: the single-device jax kernel (dict transport, device
    linearization) on the same padded columns."""
    from automerge_tpu.ops.merge import ALL_OUTPUTS, merge_columns

    return merge_columns(
        log.padded_columns(covered=covered),
        linearize="device",
        fetch=ALL_OUTPUTS,
        n_objs=log.n_objs,
    )


def _assert_res_equal(sharded, single, P):
    import numpy as np

    for k in (
        "visible", "winner", "conflicts", "elem_index", "succ_count",
        "inc_count", "counter_inc", "is_elem", "parent_row",
        "obj_vis_len", "obj_text_width",
    ):
        a, b = np.asarray(sharded[k]), np.asarray(single[k])
        m = min(len(a), len(b))
        assert np.array_equal(a[:m], b[:m]), k


def test_sharded_large_fanin_100k():
    """>=100k ops through the fully-sharded path (scatter winners +
    sharded linearization) on the 8-device mesh, equal to the
    single-device kernel and converging to the native sequential apply."""
    from automerge_tpu import bench as W

    trace = W.synth_edit_trace(60_000)
    base = W.build_base(trace, 40_000)
    changes = list(base.changes) + W.synth_fanin(base, trace, 128, 500, 40_000)
    log = OpLog.from_changes(changes)
    assert log.n >= 100_000
    mesh = default_mesh(8)
    res = sharded_merge_columns(
        log.padded_columns(), mesh, n_objs=log.n_objs, n_props=len(log.props)
    )
    single = _single_device_res(log)
    _assert_res_equal(res, single, log.n)
    # end-to-end convergence vs the independent native oracle
    t_native, native_text = W.seq_apply_baseline(changes, base.text_obj)
    dev = DeviceDoc(log, res)
    assert dev.text(base.text_exid) == native_text


def test_sharded_marks_and_historical():
    """Marks + counters through the sharded path, current AND historical
    (covered-mask) views, equal to the single-device kernel."""
    import numpy as np

    from automerge_tpu.types import ObjType, ScalarValue

    base = AutoDoc(actor=actor(1))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "the quick brown fox jumps")
    base.put("_root", "n", ScalarValue("counter", 10))
    base.commit()
    forks = [base.fork(actor=actor(20 + i)) for i in range(3)]
    forks[0].mark(t, 0, 9, "bold", True)
    forks[0].increment("_root", "n", 5)
    forks[0].commit()
    forks[1].mark(t, 4, 15, "italic", True, expand="both")
    forks[1].splice_text(t, 10, 5, "red")
    forks[1].commit()
    forks[2].delete("_root", "n")
    forks[2].splice_text(t, 0, 3, "a")
    forks[2].commit()

    log = OpLog.from_documents(forks)
    mesh = default_mesh(4)
    # current state
    res = sharded_merge_columns(
        log.padded_columns(), mesh, n_objs=log.n_objs, n_props=len(log.props)
    )
    _assert_res_equal(res, _single_device_res(log), log.n)
    dev = DeviceDoc(log, res)
    host = AutoDoc(actor=actor(99))
    for f in forks:
        host.merge(f)
    assert dev.hydrate() == host.hydrate()
    assert dev.marks(log.export_id(log.import_id(t))) == host.marks(t)
    # historical view: clock cut at half the log's ops
    covered = np.zeros(log.n, np.bool_)
    covered[: log.n // 2] = True
    res_h = sharded_merge_columns(
        log.padded_columns(covered=covered), mesh,
        n_objs=log.n_objs, n_props=len(log.props),
    )
    _assert_res_equal(res_h, _single_device_res(log, covered=covered), log.n)


def test_sharded_packed_transport():
    """The slope-RLE packed transport through the sharded path matches the
    dict transport exactly."""
    from automerge_tpu import bench as W

    trace = W.synth_edit_trace(6_000)
    base = W.build_base(trace, 3_000)
    changes = list(base.changes) + W.synth_fanin(base, trace, 16, 100, 3_000)
    log = OpLog.from_changes(changes)
    mesh = default_mesh(4)
    kw = dict(n_objs=log.n_objs, n_props=len(log.props))
    res_d = sharded_merge_columns(log.padded_columns(), mesh, **kw)
    res_p = sharded_merge_columns(
        log.padded_columns(), mesh, transport="packed", **kw
    )
    _assert_res_equal(res_p, res_d, log.n)


def test_sharded_sort_fallback_path():
    """A sparse obj x prop space exceeds the dense group-table budget and
    exercises the replicated sort-based fallback, still sharded-scatter."""
    doc = AutoDoc(actor=actor(9))
    from automerge_tpu.types import ObjType

    for i in range(200):
        o = doc.put_object("_root", f"o{i}", ObjType.MAP)
        doc.put(o, f"p{i}a", i)
        doc.put(o, f"p{i}b", -i)
    doc.commit()
    log = OpLog.from_documents([doc])
    mesh = default_mesh(2)
    res = sharded_merge_columns(
        log.padded_columns(), mesh, n_objs=log.n_objs, n_props=len(log.props)
    )
    _assert_res_equal(res, _single_device_res(log), log.n)


def test_linearize_collectives_scale_with_chains_not_rows():
    """The condensed linearization's per-doubling-step collectives must be
    sized to the CONDENSED chain bucket (R2/n per shard), not to the row
    capacity — the o(P) communication requirement. Captured by recording
    every all_gather's shard shape at trace time."""
    import numpy as np

    import automerge_tpu.parallel.sharding as S
    from automerge_tpu import bench as W

    # early-trace slices are sequential typing runs -> long first-child
    # chains -> strong condensation (the shape the optimization targets)
    trace = W.synth_edit_trace(8_000)
    base = W.build_base(trace, 6_000)
    changes = list(base.changes) + W.synth_fanin(base, trace, 8, 200, 0)
    log = OpLog.from_changes(changes)
    cols = log.padded_columns()
    Ptot = len(cols["action"])
    n = 4
    mesh = default_mesh(n)
    n_objs2 = log.n_objs + 2
    R2, cond_np = S.condense_host(cols, n_objs2, n)
    assert R2 <= Ptot // 4, "workload must actually condense"

    gathered = []
    orig = jax.lax.all_gather

    def spy(x, axis_name, **kw):
        gathered.append(tuple(x.shape))
        return orig(x, axis_name, **kw)

    S._make_sharded_fn.cache_clear()
    jax.lax.all_gather, patched = spy, True
    try:
        res = sharded_merge_columns(
            cols, mesh, n_objs=log.n_objs, n_props=len(log.props)
        )
    finally:
        jax.lax.all_gather = orig
        S._make_sharded_fn.cache_clear()

    # correctness unchanged
    _assert_res_equal(res, _single_device_res(log), log.n)

    Rl, Pl = R2 // n, Ptot // n
    small = [s for s in gathered if s[0] <= Rl]
    big = [s for s in gathered if s[0] >= Pl]
    assert small, "condensed doubling ran no chain-sized collectives"
    # the doubling loops (2 loops x ~log R2 steps x 2-3 arrays) all move
    # chain-bucket slices; only O(1) full-row collectives remain (winner /
    # conflicts / the single expansion gather), NOT one per doubling step
    assert len(big) <= 4, (len(big), sorted(set(gathered)))
    assert all(s[0] <= Rl or s[0] >= Pl for s in gathered), sorted(set(gathered))
    # communication volume: bytes per doubling step bounded by the chain
    # bucket, an order of magnitude under the row capacity here
    assert Rl * 8 < Pl, (Rl, Pl)
