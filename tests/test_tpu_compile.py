"""The main path's kernels compile for a described v5e (no chip needed).

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what it refuses (an unaligned slice, too much
fast memory, a program that cannot be partitioned) fails here at no chip
time. Each case compiles one kernel at a 4,096-row bucket with the real
columns' widths and dtypes: the run-native resolution bodies, the
packed-transport kernel, and the 4-device sharded merge on a mesh of the
described chips.

The topology is described only inside the module fixture (never at
import or collection): one process at a time may load libtpu, and every
xdist worker imports this file. The persistent compilation cache is off
around the compiles: an entry written for a described chip cannot be
read back without one.
"""

import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from automerge_tpu import bench as W
from automerge_tpu.ops import OpLog
from automerge_tpu.ops import merge as M
from automerge_tpu.ops.oplog import pad_columns

N_EDITS = 4000  # 4,001 rows -> the 4,096-row bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def doc_log():
    trace = W.synth_edit_trace(N_EDITS, seed=0)
    return OpLog.from_changes(W.build_base(trace, N_EDITS).changes)


def _spec(a, sharding):
    return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                sharding=sharding)


@pytest.mark.parametrize("body", ["core", "scatter", "full"])
def test_run_native_body_compiles(body, one_chip, doc_log):
    log = doc_log
    cols = pad_columns(log.columns(), log.n_objs)
    Pn = len(cols["action"])
    assert Pn == 4096
    dense, stacks, plan = M.stage_cols_run_native(cols)
    assert plan, "the edit-trace columns must run-encode"
    geom = M.resolution_geom(
        Pn, log.n_objs, len(log.props) if body == "scatter" else None,
        full=(body == "full"),
    )
    assert geom[0] == body
    fetch = M.RESOLVE_FETCH + (("elem_index",) if body == "full" else ())
    fn = M.run_native_kernel(plan, geom, fetch)
    compiled = fn.lower(
        {k: _spec(v, one_chip) for k, v in dense.items()},
        tuple(tuple(_spec(a, one_chip) for a in st) for st in stacks),
    ).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 64 << 20


def test_packed_transport_kernel_compiles(one_chip, doc_log):
    from automerge_tpu.ops.device_doc import DeviceDoc

    log = doc_log
    cols = pad_columns(log.columns(), log.n_objs)
    Pn, Q = len(cols["action"]), len(cols["pred_src"])
    static_key, arrays = M.encode_transport(cols)
    fetch = tuple(k for k in DeviceDoc.READ_FETCH if k != "elem_index")
    fn = M._runs_fn(fetch, M._obj_cap(log.n_objs, Pn), static_key, Pn, Q,
                    M.scatter_geom_key(log.n_objs, len(log.props)))
    fn.lower({k: _spec(v, one_chip) for k, v in arrays.items()}).compile()


def test_sharded_merge_compiles_on_four_chips(topo, doc_log):
    import automerge_tpu.parallel.sharding as S

    log = doc_log
    n = 4
    mesh = Mesh(np.array(topo.devices[:n]), (S.AXIS,))
    cols = dict(log.padded_columns())
    cols["pred_src"] = S._pad_to_multiple(cols["pred_src"], n, 0)
    cols["pred_tgt"] = S._pad_to_multiple(cols["pred_tgt"], n, -1)
    Ptot, n_objs2 = len(cols["action"]), log.n_objs + 2
    R2, cond = S.condense_host(cols, n_objs2, n)
    fn = S._make_sharded_fn(mesh, Ptot, n_objs2, len(log.props), None, R2)
    args = (
        {k: _spec(v, NamedSharding(mesh, S.COLUMN_SPECS[k]))
         for k, v in cols.items()},
        {k: _spec(v, NamedSharding(mesh, P())) for k, v in cond.items()},
    )
    try:
        compiled = fn.lower(*args).compile()
    finally:
        S._make_sharded_fn.cache_clear()
    assert "all-gather" in compiled.as_text()
