"""The main path's kernels compile for a described v5e (no chip needed).

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what it refuses (an unaligned slice, too much
fast memory, a program that cannot be partitioned) fails here at no chip
time. Each case compiles one kernel at a 4,096-row bucket with the real
columns' widths and dtypes: the plain launch's resolution bodies, the
packed-transport kernel, and the 4-device sharded merge on a mesh of the
described chips. The run-table expansion of the packed kernel, over
either winner body, is checked to compile without a loop.

The topology is described only inside the module fixture (never at
import or collection): one process at a time may load libtpu, and every
xdist worker imports this file. The persistent compilation cache is off
around the compiles: an entry written for a described chip cannot be
read back without one.
"""

import os
import re

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
def test_resolution_body_compiles(body, one_chip, doc_log):
    log = doc_log
    cols = pad_columns(log.columns(), log.n_objs)
    Pn = len(cols["action"])
    assert Pn == 4096
    geom = M.resolution_geom(
        Pn, log.n_objs, len(log.props) if body == "scatter" else None,
        full=(body == "full"),
    )
    assert geom[0] == body
    fetch = M.RESOLVE_FETCH + (("elem_index",) if body == "full" else ())
    fn = M.geometry_kernel(geom, fetch)
    compiled = fn.lower(
        {k: _spec(v, one_chip) for k, v in cols.items()}
    ).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 64 << 20


def test_packed_transport_kernel_compiles(one_chip, doc_log):
    from automerge_tpu.ops.device_doc import DeviceDoc

    log = doc_log
    cols = pad_columns(log.columns(), log.n_objs)
    Pn = len(cols["action"])
    arrays = M.plain_transport(cols, log.n_objs)
    fetch = tuple(k for k in DeviceDoc.READ_FETCH if k != "elem_index")
    fn = M._packed_fn(fetch, M._obj_cap(log.n_objs, Pn),
                      M.scatter_geom_key(log.n_objs, len(log.props)))
    fn.lower({k: _spec(v, one_chip) for k, v in arrays.items()}).compile()


def _while_op_names(hlo_text):
    """The ``op_name`` metadata of every ``while`` op of an HLO text."""
    return [
        m.group(1) if m else ""
        for line in hlo_text.splitlines() if " while(" in line
        for m in [re.search(r'op_name="([^"]*)"', line)]
    ]


@pytest.mark.parametrize("program", ["packed", "packed_core"])
def test_run_expansion_has_no_loop(program, one_chip, doc_log):
    # the mesh path's run tables expand with one scatter and a prefix
    # sum; a search over the runs lowers to a while loop over every row,
    # per column. Here they feed the per-document program's resolution:
    # ``packed`` resolves winners with the scatter body, ``packed_core``
    # with the sort-based one
    from automerge_tpu.ops.device_doc import DeviceDoc

    log = doc_log
    cols = pad_columns(log.columns(), log.n_objs)
    Pn, Q = len(cols["action"]), len(cols["pred_src"])
    static_key, arrays = M.encode_transport(cols)
    assert static_key[0], "the edit-trace columns must run-encode"
    fetch = tuple(k for k in DeviceDoc.READ_FETCH if k != "elem_index")
    obj_cap = M._obj_cap(log.n_objs, Pn)
    geom = (("scatter", *M.scatter_geom_key(log.n_objs, len(log.props)))
            if program == "packed" else ("core", obj_cap))
    body = M._resolution_body(geom)
    fn = jax.jit(lambda a: M._emit(
        body(M._unpack_transport(static_key, a, Pn, Q)), fetch, obj_cap))
    lowered = fn.lower({k: _spec(v, one_chip) for k, v in arrays.items()})
    names = _while_op_names(lowered.compile().as_text())
    assert not [n for n in names
                if "transport_unpack" in n or "expand_runs" in n], names


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
