"""Differential suite for the one resolution route every backend takes
over compressed and dense residency: ``host_batch.stage_docs`` then the
shared plain launch or, for a lone document, the packed transport
(ops/batched.py, ops/merge.py), plus the double-buffered drain.

Dense residency (``AUTOMERGE_TPU_COMPRESSED=0``) is the oracle: the same
multi-seed workloads resolved with compressed residency must leave every
document bit-identical — column-level OpLog equality, full DeviceDoc
state including ``conflicts``, identical ``at(heads)`` views — across
the stage_docs + shared launch path, ``apply_batches`` (a generation of
one), the pipelined (double-buffered) drain with out-of-order/duplicate
delivery, and high-entropy edits whose resident columns the ratio gate
demotes dense. Each drive asserts that the launches it means ran:
``device.kernel_launches{path=batched}`` for the shared launch,
``device.kernel`` spans with ``program=packed`` for a lone document's.
Plus launch-level properties: the packed transport decodes its run
tables exactly, ships a degenerate column plain and counts it, and the
shared launch uploads plain columns.
"""

import jax
import numpy as np
import pytest

from automerge_tpu import obs
from automerge_tpu.ops import host_batch, merge
from automerge_tpu.ops.batched import apply_cross_doc, resolve_stages
from automerge_tpu.ops.device_doc import DeviceDoc
from automerge_tpu.ops.oplog import OpLog, pad_columns

from .test_host_batch import assert_identical, build_workload


def _batched_launches():
    return obs.counter_values("device.kernel_launches", "path").get(
        "batched", 0)


def _packed_kernels():
    return sum(1 for r in obs.recorder.snapshot()
               if r.name == "device.kernel"
               and r.fields.get("program") == "packed")


def _drive_staged(docs, deltas, cycles):
    """The stage_docs + shared packed launch path (the serve drain)."""
    devs = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    for c in range(cycles):
        stages, results = host_batch.stage_docs(
            [(devs[i], [deltas[i][c]]) for i in range(len(docs))]
        )
        for r in results.values():
            assert r.error is None, repr(r.error)
        if stages:
            resolve_stages(stages)
    return devs


def _drive_pipelined(docs, deltas, cycles, step):
    """The double-buffered drain: chunked apply_cross_doc with chunk
    N+1's host staging under chunk N's in-flight packed kernel."""
    devs = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
    for c in range(cycles):
        apply_cross_doc(
            [(devs[i], [deltas[i][c]]) for i in range(len(docs))],
            max_docs_per_launch=step,
            pipeline=True,
        )
    return devs


def _check_same(got, oracle, docs):
    for i in range(len(docs)):
        assert_identical(got[i], oracle[i], i)
        heads = got[i].current_heads()
        assert got[i].at(heads).hydrate() == oracle[i].at(heads).hydrate()
        assert got[i].at([]).hydrate() == oracle[i].at([]).hydrate()


# -- end-to-end differential: compressed vs dense residency -----------------


@pytest.mark.parametrize("seed", [2, 17, 40])
def test_differential_staged_launches(monkeypatch, seed):
    docs, deltas = build_workload(seed, n_docs=4, cycles=4)
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    obs.recorder.clear()
    b0 = _batched_launches()
    comp = _drive_staged(docs, deltas, 4)
    b1 = _batched_launches()
    packed = _packed_kernels()
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "0")
    dense = _drive_staged(docs, deltas, 4)
    _check_same(comp, dense, docs)
    # non-vacuous: the shared launch carried the drains, and the
    # documents' own resolutions took the packed transport
    assert b1 > b0
    assert packed > 0


@pytest.mark.parametrize("seed", [7, 29])
def test_differential_per_doc_async_dispatch(monkeypatch, seed):
    # apply_batches: a generation of one through stage_docs, resolved
    # by the document's own packed launch
    docs, deltas = build_workload(seed, n_docs=2, cycles=4, dup=True)

    def run():
        devs = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
        obs.recorder.clear()
        for i, dv in enumerate(devs):
            dv.apply_batches([deltas[i][c] for c in range(4)])
        return devs, _packed_kernels()

    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    b0 = _batched_launches()
    comp, packed = run()
    assert packed >= len(docs)
    assert _batched_launches() == b0
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "0")
    dense, _ = run()
    _check_same(comp, dense, docs)


@pytest.mark.parametrize("seed", [5, 33])
def test_differential_pipelined_drain(monkeypatch, seed):
    # out-of-order + duplicate delivery through the double-buffered
    # chunked drain (2-doc chunks → dispatch/stage/collect interleave)
    docs, deltas = build_workload(seed, n_docs=5, cycles=4, dup=True,
                                  shuffle=True)
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    b0 = _batched_launches()
    piped = _drive_pipelined(docs, deltas, 4, step=2)
    assert _batched_launches() > b0
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "0")
    dense = _drive_pipelined(docs, deltas, 4, step=2)
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    serial = _drive_staged(docs, deltas, 4)
    _check_same(piped, dense, docs)
    _check_same(serial, dense, docs)


def _fallbacks(reason):
    return sum(
        e["value"] for e in obs.snapshot()
        if e["type"] == "counter" and e["name"] == "oplog.compress_fallback"
        and e["labels"].get("reason") == reason
    )


def test_differential_gate_demoted_mixed_encodings(monkeypatch):
    # high-entropy edits (many tiny objects, scattered splice points)
    # drive some resident columns past the run gate: a MIX of run-encoded
    # and dense-demoted columns must still match the oracle through the
    # shared launch, and the demotions must be counted per column
    docs, deltas = build_workload(13, n_docs=3, cycles=4, shuffle=True)
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    fb0, b0 = _fallbacks("ratio"), _batched_launches()
    comp = _drive_staged(docs, deltas, 4)
    for dv in comp:
        dv.resident_nbytes()  # syncs the compressed image
    fb1, b1 = _fallbacks("ratio"), _batched_launches()
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "0")
    dense = _drive_staged(docs, deltas, 4)
    _check_same(comp, dense, docs)
    assert fb1 > fb0  # some column really did demote dense
    assert b1 > b0


# -- launch-level properties -------------------------------------------------


def _transport_cols(n=256, seed=3):
    rng = np.random.default_rng(seed)
    return {
        "action": np.ones(n, np.int32),                            # 1 run
        "insert": np.arange(n) % 64 < 40,                          # bool RLE
        "value_tag": np.zeros(n, np.int32),
        "covered": np.ones(n, bool),
        "prop": np.repeat(np.arange(8, dtype=np.int32), n // 8),   # RLE
        "elem_ref": (np.arange(n) - 1).astype(np.int32),           # slope
        "obj_dense": np.zeros(n, np.int32),
        "value_i32": rng.integers(0, 1 << 20, n).astype(np.int32),  # noise
        "width": np.ones(n, np.int32),
        "pred_src": np.arange(n, dtype=np.int32),
        "pred_tgt": (np.arange(n) - 1).astype(np.int32),
    }


def test_packed_transport_decodes_exactly():
    cols = _transport_cols()
    n = len(cols["action"])
    static_key, arrays = merge.encode_transport(cols)
    run_p, plain_p, run_q, plain_q = static_key
    assert "elem_ref" in run_p and "value_i32" in plain_p
    assert set(run_q) == {"pred_src", "pred_tgt"} and not plain_q
    got = jax.jit(
        lambda a: merge._unpack_transport(static_key, a, n, n)
    )({k: np.asarray(v) for k, v in arrays.items()})
    for k, v in cols.items():
        have = np.asarray(got[k])
        assert have.dtype == v.dtype and np.array_equal(have, v), k
    # the noise column alone ships plain; the runs undercut the dense image
    sent = sum(a.nbytes for a in arrays.values())
    assert sent * 2 < sum(v.nbytes for v in cols.values())


def test_packed_transport_ships_degenerate_columns_plain():
    cols = _transport_cols()
    rng = np.random.default_rng(9)
    cols["prop"] = rng.integers(0, 1 << 20, len(cols["prop"])).astype(
        np.int32)
    static_key, arrays = merge.encode_transport(cols)
    assert set(static_key[1]) == {"prop", "value_i32"}
    assert arrays["plainP"].shape == (2, len(cols["prop"]))

    def plain(column):
        return sum(
            e["value"] for e in obs.snapshot()
            if e["type"] == "counter"
            and e["name"] == "oplog.compress_fallback"
            and e["labels"].get("reason") == "transport"
            and e["labels"].get("column") == column
        )

    before = {k: plain(k) for k in ("prop", "value_i32", "elem_ref")}
    merge.count_transport(static_key)
    assert plain("prop") == before["prop"] + 1
    assert plain("value_i32") == before["value_i32"] + 1
    assert plain("elem_ref") == before["elem_ref"]


def test_shared_launch_uploads_plain_columns():
    docs, _ = build_workload(4, n_docs=1, cycles=1)
    log = OpLog.from_documents(docs)
    cols = pad_columns(log.columns(), log.n_objs)
    dense = sum(np.asarray(v).nbytes for v in cols.values())
    h0 = obs.counter_values("device.h2d_bytes", "").get("", 0)
    d0 = obs.counter_values("device.h2d_dense_bytes", "").get("", 0)
    obs.recorder.clear()
    out = merge.prepare_resolution(cols, log.n_objs, len(log.props))()
    assert obs.counter_values("device.h2d_bytes", "").get("", 0) - h0 == dense
    assert obs.counter_values(
        "device.h2d_dense_bytes", "").get("", 0) - d0 == dense
    (k,) = [r for r in obs.recorder.snapshot() if r.name == "device.kernel"]
    assert k.fields["program"] in ("scatter", "dense")
    ref = merge.merge_columns(cols, linearize="native",
                              fetch=merge.RESOLVE_FETCH, n_objs=log.n_objs,
                              n_props=len(log.props))
    for f in merge.RESOLVE_FETCH:
        np.testing.assert_array_equal(
            np.asarray(out[f])[: len(ref[f])], ref[f], err_msg=f)
