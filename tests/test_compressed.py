"""Differential suite for compressed resident columns (ops/compressed.py).

Dense residency (``AUTOMERGE_TPU_COMPRESSED=0``) is the oracle: the same
random interleavings x out-of-order/duplicate delivery staged under
compressed residency must leave every document bit-identical —
column-level OpLog equality, full DeviceDoc arrays, identical
``at(heads)`` views. Plus codec-level properties: encode/decode/slice/
splice roundtrips, tail-append run extension (the last run extends
instead of re-encoding), the offset-value-coded join against the
searchsorted oracle, degenerate-run demotion through the ratio gate, and
the compressed H2D staging expanding bit-identically on device.
"""

import numpy as np
import pytest

from automerge_tpu import obs
from automerge_tpu.api import AutoDoc
from automerge_tpu.ops import compressed as C
from automerge_tpu.ops import host_batch
from automerge_tpu.ops.batched import resolve_stages
from automerge_tpu.ops.compressed import CompressedOpColumns, StrideRuns
from automerge_tpu.ops.device_doc import DeviceDoc
from automerge_tpu.ops.oplog import OpLog
from automerge_tpu.types import ActorId, ObjType

from .test_host_batch import assert_identical, build_workload

# -- codec properties ---------------------------------------------------------


def _random_column(rng, n, kind):
    if kind == 0:  # low-cardinality (action/vtag shape)
        return rng.integers(0, 3, n).astype(np.int32)
    if kind == 1:  # strictly sorted keys (id_key shape)
        return np.cumsum(rng.integers(1, 5, n)).astype(np.int64)
    if kind == 2:  # typing chain (elem_ref shape)
        return (np.arange(n) - 1).astype(np.int32)
    return rng.integers(-50, 50, n).astype(np.int32)  # degenerate


@pytest.mark.parametrize("seed", [0, 3, 11])
def test_codec_roundtrip_slice_splice(seed):
    rng = np.random.default_rng(seed)
    for trial in range(60):
        n = int(rng.integers(0, 120))
        x = _random_column(rng, n, trial % 4)
        for stride in (True, False):
            r = StrideRuns.encode(x, stride=stride)
            assert np.array_equal(r.decode(), x)
            assert r.nbytes == 24 * r.run_count
            if n:
                lo = int(rng.integers(0, n))
                hi = int(rng.integers(lo, n + 1))
                assert np.array_equal(r.slice(lo, hi).decode(), x[lo:hi])
                pos = int(rng.integers(0, n + 1))
                ins = _random_column(rng, int(rng.integers(0, 9)), trial % 4)
                spliced = r.splice(pos, ins)
                assert np.array_equal(
                    spliced.decode(),
                    np.concatenate([x[:pos], ins.astype(x.dtype), x[pos:]]),
                )


@pytest.mark.parametrize("seed", [1, 8])
def test_tail_extension_matches_reencode(seed):
    rng = np.random.default_rng(seed)
    for trial in range(80):
        n = int(rng.integers(0, 80))
        k = int(rng.integers(0, 40))
        kind = trial % 4
        x = _random_column(rng, n + k, kind)
        for stride in (True, False):
            r = StrideRuns.encode(x[:n], stride=stride)
            r.extend_tail(x[n:])
            assert np.array_equal(r.decode(), x), (trial, stride)


def test_tail_append_extends_last_run_not_reencodes():
    # the typing-chain contract: continuing runs stay ONE run
    x = np.arange(4096, dtype=np.int64)
    r = StrideRuns.encode(x[:1024])
    for lo in range(1024, 4096, 256):
        r.extend_tail(x[lo:lo + 256])
    assert r.run_count == 1
    assert r.is_sorted
    y = np.full(4096, 9, np.int32)
    r = StrideRuns.encode(y[:100], stride=False)
    r.extend_tail(y[100:])
    assert r.run_count == 1


@pytest.mark.parametrize("seed", [2, 13])
def test_ovc_join_matches_searchsorted_oracle(seed):
    rng = np.random.default_rng(seed)
    for _ in range(40):
        x = np.unique(rng.integers(0, 50_000, int(rng.integers(1, 400))))
        r = StrideRuns.encode(x.astype(np.int64))
        keys = rng.integers(-100, 50_100, 300).astype(np.int64)
        pos = np.searchsorted(x, keys)
        posc = np.clip(pos, 0, len(x) - 1)
        expect = np.where(x[posc] == keys, posc, -3).astype(np.int32)
        assert np.array_equal(r.join(keys, -3), expect)
    # join after a tail extension sees the extended rows
    x = np.unique(rng.integers(0, 10_000, 500)).astype(np.int64)
    r = StrideRuns.encode(x[:300])
    r.extend_tail(x[300:])
    keys = x[::7]
    assert np.array_equal(r.join(keys, -1), np.arange(len(x))[::7])


def test_unsorted_column_refuses_join():
    r = StrideRuns.encode(np.array([5, 3, 9], np.int64))
    assert not r.is_sorted
    with pytest.raises(ValueError):
        r.join(np.array([3], np.int64), -1)


def _fallbacks(reason):
    """``oplog.compress_fallback{reason}`` summed over its columns."""
    return sum(e["value"] for e in obs.snapshot()
               if e["type"] == "counter"
               and e["name"] == "oplog.compress_fallback"
               and e["labels"].get("reason") == reason)


def test_ratio_gate_demotes_degenerate_runs(monkeypatch):
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")

    class FakeLog:
        pass

    log = FakeLog()
    rng = np.random.default_rng(5)
    n = 512
    log.n = n
    log.pred_src = np.empty(0, np.int32)
    log.pred_tgt = np.empty(0, np.int32)
    log.pred_key = np.empty(0, np.int64)
    for name, _, _ in C.ROW_SPEC:
        setattr(log, name, rng.integers(0, 1 << 30, n).astype(np.int64))
    log.insert = np.asarray(rng.integers(0, 2, n), np.bool_)
    log.expand = np.asarray(rng.integers(0, 2, n), np.bool_)
    before = _fallbacks("ratio")
    comp = CompressedOpColumns().sync(log)
    after = _fallbacks("ratio")
    # random int columns cross the run gate and demote to dense
    demoted = [k for k, v in comp.run_counts().items() if v == -1]
    assert "id_key" in demoted and "action" in demoted, demoted
    assert after > before
    assert comp.id_runs() is None
    # demoted columns account dense; the bool columns still compress
    assert comp.nbytes(log) <= comp.dense_nbytes(log)


def test_compressed_image_decodes_to_live_columns():
    base = AutoDoc(actor=ActorId(bytes([20]) * 16))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "compressed residency " * 8)
    base.commit()
    log = OpLog.from_documents([base])
    comp = log.compressed()
    assert comp is not None
    for name, _, _ in C.ROW_SPEC:
        ent = comp.entries.get(name)
        if ent is None or ent is C._DENSE:
            continue
        col = getattr(log, name)
        if name in ("insert", "expand"):
            col = np.asarray(col, np.bool_).view(np.int8)
        assert np.array_equal(ent.decode(), np.asarray(col)), name
    # the typing doc compresses well and the accounting says so
    assert log.resident_column_nbytes() * 2 < log.dense_column_nbytes()
    assert log.compress_ratio() > 2.0


# -- run-encoded H2D staging -------------------------------------------------


def test_packed_transport_decodes_what_the_dict_path_uploads():
    from automerge_tpu.ops import merge as M

    base = AutoDoc(actor=ActorId(bytes([20]) * 16))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "expand on device " * 40)
    base.put("_root", "k", 7)
    base.commit()
    log = OpLog.from_documents([base])
    cols = log.padded_columns()
    h0 = obs.counter_values("device.h2d_bytes", "").get("", 0)
    d0 = obs.counter_values("device.h2d_dense_bytes", "").get("", 0)
    packed = M.merge_columns(cols, fetch=M.RESOLVE_FETCH, n_objs=log.n_objs)
    h1 = obs.counter_values("device.h2d_bytes", "").get("", 0)
    d1 = obs.counter_values("device.h2d_dense_bytes", "").get("", 0)
    plain = M.merge_columns(cols, linearize="native", fetch=M.RESOLVE_FETCH,
                            n_objs=log.n_objs)
    h2 = obs.counter_values("device.h2d_bytes", "").get("", 0)
    for k in M.RESOLVE_FETCH:
        want = plain[k]
        if k == "conflicts":
            want = M.conflict_flags(want)
        m = log.n_objs + 2 if k in M._OBJ_STATS else log.n
        assert np.array_equal(np.asarray(packed[k])[:m], want[:m]), k
    # the packed launch uploads its two plain int32 stacks, counted at
    # their own size; folding the four flag columns into one word moves
    # fewer bytes than the dict path's columns
    P, Q = len(cols["action"]), len(cols["pred_src"])
    assert h1 - h0 == d1 - d0 == 4 * (6 * P + 2 * Q)
    assert h1 - h0 < h2 - h1, (h1 - h0, h2 - h1)


# -- end-to-end differential: compressed vs dense residency -------------------


def _drive(docs, deltas, cycles):
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


@pytest.mark.parametrize("seed", [4, 23])
def test_differential_compressed_vs_dense(monkeypatch, seed):
    docs, deltas = build_workload(seed, n_docs=4, cycles=4)
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    ovc0 = obs.counter_values("oplog.ovc_join", "").get("", 0)
    comp = _drive(docs, deltas, 4)
    ovc1 = obs.counter_values("oplog.ovc_join", "").get("", 0)
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "0")
    dense = _drive(docs, deltas, 4)
    for i in range(len(docs)):
        assert_identical(comp[i], dense[i], i)
        heads = comp[i].current_heads()
        assert comp[i].at(heads).hydrate() == dense[i].at(heads).hydrate()
        assert comp[i].at([]).hydrate() == dense[i].at([]).hydrate()
    # non-vacuous: the offset-value-coded join actually ran
    assert ovc1 > ovc0


def test_scalar_append_path_differential(monkeypatch):
    # the per-doc apply_changes path (OpLog.append_changes) under both
    # modes, including out-of-order delivery that forces non-tail
    # splices and pending buffering — the cache-invalidation edge
    docs, deltas = build_workload(31, n_docs=2, cycles=4, dup=True)

    def run():
        devs = [DeviceDoc.resolve(OpLog.from_documents([d])) for d in docs]
        for i, dv in enumerate(devs):
            order = [2, 0, 1, 3] if i % 2 else [1, 3, 0, 2]
            for c in order:
                dv.apply_changes(deltas[i][c])
        return devs

    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    comp = run()
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "0")
    dense = run()
    for i in range(len(docs)):
        assert_identical(comp[i], dense[i], i)
        # the compressed image (rebuilt after any invalidation) still
        # decodes to the live columns
        monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
        cc = comp[i].log.compressed()
        for name, _, _ in C.ROW_SPEC:
            ent = cc.entries.get(name)
            if ent is None or ent is C._DENSE:
                continue
            col = getattr(comp[i].log, name)
            if name in ("insert", "expand"):
                col = np.asarray(col, np.bool_).view(np.int8)
            assert np.array_equal(ent.decode(), np.asarray(col)), (i, name)


def test_splice_into_run_boundaries():
    # splice at run head / mid-run / run tail / between runs
    x = np.repeat(np.arange(4, dtype=np.int32), 10)
    r = StrideRuns.encode(x, stride=False)
    for pos in (0, 5, 10, 19, 20, 39, 40):
        out = r.splice(pos, np.array([99], np.int32))
        expect = np.concatenate([x[:pos], [99], x[pos:]]).astype(np.int32)
        assert np.array_equal(out.decode(), expect), pos
        r = StrideRuns.encode(x, stride=False)  # splice may mutate (tail)


def test_gauges_report_true_resident_bytes(monkeypatch):
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    base = AutoDoc(actor=ActorId(bytes([20]) * 16))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "gauge " * 200)
    base.commit()
    dev = DeviceDoc.resolve(OpLog.from_documents([base]))
    dev.obs_name = "gauged"
    dev._export_doc_gauges()
    snap = {
        (e["name"], e["labels"].get("doc")): e["value"]
        for e in obs.snapshot()
        if e["type"] == "gauge" and e["name"].startswith("doc.")
    }
    got = snap[("doc.device_bytes", "gauged")]
    assert got == dev.resident_nbytes()
    assert got < dev.dense_nbytes()  # true bytes, not dense-equivalent
    assert snap[("doc.compress_ratio", "gauged")] > 1.5
    # the store's admission estimate sees the same truth
    from automerge_tpu.store.policy import device_resident_bytes

    assert device_resident_bytes(dev) == dev.resident_nbytes()


def test_cross_thread_estimate_never_touches_compressed_image(monkeypatch):
    # the DocStore evict sweeper reads residency OFF-thread: its
    # estimate must be pure reads — syncing the compressed image there
    # would race an in-flight append's eager id-run extension
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    base = AutoDoc(actor=ActorId(bytes([20]) * 16))
    t = base.put_object("_root", "t", ObjType.TEXT)
    base.splice_text(t, 0, 0, "estimate " * 50)
    base.commit()
    dev = DeviceDoc.resolve(OpLog.from_documents([base]))
    from automerge_tpu.store.policy import device_resident_bytes

    assert dev.log._comp is None
    est = device_resident_bytes(dev)
    assert dev.log._comp is None  # pure read: image untouched
    assert est == dev.dense_nbytes()  # dense fallback before first stamp
    # the owning thread stamps the cache; the observer then sees truth
    true = dev.resident_nbytes()
    assert device_resident_bytes(dev) == true
    assert true < est


def test_migration_wire_codec_roundtrip(monkeypatch):
    from automerge_tpu.cluster.node import _unwire_blob, _wire_blob

    payload = b"journal rows " * 400
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "1")
    b64, codec = _wire_blob(payload)
    assert codec == "zlib"
    assert len(b64) < len(payload)  # compressed on the wire
    assert _unwire_blob(b64, codec) == payload
    # small payloads and dense mode ship raw; absent codec decodes raw
    b64s, codec_s = _wire_blob(b"tiny")
    assert codec_s is None and _unwire_blob(b64s, None) == b"tiny"
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED", "0")
    b64d, codec_d = _wire_blob(payload)
    assert codec_d is None and _unwire_blob(b64d, codec_d) == payload
