"""On-device decode of slope-RLE run tables (ops/merge.py ``_expand_runs``).

A stack of run tables expands with one scatter of each run's step at its
first row and one prefix sum over the rows. These tests hold it to the
column ``_slope_rle`` was given, bit for bit, on the shapes the encoder
produces (one run, constant, slope 1, a stride-N slope, the n/8 gate, a
run capacity padded far past the runs, values and steps at the ends of
int32, 32 rows, the flag column the bool columns ride): the mesh path's
transport. Then they hold the two launches every backend takes — a
document's own launch (``merge_columns`` with a ``fetch``) and the
shared launch (``prepare_resolution``), both of which upload plain
columns — over compressed and dense residency to the dict path's
reference (an explicit ``linearize`` pin, dense residency) on small edit
traces, check that one program serves a document's launch whatever its
columns' runs, and check the counters that say how many columns a launch
decoded from runs.
"""

import functools

import numpy as np
import pytest

import jax

from automerge_tpu import bench as W
from automerge_tpu import obs
from automerge_tpu.api import AutoDoc
from automerge_tpu.ops import OpLog, batched
from automerge_tpu.ops import merge as M
from automerge_tpu.ops.oplog import _next_pow2, pad_columns
from automerge_tpu.parallel.sharding import default_mesh, sharded_merge_columns
from automerge_tpu.types import ActorId

I32_MIN, I32_MAX = -(1 << 31), (1 << 31) - 1


def _column(case, n=4096, seed=0):
    rng = np.random.default_rng(seed)
    i = np.arange(n, dtype=np.int64)
    if case == "one_run":
        x = 100 + 3 * i
    elif case == "constant":
        x = np.full(n, 7)
    elif case == "slope_1":
        # typing chains: elem_ref = row - 1, broken by cursor jumps
        x = i - 1 + np.repeat(rng.integers(-500, 500, n // 64), 64)
    elif case == "stride_n":
        # 16 replicas' same-counter ops interleaved in Lamport order
        x = 16 * i + np.repeat(rng.integers(-50, 50, n // 128), 128)
    elif case == "at_gate":
        # the most runs the encoder accepts: n/8
        v = np.cumsum(rng.integers(1, 1000, n // 8))
        x = np.repeat(v, 8)
    elif case == "wrap":
        # every step overflows int32
        x = np.repeat(np.resize([I32_MAX, I32_MIN, I32_MAX - 1, I32_MIN + 1],
                                n // 32), 32)
    elif case == "full_range":
        x = np.repeat(rng.integers(I32_MIN, I32_MAX, n // 256,
                                   endpoint=True), 256)
    elif case == "short":
        x = np.array([5] * 20 + [-3] * 12)
    else:
        raise ValueError(case)
    return x.astype(np.int32)


def _table(encs, n, rcap):
    K = len(encs)
    Wt = np.zeros((K, rcap), np.int32)
    Ct = np.full((K, rcap), np.int32(n), np.int32)
    St = np.empty(K, np.int32)
    for k, (w, cum, s) in enumerate(encs):
        Wt[k, : len(w)] = w
        Ct[k, : len(cum)] = cum
        St[k] = s
    return Wt, Ct, St


CASES = ["one_run", "constant", "slope_1", "stride_n", "at_gate", "wrap",
         "full_range", "short"]


@pytest.mark.parametrize("pad", [1, 64], ids=["tight", "padded"])
@pytest.mark.parametrize("case", CASES)
def test_expand_runs_decodes_what_was_encoded(case, pad):
    x = _column(case)
    n = len(x)
    enc = M._slope_rle(x)
    assert enc is not None
    w, cum, s = enc
    if case == "one_run":
        assert len(w) == 1 and s == 3
    if case == "stride_n":
        assert s == 16
    if case == "at_gate":
        assert len(w) == n // 8
    if case == "short":
        assert n == 32
    rcap = pad * _next_pow2(max(len(w), 16))
    Wt, Ct, St = _table([enc], n, rcap)
    fn = jax.jit(M._expand_runs, static_argnums=3)
    got = np.asarray(fn(Wt, Ct, St, n))
    assert got.dtype == np.int32 and got.shape == (1, n)
    np.testing.assert_array_equal(got[0], x)
    if s == 0:
        # the run-native kernels drop the stride term of a pure-RLE stack
        np.testing.assert_array_equal(np.asarray(fn(Wt, Ct, None, n))[0], x)


def test_expand_runs_decodes_a_stack_at_once():
    # one call decodes every table of a stack; the runniest sets rcap
    cols = [_column(c) for c in ("slope_1", "constant", "wrap", "stride_n")]
    encs = [M._slope_rle(x) for x in cols]
    n = len(cols[0])
    rcap = _next_pow2(max(len(e[0]) for e in encs))
    got = np.asarray(M._expand_runs(*_table(encs, n, rcap), n))
    np.testing.assert_array_equal(got, np.stack(cols))


def test_transport_unpack_restores_the_flag_columns():
    # action/insert/value_tag/covered ride one run-encoded flags column;
    # the decode hands back the kernel's dtypes, bools included
    n = 256
    ins = np.zeros(n, bool)
    ins[40:90] = True
    ins[200:] = True
    cols = {
        "action": np.where(ins, 1, 3).astype(np.int32),
        "insert": ins,
        "value_tag": np.repeat(np.arange(4, dtype=np.int32), n // 4),
        "covered": np.arange(n) < 128,
        "prop": np.zeros(n, np.int32),
        "elem_ref": _column("slope_1", n),
        "obj_dense": np.zeros(n, np.int32),
        "value_i32": np.zeros(n, np.int32),
        "width": np.ones(n, np.int32),
        "pred_src": np.zeros(32, np.int32),
        "pred_tgt": np.zeros(32, np.int32),
    }
    static_key, arrays = M.encode_transport(cols)
    assert "flags" in static_key[0]
    got = jax.jit(lambda a: M._unpack_transport(static_key, a, n, 32))(
        {k: np.asarray(v) for k, v in arrays.items()})
    for k in ("action", "insert", "value_tag", "covered"):
        have = np.asarray(got[k])
        assert have.dtype == cols[k].dtype, k
        np.testing.assert_array_equal(have, cols[k], err_msg=k)


# -- the two launches against the reference ---------------------------------


def _log(kind, monkeypatch=None, residency=None):
    """``edit``: an edit trace; ``fanin``: the same with 12 replicas'
    16-edit changes merged in; ``typing``: one sequential typing run with
    those replicas, whose pred columns run-encode with slopes. With a
    ``residency``, the log is built under it from the first half of the
    history and the second half is spliced in (``append_changes``), as a
    resident document grows (a one-change history is built whole)."""
    n_edits = 1500
    trace = W.synth_edit_trace(n_edits, seed=3)
    typed = [[i, 0, "a"] for i in range(n_edits)]
    base = W.build_base(typed if kind == "typing" else trace, n_edits)
    changes = list(base.changes)
    if kind != "edit":
        changes += W.synth_fanin(base, trace, 12, 16, n_edits)
    if residency is None:
        return OpLog.from_changes(changes)
    monkeypatch.setenv("AUTOMERGE_TPU_COMPRESSED",
                       "1" if residency == "compressed" else "0")
    half = max(len(changes) // 2, 1)
    log = OpLog.from_changes(changes[:half])
    if half < len(changes):
        assert log.append_changes(changes[half:]) is not None
    return log


def _resolve(log, site):
    """``site``: ``packed`` (``merge_columns`` with ``fetch``, as a
    document's own launch), ``batched`` (the shared launch: plain
    columns through ``prepare_resolution``, ``conflicts`` as flags),
    ``dict`` (the reference: an explicit ``linearize`` pin)."""
    kw = dict(n_objs=log.n_objs, n_props=len(log.props))
    if site == "batched":
        return batched._collect_packed(batched._dispatch_packed(
            pad_columns(log.columns(), log.n_objs), **kw))
    return M.merge_columns(log.columns(), fetch=M.RESOLVE_FETCH,
                           linearize="native" if site == "dict" else "auto",
                           **kw)


@functools.lru_cache(maxsize=None)
def _reference(kind):
    return _resolve(_log(kind), "dict")


def _runs_expanded(path):
    return obs.counter_values("device.runs_expanded", "path").get(path, 0)


def _h2d():
    return tuple(obs.counter_values(name, "").get("", 0)
                 for name in ("device.h2d_bytes", "device.h2d_dense_bytes"))


def _matches(got, dense, log):
    for k in M.RESOLVE_FETCH:
        have, want = got[k], dense[k]
        if k == "conflicts":
            # both launches return conflict_flags (1 or 2)
            want = M.conflict_flags(want)
        # per-row outputs to the log's rows, per-object stats to its
        # objects (the launches return them at bucketed capacities)
        m = log.n_objs + 2 if k in M._OBJ_STATS else log.n
        np.testing.assert_array_equal(have[:m], want[:m], err_msg=k)


@pytest.mark.parametrize("kind", ["edit", "fanin", "typing"])
@pytest.mark.parametrize("residency", ["compressed", "dense"])
@pytest.mark.parametrize("site", ["batched", "packed"])
def test_site_matches_the_dense_oracle(site, residency, kind, monkeypatch):
    dense = _reference(kind)
    log = _log(kind, monkeypatch, residency)
    assert log.n == len(dense["visible"][: log.n])
    before = _runs_expanded("packed")
    h0, d0 = _h2d()
    got = _resolve(log, site)
    # neither launch decodes a column from runs: both upload plain
    assert _runs_expanded("packed") == before
    if site == "packed":
        # the two stacks, each padded column written once: what moved
        # is its own dense size
        cols = pad_columns(log.columns(), log.n_objs)
        P, Q = len(cols["action"]), len(cols["pred_src"])
        h1, d1 = _h2d()
        assert h1 - h0 == d1 - d0 == 4 * (6 * P + 2 * Q)
    _matches(got, dense, log)


def _map_log(runny):
    """512 puts of one change to 16 root keys, each key written first in
    order and then overwritten (preds), so both kinds pad to the same
    rows, preds and group geometry. ``runny``: keys in blocks of 32 and
    values counting up (their columns run-encode); otherwise keys and
    values drawn at random (``prop`` and ``value_i32`` do not)."""
    rng = np.random.default_rng(11)
    doc = AutoDoc(actor=ActorId(bytes([7]) * 16))
    keys = (np.arange(512) // 32 if runny else
            np.concatenate([np.arange(16), rng.integers(0, 16, 496)]))
    values = np.arange(512) if runny else rng.integers(0, 1 << 20, 512)
    for k, v in zip(keys, values):
        doc.put("_root", f"k{k:02d}", int(v))
    doc.commit()
    return OpLog.from_documents([doc])


def test_one_program_serves_columns_that_compress_differently(monkeypatch):
    # the run encoder tells the two logs apart, as the per-document
    # program's key once did; with plain stacks one program serves both
    monkeypatch.setattr(M, "_packed_cache", {})
    logs = [_map_log(runny) for runny in (True, False)]
    keys = [M.encode_transport(pad_columns(log.columns(), log.n_objs))[0]
            for log in logs]
    assert keys[0] != keys[1]
    assert {"prop", "value_i32"} <= set(keys[1][1]) - set(keys[0][1])
    shapes = set()
    for log in logs:
        kw = dict(n_objs=log.n_objs, n_props=len(log.props))
        stacks = M.plain_transport(log.columns(), log.n_objs)
        shapes.add((stacks["P"].shape, stacks["Q"].shape, kw["n_objs"],
                    kw["n_props"]))
        got = M.merge_columns(log.columns(), fetch=M.RESOLVE_FETCH, **kw)
        dense = M.merge_columns(log.columns(), fetch=M.RESOLVE_FETCH,
                                linearize="native", **kw)
        _matches(got, dense, log)
    assert len(shapes) == 1
    assert len(M._packed_cache) == 1


def _fallbacks(reason):
    return sum(
        e["value"] for e in obs.snapshot()
        if e["type"] == "counter" and e["name"] == "oplog.compress_fallback"
        and e["labels"].get("reason") == reason
    )


def test_packed_launch_counts_every_column(monkeypatch):
    # a document's own launch decodes nothing from runs and counts no
    # column; the mesh's packed transport counts each column once,
    # decoded from runs or shipped plain
    log = _log("edit")
    cols = pad_columns(log.columns(), log.n_objs)
    static_key, _ = M.encode_transport(cols)
    runs = len(static_key[0]) + len(static_key[2])
    plain = len(static_key[1]) + len(static_key[3])
    assert runs and plain
    r0, f0 = _runs_expanded("packed"), _fallbacks("transport")
    _resolve(log, "packed")
    r1, f1 = _runs_expanded("packed"), _fallbacks("transport")
    assert (r1, f1) == (r0, f0)
    sharded_merge_columns(cols, default_mesh(2), n_objs=log.n_objs,
                          n_props=len(log.props), transport="packed")
    r2, f2 = _runs_expanded("packed"), _fallbacks("transport")
    assert (r2 - r1, f2 - f1) == (runs, plain)
    assert r2 - r1 + f2 - f1 == len(M._P_ORDER) + len(M._Q_ORDER)
