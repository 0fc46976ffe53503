"""Vectorized change-column extraction: chunk bytes -> numpy op columns.

The north-star load path (BASELINE.json): instead of materializing one
Python ChangeOp per op and walking them into the op log, the change
chunk's own columnar encoding (reference: change/change_op_columns.rs) is
decoded straight into numpy arrays by the native codec core
(automerge_tpu/native/codecs.cpp) and assembled into the device column
layout. Strings (map keys, mark names) stay on the host path; scalar
payloads are kept as (type_code, offset, length) views into the raw value
buffer and materialized lazily on readback.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from .. import native
from ..storage.change import (
    COL_ACTION,
    COL_EXPAND,
    COL_INSERT,
    COL_KEY_ACTOR,
    COL_KEY_CTR,
    COL_KEY_STR,
    COL_MARK_NAME,
    COL_OBJ_ACTOR,
    COL_OBJ_CTR,
    COL_PRED_ACTOR,
    COL_PRED_CTR,
    COL_PRED_GROUP,
    COL_VAL_META,
    COL_VAL_RAW,
    StoredChange,
)
from ..types import ScalarValue
from ..utils.codecs import rle_decode
from ..utils.leb128 import decode_sleb, decode_uleb

# value-metadata type codes (storage/values.py) — identical to the OpLog
# TAG_* codes for 0..9; anything else maps to TAG_UNKNOWN at readback
_CODE_ULEB = 3
_INT_CODES = (3, 4, 8, 9)  # uint, int, counter, timestamp


from ..errors import AutomergeError


def _str_widths(raw: bytes, voff, vlen, vcode, n) -> "np.ndarray":
    """Per-row text widths in the configured index unit, vectorized over
    the raw value buffer (reference: text_value.rs width-per-encoding)."""
    from ..types import get_text_encoding

    width = np.ones(n, np.int32)
    if not len(raw):
        return width
    srows = vcode == 6
    enc = get_text_encoding()
    if enc == "utf8":
        width[srows] = vlen[srows].astype(np.int32)
        return width
    rb = np.frombuffer(raw, np.uint8)
    cont = np.concatenate([[0], np.cumsum((rb & 0xC0) == 0x80)])
    cps = (vlen[srows] - (cont[(voff + vlen)[srows]] - cont[voff[srows]])).astype(
        np.int32
    )
    if enc == "utf16":
        # supplementary-plane code points (4-byte UTF-8) take two units
        supp = np.concatenate([[0], np.cumsum((rb & 0xF8) == 0xF0)])
        cps = cps + (supp[(voff + vlen)[srows]] - supp[voff[srows]]).astype(np.int32)
    width[srows] = cps
    return width


class ExtractError(AutomergeError):
    pass


def change_arrays(change: StoredChange) -> Dict[str, np.ndarray]:
    """Decode one change's op columns to arrays (chunk-local actor idxs)."""
    cols = change.op_col_data
    if cols is None:
        raise ExtractError("change has no retained column data")

    def col(spec) -> bytes:
        return cols.get(spec, b"")

    n = len(change.ops)
    cap = n + 1

    action, amask = native.rle_decode_array(col(COL_ACTION), False, cap)
    if len(action) != n or not amask.all():
        raise ExtractError("action column mismatch")
    obj_ctr, obj_mask = _padded(*native.rle_decode_array(col(COL_OBJ_CTR), False, cap), n)
    obj_actor, obj_amask = _padded(*native.rle_decode_array(col(COL_OBJ_ACTOR), False, cap), n)
    key_ctr, key_ctr_mask = _padded(*native.delta_decode_array(col(COL_KEY_CTR), cap), n)
    key_actor, key_actor_mask = _padded(
        *native.rle_decode_array(col(COL_KEY_ACTOR), False, cap), n
    )
    insert = _padded_bool(native.bool_decode_array(col(COL_INSERT), cap), n)
    expand = _padded_bool(native.bool_decode_array(col(COL_EXPAND), cap), n)
    meta, meta_mask = _padded(*native.rle_decode_array(col(COL_VAL_META), False, cap), n)
    meta = np.where(meta_mask, meta, 0)

    pred_num, pn_mask = _padded(*native.rle_decode_array(col(COL_PRED_GROUP), False, cap), n)
    pred_num = np.where(pn_mask, pred_num, 0)
    total_preds = int(pred_num.sum())
    pred_ctr, pc_mask = native.delta_decode_array(col(COL_PRED_CTR), total_preds + 1)
    pred_actor, pa_mask = native.rle_decode_array(col(COL_PRED_ACTOR), False, total_preds + 1)
    if len(pred_ctr) < total_preds or len(pred_actor) < total_preds:
        raise ExtractError("truncated pred columns")
    if total_preds and not (pc_mask[:total_preds].all() and pa_mask[:total_preds].all()):
        raise ExtractError("null pred entries")

    # value payloads: code + (offset, length) views into the raw buffer
    raw = cols.get(COL_VAL_RAW, b"")
    vcode = (meta & 0xF).astype(np.int32)
    vlen = (meta >> 4).astype(np.int64)
    voff = np.concatenate([[0], np.cumsum(vlen)])[:-1]
    if len(vlen) and int(voff[-1] + vlen[-1]) > len(raw):
        raise ExtractError("value raw column overrun")

    # integer payloads (uint/int/counter/timestamp + booleans) decoded now —
    # the kernel needs them; str/bytes/f64 stay lazy
    value_int = np.zeros(n, np.int64)
    int_rows = np.flatnonzero(np.isin(vcode, _INT_CODES) & (vlen > 0))
    for r in int_rows:
        o = int(voff[r])
        if vcode[r] == _CODE_ULEB:
            value_int[r], _ = decode_uleb(raw, o)
        else:
            value_int[r], _ = decode_sleb(raw, o)
    value_int[vcode == 2] = 1  # true

    width = _str_widths(raw, voff, vlen, vcode, n)

    # string-ish host columns (map keys, mark names): python decode, cheap
    # because RLE runs collapse repeats; None = entirely-null column (the
    # common case for text workloads), letting callers skip per-row work
    ks_bytes = col(COL_KEY_STR)
    if ks_bytes:
        key_str = rle_decode(ks_bytes, "str", n)
        key_str += [None] * (n - len(key_str))
    else:
        key_str = None
    mn_bytes = col(COL_MARK_NAME)
    if mn_bytes:
        mark_name = rle_decode(mn_bytes, "str", n)
        mark_name += [None] * (n - len(mark_name))
    else:
        mark_name = None

    return {
        "n": n,
        "action": action.astype(np.int32),
        "obj_ctr": np.where(obj_mask, obj_ctr, 0),
        "obj_has": obj_mask & obj_amask,
        "obj_actor": np.where(obj_amask, obj_actor, 0),
        "key_ctr": np.where(key_ctr_mask, key_ctr, -1),
        "key_has_ctr": key_ctr_mask,
        "key_actor": np.where(key_actor_mask, key_actor, 0),
        "key_has_actor": key_actor_mask,
        "key_str": key_str,
        "insert": insert,
        "expand": expand,
        "vcode": vcode,
        "voff": voff.astype(np.int64),
        "vlen": vlen,
        "vraw": raw,
        "value_int": value_int,
        "width": width,
        "pred_num": pred_num.astype(np.int64),
        "pred_ctr": pred_ctr[:total_preds],
        "pred_actor": pred_actor[:total_preds],
        "mark_name": mark_name,
    }


def _col_batch(changes, spec):
    """(concatenated bytes, per-change offsets, per-change lengths)."""
    parts = []
    off = np.empty(len(changes), np.int64)
    ln = np.empty(len(changes), np.int64)
    pos = 0
    for i, ch in enumerate(changes):
        b = ch.op_col_data.get(spec, b"")
        off[i] = pos
        ln[i] = len(b)
        pos += len(b)
        parts.append(b)
    return b"".join(parts), off, ln


def _np_u8(buf: bytes) -> np.ndarray:
    return np.frombuffer(buf, np.uint8) if len(buf) else np.zeros(1, np.uint8)


_POOL = None
_POOL_INIT = False
_POOL_LOCK = threading.Lock()


def _decode_pool():
    """Shared column-decode thread pool, or None on effectively-single-CPU
    hosts (scheduler affinity, not raw core count — cgroup-limited
    containers report many cpu_count cores they cannot use)."""
    global _POOL, _POOL_INIT
    if not _POOL_INIT:
        with _POOL_LOCK:
            if _POOL_INIT:  # lost the race; another thread built it
                return _POOL
            import os

            try:
                n = len(os.sched_getaffinity(0))
            except AttributeError:  # non-Linux
                n = os.cpu_count() or 1
            if n > 1:
                from concurrent.futures import ThreadPoolExecutor

                _POOL = ThreadPoolExecutor(
                    max_workers=min(8, n), thread_name_prefix="am-decode"
                )
            _POOL_INIT = True
    return _POOL



def _strtab_decode(buf: bytes, off, ln, row_off, nc: int, n_rows: int):
    """Drive am_rle_decode_batch_strtab: (ids per row, string table)."""
    lib = native.load()
    ids = np.empty(max(n_rows, 1), np.int32)
    max_tab = 1 << 20
    tab_off = np.empty(max_tab, np.int64)
    tab_len = np.empty(max_tab, np.int64)
    bufa = _np_u8(buf)
    tn = lib.am_rle_decode_batch_strtab(
        native._u8(bufa), native._i64(off), native._i64(ln),
        native._i64(row_off), nc, native._i32(ids), native._i64(tab_off),
        native._i64(tab_len), max_tab,
    )
    if tn < 0:
        raise ExtractError(f"malformed string column ({tn})")
    table = [
        buf[int(tab_off[i]) : int(tab_off[i]) + int(tab_len[i])].decode("utf-8")
        for i in range(tn)
    ]
    return ids[:n_rows], table


def batch_arrays(changes) -> Dict[str, object]:
    """Decode ALL changes' op columns in one native pass per column kind.

    Output rows are change-concatenated (same order the one-change-at-a-time
    path produced); actor columns still carry chunk-local indices — the
    caller translates them with one table gather (ops/oplog.py).
    """
    import ctypes

    lib = native.load()
    if lib is None:
        raise native.NativeUnavailable("native codecs not available")
    nc = len(changes)
    n_ops = np.asarray([len(ch.ops) for ch in changes], np.int64)
    for ch in changes:
        if ch.op_col_data is None:
            raise ExtractError("change has no retained column data")
    row_off = np.concatenate([[0], np.cumsum(n_ops)]).astype(np.int64)
    N = int(row_off[-1])

    def rle(spec, signed=False):
        buf, off, ln = _col_batch(changes, spec)
        out = np.empty(max(N, 1), np.int64)
        mask = np.empty(max(N, 1), np.uint8)
        rc = lib.am_rle_decode_batch(
            native._u8(_np_u8(buf)), native._i64(off), native._i64(ln),
            native._i64(row_off), nc, int(signed), native._i64(out),
            native._u8(mask),
        )
        if rc != 0:
            raise ExtractError(f"malformed column {spec} in change {-rc - 1}")
        return out[:N], mask[:N].astype(bool)

    def delta(spec):
        buf, off, ln = _col_batch(changes, spec)
        out = np.empty(max(N, 1), np.int64)
        mask = np.empty(max(N, 1), np.uint8)
        rc = lib.am_delta_decode_batch(
            native._u8(_np_u8(buf)), native._i64(off), native._i64(ln),
            native._i64(row_off), nc, native._i64(out), native._u8(mask),
        )
        if rc != 0:
            raise ExtractError(f"malformed column {spec} in change {-rc - 1}")
        return out[:N], mask[:N].astype(bool)

    def boolean(spec):
        buf, off, ln = _col_batch(changes, spec)
        out = np.empty(max(N, 1), np.uint8)
        rc = lib.am_bool_decode_batch(
            native._u8(_np_u8(buf)), native._i64(off), native._i64(ln),
            native._i64(row_off), nc, native._u8(out),
        )
        if rc != 0:
            raise ExtractError(f"malformed column {spec} in change {-rc - 1}")
        return out[:N].astype(bool)

    def strtab(spec):
        buf, off, ln = _col_batch(changes, spec)
        if not len(buf):
            return None, []
        return _strtab_decode(buf, off, ln, row_off, nc, N)

    # One task list, two execution strategies: on multi-core hosts the
    # independent column decodes overlap in the shared thread pool (the
    # Python byte assembly holds the GIL but every native decode releases
    # it via ctypes); effectively-single-core hosts (cgroup affinity, like
    # the bench box) run the same list serially — a pool there is pure
    # overhead.
    tasks = [
        (rle, COL_ACTION), (rle, COL_OBJ_CTR), (rle, COL_OBJ_ACTOR),
        (delta, COL_KEY_CTR), (rle, COL_KEY_ACTOR), (boolean, COL_INSERT),
        (boolean, COL_EXPAND), (rle, COL_VAL_META), (strtab, COL_KEY_STR),
        (strtab, COL_MARK_NAME), (rle, COL_PRED_GROUP),
    ]
    # small batches (incremental deltas) run serially: the pool's submit/
    # wait round-trip costs more than the decodes themselves below ~16k ops
    pool = _decode_pool() if N >= (1 << 14) else None
    if pool is not None:
        futs = [pool.submit(fn, spec) for fn, spec in tasks]
        results = [f.result() for f in futs]
    else:
        results = [fn(spec) for fn, spec in tasks]
    (
        (action, amask), (obj_ctr, obj_mask), (obj_actor, obj_amask),
        (key_ctr, key_ctr_mask), (key_actor, key_actor_mask), insert,
        expand, (meta, meta_mask), (key_ids, key_table),
        (mark_ids, mark_table), (pred_num, pn_mask),
    ) = results
    if not amask.all():
        raise ExtractError("action column mismatch")
    meta = np.where(meta_mask, meta, 0)
    pred_num = np.where(pn_mask, pred_num, 0)
    pn_cum = np.concatenate([[0], np.cumsum(pred_num)]).astype(np.int64)
    per_change_preds = pn_cum[row_off[1:]] - pn_cum[row_off[:-1]]
    pred_row_off = np.concatenate([[0], np.cumsum(per_change_preds)]).astype(np.int64)
    Q = int(pred_row_off[-1])

    def pred_col(spec, is_delta):
        buf, off, ln = _col_batch(changes, spec)
        out = np.empty(max(Q, 1), np.int64)
        mask = np.empty(max(Q, 1), np.uint8)
        fn = lib.am_delta_decode_batch if is_delta else None
        if is_delta:
            rc = lib.am_delta_decode_batch(
                native._u8(_np_u8(buf)), native._i64(off), native._i64(ln),
                native._i64(pred_row_off), nc, native._i64(out), native._u8(mask),
            )
        else:
            rc = lib.am_rle_decode_batch(
                native._u8(_np_u8(buf)), native._i64(off), native._i64(ln),
                native._i64(pred_row_off), nc, 0, native._i64(out),
                native._u8(mask),
            )
        if rc != 0:
            raise ExtractError(f"malformed pred column {spec} in change {-rc - 1}")
        if Q and not mask[:Q].all():
            raise ExtractError("null pred entries")
        return out[:Q]

    pred_ctr = pred_col(COL_PRED_CTR, True)
    pred_actor = pred_col(COL_PRED_ACTOR, False)

    # value payloads: per-change raw buffers concatenated; offsets rebased
    raw, raw_off, raw_ln = _col_batch(changes, COL_VAL_RAW)
    vcode = (meta & 0xF).astype(np.int32)
    vlen = (meta >> 4).astype(np.int64)
    change_of_row = np.repeat(np.arange(nc), n_ops)
    vend = np.cumsum(vlen)
    voff = vend - vlen
    # rebase per change: local offset + that change's slice start in `raw`
    base = np.zeros(nc, np.int64)
    if N:
        base_local = voff[row_off[:-1].clip(max=max(N - 1, 0))]
        base_local[n_ops == 0] = 0
        base = base_local
    voff = voff - base[change_of_row] + raw_off[change_of_row]
    limit = (raw_off + raw_ln)[change_of_row]
    if N and np.any(voff + vlen > limit):
        raise ExtractError("value raw column overrun")

    # integer payloads (the kernel needs them eagerly)
    value_int = np.empty(max(N, 1), np.int64)
    rawa = _np_u8(raw)
    rc = lib.am_leb_decode_rows(
        native._u8(rawa), len(raw), native._i64(voff), native._i64(vlen),
        native._i32(vcode), N, native._i64(value_int),
    )
    if rc != 0:
        raise ExtractError(f"bad integer value payload at row {-rc - 1}")
    value_int = value_int[:N]

    width = _str_widths(raw, voff, vlen, vcode, N)

    return {
        "n": N,
        "n_ops": n_ops,
        "row_off": row_off,
        "raw_off": raw_off,
        "raw_ln": raw_ln,
        "change_of_row": change_of_row,
        "action": action.astype(np.int32),
        "obj_ctr": np.where(obj_mask, obj_ctr, 0),
        "obj_has": obj_mask & obj_amask,
        "obj_actor": np.where(obj_amask, obj_actor, 0),
        "key_ctr": np.where(key_ctr_mask, key_ctr, -1),
        "key_actor": np.where(key_actor_mask, key_actor, 0),
        "key_has_actor": key_actor_mask,
        "key_ids": key_ids,
        "key_table": key_table,
        "mark_ids": mark_ids,
        "mark_table": mark_table,
        "insert": insert,
        "expand": expand,
        "vcode": vcode,
        "voff": voff,
        "vlen": vlen,
        "vraw": raw,
        "value_int": value_int,
        "width": width,
        "pred_num": pred_num.astype(np.int64),
        "pred_ctr": pred_ctr,
        "pred_actor": pred_actor,
        "pred_row_off": pred_row_off,
    }


from ..types import ACTOR_BITS  # packed id layout: ctr << bits | actor rank


def ranked_batch(changes, rank_of) -> Dict[str, object]:
    """batch_arrays + packed-id rank translation, shared by the device log
    (ops/oplog.py) and the host bulk rebuild (core/bulk_load.py).

    Returns the raw batch under ``"a"`` plus the translated columns:
    ``id_key`` (per-op packed id), ``obj`` (0 = root), ``prop_ids``
    (string-table id, -1 = seq key), ``elem`` (-1 = map op, 0 = HEAD,
    else packed id), ``pred_src`` (source row per pred edge) and
    ``pred_key`` (packed pred target). Raises ExtractError when a
    chunk-local actor index exceeds its change's actor table.
    """
    a = batch_arrays(changes)
    N = a["n"]
    nc = len(changes)
    cor = a["change_of_row"]
    tab = np.asarray(
        [rank_of[bytes(x)] for ch in changes for x in ch.actors], np.int64
    )
    tab_off = np.concatenate(
        [[0], np.cumsum([len(ch.actors) for ch in changes])]
    )[:-1].astype(np.int64)
    row_tab = tab_off[cor]
    author = tab[tab_off] if nc else np.empty(0, np.int64)
    start_op = np.asarray([ch.start_op for ch in changes], np.int64)
    tab_size = np.asarray([len(ch.actors) for ch in changes], np.int64)
    if N and (
        np.any(a["obj_actor"][a["obj_has"]] >= tab_size[cor][a["obj_has"]])
        or np.any(
            a["key_actor"][a["key_has_actor"]] >= tab_size[cor][a["key_has_actor"]]
        )
    ):
        raise ExtractError("actor index out of chunk-local table range")

    within = np.arange(N, dtype=np.int64) - a["row_off"][:-1][cor]
    id_key = ((start_op[cor] + within) << ACTOR_BITS) | author[cor]
    clip = max(len(tab) - 1, 0)
    obj = np.where(
        a["obj_has"],
        (a["obj_ctr"] << ACTOR_BITS) | tab[(row_tab + a["obj_actor"]).clip(max=clip)],
        np.int64(0),
    )
    prop_ids = a["key_ids"] if a["key_ids"] is not None else np.full(N, -1, np.int32)
    elem = np.where(
        prop_ids >= 0,
        np.int64(-1),
        np.where(
            a["key_has_actor"],
            (a["key_ctr"] << ACTOR_BITS) | tab[(row_tab + a["key_actor"]).clip(max=clip)],
            np.int64(0),  # HEAD (ctr 0, no actor)
        ),
    )
    pred_src = np.repeat(np.arange(N, dtype=np.int64), a["pred_num"])
    per_change_preds = np.diff(a["pred_row_off"])
    cop = np.repeat(np.arange(nc), per_change_preds)
    if len(cop) and np.any(a["pred_actor"] >= tab_size[cop]):
        raise ExtractError("pred actor index out of chunk-local table range")
    pred_key = (a["pred_ctr"] << ACTOR_BITS) | tab[
        (tab_off[cop] + a["pred_actor"]).clip(max=clip)
    ]
    return {
        "a": a,
        "id_key": id_key,
        "obj": obj,
        "prop_ids": prop_ids,
        "elem": elem,
        "pred_src": pred_src,
        "pred_key": pred_key,
    }


def _padded(vals: np.ndarray, mask: np.ndarray, n: int):
    if len(vals) > n:
        raise ExtractError("column longer than op count")
    if len(vals) < n:
        vals = np.concatenate([vals, np.zeros(n - len(vals), vals.dtype)])
        mask = np.concatenate([mask, np.zeros(n - len(mask), bool)])
    return vals, mask


def _padded_bool(vals: np.ndarray, n: int) -> np.ndarray:
    if len(vals) > n:
        raise ExtractError("boolean column longer than op count")
    if len(vals) < n:
        vals = np.concatenate([vals, np.zeros(n - len(vals), bool)])
    return vals.astype(bool)


_TAG_NAME = {
    0: "null",
    3: "uint",
    4: "int",
    5: "f64",
    6: "str",
    7: "bytes",
    8: "counter",
    9: "timestamp",
}


def _value_cache_cap() -> int:
    import os

    return int(os.environ.get("AUTOMERGE_TPU_VALUE_CACHE", 1 << 16))


class LazyValues:
    """Row -> ScalarValue, materialized on demand from the raw value buffer.

    Drop-in for the eager python list the slow extraction path produces.
    The per-row cache is BOUNDED (``cap``, default 65536 entries, env knob
    AUTOMERGE_TPU_VALUE_CACHE): a long-lived DeviceDoc over a multi-million
    row log would otherwise accrete one ScalarValue per row ever read.
    Eviction is insertion-order FIFO (one dict pop); ``hits``/``misses``
    count cache effectiveness for the bench / trace output.
    """

    __slots__ = ("code", "off", "ln", "raw", "cache", "cap", "hits", "misses")

    def __init__(self, code: np.ndarray, off: np.ndarray, ln: np.ndarray,
                 raw: bytes, cap: Optional[int] = None):
        self.code = code
        self.off = off
        self.ln = ln
        self.raw = raw
        self.cache: Dict[int, ScalarValue] = OrderedDict()
        self.cap = _value_cache_cap() if cap is None else cap
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self.code)

    @property
    def nbytes(self) -> int:
        """Resident footprint of the heap (per-row index columns + the
        raw byte buffer) — the values side of the per-doc residency
        accounting (ops/compressed.py covers the op columns)."""
        return (
            self.code.nbytes + self.off.nbytes + self.ln.nbytes
            + len(self.raw)
        )

    def __getitem__(self, row: int) -> ScalarValue:
        v = self.cache.get(row)
        if v is None:
            self.misses += 1
            v = self._decode(row)
            if self.cap > 0:
                if len(self.cache) >= self.cap:
                    # O(1): next(iter(dict)) walks every slot already
                    # popped, so a plain dict's FIFO grows quadratic
                    self.cache.popitem(last=False)
                self.cache[row] = v
        else:
            self.hits += 1
        return v

    def stats(self) -> Dict[str, int]:
        return {
            "hits": self.hits, "misses": self.misses,
            "size": len(self.cache), "cap": self.cap,
        }

    def _decode(self, row: int) -> ScalarValue:
        import struct

        code = int(self.code[row])
        o = int(self.off[row])
        ln = int(self.ln[row])
        # the raw heap may be a (shared, append-only) bytearray; values
        # must come out as immutable bytes
        chunk = bytes(self.raw[o : o + ln])
        if code == 0:
            return ScalarValue("null")
        if code == 1:
            return ScalarValue("bool", False)
        if code == 2:
            return ScalarValue("bool", True)
        if code == 3:
            return ScalarValue("uint", decode_uleb(chunk, 0)[0])
        if code == 4:
            return ScalarValue("int", decode_sleb(chunk, 0)[0])
        if code == 5:
            return ScalarValue("f64", struct.unpack("<d", chunk)[0])
        if code == 6:
            return ScalarValue("str", chunk.decode("utf-8"))
        if code == 7:
            return ScalarValue("bytes", chunk)
        if code == 8:
            return ScalarValue("counter", decode_sleb(chunk, 0)[0])
        if code == 9:
            return ScalarValue("timestamp", decode_sleb(chunk, 0)[0])
        return ScalarValue("unknown", (code, chunk))


# -- per-change-hash extraction cache ----------------------------------------
# Sync re-delivers changes as FRESH StoredChange objects (parsed off the
# wire), so the per-object ``cached_cols`` memo never hits for them. This
# bounded hash-keyed cache makes a re-delivered (or re-parsed) change's
# column decode one dict hit. LRU by re-insertion; the cap bounds worst-case
# host memory at a few thousand decoded changes.

_CHANGE_COLS_CACHE: "OrderedDict[bytes, object]" = None  # type: ignore[assignment]
_CHANGE_COLS_CAP = 4096


def _change_cache() -> "OrderedDict[bytes, object]":
    global _CHANGE_COLS_CACHE
    if _CHANGE_COLS_CACHE is None:
        from collections import OrderedDict

        _CHANGE_COLS_CACHE = OrderedDict()
    return _CHANGE_COLS_CACHE


def cached_cols_for_hash(h: Optional[bytes]):
    """Decoded ChangeCols for a change hash, or None (counts hit/miss)."""
    from .. import obs

    if h is None:
        return None
    cache = _change_cache()
    cc = cache.get(h)
    if cc is not None:
        cache.move_to_end(h)
        obs.count("extract.change_cache_hit")
    else:
        obs.count("extract.change_cache_miss")
    return cc


def remember_cols_for_hash(h: Optional[bytes], cc) -> None:
    if h is None or cc is None:
        return
    cache = _change_cache()
    cache[h] = cc
    cache.move_to_end(h)
    while len(cache) > _CHANGE_COLS_CAP:
        cache.popitem(last=False)


def doc_op_arrays(col_data) -> Dict[str, object]:
    """Decode document-chunk op columns (storage/document.py OP_*) into
    numpy arrays via the native codec core — the fast load path's input.

    Strict about shape regularities the encoder always produces (action
    column defines the row count and every other column covers or
    null-pads it); anything irregular raises ExtractError and the caller
    falls back to the per-op python decoder, which reports precise
    errors for genuinely malformed files.
    """
    from ..storage import document as D

    lib = native.load()
    if lib is None:
        raise native.NativeUnavailable("native codecs not available")

    def col(s) -> bytes:
        return col_data.get(s, b"")

    def rle_full(buf, signed=False):
        cap = max(1024, len(buf))
        while True:
            v, m = native.rle_decode_array(buf, signed, cap)
            if len(v) < cap:
                return v, m
            cap *= 4

    def delta_full(buf):
        cap = max(1024, len(buf))
        while True:
            v, m = native.delta_decode_array(buf, cap)
            if len(v) < cap:
                return v, m
            cap *= 4

    action, amask = rle_full(col(D.OP_ACTION))
    n = len(action)
    if n == 0 or not amask.all():
        raise ExtractError("doc ops: empty or null action column")

    def pad_to_n(v, m):
        if len(v) > n:
            raise ExtractError("doc ops: column longer than action column")
        if len(v) < n:
            v2 = np.zeros(n, v.dtype)
            v2[: len(v)] = v
            m2 = np.zeros(n, bool)
            m2[: len(m)] = m
            return v2, m2
        return v, m

    id_ctr, id_cm = pad_to_n(*delta_full(col(D.OP_ID_CTR)))
    id_actor, id_am = pad_to_n(*rle_full(col(D.OP_ID_ACTOR)))
    if not (id_cm.all() and id_am.all()):
        raise ExtractError("doc ops: missing id column values")
    obj_ctr, obj_cm = pad_to_n(*rle_full(col(D.OP_OBJ_CTR)))
    obj_actor, obj_am = pad_to_n(*rle_full(col(D.OP_OBJ_ACTOR)))
    if not np.array_equal(obj_cm, obj_am):
        raise ExtractError("doc ops: half-null object id")
    key_ctr, key_cm = pad_to_n(*delta_full(col(D.OP_KEY_CTR)))
    key_actor, key_am = pad_to_n(*rle_full(col(D.OP_KEY_ACTOR)))

    def bools(buf):
        out = native.bool_decode_array(buf, n)
        if len(out) < n:
            out = np.concatenate([out, np.zeros(n - len(out), bool)])
        return out.astype(np.uint8)

    insert = bools(col(D.OP_INSERT))
    expand = bools(col(D.OP_EXPAND))

    def strtab(buf):
        if not len(buf):
            return np.full(n, -1, np.int32), []
        return _strtab_decode(
            buf, np.zeros(1, np.int64), np.asarray([len(buf)], np.int64),
            np.asarray([0, n], np.int64), 1, n,
        )

    key_ids, key_table = strtab(col(D.OP_KEY_STR))
    mark_ids, mark_table = strtab(col(D.OP_MARK_NAME))

    vm, vmm = pad_to_n(*rle_full(col(D.OP_VAL_META)))
    if not vmm.all():
        raise ExtractError("doc ops: null value metadata")
    vcode = (vm & 15).astype(np.int32)
    vlen = (vm >> 4).astype(np.int64)
    voff = np.concatenate([[0], np.cumsum(vlen)[:-1]]).astype(np.int64)
    raw = col(D.OP_VAL_RAW)
    if n and int(voff[-1] + vlen[-1]) > len(raw):
        raise ExtractError("doc ops: value raw column overrun")

    succ_num, snm = pad_to_n(*rle_full(col(D.OP_SUCC_GROUP)))
    succ_num = np.where(snm, succ_num, 0).astype(np.int64)
    total = int(succ_num.sum())
    sa, sam = rle_full(col(D.OP_SUCC_ACTOR))
    sc, scm = delta_full(col(D.OP_SUCC_CTR))
    if len(sa) < total or len(sc) < total:
        raise ExtractError("doc ops: truncated succ columns")
    if not (sam[:total].all() and scm[:total].all()):
        raise ExtractError("doc ops: null succ id")

    return {
        "n": n,
        "action": action.astype(np.int64),
        "id_ctr": id_ctr.astype(np.int64),
        "id_actor": id_actor.astype(np.int64),
        "obj_ctr": np.where(obj_cm, obj_ctr, 0).astype(np.int64),
        "obj_actor": np.where(obj_am, obj_actor, 0).astype(np.int64),
        "obj_mask": obj_cm,
        "key_ctr": key_ctr.astype(np.int64),
        "key_ctr_mask": key_cm,
        "key_actor": np.where(key_am, key_actor, 0).astype(np.int64),
        "key_actor_mask": key_am,
        "key_ids": key_ids,
        "key_table": key_table,
        "mark_ids": mark_ids,
        "mark_table": mark_table,
        "insert": insert,
        "expand": expand,
        "vcode": vcode,
        "vlen": vlen,
        "voff": voff,
        "vraw": raw,
        "succ_num": succ_num,
        "succ_ctr": sc[:total].astype(np.int64),
        "succ_actor": sa[:total].astype(np.int64),
    }


def validate_doc_arrays(a, n_actors: int) -> None:
    """Bounds/magnitude guards over doc_op_arrays output: actor indices in
    [0, n_actors), counters within the 43-bit packed-id range. Raises
    ExtractError — callers fall back to the per-op python decoder, which
    reports the canonical error for genuinely malformed files."""
    lim = 1 << 43

    def ctr_ok(v, mask=None):
        if mask is not None:
            v = v[mask]
        if len(v) and (int(v.min()) < 0 or int(v.max()) >= lim):
            raise ExtractError("counter outside packed range")

    def actor_ok(v, mask=None):
        if mask is not None:
            v = v[mask]
        if len(v) and (int(v.min()) < 0 or int(v.max()) >= n_actors):
            raise ExtractError("actor index out of range")

    ctr_ok(a["id_ctr"])
    ctr_ok(a["succ_ctr"])
    ctr_ok(a["obj_ctr"], a["obj_mask"].astype(bool))
    ctr_ok(a["key_ctr"], a["key_ctr_mask"].astype(bool))
    actor_ok(a["id_actor"])
    actor_ok(a["succ_actor"])
    actor_ok(a["obj_actor"], a["obj_mask"].astype(bool))
    actor_ok(a["key_actor"], a["key_actor_mask"].astype(bool))
