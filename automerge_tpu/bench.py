"""Benchmark workload builders + the native sequential-apply baseline.

Workloads mirror the reference's benchmark surface (BASELINE.md configs;
reference harnesses: rust/edit-trace/src/main.rs, rust/automerge/benches/
{map,sync}.rs) at real scale:

  1. replay      — the full 259,778-op edit trace through the host
                   transaction layer (edit-trace/src/main.rs:23-55)
  2. fanin       — N genuinely divergent replicas of the trace document,
                   merged (automerge.rs:460,917 fork/merge)
  3. mapcounter  — many actors concurrently incrementing shared counters +
                   conflicting map puts (pure commutative merge)
  4. rga         — many actors interleaving insert/delete on one sequence
  5. sync        — two replicas with a large divergence catching up over
                   generate/receive_sync_message (sync.rs:25-68)

Replica changes are synthesized directly at the change level — each replica
gets a distinct actor, distinct anchor positions, and distinct payload
drawn from its own trace slice, with deps = the base heads. This is the
same byte format a real fork would commit (build_change recomputes columns
and hashes), without paying a full per-replica document replay.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .api import AutoDoc
from .storage.change import HEAD_STORED, ROOT_STORED, ChangeOp, Key, StoredChange, build_change
from .types import ActorId, ObjType, ScalarValue

# Automerge's rust/edit-trace replays 259,778 keystroke edits (the LaTeX
# source of a paper, typed over many sessions); every text workload here
# has that length and a generated trace of that shape
EDIT_TRACE_EDITS = 259_778

_ACTION_PUT = 1
_ACTION_DELETE = 3
_ACTION_INCREMENT = 5

_TRACE_ALPHABET = np.frombuffer(b"etaoinshrdlcumwfgypbvk        \n", np.uint8)


def synth_edit_trace(n_edits: int = EDIT_TRACE_EDITS, seed: int = 0) -> list:
    """A seeded keystroke-shaped editing trace in edit-trace's format:
    ``[pos, 0, ch]`` inserts one character, ``[pos, 1]`` deletes one.

    The shape (all parameters assumed, not fitted): the cursor types runs
    of geometric length (mean 12); half of them follow a backspace burst
    (mean 10), which puts inserts at ~70% of edits as in edit-trace; between runs
    jumps a short distance (80%, within ±64 characters) or anywhere in the
    document (20%), so the trace has typing runs, cursor locality and
    backspace bursts. Deterministic in ``seed``."""
    rng = np.random.default_rng(seed)
    edits: list = []
    length = 0
    cur = 0
    while len(edits) < n_edits:
        if length and rng.random() < 0.5:
            burst = min(int(rng.geometric(1 / 10)), cur)
            for _ in range(burst):
                cur -= 1
                edits.append([cur, 1])
            length -= burst
        run = int(rng.geometric(1 / 12))
        chars = _TRACE_ALPHABET[rng.integers(0, len(_TRACE_ALPHABET), run)]
        for ch in chars.tobytes().decode():
            edits.append([cur, 0, ch])
            cur += 1
        length += run
        if rng.random() < 0.8:
            cur = int(np.clip(cur + rng.integers(-64, 65), 0, length))
        else:
            cur = int(rng.integers(0, length + 1))
    return edits[:n_edits]


def apply_edits(doc: AutoDoc, text_obj: str, edits: Iterable) -> int:
    """Replay trace edits; returns the number of ops issued.

    Mirrors the reference replay loop (rust/edit-trace/src/main.rs:23-31):
    one splice_text call per edit, no per-edit length query — the length
    used for clamping synthetic traces is tracked arithmetically."""
    from .types import str_width

    n = 0
    ln = doc.length(text_obj)
    splice = doc.splice_text
    for e in edits:
        pos = e[0]
        if pos > ln:
            pos = ln
        ndel = e[1]
        if ndel > ln - pos:
            ndel = ln - pos
        text = "".join(e[2:])
        splice(text_obj, pos, ndel, text)
        w = str_width(text)
        ln += w - ndel
        n += ndel + len(text)
    return n


class BaseInfo:
    """Everything the synthesizers need to know about the base document."""

    def __init__(self, doc: AutoDoc, text_exid: str):
        d = doc.doc
        self.doc = doc
        self.text_exid = text_exid
        self.heads = d.get_heads()
        self.max_op = d.max_op
        self.changes = [a.stored for a in d.history]
        ctr_s, actor_hex = text_exid.split("@", 1)
        self.text_obj: Tuple[int, bytes] = (int(ctr_s), bytes.fromhex(actor_hex))
        # visible elements in document order as (counter, actor bytes)
        info = d.ops.get_obj(d.import_obj(text_exid))
        elems: List[Tuple[int, bytes]] = []
        for el in info.data.elements():
            if el.winner() is not None:
                eid = el.elem_id
                elems.append((eid[0], d.actors.get(eid[1]).bytes))
        self.elems = elems


def build_base(trace: Sequence, n_edits: int) -> BaseInfo:
    base = AutoDoc(actor=ActorId(bytes([1]) * 16))
    text = base.put_object("_root", "text", ObjType.TEXT)
    # bulk native ingest: the same change bytes as an apply_edits replay
    base.splice_text_many(text, trace[:n_edits])
    base.commit()
    return BaseInfo(base, text)


def _replica_actor(i: int) -> bytes:
    return b"\x03" + i.to_bytes(3, "big") + bytes(12)


def synth_seq_change(
    base: BaseInfo,
    actor: bytes,
    edits: Sequence,
    seed: int,
) -> StoredChange:
    """One replica's divergent change against ``base``: trace-slice edits
    re-anchored onto the base document's element ids.

    Inserts chain off one another exactly as a replayed splice would
    (transaction/inner.rs:672-683); deletes pred the element's insert op
    (elements of a pure-splice doc are never overwritten). Anchors come
    from the slice's own positions, so every replica diverges genuinely.
    """
    rng = np.random.default_rng(seed)
    n_base = len(base.elems)
    # chunk-local actor table: author first, then referenced others sorted
    others = sorted(({a for _, a in base.elems} | {base.text_obj[1]}) - {actor})
    local = {actor: 0, **{a: i + 1 for i, a in enumerate(others)}}
    obj = (base.text_obj[0], local[base.text_obj[1]])

    ops: List[ChangeOp] = []
    ctr = base.max_op  # ids start at max_op + 1
    deleted: set = set()
    last_insert: Optional[Tuple[int, int]] = None
    last_insert_pos = -2
    for e in edits:
        pos = min(int(e[0]), max(n_base - 1, 0))
        text = "".join(e[2:])
        if e[1] and n_base:
            # delete a not-yet-deleted base element near the trace position
            k = pos
            for _ in range(8):
                if k not in deleted and k < n_base:
                    break
                k = int(rng.integers(0, n_base))
            if k in deleted or k >= n_base:
                continue
            deleted.add(k)
            ec, ea = base.elems[k]
            elem = (ec, local[ea])
            ctr += 1
            ops.append(
                ChangeOp(
                    obj=obj,
                    key=Key.seq(elem),
                    insert=False,
                    action=_ACTION_DELETE,
                    value=ScalarValue("null"),
                    pred=[elem],
                )
            )
        for ch in text:
            if last_insert is not None and pos == last_insert_pos + 1:
                elem = last_insert  # chain onto our own previous insert
            elif pos == 0 or n_base == 0:
                elem = HEAD_STORED
            else:
                ec, ea = base.elems[min(pos - 1, n_base - 1)]
                elem = (ec, local[ea])
            ctr += 1
            ops.append(
                ChangeOp(
                    obj=obj,
                    key=Key.seq(elem),
                    insert=True,
                    action=_ACTION_PUT,
                    value=ScalarValue("str", ch),
                )
            )
            last_insert = (ctr, 0)
            last_insert_pos = pos
            pos += 1
    return build_change(
        StoredChange(
            dependencies=list(base.heads),
            actor=actor,
            other_actors=others,
            seq=1,
            start_op=base.max_op + 1,
            timestamp=0,
            message=None,
            ops=ops,
        )
    )


def synth_fanin(
    base: BaseInfo, trace: Sequence, n_replicas: int, per_replica: int, offset: int
) -> List[StoredChange]:
    """Config 2: N divergent replicas, each replaying its own trace slice.

    Slices wrap within [offset/2, end) — the full-trace base leaves no
    tail, and the LATE trace is what carries real editing behavior
    (cursor jumps, deletes, spread positions). Early-trace slices are
    pure sequential typing whose inserts all chain locally, which would
    flatter every engine's fast path and measure nothing."""
    out = []
    lo0 = min(offset // 2, max(len(trace) - per_replica - 1, 0))
    span = max(len(trace) - lo0 - per_replica, 1)
    for i in range(n_replicas):
        lo = lo0 + (offset // 2 + i * per_replica) % span
        out.append(
            synth_seq_change(
                base, _replica_actor(i), trace[lo : lo + per_replica], seed=1000 + i
            )
        )
    return out


def synth_rga(
    base: BaseInfo, n_actors: int, ops_per_actor: int
) -> List[StoredChange]:
    """Config 4: interleaved insert/delete storms on one shared sequence."""
    out = []
    n_base = len(base.elems)
    for i in range(n_actors):
        rng = np.random.default_rng(7000 + i)
        edits = []
        for j in range(ops_per_actor):
            pos = int(rng.integers(0, max(n_base, 1)))
            if j % 3 == 2:
                edits.append([pos, 1])
            else:
                edits.append([pos, 0, chr(97 + (i + j) % 26)])
        out.append(synth_seq_change(base, _replica_actor(i), edits, seed=7000 + i))
    return out


def build_counter_base(n_counters: int) -> Tuple[AutoDoc, List[str]]:
    doc = AutoDoc(actor=ActorId(bytes([1]) * 16))
    keys = [f"c{j}" for j in range(n_counters)]
    for k in keys:
        doc.put("_root", k, ScalarValue("counter", 0))
    doc.commit()
    return doc, keys


def synth_mapcounter(
    doc: AutoDoc, keys: List[str], n_actors: int, incs_per_actor: int
) -> Tuple[List[StoredChange], Dict[str, int]]:
    """Config 3: many actors increment shared counters + conflicting puts.

    Increment preds name the counter put op (transaction.rs increment path);
    every replica also puts a few shared map keys so the merge resolves real
    conflicts, not just commutative adds. Returns (changes, expected
    per-key counter totals) so callers can verify the merge exactly.

    Changes are built straight at the column level (the array-native
    ``build_change(cols=...)`` path also used by document load) — one
    replica's whole op block is numpy arrays, never ChangeOp objects, so
    synthesizing the BASELINE-scale 1M-op divergence takes ~1s instead of
    dominating the config's wall time.
    """
    from .storage.change import LazyOps, encode_change_cols_arrays

    d = doc.doc
    base_heads = sorted(d.get_heads())
    base_max = d.max_op
    base_actor = d.actor.bytes
    # counter put op ids in commit order: root puts are ops 1..n by actor 1
    put_id: Dict[str, Tuple[int, bytes]] = {}
    info = d.ops.get_obj((0, 0))
    for prop_idx, run in info.data.props.items():
        name = d.props.get(prop_idx)
        for op in run:
            put_id[name] = (op.id[0], d.actors.get(op.id[1]).bytes)

    # one rng for the whole workload (deterministic, vectorized)
    rng = np.random.default_rng(3000)
    picks = rng.integers(0, len(keys), (n_actors, incs_per_actor))
    counts = np.bincount(picks.reshape(-1), minlength=len(keys))
    expected = {k: int(counts[j]) for j, k in enumerate(keys) if counts[j]}

    # column templates shared by every replica change: incs then 4 puts
    m = incs_per_actor + 4
    key_table = list(keys) + [f"w{j}" for j in range(4)]
    put_ctr = np.asarray([put_id[k][0] for k in keys], np.int64)
    zeros = np.zeros(m, np.int64)
    zeros_u8 = np.zeros(m, np.uint8)
    action = np.concatenate([
        np.full(incs_per_actor, _ACTION_INCREMENT, np.int64),
        np.full(4, _ACTION_PUT, np.int64),
    ])
    pred_num = np.concatenate([
        np.ones(incs_per_actor, np.int64), np.zeros(4, np.int64)
    ])
    # increments carry int 1 (sleb 0x01, meta 0x14); puts carry int i
    inc_meta = np.full(incs_per_actor, (1 << 4) | 4, np.int64)
    inc_raw = b"\x01" * incs_per_actor
    mark_ids = np.full(m, -1, np.int64)

    from .utils.leb128 import sleb_bytes

    # 12 of the 14 columns are identical across replicas (obj, key-elem,
    # insert, action, expand, marks, pred_actor/num, ...) — encode them ONCE
    # via the shared array-native encoder, then per replica only the three
    # varying columns (key ids, pred counters, value payload) are rebuilt.
    template = encode_change_cols_arrays(
        {
            "obj_mask": zeros_u8,
            "obj_ctr": zeros,
            "obj_actor": zeros,
            "key_str_ids": np.concatenate(
                [picks[0], np.arange(len(keys), len(keys) + 4)]
            ),
            "key_str_table": key_table,
            "key_ctr": zeros,
            "key_ctr_mask": zeros_u8,
            "key_actor": zeros,
            "key_actor_mask": zeros_u8,
            "insert": zeros_u8,
            "action": action,
            "val_meta": np.concatenate([inc_meta, np.full(4, (1 << 4) | 4, np.int64)]),
            "val_raw": b"",
            "pred_num": pred_num,
            "pred_ctr": put_ctr[picks[0]],
            "pred_actor": np.ones(incs_per_actor, np.int64),  # base actor
            "expand": zeros_u8,
            "mark_ids": mark_ids,
            "mark_table": [],
        }
    )
    from .storage.change import (
        COL_KEY_STR, COL_PRED_CTR, COL_VAL_META, COL_VAL_RAW,
    )
    base_cols = dict(template)
    # the varying columns are rebuilt per replica below; drop them from the
    # shared template so any accidental reliance fails loudly
    for _c in (COL_KEY_STR, COL_PRED_CTR, COL_VAL_META, COL_VAL_RAW):
        base_cols.pop(_c, None)
    key_tail = np.arange(len(keys), len(keys) + 4)
    ones_p = np.ones(incs_per_actor, np.uint8)
    meta_cache: Dict[int, bytes] = {}
    from . import native as _native

    out = []
    for i in range(n_actors):
        actor = _replica_actor(i)
        put_raw = sleb_bytes(i)
        put_meta = (len(put_raw) << 4) | 4
        vm = meta_cache.get(put_meta)
        if vm is None:
            vm = _native.rle_encode_array(
                np.concatenate([inc_meta, np.full(4, put_meta, np.int64)]),
                np.ones(m, np.uint8), False,
            )
            meta_cache[put_meta] = vm
        cols_d = dict(base_cols)
        cols_d[COL_KEY_STR] = _native.rle_encode_strtab(
            np.concatenate([picks[i], key_tail]), key_table
        )
        cols_d[COL_PRED_CTR] = _native.delta_encode_array(put_ctr[picks[i]], ones_p)
        cols_d[COL_VAL_META] = vm
        cols_d[COL_VAL_RAW] = inc_raw + put_raw * 4
        cols = sorted(cols_d.items())  # chunk columns must ascend by spec
        sc = StoredChange(
            dependencies=list(base_heads),
            actor=actor,
            other_actors=[base_actor],
            seq=1,
            start_op=base_max + 1,
            timestamp=0,
            message=None,
            ops=LazyOps(cols_d, m),
        )
        out.append(build_change(sc, cols=cols))
    return out, expected


def synth_delta_chain(
    base: BaseInfo, trace_edits: Sequence, k: int, ops_per_delta: int,
    offset: int, actor: Optional[bytes] = None,
) -> List[List[StoredChange]]:
    """The incremental workload: K successive small deltas from ONE editing
    replica against a large resident base — each delta is one change whose
    deps chain off the previous delta (seq ascending), exactly what a live
    peer streams over sync. Returns K single-change batches."""
    import copy

    actor = actor if actor is not None else _replica_actor(0)
    out: List[List[StoredChange]] = []
    cur = copy.copy(base)  # shallow view; heads/max_op advance per delta
    lo0 = min(offset // 2, max(len(trace_edits) - ops_per_delta - 1, 0))
    span = max(len(trace_edits) - lo0 - ops_per_delta, 1)
    for i in range(k):
        lo = lo0 + (offset // 2 + i * ops_per_delta) % span
        ch = synth_seq_change(
            cur, actor, trace_edits[lo : lo + ops_per_delta], seed=5000 + i
        )
        if i > 0:  # the committing replica's seq advances along the chain
            ch = build_change(
                StoredChange(
                    dependencies=list(cur.heads),
                    actor=actor,
                    other_actors=ch.other_actors,
                    seq=i + 1,
                    start_op=cur.max_op + 1,
                    timestamp=0,
                    message=None,
                    ops=ch.ops,
                )
            )
        cur = copy.copy(cur)
        cur.heads = [ch.hash]
        cur.max_op = ch.max_op
        out.append([ch])
    return out


# -- the native sequential-apply baseline -----------------------------------


def seq_apply_baseline(
    changes: Sequence[StoredChange], query_obj: Tuple[int, bytes],
    reps: int = 1,
):
    """Run the native sequential apply over ``changes``; returns
    (best-of-``reps`` elapsed seconds, merged text of query_obj).

    The measured equivalent of the reference's sequential Rust
    ``apply_changes`` loop on this host (see BASELINE.md for how this is
    used as the honest baseline). The timed region covers the SAME input
    boundary the framework side is measured from — change chunks with
    retained column bytes — so it includes the columnar change decode
    (reference: change_op_columns.rs iter_ops feeds every applied op) and
    the actor-rank import (automerge.rs:860 import_ops), both via the
    same native codec core the framework uses. It does NOT include the
    reference's B-tree index maintenance or per-op tree seeks beyond a
    hash lookup + Lamport sibling scan, which keeps the model generous
    (faster than the reference), hence the conservative max() with the
    pin. ``reps`` takes the minimum like the framework side's loop.
    """
    import numpy as np

    from . import native
    from .ops.extract import ranked_batch
    from .ops.oplog import ACTOR_BITS

    dt = float("inf")
    flat = None
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        # decode + import: chunk column bytes -> flat causal-order arrays
        actor_bytes = sorted({bytes(a) for ch in changes for a in ch.actors})
        rank_of = {a: i for i, a in enumerate(actor_bytes)}
        r = ranked_batch(list(changes), rank_of)
        a = r["a"]
        n = a["n"]
        prop = r["prop_ids"].astype(np.int32)
        # am_seq_apply's elem convention: 0 = HEAD / map op
        elem = np.where(r["elem"] > 0, r["elem"], 0)
        pred_off = np.bincount(
            r["pred_src"] + 1, minlength=n + 1
        ).cumsum().astype(np.int64)
        # pred edges arrive grouped by source row already (change order)
        rows = native.seq_apply(
            r["id_key"], r["obj"], elem, prop,
            a["action"].astype(np.int32), a["insert"].astype(np.uint8),
            (a["vcode"] == 8).astype(np.uint8),
            pred_off, r["pred_key"],
            (query_obj[0] << ACTOR_BITS) | rank_of[query_obj[1]],
        )
        if time.perf_counter() - t0 < dt:
            dt = time.perf_counter() - t0
            flat = (a, rows)
    a, rows = flat
    from .ops.extract import LazyValues

    vals = LazyValues(a["vcode"], a["voff"], a["vlen"], a["vraw"])
    text = "".join(
        vals[int(r)].value if a["vcode"][r] == 6 else "￼" for r in rows
    )
    return dt, text
