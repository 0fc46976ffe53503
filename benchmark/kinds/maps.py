"""Deployment kind ``maps``: ``docs`` durable map documents of a local-first
app. Each is a root map of ``root_keys`` scalar keys and ``child_maps``
child maps of ``child_keys`` scalar keys each, written by one creating
actor in one change of ``ops_per_doc`` ops: the creating puts, then
overwrites of keys drawn Zipf(``zipf``) over the document's scalar keys.

Its plain reference is here: a last-writer-wins map (``MapRef``), written
from Automerge's published data model and importing nothing of the
program. For each (object, key) the visible values are the puts that no
applied op lists as a predecessor; the winner is the greatest op id, by
counter and then by actor bytes. So is its change encoder
(``MapEncoder``), which writes abstract map ops as change bytes with the
program's own chunk writer, as a client library would, and ``compare``.

Abstract ops, with ids as ``reference.op_id`` ints and ``ROOT`` for the
root object: ``("make", obj, key)`` makes a child map, ``("put", obj,
key, value, preds)`` and ``("del", obj, key, preds)``; op ``i`` of a change
gets counter ``start_op + i``. Nothing here imports JAX.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference as R
from benchmark import synth as S
from benchmark.deploy import Doc

ROOT = 0
MAP = ("map",)  # the value of a key that holds a child map
_MASK = (1 << R.ACTOR_BITS) - 1
ACTION_MAKE_MAP, ACTION_PUT, ACTION_DELETE = 0, 1, 3


class MapRef:
    """The visible ops of every (object, key): ``{op id: value}``."""

    def __init__(self):
        self.vis = {}

    def apply(self, eid, op):
        kind, obj, key = op[0], op[1], op[2]
        cell = self.vis.setdefault((obj, key), {})
        if kind != "make":
            for p in op[-1]:
                cell.pop(p, None)
        if kind == "put":
            cell[eid] = op[3]
        elif kind == "make":
            cell[eid] = MAP

    def visible(self, obj, key):
        """The visible op ids of (``obj``, ``key``), ascending."""
        return sorted(self.vis.get((obj, key), ()))

    def materialize(self, obj=ROOT):
        """The winners under ``obj`` as plain values, child maps nested."""
        out = {}
        for (o, key), cell in self.vis.items():
            if o != obj or not cell:
                continue
            w = max(cell)
            out[key] = self.materialize(w) if cell[w] == MAP else cell[w]
        return out


def actor(d, rank) -> bytes:
    """The actor of rank ``rank`` in document ``d``: its creating actor
    (rank 0), then its replicas in the order of their bytes."""
    if rank == 0:
        return S.BASE_ACTOR
    return bytes([0x14]) + d.to_bytes(4, "big") + rank.to_bytes(4, "big") \
        + bytes(7)


def id_text(eid, d) -> str:
    """An op id of document ``d`` as the program exports it:
    ``counter@actor-hex``."""
    return f"{eid >> R.ACTOR_BITS}@{actor(d, eid & _MASK).hex()}"


class MapEncoder:
    """Turns abstract map ops into change chunks. ``actors`` lists every
    actor of the document sorted by bytes, so an id's rank names its
    actor."""

    def __init__(self, actors):
        from automerge_tpu.storage.change import (
            ROOT_STORED, ChangeOp, StoredChange, build_change,
        )
        from automerge_tpu.types import Key, ScalarValue

        self.actors = sorted(actors)
        self._k = (ROOT_STORED, ChangeOp, StoredChange, build_change, Key,
                   ScalarValue)

    def change(self, actor, seq, start_op, deps, ops) -> bytes:
        ROOT_STORED, ChangeOp, StoredChange, build_change, Key, SV = self._k
        used = {self.actors[e & _MASK] for op in ops
                for e in [op[1], *(op[-1] if op[0] != "make" else ())]
                if e != ROOT}
        others = sorted(used - {actor})
        local = {actor: 0, **{a: i + 1 for i, a in enumerate(others)}}

        def ref(e):
            return (e >> R.ACTOR_BITS, local[self.actors[e & _MASK]])

        out = []
        for op in ops:
            obj = ROOT_STORED if op[1] == ROOT else ref(op[1])
            if op[0] == "make":
                out.append(ChangeOp(obj=obj, key=Key.map(op[2]), insert=False,
                                    action=ACTION_MAKE_MAP, value=SV("null")))
            elif op[0] == "put":
                v = op[3]
                out.append(ChangeOp(
                    obj=obj, key=Key.map(op[2]), insert=False,
                    action=ACTION_PUT,
                    value=SV("str", v) if isinstance(v, str) else SV("int", v),
                    pred=[ref(p) for p in op[4]]))
            else:
                out.append(ChangeOp(obj=obj, key=Key.map(op[2]), insert=False,
                                    action=ACTION_DELETE, value=SV("null"),
                                    pred=[ref(p) for p in op[3]]))
        ch = build_change(StoredChange(
            dependencies=list(deps), actor=actor, other_actors=others,
            seq=seq, start_op=start_op, timestamp=0, message=None, ops=out))
        return ch.raw_bytes


def shape(cfg):
    """The scalar keys ``[(object slot, key)]`` (slot 0 the root, slot
    ``j + 1`` child map ``j``) and the child maps' keys in the root."""
    keys = [(0, f"k{i}") for i in range(cfg["root_keys"])]
    keys += [(j + 1, f"f{i}") for j in range(cfg["child_maps"])
             for i in range(cfg["child_keys"])]
    return keys, [f"m{j}" for j in range(cfg["child_maps"])]


def zipf_keys(r, order, n, s):
    """``n`` key indices drawn Zipf(``s``) over the ranks of ``order``."""
    w = 1.0 / np.arange(1, len(order) + 1) ** s
    return np.asarray(order)[r.choice(len(order), size=n, p=w / w.sum())]


def value(r):
    """A scalar as an app writes one: an int or a short string."""
    if r.random() < 0.5:
        return int(r.integers(0, 1 << 30))
    return "".join(chr(97 + c) for c in r.integers(0, 26, int(r.integers(3, 13))))


def base_doc(cfg, path):
    """(change bytes, reference, hot-key order, child map ids) of one
    document that its creating actor wrote in one change."""
    r = np.random.default_rng(path)
    keys, makes = shape(cfg)
    ops, objs = [], [ROOT]
    for slot, key in keys:
        if slot == 0:
            ops.append(("put", ROOT, key, value(r), []))
    for j, m in enumerate(makes):
        objs.append(R.op_id(len(ops) + 1, 0))
        ops.append(("make", ROOT, m))
        for slot, key in keys:
            if slot == j + 1:
                ops.append(("put", objs[-1], key, value(r), []))
    ref = MapRef()
    for i, op in enumerate(ops):
        ref.apply(R.op_id(i + 1, 0), op)
    order = r.permutation(len(keys))
    for k in zipf_keys(r, order, cfg["ops_per_doc"] - len(ops), cfg["zipf"]):
        slot, key = keys[k]
        op = ("put", objs[slot], key, value(r), ref.visible(objs[slot], key))
        ref.apply(R.op_id(len(ops) + 1, 0), op)
        ops.append(op)
    raw = MapEncoder([S.BASE_ACTOR]).change(S.BASE_ACTOR, 1, 1, [], ops)
    return raw, ref, order, objs


class Deployment:
    def __init__(self, cfg, seed, pool):
        self.cfg, self.seed, self.pool = cfg, seed, pool
        self.keys, _ = shape(cfg)
        self._futs = [pool.submit(base_doc, cfg, [seed % (1 << 63), 1, d])
                      for d in range(cfg["docs"])]
        self.docs = None

    def build(self):
        out = [f.result() for f in self._futs]
        self.docs = [Doc(f"maps-{d}", raw, ref)
                     for d, (raw, ref, _, _) in enumerate(out)]
        self.index = {id(doc): d for d, doc in enumerate(self.docs)}
        self.order = [o for _, _, o, _ in out]
        self.objs = [objs for _, _, _, objs in out]
        self.base_max_op = self.cfg["ops_per_doc"]
        return self.docs

    def apply(self, doc, rank, start_op, ops):
        for i, op in enumerate(ops):
            doc.ref.apply(R.op_id(start_op + i, rank), op)

    def compare(self, client, srv, doc) -> dict:
        """``doc`` as served against the reference, 1 where it differs:
        RPC ``materialize``, RPC ``getAll`` of every scalar key (its
        conflicts, ordered by op id), ``docDigest`` (recomputed from the
        change hashes), and the device mirror's resolved values and the
        visible ops of every key."""
        d = self.index[id(doc)]
        objs = self.objs[d]
        obj_text = ["_root"] + [id_text(o, d) for o in objs[1:]]
        calls = [("materialize", {"doc": doc.handle}),
                 ("docDigest", {"doc": doc.handle})]
        calls += [("getAll", {"doc": doc.handle, "obj": obj_text[slot],
                              "prop": key}) for slot, key in self.keys]
        got = client.pipeline(calls)
        ref = doc.ref
        want = ref.materialize()
        get_all = any(
            g != [ref.vis[(objs[slot], key)][e]
                  for e in ref.visible(objs[slot], key)]
            for g, (slot, key) in zip(got[2:], self.keys))
        want_vis = {(obj_text[objs.index(o)], key): sorted(
                        id_text(e, d) for e in cell)
                    for (o, key), cell in ref.vis.items() if cell}
        dd = srv.rpc._docs[doc.handle]
        with dd.lock:
            dev = dd.device_doc
            device_state = (dev is None or dev.hydrate() != want
                            or device_visible(dev) != want_vis)
        return {
            "rpc_materialize": int(got[0] != want),
            "rpc_get_all": int(get_all),
            "doc_digest": int(got[1].get("digest") != R.doc_digest(
                doc.hashes, doc.heads())),
            "device_state": int(device_state),
        }


def device_visible(dev) -> dict:
    """{(object id, key): sorted visible op ids} of a device mirror, from
    its resolution (the rows the kernel left visible)."""
    log = dev.log
    out = {}
    for r in np.flatnonzero(np.asarray(dev.visible[:log.n])):
        key = (log.export_id(int(log.obj_key[r])),
               log.props[int(log.prop[r])])
        out.setdefault(key, []).append(log.export_id(int(log.id_key[r])))
    return {k: sorted(v) for k, v in out.items()}
