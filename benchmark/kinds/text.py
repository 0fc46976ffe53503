"""Deployment kind ``text``: ``docs`` durable text documents, each typed by
one writer from a generated edit trace of ``edits_per_doc`` keystrokes.
Nothing here imports JAX."""

from __future__ import annotations

from benchmark import reference as R
from benchmark import synth as S
from benchmark.deploy import Doc


class Deployment:
    def __init__(self, cfg, seed, pool):
        self.cfg, self.seed = cfg, seed
        self._futs = [pool.submit(S.replay_base, cfg["edits_per_doc"],
                                  [seed % (1 << 63), 1, d])
                      for d in range(cfg["docs"])]
        self.docs = None
        self.traces = None

    def build(self):
        out = [f.result() for f in self._futs]
        self.traces = [t for t, _ in out]
        self.docs = [Doc(f"text-{d}", S.text_base_change(t), ref)
                     for d, (t, ref) in enumerate(out)]
        self.base_max_op = self.cfg["edits_per_doc"] + 1
        return self.docs

    def base_vis(self, d):
        return [e for v in self.docs[d].ref.vis for e in v]

    def apply(self, doc, rank, start_op, ops):
        ref = doc.ref
        for i, op in enumerate(ops):
            if op[0] == "ins":
                ref.insert_after(op[1], R.op_id(start_op + i, rank), op[2])
            else:
                ref.delete(op[1])

    def compare(self, client, srv, doc) -> dict:
        """``doc`` as served against the reference, 1 where it differs:
        the RPC ``text`` and ``materialize``, ``docDigest`` (recomputed
        from the change hashes) and the device mirror's resolved values."""
        text, mat, digest = client.pipeline([
            ("text", {"doc": doc.handle, "obj": S.TEXT_OBJ}),
            ("materialize", {"doc": doc.handle}),
            ("docDigest", {"doc": doc.handle})])
        want = {"text": doc.ref.text()}
        dd = srv.rpc._docs[doc.handle]
        with dd.lock:
            dev = dd.device_doc
            device_state = dev is None or dev.hydrate() != want
        return {
            "rpc_text": int(text != want["text"]),
            "rpc_materialize": int(mat != want),
            "doc_digest": int(digest.get("digest") != R.doc_digest(
                doc.hashes, doc.heads())),
            "device_state": int(device_state),
        }
