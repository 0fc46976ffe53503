"""Traffic writer ``keystroke``: one op at an editor's cursor a change."""

from __future__ import annotations

from benchmark import synth as S
from benchmark.deploy import rng
from benchmark.reference import op_id
from benchmark.traffic import Plan, arrivals, chain_changes


def plan(t, dep, seed, seconds):
    """Open loop: ``writers_per_doc`` editors on each document, one
    connection each, ``rate_per_s`` keystrokes a second over all of
    them, one change a request. Every change of the window is replayed in
    set-up on a scratch copy of its document, in the order they are due,
    ``growth_every`` at a time (see ``Plan.growth``)."""
    p = Plan()
    n_docs = len(dep.docs)
    E = t["writers_per_doc"]
    per = int(round(t["rate_per_s"] * seconds / (n_docs * E)))
    for d, doc in enumerate(dep.docs):
        actors = [S.actor_of(1, d * E + e) for e in range(E)]
        enc = S.Encoder([S.BASE_ACTOR] + actors)
        vis = dep.base_vis(d)
        timed = []
        for e, actor in enumerate(actors):
            r = rng(seed, 2, d, e)
            ed = S.Editor(vis, r, t["insert_share"], t["jump_share"])
            rank = enc.rank(actor)
            chain = S.Chain(actor, [doc.base_hash], dep.base_max_op + 1)
            ch = chain_changes(chain, enc, d, rank,
                               lambda s: [ed.keystroke(op_id(s, rank))],
                               t["warmup_per_writer"] + per)
            p.warm += ch[:t["warmup_per_writer"]]
            due = arrivals(r, per, seconds)
            reqs = [[c] for c in ch[t["warmup_per_writer"]:]]
            p.clients.append({"conn": len(p.clients), "requests": reqs,
                              "due": due})
            timed += zip(due, reqs)
        timed.sort(key=lambda x: x[0])
        p.growth.append((d, [r for _, r in timed], t["growth_every"]))
    p.connections = len(p.clients)
    return p
