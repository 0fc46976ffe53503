"""Traffic writer ``replica``: a slice of the document's own trace a change,
re-anchored on the base text, as a replica that was offline sends it."""

from __future__ import annotations

import numpy as np

from benchmark import synth as S
from benchmark.deploy import rng
from benchmark.reference import op_id
from benchmark.traffic import Plan, chain_changes


def plan(t, dep, seed, seconds):
    """Closed loop through one relay: ``writers`` replicas reconnect to
    document ``doc``, each with its chain of ``changes_per_writer``
    changes of ``edits_per_change`` trace edits, and one peer connection
    relays them, ``changes_per_request`` changes a request, one request
    in flight. The relay's order is drawn from the seed: the chains
    interleaved at random, each in its own order. Every drain then ends on
    a request's boundary, so set-up can replay each state the window can
    reach: the first ``growth_requests`` requests, ``growth_every`` at a
    time (``Plan.growth``). ``warmup_writers`` more replicas send one
    change of ``warmup_edits_per_change`` edits each in set-up: enough to
    carry the document past the row bucket it starts just under."""
    p = Plan()
    p.mode = "closed"
    d = t["doc"]
    doc = dep.docs[d]
    n_w, n_warm = t["writers"], t["warmup_writers"]
    actors = [S.actor_of(2, i) for i in range(n_w + n_warm)]
    enc = S.Encoder([S.BASE_ACTOR] + actors)
    ranks = {a: i + 1 for i, a in enumerate(sorted(actors))}
    vis = dep.base_vis(d)
    K = t["changes_per_writer"]
    slices = S.replica_slices(dep.traces[d], n_w, t["edits_per_change"], K)
    slices += S.replica_slices(dep.traces[d], n_warm,
                               t["warmup_edits_per_change"], 1)
    chains = []
    for i, actor in enumerate(actors):
        r = rng(seed, 3, i)
        rank = ranks[actor]
        chain = S.Chain(actor, [doc.base_hash], dep.base_max_op + 1)
        deleted = set()
        it = iter(slices[i])

        def make(start, _r=r, _rank=rank, _del=deleted, _it=it):
            return S.replica_ops(vis, next(_it), _r, _del,
                                 lambda j: op_id(start + j, _rank))

        ch = chain_changes(chain, enc, d, rank, make,
                           1 if i >= n_w else K)
        if i >= n_w:
            p.warm += ch
        else:
            chains.append(iter(ch))
    who = rng(seed, 5).permutation(np.repeat(np.arange(n_w), K))
    order = [next(chains[i]) for i in who]
    m = t["changes_per_request"]
    reqs = [order[j:j + m] for j in range(0, len(order), m)]
    p.clients.append({"conn": 0, "requests": reqs})
    p.growth = [(d, reqs[:t["growth_requests"]], t["growth_every"])]
    return p
