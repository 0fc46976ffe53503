"""The one traffic generator. A traffic mix is a data file,
``benchmark/traffic/<mix>.json``; its keys pick and size what is made:

* ``loop``: ``open`` (every writer sends on its own Poisson schedule) or
  ``closed`` (a client's next request is due when the previous one is
  answered);
* ``writer``: what each change holds, the file
  ``benchmark/writers/<writer>.py``. Its ``plan(t, dep, seed, seconds)``
  makes the ``Plan``. It may also give ``warm(client, dep, plan, cfg,
  meter)``, which set-up then runs in place of ``run.warm_growth`` and
  which returns the resolutions it made;
* sizes: writers, rates, changes per writer and per request, the warm-up
  and the replay of the window's growth in set-up.

Every change is generated in set-up, from the seed, with its abstract ops
for the reference. A client sends requests, each a list of changes in one
``applyChanges``. Each seed gives the same number of writers, changes,
requests and arrivals; only positions, keys and the order of arrivals
differ. What every writer shares is here. The documents a writer writes
to come from the configuration's deployment kind,
``benchmark/kinds/<kind>.py`` (``deploy.py``).
"""

from __future__ import annotations

import os
from collections import namedtuple

import numpy as np

from . import load

WRITERS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "writers")

Change = namedtuple("Change", "doc rank start_op ops raw hash deps")


class Plan:
    def __init__(self):
        self.warm = []        # changes sent in set-up, in order
        self.clients = []     # {"conn", "requests", "due"}
        self.connections = 1
        self.mode = "open"
        # [(document, the window's requests in the order they reach it,
        # the replay's stride)]: replayed on scratch copies in set-up so
        # that every program shape the window can reach is compiled
        # before it opens (run.warm_growth, unless the writer brings its
        # own ``warm``)
        self.growth = []


def chain_changes(chain, enc, doc, rank, make_ops, n):
    out = []
    for _ in range(n):
        start, deps = chain.next_op, list(chain.deps)
        ops = make_ops(start)
        raw, h = chain.add(enc, ops)
        out.append(Change(doc, rank, start, ops, raw, h, deps))
    return out


def arrivals(r, n, seconds):
    """``n`` due times in [0, ``seconds``) with exponential gaps, as a
    Poisson stream has them; every seed gets the same set of gaps, the
    quantiles of the exponential, in another order, so that seeds
    change where the bursts fall and not how many there are."""
    if n <= 0:
        return []
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds * n / ((n + 1) * gaps.sum())
    return np.cumsum(r.permutation(gaps)).tolist()


def writer(name: str):
    """The module of traffic writer ``name``."""
    return load(WRITERS_DIR, name)


def make(t, dep, seed, seconds) -> Plan:
    return writer(t["writer"]).plan(t, dep, seed, seconds)
