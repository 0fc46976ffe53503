"""Deployments: the documents a configuration holds, made from the seed,
loaded into the server, and checked against the reference.

``kind`` in a configuration's file names its deployment kind, the file
``benchmark/kinds/<kind>.py``. That module's ``Deployment(cfg, seed,
pool)`` gives ``build()`` (the documents, made from the seed; ``pool`` is
a spawned process pool for the heavy part), ``docs``, ``base_max_op``,
``apply(doc, rank, start_op, ops)`` (one change's abstract ops into the
reference) and ``compare(client, srv, doc)``: ``{check: 0 or 1}``, the
comparisons of one served document with its reference that decide
``correct``. What every kind shares is here. The changes sent to a
deployment come from a traffic mix's writer, ``benchmark/writers/<writer>.py``
(``traffic.py``).

Every document is opened with a device mirror (``device``) and the
journal policy ``fsync``. Nothing here imports JAX.
"""

from __future__ import annotations

import base64
import os

import numpy as np

from . import load
from . import reference as R

KINDS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kinds")


def rng(seed: int, *path: int):
    """The generator of one purpose, one document, one writer."""
    return np.random.default_rng([seed % (1 << 63), *path])


def b64(raw: bytes) -> str:
    return base64.b64encode(raw).decode("ascii")


class Doc:
    """One document: its name, its base change, and the reference state
    with every change applied to it so far, in causal order."""

    def __init__(self, name, base_raw, ref):
        self.name, self.base_raw, self.ref = name, base_raw, ref
        self.base_hash = R.change_hash(base_raw)
        self.hashes = [self.base_hash]
        self.held = {self.base_hash}
        self.deps = set()
        self.handle = None

    def applied(self, h, deps):
        self.hashes.append(h)
        self.held.add(h)
        self.deps.update(deps)

    def heads(self):
        return [h for h in self.hashes if h not in self.deps]


def kind(name: str):
    """The module of deployment kind ``name``."""
    return load(KINDS_DIR, name)


def make(cfg, seed, pool):
    return kind(cfg["kind"]).Deployment(cfg, seed, pool)
