"""Generation is pinned: for each cell at ``test_harness.small_spec``'s
sizes and one seed, a SHA-256 over what the traffic writer makes. Each
client's requests (the change bytes and due times), the warm-up changes
and the growth replay all enter it, with each change's abstract ops for
the reference. Server handles do not enter it: the server assigns them.

The constants were computed on a ``git archive`` of the commit before
deployment kinds and traffic writers moved into files of their own
(``benchmark/kinds/``, ``benchmark/writers/``), so they show that the move
left every byte the benchmark sends where it was."""

import hashlib
from concurrent.futures import ThreadPoolExecutor

import pytest

from benchmark import deploy, traffic
from benchmark.tests.test_harness import small_spec

SEED = 3000000007
SECONDS = 3
PINNED = {
    "collab.keystroke":
        "da9350f79c8ccbe65fa02d2f7833b5a9ebc500353279c7c349b6c7d5664e9118",
    "fanin.storm":
        "cb994823e051bee75bc81fc0d94d121b8402a38c36e724c09b4a2dd4b6266d87",
}


def _changes(h, chs):
    for ch in chs:
        h.update(repr((ch.doc, ch.rank, ch.start_op, ch.ops, ch.hash,
                       ch.deps)).encode())
        h.update(ch.raw)


def generation_digest(cell: str) -> str:
    spec = small_spec(cell)
    with ThreadPoolExecutor(max_workers=1) as pool:
        dep = deploy.make(spec["config"], SEED, pool)
        dep.build()
    plan = traffic.make(spec["traffic"], dep, SEED, SECONDS)
    h = hashlib.sha256()
    h.update(repr((plan.mode, plan.connections)).encode())
    for cl in plan.clients:
        h.update(repr((cl["conn"], cl.get("due"))).encode())
        for req in cl["requests"]:
            h.update(b"request")
            _changes(h, req)
    h.update(b"warm")
    _changes(h, plan.warm)
    for d, reqs, every in plan.growth:
        h.update(repr(("growth", d, every, len(reqs))).encode())
        for req in reqs:
            h.update(b"request")
            _changes(h, req)
    return h.hexdigest()


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_generation_is_pinned(cell):
    assert generation_digest(cell) == PINNED[cell]
