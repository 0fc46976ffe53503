"""The plain reference: Automerge's text (RGA) semantics over the abstract
ops the generator made, written from the published data model and
importing nothing of the program. A deployment kind of other semantics
(``benchmark/kinds/``) brings its own reference.

An element or op id is one int, ``counter << 20 | actor rank``, where the
rank orders actors by their bytes: so ``a > b`` is Automerge's op-id order
(counter first, then actor bytes).
"""

from __future__ import annotations

import hashlib
import zlib
from typing import Dict, Iterable, List

ACTOR_BITS = 20
HEAD = -1
_CHUNK = 2048


def op_id(counter: int, rank: int) -> int:
    return (counter << ACTOR_BITS) | rank


class TextRef:
    """One text object as an RGA: every element ever inserted, in
    document order, tombstones included. The order is held in chunks of
    at most ``_CHUNK`` ids, each with the list of its visible ids beside
    it, so that an edit moves short lists in C and a positional lookup
    walks few chunks."""

    def __init__(self):
        self.full: List[List[int]] = [[]]   # ids in document order
        self.vis: List[List[int]] = [[]]    # the visible ids of each chunk
        self.home: Dict[int, List[int]] = {}  # id -> its chunk
        self._at: Dict[int, int] = {id(self.full[0]): 0}  # chunk -> index
        self.char: Dict[int, str] = {}
        self._ci = 0    # chunk of the last positional lookup
        self._cv = 0    # visible elements before that chunk

    def __setstate__(self, state):
        # ``_at`` is keyed by object identity, which pickling changes
        self.__dict__.update(state)
        self._at = {id(c): i for i, c in enumerate(self.full)}

    # -- positional replay (one writer who sees everything) --------------

    def _seek(self, pos: int):
        """(chunk index, index among its visible ids) of the ``pos``-th
        visible element, walking from the last lookup (edits have cursor
        locality)."""
        ci, cv, vis = self._ci, self._cv, self.vis
        while cv > pos:
            ci -= 1
            cv -= len(vis[ci])
        last = len(vis) - 1
        while ci < last and cv + len(vis[ci]) <= pos:
            cv += len(vis[ci])
            ci += 1
        self._ci, self._cv = ci, cv
        k = pos - cv
        if k >= len(vis[ci]):
            raise IndexError(pos)
        return ci, k

    def insert_at(self, pos: int, eid: int, ch: str) -> int:
        """Insert as a writer who sees every element: right after the
        ``pos - 1``-th visible element (every element already after it has
        a smaller id). Returns the anchor's id."""
        if pos == 0:
            self._put(0, 0, 0, eid, ch)
            return HEAD
        ci, k = self._seek(pos - 1)
        anchor = self.vis[ci][k]
        self._put(ci, self.full[ci].index(anchor) + 1, k + 1, eid, ch)
        return anchor

    def delete_at(self, pos: int) -> int:
        ci, k = self._seek(pos)
        return self.vis[ci].pop(k)

    def visible_id(self, pos: int) -> int:
        ci, k = self._seek(pos)
        return self.vis[ci][k]

    # -- by id (concurrent writers) -------------------------------------------

    def insert_after(self, anchor: int, eid: int, ch: str) -> None:
        """RGA: after ``anchor``, skip every element with a greater id
        (inserted there concurrently, they and their descendants win),
        then insert."""
        if anchor == HEAD:
            ci, j = 0, 0
        else:
            chunk = self.home[anchor]
            ci = self._at[id(chunk)]
            j = chunk.index(anchor) + 1
        while True:
            chunk = self.full[ci]
            while j < len(chunk) and chunk[j] > eid:
                j += 1
            if j < len(chunk) or ci == len(self.full) - 1:
                break
            ci, j = ci + 1, 0
        alive = set(self.vis[ci])
        k = sum(1 for e in self.full[ci][:j] if e in alive)
        self._put(ci, j, k, eid, ch)

    def delete(self, eid: int) -> None:
        ci = self._at[id(self.home[eid])]
        vis = self.vis[ci]
        try:
            k = vis.index(eid)
        except ValueError:
            return  # deleted before, concurrently
        del vis[k]
        if ci < self._ci:
            self._cv -= 1

    def _put(self, ci: int, j: int, k: int, eid: int, ch: str) -> None:
        full, vis = self.full[ci], self.vis[ci]
        full.insert(j, eid)
        vis.insert(k, eid)
        self.home[eid] = full
        self.char[eid] = ch
        if ci < self._ci:
            self._cv += 1
        if len(full) > _CHUNK:
            self._split(ci)

    def _split(self, ci: int) -> None:
        full, vis = self.full[ci], self.vis[ci]
        tail = full[_CHUNK // 2:]
        del full[_CHUNK // 2:]
        moved = set(tail)
        cut = next((i for i, e in enumerate(vis) if e in moved), len(vis))
        vtail = vis[cut:]
        del vis[cut:]
        self.full.insert(ci + 1, tail)
        self.vis.insert(ci + 1, vtail)
        for e in tail:
            self.home[e] = tail
        self._at = {id(c): i for i, c in enumerate(self.full)}
        if self._ci > ci:
            self._ci += 1

    def text(self) -> str:
        char = self.char
        return "".join(char[e] for v in self.vis for e in v)

    def __len__(self) -> int:
        return sum(len(v) for v in self.vis)


# -- change hashes and the document digest ------------------------------------

def _uleb(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _read_uleb(buf: bytes, i: int):
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, i
        shift += 7


def change_hash(chunk: bytes) -> bytes:
    """SHA-256 of a change chunk's type, length and body (Automerge's
    binary format); a compressed chunk (type 2) is hashed as the change
    (type 1) it inflates to."""
    kind = chunk[8]
    n, i = _read_uleb(chunk, 9)
    body = chunk[i:i + n]
    if kind == 2:
        body = zlib.decompress(body, -15)
        kind = 1
    return hashlib.sha256(bytes([kind]) + _uleb(len(body)) + body).digest()


DIGEST_VERSION = b"amtpu-digest-v1"


def doc_digest(hashes: Iterable[bytes], heads: Iterable[bytes]) -> str:
    """The served ``docDigest``: SHA-256 over a version tag, the change
    count, the XOR of every change hash and the sorted heads."""
    acc = bytearray(32)
    count = 0
    for h in hashes:
        for i, b in enumerate(h[:32]):
            acc[i] ^= b
        count += 1
    d = hashlib.sha256()
    d.update(DIGEST_VERSION)
    d.update(_uleb(count))
    d.update(bytes(acc))
    for h in sorted(heads):
        d.update(h)
    return d.hexdigest()
