"""Reference Python client for the automerge_tpu line-framed JSON-RPC
protocol, with the retry discipline the cluster expects.

Dependency-free (stdlib only) so bench harnesses and CI scripts can use
it without installing the package; it is also the reference
implementation of the client-side retry contract:

* an error response carrying ``retriable: true`` (Unavailable during a
  failover window, Backpressure from a full shard queue, NotLeader
  mid-promotion, a poisoned-journal degraded doc) is retried with
  **capped exponential backoff + seeded jitter** until the call's
  **deadline budget** is spent;
* ``retriable: false`` (and errors with no flag) surface immediately —
  retrying a genuinely rejected request only hides bugs;
* transport death (connection reset by a dying router/node) redials and
  retries under the same budget;
* the caller sees either a result or ``RpcError`` — never a raw socket
  exception — plus how long the call was blocked and how many attempts
  it took (the double-apply bound for non-idempotent operations).

Usage::

    c = RetryingClient("127.0.0.1:7000", deadline_s=60)
    r = c.call("openDurable", name="doc1")          # retried as needed
    r = c.call("put", doc=r["doc"], obj="_root", prop="k", value=1)
    print(c.last.attempts, c.last.blocked_s)

``applyChanges`` with a pre-built change chunk is the clean retry unit:
it is atomic, durable at ack, and idempotent (change-hash deduplicated),
so an ambiguous retry can never double-apply. ``increment`` and friends
are not idempotent — a retry whose first attempt was applied-but-unacked
may double-apply; ``last.attempts`` bounds that ambiguity.
"""

from __future__ import annotations

import json
import random
import socket
import time
from typing import Any, Dict, List, Optional, Tuple

# legacy servers (and the router's RouterError path before it carried the
# flag) signal outages by type; treat these as retriable when no explicit
# retriable flag is present
RETRIABLE_TYPES = frozenset({
    "Unavailable", "NotLeader", "Backpressure", "RouterError",
    "ReplicationTimeout", "JournalPoisoned",
    "DeadlineExceeded", "Overloaded",
})


class RpcError(Exception):
    """A (final) error response: ``.type``, ``.retriable``, ``.raw``."""

    def __init__(self, err: Dict[str, Any]):
        super().__init__(f"{err.get('type')}: {err.get('message')}")
        self.type = err.get("type")
        self.retriable = bool(err.get("retriable", False))
        self.raw = err


class Deadline(RpcError):
    """The retry budget ran out before a retriable call succeeded."""

    def __init__(self, err: Dict[str, Any], waited: float, attempts: int):
        super().__init__(err)
        self.waited = waited
        self.attempts = attempts


class IntegrityError(RpcError):
    """The server found corrupt stored or replicated state (a digest
    mismatch, a bad snapshot chunk, a journal CRC failure). NEVER
    retriable — retrying re-reads the same damaged bytes — and distinct
    from transient ``RpcError``s so callers can alert instead of loop:
    the right response is operator attention (scrub/repair), not
    backoff."""

    def __init__(self, err: Dict[str, Any]):
        super().__init__(err)
        self.retriable = False


class CallStats:
    """What the previous ``call`` cost: attempts sent and seconds spent
    blocked in backoff/redial (0.0 for a clean first-try success)."""

    __slots__ = ("attempts", "blocked_s", "errors")

    def __init__(self):
        self.attempts = 0
        self.blocked_s = 0.0
        self.errors: List[str] = []


def is_retriable(err: Dict[str, Any]) -> bool:
    """The one place the retry decision lives: an explicit boolean
    ``retriable`` wins; absent one, fall back to the legacy type set."""
    if "retriable" in err:
        return bool(err["retriable"])
    return err.get("type") in RETRIABLE_TYPES


class RetryingClient:
    """One connection to a router/server with the reference retry loop.

    ``deadline_s`` is the default per-call budget; ``call`` takes an
    override. Backoff starts at ``backoff_s`` and doubles to
    ``max_backoff_s`` with seeded jitter — deterministic per seed, like
    everything else in the chaos harness.
    """

    def __init__(
        self,
        address: str | Tuple[str, int],
        *,
        deadline_s: float = 30.0,
        backoff_s: float = 0.05,
        max_backoff_s: float = 1.0,
        seed: int = 0,
        timeout_s: Optional[float] = None,
    ):
        if isinstance(address, str):
            host, _, port = address.rpartition(":")
            address = (host or "127.0.0.1", int(port))
        self.address = address
        self.deadline_s = deadline_s
        self.backoff_s = backoff_s
        self.max_backoff_s = max_backoff_s
        self.timeout_s = timeout_s
        self.rng = random.Random(seed)
        self.last = CallStats()
        self._rid = 0
        self._sock: Optional[socket.socket] = None
        self._f = None

    # -- plumbing ------------------------------------------------------------

    def _ensure_conn(self, timeout: Optional[float] = None) -> None:
        if self._sock is not None:
            return
        if timeout is None:
            timeout = self.timeout_s
        sock = socket.create_connection(self.address, timeout=timeout)
        try:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass
        self._sock = sock
        self._f = sock.makefile("r")

    def _drop_conn(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        self._sock = None
        self._f = None

    def close(self) -> None:
        self._drop_conn()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def request(self, method: str, params: Optional[dict] = None,
                trace: Optional[dict] = None,
                timeout: Optional[float] = None,
                deadline_ms: Optional[float] = None) -> Dict[str, Any]:
        """One request, one raw response dict — no retry. Raises OSError
        on transport death OR a garbled frame (both are the retry loop's
        signal to drop the connection and redial — after either, the
        stream can no longer be trusted to be in sync). ``timeout``
        bounds this single attempt: a black-holed response path raises
        ``socket.timeout`` (an OSError) instead of blocking forever."""
        self._ensure_conn(timeout=timeout)
        if timeout is not None or self.timeout_s is not None:
            t = min(x for x in (timeout, self.timeout_s) if x is not None)
            self._sock.settimeout(max(t, 0.05))
        self._rid += 1
        req: Dict[str, Any] = {
            "id": self._rid, "method": method, "params": params or {}}
        if trace is not None:
            req["trace"] = trace
        if deadline_ms is not None and deadline_ms > 0:
            # deadline propagation: the remaining per-call budget rides
            # as a top-level field (like "trace"); router and nodes
            # refuse the request once it expires instead of executing
            # work this client already gave up on
            req["deadlineMs"] = int(deadline_ms)
        try:
            self._sock.sendall((json.dumps(req) + "\n").encode("utf-8"))
            while True:
                raw = self._f.readline()
                if not raw:
                    raise OSError("connection closed mid-request")
                try:
                    resp = json.loads(raw)
                except ValueError as e:
                    # a truncated/garbled line (peer died mid-write, or
                    # a chaos proxy chewed the stream): transport death,
                    # not a caller-visible parse error
                    raise OSError(f"garbled response frame: {e}") from e
                # match by id: a late frame for an abandoned earlier
                # attempt is discarded, exactly per the protocol contract
                if isinstance(resp, dict) and resp.get("id") == self._rid:
                    return resp
        except OSError:
            self._drop_conn()
            raise

    def pipeline(self, calls, timeout: Optional[float] = None
                 ) -> List[Dict[str, Any]]:
        """Send ``calls`` (``[(method, params), ...]``) back to back, then
        read their responses: one raw response dict per call, in call
        order, no retry. The server answers requests for one document in
        order and may interleave others, so responses match by id."""
        self._ensure_conn(timeout=timeout)
        self._sock.settimeout(timeout)
        first = self._rid + 1
        lines = []
        for method, params in calls:
            self._rid += 1
            lines.append(json.dumps(
                {"id": self._rid, "method": method, "params": params or {}}))
        try:
            self._sock.sendall(("\n".join(lines) + "\n").encode("utf-8"))
            by_id: Dict[int, Dict[str, Any]] = {}
            while len(by_id) < len(calls):
                raw = self._f.readline()
                if not raw:
                    raise OSError("connection closed mid-pipeline")
                resp = json.loads(raw)
                if first <= resp.get("id", 0) <= self._rid:
                    by_id[resp["id"]] = resp
        except (OSError, ValueError):
            self._drop_conn()
            raise
        return [by_id[first + i] for i in range(len(calls))]

    # -- the reference retry loop --------------------------------------------

    def call(self, method: str, *, deadline_s: Optional[float] = None,
             trace: Optional[dict] = None, **params) -> Any:
        """Send with retry-on-retriable. Returns the result; raises
        ``RpcError`` for a non-retriable error, ``Deadline`` when the
        budget runs out. ``self.last`` holds the attempt/blocked stats
        of this call afterwards."""
        budget = self.deadline_s if deadline_s is None else deadline_s
        deadline = time.monotonic() + budget
        stats = CallStats()
        self.last = stats
        backoff = self.backoff_s
        t_first_fail = None
        while True:
            stats.attempts += 1
            err: Dict[str, Any]
            try:
                # each attempt is bounded by what is left of the budget:
                # a peer that receives but never answers (the asymmetric
                # partition) times the attempt out instead of hanging
                # the whole call past its deadline. The same remaining
                # budget ships as deadlineMs, so server and client agree
                # on who gave up.
                attempt_budget = deadline - time.monotonic()
                resp = self.request(method, params, trace=trace,
                                    timeout=max(attempt_budget, 0.05),
                                    deadline_ms=max(attempt_budget, 0.05)
                                    * 1000.0)
                if "error" not in resp:
                    if t_first_fail is not None:
                        stats.blocked_s = time.monotonic() - t_first_fail
                    return resp.get("result")
                err = resp["error"]
                if err.get("type") == "IntegrityError":
                    raise IntegrityError(err)
                if not is_retriable(err):
                    raise RpcError(err)
            except OSError as e:
                err = {"type": "Transport", "message": str(e),
                       "retriable": True}
            if t_first_fail is None:
                t_first_fail = time.monotonic()
            stats.errors.append(str(err.get("type")))
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                stats.blocked_s = time.monotonic() - t_first_fail
                raise Deadline(err, stats.blocked_s, stats.attempts)
            # a server retryAfterMs hint (a shedding node pacing its
            # retries) overrides the exponential schedule: jittered
            # 0.75-1.25x so a shed wave does not re-arrive in lockstep,
            # still capped by max_backoff_s and the remaining budget
            ra = err.get("retryAfterMs")
            if isinstance(ra, (int, float)) and ra > 0:
                hinted = (ra / 1000.0) * (0.75 + 0.5 * self.rng.random())
                sleep = min(hinted, self.max_backoff_s, remaining)
            else:
                # capped exponential backoff with seeded jitter, clamped
                # to the remaining budget so the last sleep cannot
                # overshoot
                sleep = min(backoff * (0.5 + self.rng.random()), remaining)
                backoff = min(backoff * 2, self.max_backoff_s)
            time.sleep(sleep)
