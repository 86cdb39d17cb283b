"""The plain reference: the reference's vote-session semantics, written
straight from vacp2p/hashgraph-like-consensus (``src/utils.rs``,
``src/session.rs``, ``src/service.rs``) over in-memory storage. It imports
nothing of the program under test and takes nothing the program made: it
reads the votes the load generator built and the statuses the server sent
back, and says what the server should have answered.

Status codes are the protocol's published per-vote codes (bridge protocol:
"Statuses: 0 = OK; 1..29 mirror the reference's errors").
"""

from __future__ import annotations

import math

from . import wire

OK = 0
INVALID_EXPECTED_VOTERS_COUNT = 3
INVALID_VOTE_SIGNATURE = 5
EMPTY_SIGNATURE = 6
DUPLICATE_VOTE = 7
VOTE_EXPIRED = 9
EMPTY_VOTE_OWNER = 10
INVALID_VOTE_HASH = 11
EMPTY_VOTE_HASH = 12
PROPOSAL_EXPIRED = 13
TIMESTAMP_OLDER_THAN_CREATION_TIME = 18
SESSION_NOT_ACTIVE = 19
SESSION_NOT_FOUND = 20
PROPOSAL_ALREADY_EXIST = 21
MAX_ROUNDS_EXCEEDED = 24
ALREADY_REACHED = 28

_F64_EPSILON = 2.220446049250313e-16


def threshold_value(n: int, threshold: float) -> int:
    """utils.rs:307-313: ceil(2n/3) exactly for the default 2/3, else
    ceil(n * t) in f64."""
    if abs(threshold - 2.0 / 3.0) < _F64_EPSILON:
        return (2 * n + 2) // 3
    return max(0, math.ceil(n * threshold))


def decide(yes: int, total: int, n: int, threshold: float,
           liveness_yes: bool, timeout: bool = False) -> "bool | None":
    """utils.rs:227-286, the decision rule on counts."""
    no = max(total - yes, 0)
    silent = max(n - total, 0)
    if n <= 2:
        return None if total < n else yes == n
    required = threshold_value(n, threshold)
    if (n if timeout else total) < required:
        return None
    yes_w = yes + (silent if liveness_yes else 0)
    no_w = no + (0 if liveness_yes else silent)
    if yes_w >= required and yes_w > no_w:
        return True
    if no_w >= required and no_w > yes_w:
        return False
    if total == n and yes_w == no_w:
        return liveness_yes
    return None


def max_rounds(network: str, n: int, threshold: float) -> int:
    """session.rs:55-57,120-128: Gossipsub has 2 rounds, P2P ceil(2n/3)."""
    return 2 if network == "gossipsub" else threshold_value(n, threshold)


def deciding_vote(values, valid, n: int, threshold: float, network: str,
                  liveness_yes: bool) -> "tuple[int | None, str]":
    """Feed a fresh session ``values`` in order (``valid`` False = rejected
    before it reaches the session). Returns the index of the vote that
    ended it and the end state: "yes", "no", "failed" (round cap), or
    "active"."""
    cap = max_rounds(network, n, threshold)
    yes = total = 0
    for i, (v, ok) in enumerate(zip(values, valid)):
        if not ok:
            continue
        projected = 2 if network == "gossipsub" else total + 1
        if projected > cap:
            return i, "failed"
        total += 1
        yes += bool(v)
        result = decide(yes, total, n, threshold, liveness_yes)
        if result is not None:
            return i, "yes" if result else "no"
    return None, "active"


# ── sessions and the node ──────────────────────────────────────────────


class Session:
    __slots__ = ("n", "liveness", "timestamp", "expiration", "created_at",
                 "round", "state", "votes", "yes")

    def __init__(self, n, liveness, timestamp, expiration, created_at):
        self.n, self.liveness = n, liveness
        self.timestamp, self.expiration = timestamp, expiration
        self.created_at = created_at
        self.round = 1
        self.state = "active"  # active | yes | no | failed
        self.votes: "set[bytes]" = set()
        self.yes = 0


class Node:
    """One node's sessions, scope by scope, under one scope configuration."""

    def __init__(self, network: str, threshold: float, max_per_scope: int,
                 verify):
        """``verify(identity, payload, signature)`` is the signature
        scheme's own check (``benchmark/schemes/<scheme>.py``)."""
        self.network, self.threshold = network, threshold
        self.max_per_scope = max_per_scope
        self.verify = verify
        self.scopes: "dict[str, list]" = {}  # insertion-ordered [pid, ...]
        self.sessions: "dict[tuple[str, int], Session]" = {}

    def deliver(self, scope: str, pid: int, n: int, liveness: bool,
                timestamp: int, expiration: int, now: int) -> int:
        """A vote-free incoming proposal (service.rs process_incoming_proposal
        and the per-scope trim, service.rs:512-522)."""
        if now >= expiration:
            return PROPOSAL_EXPIRED
        if n < 1:
            return INVALID_EXPECTED_VOTERS_COUNT
        if (scope, pid) in self.sessions:
            return PROPOSAL_ALREADY_EXIST
        self.sessions[(scope, pid)] = Session(n, liveness, timestamp,
                                              expiration, now)
        order = self.scopes.setdefault(scope, [])
        order.append(pid)
        if len(order) > self.max_per_scope:
            # Stable sort, newest first, keep max: ties keep insertion order.
            ranked = sorted(order, key=lambda p: -self.sessions[(scope, p)]
                            .created_at)
            keep = set(ranked[:self.max_per_scope])
            for p in ranked[self.max_per_scope:]:
                del self.sessions[(scope, p)]
            self.scopes[scope] = [p for p in order if p in keep]
        return OK

    def vote(self, scope: str, pid: int, owner: bytes, value: bool, ts: int,
             vote_id: int, parent: bytes, received: bytes, vhash: bytes,
             signature: bytes, now: int) -> int:
        """service.rs process_incoming_vote: look up, validate (utils.rs
        127-171), then session.rs add_vote (225-249)."""
        s = self.sessions.get((scope, pid))
        if s is None:
            return SESSION_NOT_FOUND
        if not owner:
            return EMPTY_VOTE_OWNER
        if not vhash:
            return EMPTY_VOTE_HASH
        if not signature:
            return EMPTY_SIGNATURE
        if vhash != wire.vote_hash(vote_id, owner, pid, ts, value, parent,
                                   received):
            return INVALID_VOTE_HASH
        payload = wire.vote_signed_fields(vote_id, owner, pid, ts, value,
                                          parent, received, vhash)
        if not self.verify(owner, payload, signature):
            return INVALID_VOTE_SIGNATURE
        if ts < s.timestamp:
            return TIMESTAMP_OLDER_THAN_CREATION_TIME
        if ts > s.expiration or now > s.expiration:
            return VOTE_EXPIRED
        if s.state in ("yes", "no"):
            return ALREADY_REACHED
        if s.state != "active":
            return SESSION_NOT_ACTIVE
        if now >= s.expiration:
            return PROPOSAL_EXPIRED
        if self.network == "gossipsub":
            projected = 2
        else:
            projected = max(s.round - 1, 0) + 1
        if projected > max_rounds(self.network, s.n, self.threshold):
            s.state = "failed"
            return MAX_ROUNDS_EXCEEDED
        if owner in s.votes:
            return DUPLICATE_VOTE
        s.votes.add(owner)
        s.yes += bool(value)
        s.round = 2 if self.network == "gossipsub" else s.round + 1
        result = decide(s.yes, len(s.votes), s.n, self.threshold, s.liveness)
        if result is not None:
            s.state = "yes" if result else "no"
        return OK

    def result(self, scope: str, pid: int) -> int:
        """What OP_GET_RESULT should answer, as (status << 8) | result."""
        s = self.sessions.get((scope, pid))
        if s is None:
            return SESSION_NOT_FOUND << 8
        return {"yes": wire.RESULT_YES, "no": wire.RESULT_NO,
                "failed": wire.RESULT_FAILED,
                "active": wire.RESULT_UNDECIDED}[s.state]
