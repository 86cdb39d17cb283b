"""The default traffic generator. A traffic mix is a data file of
parameters (``benchmark/traffic/<mix>.json``); this module turns a
configuration, a mix, a seed and a connection index into that connection's
frames, in the order it sends them. It imports no JAX and nothing of the
program. A mix that needs another shape of traffic names a ``generator``,
a module ``benchmark/traffic/<generator>.py`` whose ``Plan`` has this one's
interface (``setup_frames``, ``warm_frames``, ``window_frames``,
``registry``, ``voters``, ``peer``); a frame whose ``due`` is set is sent at
that time (open loop), one without is sent as the in-flight limit allows
(closed loop).

Every session is a pure function of ``(seed, scope, k)``: its voters (a
seeded permutation of the configuration's voter set, ``silent_share`` of
them silent), its yes share, which votes are tampered, the vote ids and
the vote bytes, signed by the mix's ``signature`` scheme
(``benchmark/schemes/<scheme>.py``). Voters vote concurrently on the
proposal as it reached them, before they see each other's votes, so every
vote carries empty ``received_hash`` and ``parent_hash`` links (reference
``build_vote`` on a vote-free proposal). The frame sizes are a pure
function of ``(seed, connection)``, so two runs with one seed send the same
frames.

Closed loop: each connection round-robins over ``active_per_scope`` lanes
in each of its scopes. Scope ``s`` belongs to connection ``s %
connections``; a session's votes all travel on its scope's connection,
whose frames the server applies in order, so the reference can replay them
in that order.

Sessions of a scope are consumed in the order the reference evicts them
(oldest ``created_at`` first; among a batch delivered together, the last
inserted first), and a session that has had all its votes retires: a new
proposal for its scope follows at once, in a frame of its own with the next
logical ``now``, and evicts exactly the retired session.
"""

from __future__ import annotations

import hashlib
import math

import numpy as np

from . import plugins, reference, wire

BASE_TIME = 1_700_000_000
EXPIRY = 100_000_000  # proposals never expire inside a run
OWNER = b"\x42" * 20  # the proposing node's identity
LAZY_VOTES = 2_000  # votes made at a time once the pregenerated ones run out
SWEEP_TRIES = 64


def scope_name(s: int) -> str:
    return f"s{s:05d}"


def pid_of(scope: int, k: int) -> int:
    """Proposal ids are unique across the node, not only within a scope."""
    return scope * 65536 + k + 1


class Voters:
    """The deployment's voter set, from the seed, with the keys and
    identities of the mix's signature scheme."""

    def __init__(self, seed: int, n: int, scheme: str):
        rng = np.random.default_rng([seed, 0xC0FFEE])
        raw = rng.integers(0, 256, (n, 32), dtype=np.uint8)
        self.scheme = plugins.load_module("schemes", scheme)
        self.keys, self.ids = self.scheme.keys(raw)

    def sign(self, v: int, payload: bytes) -> bytes:
        return self.scheme.sign(self.keys[v], payload)


class Session:
    """One proposal and its votes, in the order they are sent."""

    __slots__ = ("scope", "k", "pid", "votes", "voters",
                 "values", "valid", "vote_ids", "hashes", "sigs",
                 "decides")

    def __init__(self, seed: int, cfg: dict, mix: dict, voters: Voters,
                 scope: int, k: int):
        n = cfg["expected_voters"]
        rng = np.random.default_rng([seed, scope, k, 1])
        m = n - int(round(mix["silent_share"] * n))
        self.scope, self.k, self.pid = scope, k, pid_of(scope, k)
        self.voters = rng.permutation(n)[:m]
        lo, hi = mix["yes_share"]
        self.values = rng.random(m) < rng.uniform(lo, hi)
        self.valid = ~(rng.random(m) < mix["tamper_share"])
        self.vote_ids = rng.integers(1, 1 << 32, m, dtype=np.uint64)
        flip = rng.integers(0, 2, m)
        self.decides, _outcome = reference.deciding_vote(
            self.values, self.valid, n, cfg["threshold"], cfg["network"],
            cfg["liveness_criteria_yes"])
        votes, hashes, sigs = [], [], []
        for i in range(m):
            v = int(self.voters[i])
            owner = voters.ids[v]
            vid = int(self.vote_ids[i])
            val = bool(self.values[i])
            h = wire.vote_hash(vid, owner, self.pid, BASE_TIME, val, b"", b"")
            fields = wire.vote_signed_fields(vid, owner, self.pid, BASE_TIME,
                                             val, b"", b"", h)
            sig = voters.sign(v, fields)
            if not self.valid[i]:  # tamper: flip the low bit of R or S
                at = 32 * int(flip[i])
                sig = sig[:at] + bytes([sig[at] ^ 1]) + sig[at + 1:]
            votes.append(wire.vote_bytes(fields, sig))
            hashes.append(h)
            sigs.append(sig)
        self.votes, self.hashes, self.sigs = votes, hashes, sigs


def frame_sizes(rng, mix: dict, count: int) -> np.ndarray:
    f = mix["frame"]
    if f["dist"] != "loguniform":
        raise ValueError(f"unknown frame size distribution {f['dist']!r}")
    x = np.exp(rng.uniform(math.log(f["min"]), math.log(f["max"] + 1), count))
    return np.clip(x.astype(np.int64), f["min"], f["max"])


class Frame:
    """One frame to send. ``kind`` is "votes" or "deliver". A vote frame
    names its votes by session id (``sids``, into the plan's registry) and
    vote index (``idx``); a delivery lists (scope, k). ``due``, where a
    generator sets it, is the open-loop send time in seconds from the start
    of the warm-up or the window (None: closed loop); ``deciding`` counts
    the sessions whose deciding vote it carries."""

    __slots__ = ("kind", "now", "payload", "sids", "idx", "items", "due",
                 "deciding")

    def __init__(self, kind, now, payload, due=None):
        self.kind, self.now, self.payload, self.due = kind, now, payload, due
        self.sids = self.idx = self.items = None
        self.deciding = 0


class Plan:
    """One connection's traffic: set-up deliveries, then vote frames with
    the deliveries that retirements call for. Sessions that the reference
    will replay (a seeded ``reference_share`` of them) are kept whole in
    ``registry``; of the others only (scope, k, deciding index) is kept."""

    def __init__(self, seed: int, cfg: dict, mix: dict, conn: int,
                 peer: int = 0, voters: "Voters | None" = None):
        self.seed, self.cfg, self.mix, self.conn = seed, cfg, mix, conn
        self.peer = peer
        self.voters = voters or Voters(seed, cfg["expected_voters"],
                                       mix["signature"])
        n_conn = mix["connections"]
        self.scopes = [s for s in range(cfg["scopes"]) if s % n_conn == conn]
        # Resident sessions per scope at set-up: the cap less the mix's
        # margin, so that the last sessions to retire are still resident,
        # with their outcomes, when the window ends; the connection's first
        # scope has no margin, so that its warm-up traffic already evicts.
        cap = cfg["max_sessions_per_scope"]
        self.setup = {s: cap - (mix["setup_margin_per_scope"] if i else 0)
                      for i, s in enumerate(self.scopes)}
        self.queue = {s: list(range(k - 1, -1, -1))
                      for s, k in self.setup.items()}
        self.next_k = dict(self.setup)
        self.registry: list = []
        self.now = BASE_TIME
        per = mix["active_per_scope"]
        self.active = {s: [None] * per for s in self.scopes}
        self.order = [(s, j) for j in range(per) for s in self.scopes]
        self.pos = self.rotation = 0
        # Closed loop: lane i of the round-robin starts i/len(order) of a
        # session's length late, so that sessions retire, and decide,
        # evenly spread in time rather than all in one frame.
        m = cfg["expected_voters"] - int(round(mix["silent_share"]
                                               * cfg["expected_voters"]))
        self.start = [i * m // len(self.order) for i in range(len(self.order))]
        self.session_votes = m
        self.rng = np.random.default_rng([seed, 0xF4A, conn])
        self.share = mix["reference_share"]

    def sampled(self, scope: int, k: int) -> bool:
        h = hashlib.sha256(f"{self.seed}/{scope}/{k}".encode()).digest()
        return int.from_bytes(h[:8], "little") < self.share * 2.0 ** 64

    def setup_frames(self) -> "list[Frame]":
        """One delivery per scope of its resident sessions, cut into frames
        of at most 1,024."""
        frames = []
        for s in self.scopes:
            ks = list(range(self.setup[s]))
            for at in range(0, len(ks), 1024):
                self.now += 1
                frames.append(self._deliver([(s, k) for k in ks[at:at + 1024]]))
        return frames

    def _deliver(self, items) -> Frame:
        blobs = [(scope_name(s), wire.proposal_bytes(
            f"{scope_name(s)}-{k}", b"", pid_of(s, k), OWNER,
            self.cfg["expected_voters"], BASE_TIME, BASE_TIME + EXPIRY,
            self.cfg["liveness_criteria_yes"])) for s, k in items]
        frame = Frame("deliver", self.now,
                      wire.deliver_payload(self.peer, self.now, blobs))
        frame.items = items
        return frame

    def _next_vote(self, scope: int, j: int):
        """The next vote of active slot ``j`` of ``scope``: (Session, sid,
        index, retired)."""
        slot = self.active[scope][j]
        if slot is None:
            k = self.queue[scope].pop(0)
            sess = Session(self.seed, self.cfg, self.mix, self.voters,
                           scope, k)
            sid = len(self.registry)
            self.registry.append(sess if self.sampled(scope, k)
                                 else (scope, k, sess.decides))
            slot = self.active[scope][j] = [sess, sid, 0]
        sess, sid, i = slot
        slot[2] = i + 1
        retired = slot[2] == len(sess.votes)
        if retired:
            self.active[scope][j] = None
        return sess, sid, i, retired

    def vote_frame(self, size: int) -> "list[Frame]":
        """A vote frame of ``size`` votes, round-robin over the active
        lanes of all this connection's scopes, then one delivery per
        retired session."""
        groups: "dict[int, list]" = {}  # scope -> [(vote, sid, index)]
        retired = []
        deciding = 0
        for _ in range(size):
            while True:
                lane = self.pos
                self.pos += 1
                if self.pos == len(self.order):
                    self.pos = 0
                    self.rotation += 1
                if self.rotation >= self.start[lane]:
                    break
            s, j = self.order[lane]
            sess, sid, i, done = self._next_vote(s, j)
            groups.setdefault(s, []).append((sess.votes[i], sid, i))
            deciding += sess.decides == i
            if done:
                retired.append(s)
        # The frame carries one group per scope; its statuses come back in
        # the frame's (grouped) order, and so do ``sids`` and ``idx``.
        rows = [r for g in groups.values() for r in g]
        frame = Frame("votes", self.now, wire.vote_batch_payload(
            self.now, self.peer,
            [(scope_name(s), [r[0] for r in g]) for s, g in groups.items()]))
        frame.sids = np.array([r[1] for r in rows], np.int32)
        frame.idx = np.array([r[2] for r in rows], np.int32)
        frame.deciding = deciding
        frames = [frame]
        for s in retired:
            k = self.next_k[s]
            self.next_k[s] = k + 1
            self.queue[s].append(k)
            self.now += 1
            frames.append(self._deliver([(s, k)]))
        return frames

    def closed_frames(self, votes: int,
                      sizes: "list[int] | None" = None) -> "list[Frame]":
        """Closed-loop frames holding at least ``votes`` votes; then, for
        the warm-up's sweep of dispatch shapes, frames of each of ``sizes``
        until one retires a session and, where the size exceeds the lanes,
        comes round to that lane's next session: it touches more sessions
        than there are lanes, the wider dispatch that the window meets at
        every size (``SWEEP_TRIES`` retirements at most, which no run has
        needed)."""
        frames, sent = [], 0
        while sent < votes:
            size = int(frame_sizes(self.rng, self.mix, 1)[0])
            frames.extend(self.vote_frame(size))
            sent += size
        lanes = len(self.order)
        for size in sizes or []:
            tries = 0
            while tries < SWEEP_TRIES:
                made = self.vote_frame(size)
                frames.extend(made)
                if len(made) > 1:
                    tries += 1
                    if size <= lanes or len(np.unique(made[0].sids)) > lanes:
                        break
        if sizes:
            frames.extend(self.one_vote_frames())
        return frames

    def _peek_lanes(self, count: int) -> "list[tuple[int, int]]":
        """The (lane, session) of the next ``count`` closed-loop votes,
        without sending them; a lane's sessions are told apart by how many
        it has retired from now on."""
        pos, rot = self.pos, self.rotation
        done: "dict[int, int]" = {}
        gen: "dict[int, int]" = {}
        out = []
        while len(out) < count:
            lane = pos
            pos += 1
            if pos == len(self.order):
                pos, rot = 0, rot + 1
            if rot < self.start[lane]:
                continue
            if lane not in done:
                slot = self.active[self.order[lane][0]][self.order[lane][1]]
                done[lane], gen[lane] = (slot[2] if slot else 0), 0
            out.append((lane, gen[lane]))
            done[lane] += 1
            if done[lane] == self.session_votes:
                done[lane], gen[lane] = 0, gen[lane] + 1
        return out

    def one_vote_frames(self) -> "list[Frame]":
        """Closed loop, for the warm-up: a frame of one vote more than
        there are lanes, opening on a session's last vote so that its lane
        comes round again to the next session: every session in it has one
        vote and there are more of them than lanes. The window meets that
        dispatch shape only now and then, so it is warmed here, after
        filler frames of the mix's sizes that bring such a vote up."""
        lanes = len(self.order)
        lo, hi = self.mix["frame"]["min"], self.mix["frame"]["max"]
        peek = self._peek_lanes(lanes * (self.session_votes + 2))
        for n in range(len(peek) - lanes - 1):
            if 0 < n < lo:
                continue
            window = peek[n:n + lanes + 1]
            if len(set(window)) == lanes + 1 and window[0][0] == window[-1][0]:
                break
        else:
            return []
        frames = []
        k = -(-n // hi)
        for i in range(k):
            frames.extend(self.vote_frame((n * (i + 1)) // k - (n * i) // k))
        frames.extend(self.vote_frame(lanes + 1))
        return frames

    def warm_frames(self) -> "list[Frame]":
        """The warm-up: ``warmup_votes_per_connection`` votes, which bring
        the staggered lanes to their steady state (the first session
        retires after about half a session's votes per lane), then the
        sweep of frame shapes and the one-vote frame."""
        return self.closed_frames(self.mix["warmup_votes_per_connection"],
                                  self.mix["warmup_sweep_sizes"])

    def window_frames(self):
        """The window's frames: ``window_votes_per_connection`` made now,
        in set-up, then more made on the fly, ``LAZY_VOTES`` at a time, if
        a fast server uses them all up (``lazy_frames`` counts those)."""
        made = self.closed_frames(self.mix["window_votes_per_connection"])
        self.pregenerated_votes = sum(len(f.sids) for f in made
                                      if f.kind == "votes")
        self.lazy_frames = 0

        def frames():
            yield from made
            while True:
                more = self.closed_frames(LAZY_VOTES)
                self.lazy_frames += len(more)
                yield from more

        return frames()
