"""The served path over a slot pool sharded across devices, and the
pool's activation and routing counters.

``BridgeServer`` engines hold a ``ShardedPool`` over four of conftest's
virtual CPU devices, as the four-chip benchmark deployment does
(``benchmark/servers/sharded.py``). Seeded random traces of proposal
deliveries and columnar vote frames (several scopes, evictions, duplicate
votes, decided sessions retired and replaced) must give every per-vote
status and every end state that ``ConsensusService`` over
``InMemoryConsensusStorage`` (the reference) gives, and the same as a
server over the one-device ``ProposalPool``.
"""

import numpy as np
import pytest

from hashgraph_tpu import (
    BroadcastEventBus,
    ConsensusError,
    ConsensusService,
    InMemoryConsensusStorage,
    StubConsensusSigner,
    build_vote,
)
from hashgraph_tpu.bridge import protocol as P
from hashgraph_tpu.bridge.server import BridgeServer
from hashgraph_tpu.engine import TpuConsensusEngine
from hashgraph_tpu.engine.pool import ProposalPool, _bucket
from hashgraph_tpu.errors import StatusCode
from hashgraph_tpu.obs import (
    POOL_ACTIVATE_DISPATCHES_TOTAL,
    POOL_ACTIVATED_SLOTS_TOTAL,
    POOL_SHARD_GRID_ROWS_TOTAL,
    POOL_SHARD_ROWS_TOTAL,
)
from hashgraph_tpu.obs import registry as default_registry
from hashgraph_tpu.parallel import ShardedPool, consensus_mesh
from hashgraph_tpu.tracing import Tracer
from hashgraph_tpu.wire import Proposal

from common import NOW

SCOPES = ("s0", "s1", "s2")
CAP = 4  # sessions kept per scope; a fifth delivery evicts the oldest
PER_DEVICE = 4  # 4 devices x 4 slots hold the 3 x 4 live sessions
LANES = 16
VOTERS = [StubConsensusSigner(bytes([7, v]) * 10) for v in range(12)]


def _server(pool_factory) -> "tuple[BridgeServer, int]":
    def engine_factory(signer):
        return TpuConsensusEngine(
            signer, event_bus=BroadcastEventBus(),
            max_sessions_per_scope=CAP, pool=pool_factory(),
        )

    server = BridgeServer(
        signer_factory=StubConsensusSigner, engine_factory=engine_factory
    )
    server.start_embedded()
    status, body = server.dispatch_frame(P.OP_ADD_PEER, P.u8(32) + b"\x21" * 32)
    assert status == P.STATUS_OK
    return server, P.Cursor(body).u32()


def _statuses(server, opcode, payload) -> "list[int]":
    status, body = server.dispatch_frame(opcode, payload)
    assert status == P.STATUS_OK
    c = P.Cursor(body)
    return list(c.raw(c.u32()))


def _code(fn) -> int:
    try:
        fn()
    except ConsensusError as exc:
        return int(exc.code)
    return int(StatusCode.OK)


def _trace(seed: int):
    """Steps of (now, kind, items): a delivery lists (scope, Proposal); a
    vote frame lists (scope, [Vote]) groups."""
    rng = np.random.default_rng(seed)
    proposals: "dict[tuple[str, int], Proposal]" = {}
    sent: "list[tuple[str, object]]" = []  # every vote sent, for duplicates
    voted: "dict[tuple[str, int], set]" = {}
    next_pid = 1
    steps = []
    for step in range(70):
        now = NOW + step
        live = list(proposals)
        if not live or rng.random() < 0.3:
            items = []
            for _ in range(int(rng.integers(1, 4))):
                scope = SCOPES[int(rng.integers(len(SCOPES)))]
                proposal = Proposal(
                    name=f"p{next_pid}", payload=b"x", proposal_id=next_pid,
                    proposal_owner=b"\x42" * 20,
                    expected_voters_count=int(rng.integers(3, 11)),
                    timestamp=now, expiration_timestamp=now + 10_000,
                    liveness_criteria_yes=bool(rng.random() < 0.5),
                )
                proposals[(scope, next_pid)] = proposal
                items.append((scope, proposal))
                next_pid += 1
            steps.append((now, "deliver", items))
            continue
        groups: "dict[str, list]" = {}
        for _ in range(int(rng.integers(1, 12))):
            if sent and rng.random() < 0.08:  # an exact duplicate
                scope, vote = sent[int(rng.integers(len(sent)))]
            else:
                key = live[int(rng.integers(len(live)))]
                scope, proposal = key[0], proposals[key]
                # Mostly voters not yet heard from, so sessions decide and
                # their scopes take replacements; some repeat a voter.
                done = voted.setdefault(key, set())
                fresh = [v for v in range(len(VOTERS)) if v not in done]
                v = (fresh[int(rng.integers(len(fresh)))]
                     if fresh and rng.random() < 0.9
                     else int(rng.integers(len(VOTERS))))
                done.add(v)
                # Built on the vote-free proposal: empty chain links, as
                # concurrent voters send them.
                vote = build_vote(Proposal.decode(proposal.encode()),
                                  bool(rng.random() < 0.7), VOTERS[v], now)
                sent.append((scope, vote))
            groups.setdefault(scope, []).append(vote)
        steps.append((now, "votes", list(groups.items())))
    return steps, proposals


def _vote_status(service, scope, vote, now) -> int:
    """The reference's answer in the wire's terms: a vote a decided
    session takes without inserting it (``Ok(ConsensusReached)``,
    reference src/session.rs:246) is ``ALREADY_REACHED``."""
    session = service.storage().get_session(scope, vote.proposal_id)
    reached = session is not None and session.state.is_reached
    code = _code(lambda: service.process_incoming_vote(scope, vote.clone(), now))
    if code == int(StatusCode.OK) and reached:
        return int(StatusCode.ALREADY_REACHED)
    return code


def _serve_reference(steps):
    service = ConsensusService(
        InMemoryConsensusStorage(), BroadcastEventBus(),
        StubConsensusSigner(b"\x21" * 20), CAP,
    )
    out = []
    for now, kind, items in steps:
        if kind == "deliver":
            out.append([_code(lambda s=s, p=p: service.process_incoming_proposal(
                s, Proposal.decode(p.encode()), now)) for s, p in items])
        else:
            out.append([_vote_status(service, s, v, now)
                        for s, votes in items for v in votes])
    return service, out


def _serve(server, peer, steps):
    out = []
    for now, kind, items in steps:
        if kind == "deliver":
            payload = P.encode_deliver_proposals(
                peer, [(s, p.encode()) for s, p in items], now)
            out.append(_statuses(server, P.OP_DELIVER_PROPOSALS, payload))
        else:
            payload = P.encode_vote_batch(
                now, [(peer, s, [v.encode() for v in votes])
                      for s, votes in items])
            out.append(_statuses(server, P.OP_VOTE_BATCH, payload))
    return out


def _end_state_reference(service, scope, pid):
    try:
        return bool(service.storage().get_consensus_result(scope, pid))
    except ConsensusError as exc:
        if exc.code == StatusCode.CONSENSUS_NOT_REACHED:
            return "undecided"
        return int(exc.code)


def _end_state_served(server, peer, scope, pid):
    status, body = server.dispatch_frame(
        P.OP_GET_RESULT, P.u32(peer) + P.string(scope) + P.u32(pid))
    if status != P.STATUS_OK:
        return status
    value = P.Cursor(body).u8()
    if value == P.RESULT_FAILED:
        return int(StatusCode.CONSENSUS_FAILED)
    return "undecided" if value == P.RESULT_UNDECIDED else value == P.RESULT_YES


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_sharded_served_path_matches_reference_and_one_device(seed):
    mesh = consensus_mesh(4)
    steps, proposals = _trace(seed)
    service, want = _serve_reference(steps)
    codes = {c for frame in want for c in frame}
    # The trace reaches what it is meant to: evictions (votes for an
    # evicted session), duplicates, and decided sessions (late votes).
    assert {int(StatusCode.SESSION_NOT_FOUND), int(StatusCode.DUPLICATE_VOTE),
            int(StatusCode.ALREADY_REACHED)} <= codes
    sharded, sharded_peer = _server(lambda: ShardedPool(PER_DEVICE, LANES, mesh))
    single, single_peer = _server(lambda: ProposalPool(4 * PER_DEVICE, LANES))
    try:
        assert _serve(sharded, sharded_peer, steps) == want
        assert _serve(single, single_peer, steps) == want
        states = []
        for scope, pid in proposals:
            state = _end_state_reference(service, scope, pid)
            states.append(state)
            assert _end_state_served(sharded, sharded_peer, scope, pid) == state
            assert _end_state_served(single, single_peer, scope, pid) == state
        # Retired sessions replaced (evicted), others still resident.
        assert int(StatusCode.SESSION_NOT_FOUND) in states
        assert len(set(states)) > 1
        fingerprint = P.OP_STATE_FINGERPRINT
        assert sharded.dispatch_frame(fingerprint, P.u32(sharded_peer)) == \
            single.dispatch_frame(fingerprint, P.u32(single_peer))
    finally:
        sharded.stop()
        single.stop()


def _counts():
    return {name: default_registry.counter(name).value for name in (
        POOL_ACTIVATED_SLOTS_TOTAL, POOL_ACTIVATE_DISPATCHES_TOTAL,
        POOL_SHARD_ROWS_TOTAL, POOL_SHARD_GRID_ROWS_TOTAL)}


def _delta(before):
    after = _counts()
    return {k: after[k] - before[k] for k in after}


def _allocate(pool, count, at=0):
    n = np.full(count, 8)
    return pool.allocate_batch(
        [("s", at + k) for k in range(count)], n, np.full(count, 6),
        np.full(count, 8), np.zeros(count, bool), np.ones(count, bool),
        np.full(count, NOW + 1_000), np.full(count, NOW),
    )


@pytest.mark.parametrize("traced", [False, True])
def test_routed_ingest_counts_rows_and_pad(traced):
    pool = ShardedPool(8, LANES, consensus_mesh(4))
    pool.tracer = Tracer(enabled=traced)
    slots = _allocate(pool, 11)
    before = _counts()
    # Six votes on five slots: five real rows; devices own 2, 1, 1, 1 of
    # them (slots are handed out round-robin), so B = 8 and the grid is
    # 4 x 8 = 32 rows.
    touched = np.array([slots[0], slots[4], slots[0], slots[1], slots[2],
                        slots[3]], np.int64)
    statuses, _ = pool.ingest(touched, np.array([0, 0, 1, 0, 0, 0]),
                              np.ones(6, bool), NOW)
    assert list(statuses) == [0] * 6
    real = len(np.unique(touched))
    per_device = np.bincount(np.unique(touched) // 8, minlength=4)
    grid = 4 * _bucket(int(per_device.max()))
    d = _delta(before)
    assert d[POOL_SHARD_ROWS_TOTAL] == real == 5
    assert d[POOL_SHARD_GRID_ROWS_TOTAL] - d[POOL_SHARD_ROWS_TOTAL] == grid - real
    assert d[POOL_ACTIVATED_SLOTS_TOTAL] == 0
    spans = pool.tracer.spans("pool.shard_route")
    if traced:
        # The activation of the 11 slots (3, 3, 3, 2 a device), then the
        # ingest.
        assert [s.attrs for s in spans] == [{"rows": 11, "grid_rows": 32},
                                            {"rows": 5, "grid_rows": grid}]
    else:
        assert spans == []


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("sharded", [False, True])
def test_activation_counts_slots_and_dispatches(sharded, traced):
    pool = (ShardedPool(8, LANES, consensus_mesh(4)) if sharded
            else ProposalPool(32, LANES))
    pool.tracer = Tracer(enabled=traced)
    before = _counts()
    _allocate(pool, 3)
    _allocate(pool, 1, at=3)
    d = _delta(before)
    assert d[POOL_ACTIVATED_SLOTS_TOTAL] == 4
    assert d[POOL_ACTIVATE_DISPATCHES_TOTAL] == 2
    # Activation routes on the sharded pool: 3 slots on three devices
    # (B = 8), then 1 slot.
    assert d[POOL_SHARD_ROWS_TOTAL] == (4 if sharded else 0)
    assert d[POOL_SHARD_GRID_ROWS_TOTAL] == (64 if sharded else 0)
    spans = pool.tracer.spans("pool.activate")
    assert [s.attrs for s in spans] == ([{"slots": 3}, {"slots": 1}]
                                        if traced else [])


@pytest.mark.parametrize("sharded", [False, True])
def test_sessions_created_through_the_engine_are_counted(sharded):
    pool = (ShardedPool(8, LANES, consensus_mesh(4)) if sharded
            else ProposalPool(32, LANES))
    engine = TpuConsensusEngine(StubConsensusSigner(b"\x05" * 20), pool=pool)
    before = _counts()
    for pid in range(1, 6):
        engine.process_incoming_proposal("s", Proposal(
            name=f"p{pid}", payload=b"x", proposal_id=pid,
            proposal_owner=b"\x42" * 20, expected_voters_count=5,
            timestamp=NOW, expiration_timestamp=NOW + 1_000,
        ), NOW)
    assert _delta(before)[POOL_ACTIVATED_SLOTS_TOTAL] == 5
    assert pool.allocated_slots == 5
