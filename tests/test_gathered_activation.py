"""Slot activation gathered over one proposal intake call.

``ingest_proposals`` holds ``ProposalPool.gather_activations`` open around
its per-item registration loop, so a delivery of many unknown proposals
activates its slots in one device dispatch. A batch delivery must leave
exactly the state that the same deliveries made one proposal per call
leave (each of those dispatches at once): device planes, slot ids, the
records' ``seq``/``created_at``, statuses and events, and then the same
decisions once votes arrive. Run on the one-device pool and on the
sharded pool over four of conftest's virtual CPU devices.
"""

import contextlib

import numpy as np
import pytest

from hashgraph_tpu import (
    BroadcastEventBus,
    ConsensusError,
    StubConsensusSigner,
    build_vote,
)
from hashgraph_tpu.engine import TpuConsensusEngine
from hashgraph_tpu.engine.pool import ProposalPool
from hashgraph_tpu.errors import StatusCode
from hashgraph_tpu.obs import (
    POOL_ACTIVATE_DISPATCHES_TOTAL,
    POOL_ACTIVATED_SLOTS_TOTAL,
)
from hashgraph_tpu.obs import registry as default_registry
from hashgraph_tpu.ops.decide import STATE_ACTIVE
from hashgraph_tpu.parallel import ShardedPool, consensus_mesh
from hashgraph_tpu.wire import Proposal

from common import NOW

LANES = 8
CAP = 3  # sessions kept per scope
VOTERS = [StubConsensusSigner(bytes([9, v]) * 10) for v in range(6)]
PLANES = ("_state", "_yes", "_tot", "_n", "_req", "_cap", "_gossip",
          "_liveness", "_vote_mask", "_vote_val")


def _pool(kind: str, slots: int) -> ProposalPool:
    if kind == "sharded":
        return ShardedPool(slots // 4, LANES, consensus_mesh(4))
    return ProposalPool(slots, LANES)


def _engine(pool, cap=CAP) -> TpuConsensusEngine:
    return TpuConsensusEngine(
        StubConsensusSigner(b"\x05" * 20), event_bus=BroadcastEventBus(),
        max_sessions_per_scope=cap, pool=pool,
    )


def _proposal(pid: int, now: int, expiry: int = 10_000, votes: int = 0):
    proposal = Proposal(
        name=f"p{pid}", payload=b"x", proposal_id=pid,
        proposal_owner=b"\x42" * 20, expected_voters_count=4 + pid % 3,
        timestamp=now, expiration_timestamp=now + expiry,
        liveness_criteria_yes=bool(pid % 2),
    )
    for v in range(votes):
        proposal.votes.append(build_vote(proposal, True, VOTERS[v], now))
    return proposal


def _dispatches() -> int:
    return default_registry.counter(POOL_ACTIVATE_DISPATCHES_TOTAL).value


def _pool_state(pool) -> dict:
    out = {name: np.asarray(getattr(pool, name)).tolist() for name in PLANES}
    out["state_host"] = pool._state_host.tolist()
    out["expiry_host"] = pool._expiry_host.tolist()
    out["meta"] = {s: (m.key, m.expiry, m.created_at)
                   for s, m in pool._meta.items()}
    out["free"] = list(pool._free)
    return out


def _engine_state(engine, rx) -> dict:
    out = _pool_state(engine.pool())
    out["index"] = dict(engine._index)
    out["records"] = {
        slot: (r.scope, r.proposal.proposal_id, r.seq, r.created_at,
               r.session is None)
        for slot, r in engine._records.items()
    }
    events = []
    while (event := rx.try_recv()) is not None:
        events.append(event)
    out["events"] = events
    return out


def _result(engine, scope, pid):
    try:
        return engine.get_consensus_result(scope, pid)
    except ConsensusError as exc:
        return int(exc.code)


# Engine cases: (pool slots, deliveries made before the batch at NOW - 10,
# the batch at NOW). A delivery item is (scope, pid, expiry, votes).
ENGINE_CASES = {
    # Two incumbents of scope "a" at an older created_at; the batch's
    # third and fourth "a" sessions each evict one of them, and that
    # release goes to the device after the activations gathered so far.
    "cap_eviction": (16, [("a", 1, 10_000, 0), ("a", 2, 10_000, 0)],
                     [("a", 3, 10_000, 0), ("a", 4, 10_000, 0),
                      ("b", 5, 10_000, 0), ("a", 6, 10_000, 0)]),
    # Eight slots for ten sessions: the tail is host-spilled.
    "pool_exhaustion": (8, [], [(s, 10 + k, 10_000, 0) for k, s in
                                enumerate("abcdabcdab")]),
    # The vote-carrying proposal loads its rows after the gathered
    # activations reach the device.
    "vote_carrying": (16, [], [("a", 20, 10_000, 0), ("b", 21, 10_000, 0),
                               ("a", 22, 10_000, 2), ("b", 23, 10_000, 0)]),
    "expired_item": (16, [], [("a", 30, 10_000, 0), ("b", 31, -5, 0),
                              ("c", 32, 10_000, 0)]),
    "duplicate_pid": (16, [], [("a", 40, 10_000, 0), ("b", 41, 10_000, 0),
                               ("a", 40, 10_000, 0), ("c", 42, 10_000, 0)]),
}

# What each case must reach, read from the single deliveries' run:
# (statuses, state before the votes, decisions).
REACHES = {
    "cap_eviction": lambda st, state, dec: dec[("a", 1)] == dec[("a", 2)]
    == int(StatusCode.SESSION_NOT_FOUND),
    "pool_exhaustion": lambda st, state, dec: sum(
        not r[4] for r in state["records"].values()) == 2,
    "vote_carrying": lambda st, state, dec: sum(state["_tot"]) == 2,
    "expired_item": lambda st, state, dec: int(StatusCode.PROPOSAL_EXPIRED) in st,
    "duplicate_pid": lambda st, state, dec: int(StatusCode.PROPOSAL_ALREADY_EXIST)
    in st,
}


def _deliver(engine, items, now, batch: bool) -> "list[int]":
    built = [(s, _proposal(pid, now, expiry, votes))
             for s, pid, expiry, votes in items]
    if batch:
        return engine.deliver_proposals(built, now)
    return [code for item in built for code in engine.deliver_proposals([item], now)]


def _run_engine(kind, case, batch: bool):
    slots, before, items = ENGINE_CASES[case]
    engine = _engine(_pool(kind, slots))
    if not batch:
        # The reference: every activation dispatched at once.
        engine.pool().gather_activations = contextlib.nullcontext
    rx = engine.event_bus().subscribe()
    _deliver(engine, before, NOW - 10, batch=False)
    d0 = _dispatches()
    statuses = _deliver(engine, items, NOW, batch)
    dispatches = _dispatches() - d0
    assert engine.pool()._gathered is None
    state = _engine_state(engine, rx)
    # Every session then hears from every voter, in one vote batch.
    live = sorted({(s, pid) for s, pid, _, _ in before + items})
    votes = [(s, build_vote(_proposal(pid, NOW), bool(v % 3), VOTERS[v], NOW + 1))
             for v in range(len(VOTERS)) for s, pid in live]
    vote_statuses = list(engine.ingest_votes(votes, NOW + 1))
    decided = {key: _result(engine, *key) for key in live}
    return (statuses, state, vote_statuses, decided,
            _engine_state(engine, rx)), dispatches


def _run_pool(kind, batch: bool):
    """A slot released while its activation is still gathered, then each
    other device operation of the pool with an activation gathered before
    it: ops straight on the pool, inside the scope or each dispatched at
    once."""
    pool = _pool(kind, 8)

    def alloc(key, n=4):
        return pool.allocate_batch(
            [key], [n], [3], [n], [False], [True], [NOW + 100], [NOW])[0]

    def plain(result):
        return {k: v.tolist() for k, v in result.items()}

    d0 = _dispatches()
    seen = []
    scope = pool.gather_activations() if batch else contextlib.nullcontext()
    with scope:
        a, b = alloc("a"), alloc("b", n=6)
        pool.release([b])
        mask = np.zeros((1, LANES), bool)
        mask[0, :2] = True
        pool.load_rows([a], [STATE_ACTIVE], [2], [2], mask, mask)
        seen.append(_pool_state(pool))  # nothing is gathered after a load
        c = alloc("c", n=5)
        assert c == b  # the released slot is claimed again
        seen.append(plain(pool.read_slots([a, c])))
        e = alloc("e")
        seen.append(pool.timeout([e]))
        d = alloc("d")
        statuses, transitions = pool.ingest(
            np.array([d, c]), np.array([0, 1]), np.ones(2, bool), NOW)
        seen.append((statuses.tolist(), transitions))
        f = alloc("f")
    dispatches = _dispatches() - d0
    seen.append(_pool_state(pool))
    statuses, transitions = pool.ingest(
        np.array([a, c, d, f, a, c]), np.array([2, 2, 1, 0, 3, 3]),
        np.ones(6, bool), NOW)
    seen.append((statuses.tolist(), transitions, _pool_state(pool)))
    return seen, dispatches


@pytest.mark.parametrize("kind", ["one", "sharded"])
@pytest.mark.parametrize("case", [*ENGINE_CASES, "pool_pending_release"])
def test_batch_intake_leaves_the_state_of_single_deliveries(kind, case):
    if case == "pool_pending_release":
        gathered, fewer = _run_pool(kind, batch=True)
        single, more = _run_pool(kind, batch=False)
    else:
        gathered, fewer = _run_engine(kind, case, batch=True)
        single, more = _run_engine(kind, case, batch=False)
        statuses, state, _, decided, _ = single
        assert REACHES[case](statuses, state, decided)
        assert any(isinstance(v, bool) for v in decided.values())
    assert gathered == single
    # The batch took the gathered path: fewer activation dispatches.
    assert 0 < fewer < more


@pytest.mark.parametrize("kind", ["one", "sharded"])
def test_a_delivery_activates_its_slots_in_one_dispatch(kind):
    engine = _engine(_pool(kind, 2048), cap=1025)
    slots = default_registry.counter(POOL_ACTIVATED_SLOTS_TOTAL)
    d0, s0 = _dispatches(), slots.value
    statuses = engine.deliver_proposals(
        [("a", _proposal(pid, NOW)) for pid in range(1, 1025)], NOW)
    assert statuses == [0] * 1024
    assert (_dispatches() - d0, slots.value - s0) == (1, 1024)
    d0, s0 = _dispatches(), slots.value
    engine.deliver_proposals([("b", _proposal(2000, NOW))], NOW)
    assert (_dispatches() - d0, slots.value - s0) == (1, 1)
    state = np.asarray(engine.pool()._state)
    assert (state == STATE_ACTIVE).sum() == 1025


@pytest.mark.parametrize("kind", ["one", "sharded"])
def test_nothing_is_left_pending_when_an_item_raises(kind, monkeypatch):
    engine = _engine(_pool(kind, 16), cap=16)
    pool = engine.pool()
    real = engine._note_chain_admitted
    calls = []

    def fail_third(*args):
        calls.append(args)
        if len(calls) == 3:
            raise RuntimeError("item fault")
        return real(*args)

    monkeypatch.setattr(engine, "_note_chain_admitted", fail_third)
    d0 = _dispatches()
    with pytest.raises(RuntimeError, match="item fault"):
        engine.deliver_proposals(
            [("a", _proposal(pid, NOW)) for pid in range(1, 6)], NOW)
    # The three registered items reached the device in one dispatch.
    assert pool._gathered is None
    assert _dispatches() - d0 == 1
    registered = [engine._index[("a", pid)] for pid in (1, 2, 3)]
    assert (np.asarray(pool._state)[registered] == STATE_ACTIVE).all()
    assert ("a", 4) not in engine._index


@pytest.mark.parametrize("kind", ["one", "sharded"])
def test_allocation_outside_the_scope_dispatches_at_once(kind):
    pool = _pool(kind, 16)

    def alloc(key):
        return pool.allocate_batch(
            [key], [4], [3], [4], [False], [True], [NOW + 100], [NOW])[0]

    d0 = _dispatches()
    a = alloc("a")
    assert _dispatches() - d0 == 1
    assert int(np.asarray(pool._state)[a]) == STATE_ACTIVE
    with pool.gather_activations():
        b = alloc("b")
        with pool.gather_activations():  # an inner scope defers
            c = alloc("c")
        assert _dispatches() - d0 == 1
        assert len(pool._gathered) == 2
    assert _dispatches() - d0 == 2
    assert (np.asarray(pool._state)[[b, c]] == STATE_ACTIVE).all()
