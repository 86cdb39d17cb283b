"""Tiered session lifecycle: demote / demand-page / GC.

Two pillars:

1. **Transparency** — a tiered engine is observably identical to an
   untier'd twin fed the same traffic: statuses, results, fingerprints,
   stats, and health scorecards (typed miss statuses nowhere). Unit
   cases pin each demand-page surface; a hypothesis property drives a
   random create/vote/decide/idle/late-vote script through both twins
   with demotions sprinkled arbitrarily into the tiered one.

2. **Policy** — the per-scope TTL knobs (``demote_after`` /
   ``evict_decided_after``), the sweep hook riding
   ``sweep_timeouts``, pinned-scope exclusions, per-scope-cap
   equivalence (demoted sessions still count and evict), and the spill
   accounting in ``occupancy()`` + the shared fleet rollup.
"""

import pytest

from hashgraph_tpu import (
    ConsensusFailed,
    CreateProposalRequest,
    ScopeConfig,
    SessionNotFound,
    StubConsensusSigner,
    build_vote,
)
from hashgraph_tpu.engine import TpuConsensusEngine
from hashgraph_tpu.errors import StatusCode
from hashgraph_tpu.obs.health import HealthMonitor
from hashgraph_tpu.sync import state_fingerprint

from common import NOW

import numpy as np

SIGNERS = [StubConsensusSigner(bytes([i + 1]) * 20) for i in range(4)]


def _engine(**kw) -> TpuConsensusEngine:
    kw.setdefault("capacity", 64)
    kw.setdefault("voter_capacity", 8)
    kw.setdefault("health_monitor", HealthMonitor())
    return TpuConsensusEngine(StubConsensusSigner(b"\x42" * 20), **kw)


def _request(n=3, name="prop", exp=50):
    return CreateProposalRequest(
        name=name,
        payload=b"payload",
        proposal_owner=b"owner",
        expected_voters_count=n,
        expiration_timestamp=exp,
        liveness_criteria_yes=True,
    )


def _author_proposal(n=3, name="prop", exp=50, now=NOW):
    """Mint a proposal (with a real pid) on a throwaway engine so twins
    can ingest identical bytes."""
    maker = _engine()
    return maker.create_proposal("author", _request(n, name, exp), now)


def _decide(engine, scope, proposal, votes=None):
    """Drive a proposal to YES with chained signed votes; returns the
    votes used (build once, reuse on a twin)."""
    if votes is None:
        votes = []
        chain = proposal.clone()
        for i in range(proposal.expected_voters_count):
            vote = build_vote(chain, True, SIGNERS[i], NOW + 1)
            chain.votes.append(vote)
            votes.append(vote)
    statuses = engine.ingest_votes(
        [(scope, v) for v in votes], NOW + 1
    )
    assert all(
        s in (int(StatusCode.OK), int(StatusCode.ALREADY_REACHED))
        for s in statuses
    )
    return votes


class TestDemotePromote:
    def test_fingerprint_invariant_across_demote_promote(self):
        engine = _engine()
        proposal = _author_proposal()
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        _decide(engine, "s", proposal)
        fp0 = state_fingerprint(engine)
        assert engine.demote_session("s", proposal.proposal_id) is True
        assert engine.demote_session("s", proposal.proposal_id) is False
        assert state_fingerprint(engine) == fp0, "demotion changed state"
        # Point read pages it back in.
        assert engine.get_consensus_result("s", proposal.proposal_id) is True
        assert engine.occupancy()["tier_sessions"] == 0
        assert state_fingerprint(engine) == fp0, "promotion changed state"

    def test_demoted_item_bytes_equal_snapshot_codec(self):
        """The stored tier bytes ARE the PR-8 snapshot item for the
        session — including for the bulk field-direct encode path."""
        from hashgraph_tpu.sync.snapshot import encode_session_item

        engine = _engine()
        proposal = _author_proposal()
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        _decide(engine, "s", proposal)
        expected = encode_session_item(
            "s", engine.export_session("s", proposal.proposal_id)
        )
        engine.demote_session("s", proposal.proposal_id)
        entry = engine._tier["s"][proposal.proposal_id]
        assert entry.item == expected

    def test_columnar_tally_session_roundtrip(self):
        """A session decided through columnar tallies (no Vote objects)
        demotes via the field-direct fast path and round-trips."""
        engine = _engine()
        proposal = _author_proposal(n=2)
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        gids = np.array(
            [engine.voter_gid(s.identity()) for s in SIGNERS[:2]], np.int64
        )
        pid = proposal.proposal_id
        statuses = engine.ingest_columnar(
            "s",
            np.array([pid, pid], np.int64),
            gids,
            np.array([True, True]),
            NOW + 1,
        )
        assert list(statuses) == [0, 0]
        fp0 = state_fingerprint(engine)
        engine.demote_session("s", pid)
        assert state_fingerprint(engine) == fp0
        session = engine.export_session("s", pid)  # promotes
        assert session.state.is_reached and session.state.result is True
        assert len(session.tallies) == 2
        assert state_fingerprint(engine) == fp0

    def test_host_spilled_session_demotes(self):
        """A session the pool cannot hold (host-spilled) demotes and
        promotes through the same tier."""
        engine = _engine(voter_capacity=2)
        proposal = _author_proposal(n=3)  # 3 voters > 2 lanes -> spill
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        assert engine.occupancy()["host_spilled"] == 1
        fp0 = state_fingerprint(engine)
        engine.demote_session("s", proposal.proposal_id)
        assert engine.occupancy()["host_spilled"] == 0
        assert state_fingerprint(engine) == fp0
        assert engine.get_consensus_result("s", proposal.proposal_id) is None
        assert engine.occupancy()["host_spilled"] == 1

    def test_unknown_session_raises(self):
        engine = _engine()
        with pytest.raises(SessionNotFound):
            engine.demote_session("s", 12345)


class TestDemandPaging:
    def _demoted_active(self, engine, n=3, exp=50):
        proposal = _author_proposal(n=n, exp=exp)
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        engine.demote_session("s", proposal.proposal_id)
        return proposal

    def test_late_vote_promotes_and_applies(self):
        engine = _engine()
        proposal = self._demoted_active(engine)
        vote = build_vote(proposal, True, SIGNERS[0], NOW + 1)
        statuses = engine.ingest_votes([("s", vote)], NOW + 1)
        assert list(statuses) == [int(StatusCode.OK)]
        assert engine.occupancy()["tier_sessions"] == 0
        assert engine.occupancy()["tier_promotions_total"] == 1

    def test_columnar_late_vote_promotes(self):
        engine = _engine()
        proposal = self._demoted_active(engine, n=2)
        gid = engine.voter_gid(SIGNERS[0].identity())
        statuses = engine.ingest_columnar(
            "s",
            np.array([proposal.proposal_id], np.int64),
            np.array([gid], np.int64),
            np.array([True]),
            NOW + 1,
        )
        assert list(statuses) == [int(StatusCode.OK)]
        assert engine.occupancy()["tier_sessions"] == 0

    def test_explain_and_proposal_reads_promote(self):
        engine = _engine()
        proposal = self._demoted_active(engine)
        out = engine.explain_decision("s", proposal.proposal_id)
        assert out["status"] == "active"
        assert engine.occupancy()["tier_sessions"] == 0
        engine.demote_session("s", proposal.proposal_id)
        assert (
            engine.get_proposal("s", proposal.proposal_id).proposal_id
            == proposal.proposal_id
        )

    def test_deliver_extension_promotes(self):
        engine = _engine()
        proposal = self._demoted_active(engine)
        extended = proposal.clone()
        extended.votes.append(build_vote(extended, True, SIGNERS[0], NOW + 1))
        status = engine.deliver_proposal("s", extended, NOW + 1)
        assert status == int(StatusCode.OK)
        session = engine.export_session("s", proposal.proposal_id)
        assert len(session.votes) == 1

    def test_strict_redelivery_rejects_without_promoting(self):
        from hashgraph_tpu.errors import ProposalAlreadyExist

        engine = _engine()
        proposal = self._demoted_active(engine)
        with pytest.raises(ProposalAlreadyExist):
            engine.process_incoming_proposal("s", proposal.clone(), NOW + 1)
        statuses = engine.ingest_proposals([("s", proposal.clone())], NOW + 1)
        assert statuses == [int(StatusCode.PROPOSAL_ALREADY_EXIST)]
        # The no-redelivery contract settles without paging anything in.
        assert engine.occupancy()["tier_sessions"] == 1

    def test_timeout_on_demoted_session(self):
        engine = _engine()
        proposal = self._demoted_active(engine)
        vote = build_vote(proposal, True, SIGNERS[0], NOW + 1)
        engine.ingest_votes([("s", vote)], NOW + 1)
        engine.demote_session("s", proposal.proposal_id)
        result = engine.handle_consensus_timeout(
            "s", proposal.proposal_id, NOW + 100
        )
        assert result is True  # liveness YES at timeout with one YES vote

    def test_sweep_fires_timeouts_for_demoted_sessions(self):
        engine = _engine()
        proposal = self._demoted_active(engine, exp=10)
        vote = build_vote(proposal, True, SIGNERS[0], NOW + 1)
        engine.ingest_votes([("s", vote)], NOW + 1)
        engine.demote_session("s", proposal.proposal_id)
        swept = engine.sweep_timeouts(NOW + 11)
        assert ("s", proposal.proposal_id, True) in swept

    def test_enumeration_reads_through_without_promoting(self):
        engine = _engine()
        active = self._demoted_active(engine, n=3)
        decided = _author_proposal(n=2, name="decided")
        engine.process_incoming_proposal("s", decided.clone(), NOW)
        _decide(engine, "s", decided)
        engine.demote_session("s", decided.proposal_id)
        stats = engine.get_scope_stats("s")
        assert stats.total_sessions == 2
        assert stats.active_sessions == 1
        assert stats.consensus_reached == 1
        actives = engine.get_active_proposals("s")
        assert [p.proposal_id for p in actives] == [active.proposal_id]
        reached = engine.get_reached_proposals("s")
        assert [(p.proposal_id, r) for p, r in reached] == [
            (decided.proposal_id, True)
        ]
        keys = set(engine.session_keys())
        assert keys == {("s", active.proposal_id), ("s", decided.proposal_id)}
        # All of the above read THROUGH the tier.
        assert engine.occupancy()["tier_sessions"] == 2


class TestLifecyclePolicy:
    def _tiered_scope(self, engine, demote=5.0, evict=None):
        engine.set_scope_config(
            "s", ScopeConfig(demote_after=demote, evict_decided_after=evict)
        )

    def test_ttl_demotes_idle_then_gc(self):
        engine = _engine()
        self._tiered_scope(engine, demote=5.0, evict=20.0)
        proposal = _author_proposal(n=2, name="x")
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        _decide(engine, "s", proposal)
        out = engine.lifecycle_sweep(NOW + 3)
        assert out == {"demoted": 0, "gc_live": 0, "gc_tier": 0}
        out = engine.lifecycle_sweep(NOW + 7)
        assert out["demoted"] == 1
        assert engine.occupancy()["tier_sessions"] == 1
        out = engine.lifecycle_sweep(NOW + 30)
        assert out["gc_tier"] == 1
        assert engine.occupancy()["tier_sessions"] == 0
        with pytest.raises(SessionNotFound):
            engine.get_consensus_result("s", proposal.proposal_id)

    def test_gc_live_without_demotion_window(self):
        engine = _engine()
        self._tiered_scope(engine, demote=None, evict=5.0)
        proposal = _author_proposal(n=2, name="y")
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        _decide(engine, "s", proposal)
        out = engine.lifecycle_sweep(NOW + 10)
        assert out["gc_live"] == 1
        assert engine.occupancy()["tier_gc_total"] == 1

    def test_active_sessions_never_gc(self):
        engine = _engine()
        self._tiered_scope(engine, demote=2.0, evict=4.0)
        proposal = _author_proposal(n=3, name="z", exp=1000)
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        engine.lifecycle_sweep(NOW + 100)
        occ = engine.occupancy()
        assert occ["tier_sessions"] == 1  # demoted, NOT collected
        assert occ["tier_gc_total"] == 0

    def test_pinned_scope_excluded(self):
        engine = _engine()
        self._tiered_scope(engine, demote=1.0, evict=2.0)
        proposal = _author_proposal(n=2, name="pin")
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        _decide(engine, "s", proposal)
        engine.pin_scope("s")
        out = engine.lifecycle_sweep(NOW + 100)
        assert out == {"demoted": 0, "gc_live": 0, "gc_tier": 0}
        engine.unpin_scope("s")
        out = engine.lifecycle_sweep(NOW + 100)
        assert out["gc_live"] == 1

    def test_sweep_timeouts_runs_lifecycle(self):
        engine = _engine()
        self._tiered_scope(engine, demote=5.0)
        proposal = _author_proposal(n=2, name="sw")
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        _decide(engine, "s", proposal)
        engine.sweep_timeouts(NOW + 7)
        assert engine.occupancy()["tier_sessions"] == 1

    def test_promotion_preserves_idle_clock(self):
        """Demote -> promote -> the session demotes again at the SAME
        TTL point it would have without the round-trip."""
        engine = _engine()
        self._tiered_scope(engine, demote=10.0)
        proposal = _author_proposal(n=2, name="clock")
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        _decide(engine, "s", proposal)  # last activity NOW + 1
        engine.lifecycle_sweep(NOW + 12)
        assert engine.occupancy()["tier_sessions"] == 1
        assert engine.get_consensus_result("s", proposal.proposal_id) is True
        out = engine.lifecycle_sweep(NOW + 13)
        assert out["demoted"] == 1  # still idle since NOW+1, re-demotes

    def test_churn_waves_hold_slot_and_population_ceilings(self):
        """Waves of create -> decide -> demote-after-TTL -> GC-after-TTL
        through a fixed-size engine: many times its slot capacity in
        cumulative sessions, with the device-slot, live and tier
        population ceilings held on EVERY wave."""
        scopes, per_scope, voters = ["c0", "c1"], 8, 4
        demote_after, evict_after = 2.0, 4.0
        wave = len(scopes) * per_scope
        # Live: waves inside the demotion window plus the in-flight one.
        # Tier: waves between demotion and GC.
        live_ceiling = wave * (int(demote_after) + 2)
        tier_ceiling = wave * (int(evict_after - demote_after) + 2)
        engine = _engine(
            capacity=live_ceiling,
            voter_capacity=voters,
            max_sessions_per_scope=live_ceiling + tier_ceiling,
        )
        for scope in scopes:
            engine.set_scope_config(
                scope,
                ScopeConfig(
                    demote_after=demote_after, evict_decided_after=evict_after
                ),
            )
        gids = np.array(
            [engine.voter_gid(bytes([1 + i]) * 20) for i in range(voters)],
            np.int64,
        )
        quorum = -(-2 * voters // 3)  # gossipsub default: div_ceil(2n, 3)
        requests = [_request(n=voters, exp=10**8)] * per_scope
        n_waves = 12
        for k in range(n_waves):
            now = NOW + k
            batches = engine.create_proposals_multi(
                [(scope, requests) for scope in scopes], now
            )
            pids = np.array(
                [p.proposal_id for batch in batches for p in batch], np.int64
            )
            sidx = np.repeat(np.arange(len(scopes), dtype=np.int64), per_scope)
            statuses = engine.ingest_columnar_multi(
                scopes,
                np.repeat(sidx, quorum),
                np.repeat(pids, quorum),
                np.tile(gids[:quorum], wave),
                np.ones(wave * quorum, bool),
                now,
            )
            assert (statuses == int(StatusCode.OK)).all(), np.unique(statuses)
            engine.lifecycle_sweep(now)
            occ = engine.occupancy()
            assert occ["device_slots_used"] <= live_ceiling, (k, occ)
            assert occ["live_sessions"] <= live_ceiling, (k, occ)
            assert occ["tier_sessions"] <= tier_ceiling, (k, occ)
        occ = engine.occupancy()
        # The ceilings held because sessions really cycled through the
        # tier and out of it, not because the engine stayed small.
        assert n_waves * wave > 2 * live_ceiling
        assert occ["tier_demotions_total"] > 0
        assert occ["tier_gc_total"] > 0


class TestCapEquivalence:
    def test_demoted_sessions_count_against_the_scope_cap(self):
        tiered = _engine(max_sessions_per_scope=3)
        plain = _engine(max_sessions_per_scope=3)
        proposals = [
            _author_proposal(n=2, name=f"c{i}") for i in range(5)
        ]
        for k, proposal in enumerate(proposals):
            for engine in (tiered, plain):
                engine.process_incoming_proposal(
                    "s", proposal.clone(), NOW + k
                )
            if k == 1:
                # Invisible op on the tiered twin only.
                tiered.demote_session("s", proposals[0].proposal_id)
        assert state_fingerprint(tiered) == state_fingerprint(plain)
        assert set(tiered.session_keys()) == set(plain.session_keys())
        assert len(tiered.session_keys()) == 3


class TestAccounting:
    def test_occupancy_tier_counters(self):
        engine = _engine()
        proposal = _author_proposal(n=2)
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        _decide(engine, "s", proposal)
        engine.demote_session("s", proposal.proposal_id)
        occ = engine.occupancy()
        assert occ["tier_sessions"] == 1
        assert occ["tier_bytes"] > 0
        assert occ["tier_demotions_total"] == 1
        assert occ["tier_promotions_total"] == 0
        engine.get_consensus_result("s", proposal.proposal_id)
        occ = engine.occupancy()
        assert (occ["tier_sessions"], occ["tier_bytes"]) == (0, 0)
        assert occ["tier_promotions_total"] == 1

    def test_shared_rollup_carries_tier_keys(self):
        from hashgraph_tpu.parallel.rollup import (
            OCCUPANCY_SUM_KEYS,
            aggregate_occupancy,
        )

        engine = _engine()
        proposal = _author_proposal(n=2)
        engine.process_incoming_proposal("s", proposal.clone(), NOW)
        engine.demote_session("s", proposal.proposal_id)
        entry = engine.occupancy()
        for key in OCCUPANCY_SUM_KEYS:
            assert key in entry, f"engine occupancy missing {key}"
        total = aggregate_occupancy(
            [entry, {"recovering": True}, {"migrating": True}]
        )
        assert total["tier_sessions"] == 1
        assert total["unavailable_shards"] == 2

    def test_tier_metric_families_installed(self):
        from hashgraph_tpu.obs import (
            TIER_BYTES,
            TIER_DEMOTED_SESSIONS,
            TIER_DEMOTIONS_TOTAL,
            TIER_GC_TOTAL,
            TIER_PROMOTIONS_TOTAL,
            registry,
        )

        text = registry.render_prometheus()
        for family in (
            TIER_DEMOTED_SESSIONS,
            TIER_BYTES,
            TIER_DEMOTIONS_TOTAL,
            TIER_PROMOTIONS_TOTAL,
            TIER_GC_TOTAL,
        ):
            assert family in text


# ── Decision-identity: tiered twin vs untier'd oracle ──────────────────
#
# The script runner is shared with tests/test_property_tiering.py (the
# hypothesis-driven search over the same op space); the seeded trials
# below always run, external-fuzzer-free (the test_wal_recovery pattern).


def run_identity_script(script):
    """Random create/vote/decide/idle/late-vote script through a tiered
    engine and an untier'd twin: identical statuses, results,
    fingerprints, and health scorecards — demotions are invisible."""
    tiered = _engine(max_sessions_per_scope=5)
    plain = _engine(max_sessions_per_scope=5)
    sessions = []  # (scope, pid, chain proposal mirror)
    clock = NOW
    n_created = 0
    for op in script:
        kind = op[0]
        if kind == "create":
            n = op[1]
            proposal = _author_proposal(n=n, name=f"p{n_created}", now=clock)
            n_created += 1
            outcomes = []
            for engine in (tiered, plain):
                try:
                    engine.process_incoming_proposal(
                        "s", proposal.clone(), clock
                    )
                    outcomes.append(None)
                except Exception as exc:  # noqa: BLE001 — compared by type
                    outcomes.append(type(exc))
            assert outcomes[0] == outcomes[1]
            if outcomes[0] is None:
                sessions.append(("s", proposal.proposal_id, proposal.clone()))
        elif kind == "vote":
            if not sessions:
                continue
            _, pid, chain = sessions[op[1] % len(sessions)]
            vote = build_vote(chain, op[3], SIGNERS[op[2]], clock)
            st_t = tiered.ingest_votes([("s", vote)], clock)
            st_p = plain.ingest_votes([("s", vote)], clock)
            assert list(st_t) == list(st_p)
            if int(st_p[0]) == int(StatusCode.OK):
                chain.votes.append(vote.clone())
        elif kind == "timeout":
            if not sessions:
                continue
            _, pid, _ = sessions[op[1] % len(sessions)]
            out_t = out_p = err_t = err_p = None
            try:
                out_t = tiered.handle_consensus_timeout("s", pid, clock)
            except Exception as exc:  # noqa: BLE001 — compared by type
                err_t = type(exc)
            try:
                out_p = plain.handle_consensus_timeout("s", pid, clock)
            except Exception as exc:  # noqa: BLE001
                err_p = type(exc)
            assert (out_t, err_t) == (out_p, err_p)
        elif kind == "sweep":
            clock += op[1]
            swept_t = tiered.sweep_timeouts(clock)
            swept_p = plain.sweep_timeouts(clock)
            assert sorted(swept_t) == sorted(swept_p)
        elif kind == "demote":
            if not sessions:
                continue
            _, pid, _ = sessions[op[1] % len(sessions)]
            try:
                tiered.demote_session("s", pid)
            except SessionNotFound:
                pass  # evicted on BOTH twins by the scope cap
        elif kind == "demote_all":
            for _, pid, _ in sessions:
                try:
                    tiered.demote_session("s", pid)
                except SessionNotFound:
                    pass
    # Terminal equivalence: every read surface agrees.
    assert state_fingerprint(tiered) == state_fingerprint(plain)
    assert set(tiered.session_keys()) == set(plain.session_keys())
    stats_t, stats_p = (
        engine.get_scope_stats("s") for engine in (tiered, plain)
    )
    assert (
        stats_t.total_sessions,
        stats_t.active_sessions,
        stats_t.failed_sessions,
        stats_t.consensus_reached,
    ) == (
        stats_p.total_sessions,
        stats_p.active_sessions,
        stats_p.failed_sessions,
        stats_p.consensus_reached,
    )
    for scope, pid, _ in sessions:
        res_t = res_p = err_t = err_p = None
        try:
            res_t = tiered.get_consensus_result(scope, pid)
        except (SessionNotFound, ConsensusFailed) as exc:
            err_t = type(exc)
        try:
            res_p = plain.get_consensus_result(scope, pid)
        except (SessionNotFound, ConsensusFailed) as exc:
            err_p = type(exc)
        assert (res_t, err_t) == (res_p, err_p)
    # Health scorecards: same peers, same counters.
    peers_t = tiered.health.snapshot()["peers"]
    peers_p = plain.health.snapshot()["peers"]
    assert peers_t == peers_p


def _random_script(rng, n_ops):
    ops = []
    for _ in range(n_ops):
        roll = rng.random()
        if roll < 0.25:
            ops.append(("create", rng.randint(1, 4)))
        elif roll < 0.55:
            ops.append(
                (
                    "vote",
                    rng.randrange(8),
                    rng.randrange(4),
                    rng.random() < 0.6,
                )
            )
        elif roll < 0.65:
            ops.append(("timeout", rng.randrange(8)))
        elif roll < 0.78:
            ops.append(("sweep", rng.randint(1, 30)))
        elif roll < 0.92:
            ops.append(("demote", rng.randrange(8)))
        else:
            ops.append(("demote_all",))
    return ops


def test_tiered_untiered_decision_identity_seeded():
    import random

    for seed in range(12):
        rng = random.Random(1000 + seed)
        run_identity_script(_random_script(rng, rng.randint(5, 20)))
