"""ConsensusFleet end-to-end: scope-sharded engines over the virtual
8-device CPU mesh (conftest) — routing, the one-psum fleet tally,
per-shard WAL crash/recovery isolation, and elastic membership.
"""

import threading

import numpy as np
import pytest

from hashgraph_tpu import (
    CreateProposalRequest,
    ScopeConfigBuilder,
    StatusCode,
    StubConsensusSigner,
    build_vote,
)
from hashgraph_tpu.parallel import ConsensusFleet, ShardRecoveringError

NOW = 1_700_000_000


def signer_factory(k: int):
    return StubConsensusSigner(bytes([k + 1]) * 20)


def make_fleet(n_shards=4, wal_root=None, **kw):
    kw.setdefault("capacity_per_shard", 32)
    kw.setdefault("voter_capacity", 8)
    return ConsensusFleet(
        signer_factory, n_shards=n_shards, wal_root=wal_root, **kw
    )


def request(n=4, expiry=10_000, liveness=True):
    return CreateProposalRequest(
        name="p", payload=b"", proposal_owner=b"o",
        expected_voters_count=n, expiration_timestamp=expiry,
        liveness_criteria_yes=liveness,
    )


def scopes_covering_all_shards(fleet, per_shard=1, prefix="s"):
    """Deterministically probe scope names until every shard owns
    ``per_shard`` of them; returns {shard_id: [scopes]}."""
    got = {sid: [] for sid in fleet.shard_ids}
    i = 0
    while any(len(v) < per_shard for v in got.values()):
        scope = f"{prefix}{i}"
        i += 1
        sid = fleet.owner_of(scope)
        if len(got[sid]) < per_shard:
            got[sid].append(scope)
    return got


@pytest.fixture
def fleet():
    f = make_fleet()
    yield f
    f.close()


# ── Routing ────────────────────────────────────────────────────────────


def test_distinct_devices_per_shard(fleet):
    devices = [fleet.shard(sid).device for sid in fleet.shard_ids]
    assert len(set(devices)) == len(devices)


def test_columnar_multi_routes_and_stitches(fleet):
    by_shard = scopes_covering_all_shards(fleet, per_shard=2)
    scopes = [s for group in by_shard.values() for s in group]
    for s in scopes:
        fleet.set_scope_config(
            s, ScopeConfigBuilder().gossipsub_preset().build()
        )
    pids = {
        s: [p.proposal_id for p in fleet.create_proposals(s, [request()] * 3, NOW)]
        for s in scopes
    }
    owners = [bytes([9 + i]) * 20 for i in range(3)]
    sidx, cpids, cgids, cvals = [], [], [], []
    for k, s in enumerate(scopes):
        gids = [fleet.voter_gid(s, o) for o in owners]
        for pid in pids[s]:
            for g in gids:
                sidx.append(k)
                cpids.append(pid)
                cgids.append(g)
                cvals.append(True)
    # Shuffle rows so every shard's rows interleave — the router must
    # stitch statuses back into input order.
    rng = np.random.default_rng(5)
    order = rng.permutation(len(cpids))
    st = fleet.ingest_columnar_multi(
        scopes,
        np.array(sidx)[order],
        np.array(cpids)[order],
        np.array(cgids)[order],
        np.array(cvals, bool)[order],
        NOW,
    )
    assert (st == int(StatusCode.OK)).all()
    # 3 YES on n=4 at gossip default threshold (2/3): every session decided.
    for s in scopes:
        stats = fleet.get_scope_stats(s)
        assert stats.consensus_reached == 3, (s, stats.__dict__)
    # Unknown pid rows report SESSION_NOT_FOUND in place.
    st2 = fleet.ingest_columnar_multi(
        scopes,
        np.zeros(1, np.int64),
        np.array([999_999], np.int64),
        np.zeros(1, np.int64),
        np.ones(1, bool),
        NOW,
    )
    assert st2.tolist() == [int(StatusCode.SESSION_NOT_FOUND)]


def test_single_scope_entry_points_route_to_owner(fleet):
    scope = "solo"
    sid = fleet.owner_of(scope)
    fleet.scope(scope).with_threshold(1.0).initialize()
    created = fleet.create_proposal(scope, request(n=2), NOW)
    # The session must live on the owning shard's engine, nowhere else.
    owner_engine = fleet.shard(sid).engine
    assert owner_engine.get_scope_stats(scope).total_sessions == 1
    for other in fleet.shard_ids:
        if other != sid:
            assert (
                fleet.shard(other).engine.get_scope_stats(scope).total_sessions
                == 0
            )
    st = fleet.ingest_columnar(
        scope,
        np.array([created.proposal_id], np.int64),
        np.array([fleet.voter_gid(scope, b"v" * 20)], np.int64),
        np.ones(1, bool),
        NOW,
    )
    assert st.tolist() == [int(StatusCode.OK)]
    assert fleet.get_consensus_result(scope, created.proposal_id) is None


def test_ingest_votes_and_pipelined_route(fleet):
    by_shard = scopes_covering_all_shards(fleet, prefix="v")
    scopes = [g[0] for g in by_shard.values()]
    ferries = {}
    for s in scopes:
        fleet.scope(s).with_threshold(1.0).initialize()
        p = fleet.create_proposal(s, request(n=6), NOW)
        ferries[s] = fleet.get_proposal(s, p.proposal_id)
    signers = [StubConsensusSigner(bytes([40 + i]) * 20) for i in range(4)]

    def batch_for(round_idx):
        items = []
        for s in scopes:
            ferry = ferries[s]
            v = build_vote(ferry, True, signers[round_idx], NOW + 1)
            ferry.votes.append(v)
            items.append((s, v))
        return items

    st = fleet.ingest_votes(batch_for(0), NOW + 2, pre_validated=True)
    assert (st == int(StatusCode.OK)).all()
    batches = [batch_for(1), batch_for(2), batch_for(3)]
    results = fleet.ingest_votes_pipelined(batches, NOW + 3, pre_validated=True)
    assert len(results) == 3
    for st in results:
        assert (st == int(StatusCode.OK)).all()


def test_deliver_proposals_watermark_per_shard(fleet):
    """Growing-chain redelivery through the router: each shard's
    validated-chain watermark behaves exactly like the engine's."""
    by_shard = scopes_covering_all_shards(fleet, prefix="d")
    scopes = [g[0] for g in by_shard.values()][:2]
    for s in scopes:
        fleet.scope(s).with_threshold(1.0).initialize()
    bases = {s: fleet.create_proposal(s, request(n=8), NOW) for s in scopes}
    signers = [StubConsensusSigner(bytes([60 + i]) * 20) for i in range(3)]
    chains = {}
    for s in scopes:
        chain = bases[s].clone()
        for k, signer in enumerate(signers):
            chain.votes.append(build_vote(chain, bool(k % 2), signer, NOW + 1 + k))
        chains[s] = chain
    for length in range(1, len(signers) + 1):
        items = []
        for s in scopes:
            grown = chains[s].clone()
            grown.votes = [v.clone() for v in chains[s].votes[:length]]
            items.append((s, grown))
        codes = fleet.deliver_proposals(items, NOW + 50)
        assert codes == [int(StatusCode.OK)] * len(items), (length, codes)
    # Full redelivery settles crypto-free as ALREADY_EXIST on every shard.
    codes = fleet.deliver_proposals(
        [(s, chains[s].clone()) for s in scopes], NOW + 50
    )
    assert codes == [int(StatusCode.PROPOSAL_ALREADY_EXIST)] * len(scopes)


# ── Fleet tally / breakdown ────────────────────────────────────────────


def test_fleet_state_counts_psum_matches_host_mirrors(fleet):
    from hashgraph_tpu.ops.decide import STATE_ACTIVE, STATE_FREE

    by_shard = scopes_covering_all_shards(fleet, prefix="t")
    total = 0
    for group in by_shard.values():
        s = group[0]
        fleet.scope(s).with_threshold(1.0).initialize()
        fleet.create_proposals(s, [request(n=4)] * 2, NOW)
        total += 2
    # Device-psum path engaged (distinct devices) and equal to the host sum.
    assert fleet._tally() is not None
    counts = fleet.fleet_state_counts()
    host = {}
    for sid in fleet.shard_ids:
        for code, c in fleet.shard(sid).pool().state_counts().items():
            host[code] = host.get(code, 0) + c
    for code, c in host.items():
        assert counts.get(code, 0) == c, (code, counts, host)
    assert counts[STATE_ACTIVE] == total
    assert counts[STATE_FREE] == 32 * 4 - total


def test_mixed_presets_sweep_and_tally_on_two_shards():
    """A sustained mixed gossipsub/P2P workload on two shards: shuffled
    columnar rows route to their owners with no unresolved id or stale
    voter, scopes with an SLO objective and adaptive timeout bounds ride
    the same path, and the timeout sweep settles every session so the
    one-psum tally reports none left active."""
    from hashgraph_tpu.ops.decide import STATE_ACTIVE, STATE_FREE

    fleet = make_fleet(n_shards=2, capacity_per_shard=16, voter_capacity=16)
    try:
        rng = np.random.default_rng(31)
        by_shard = scopes_covering_all_shards(fleet, per_shard=2, prefix="m")
        scopes = [s for group in by_shard.values() for s in group]
        for i, s in enumerate(scopes):
            builder = ScopeConfigBuilder()
            builder = builder.p2p_preset() if i % 2 else builder.gossipsub_preset()
            builder = builder.with_decide_p99_ms(5_000.0)
            if i // 2 % 2:  # each preset with and without timeout bounds
                builder = builder.with_timeout_bounds(0.5, 30.0)
            fleet.set_scope_config(s, builder.build())
        per_scope = 6
        pids = {
            s: [
                p.proposal_id
                for p in fleet.create_proposals(
                    s, [request(n=16, expiry=100)] * per_scope, NOW
                )
            ]
            for s in scopes
        }
        present = 8
        owners = [bytes([70 + i]) * 20 for i in range(present)]
        rows = [
            (k, pid, fleet.voter_gid(s, o))
            for k, s in enumerate(scopes)
            for pid in pids[s]
            for o in owners
        ]
        order = rng.permutation(len(rows))
        sidx, cpids, cgids = (np.array(col)[order] for col in zip(*rows))
        st = fleet.ingest_columnar_multi(
            scopes, sidx, cpids, cgids, rng.random(len(rows)) < 0.5, NOW
        )
        assert (st == int(StatusCode.OK)).all(), np.unique(st)
        swept = fleet.sweep_timeouts(NOW + 200)
        assert len(swept) == len(scopes) * per_scope
        counts = fleet.fleet_state_counts()
        assert fleet._tally() is not None  # the device psum path
        assert counts.get(STATE_ACTIVE, 0) == 0
        assert counts[STATE_FREE] == 2 * 16 - len(scopes) * per_scope
    finally:
        fleet.close()


def test_occupancy_and_health_breakdown(fleet):
    by_shard = scopes_covering_all_shards(fleet, prefix="o")
    for group in by_shard.values():
        s = group[0]
        fleet.scope(s).with_threshold(1.0).initialize()
        fleet.create_proposal(s, request(), NOW)
    occ = fleet.occupancy()
    assert set(occ) == set(fleet.shard_ids)
    for sid, entry in occ.items():
        assert entry["live_sessions"] == 1
        assert entry["device_slots_used"] == 1
        assert entry["capacity"] == 32
        assert sum(entry["per_device_slots_used"]) == 1
    health = fleet.health_report(NOW)
    assert set(health) == set(fleet.shard_ids)
    for rep in health.values():
        assert "peers" in rep and "alerts" in rep


# ── Elastic membership ─────────────────────────────────────────────────


def test_pinned_scopes_survive_add_shard(fleet):
    by_shard = scopes_covering_all_shards(fleet, per_shard=2, prefix="e")
    live = {}
    for group in by_shard.values():
        s = group[0]
        fleet.scope(s).with_threshold(1.0).initialize()
        p = fleet.create_proposal(s, request(), NOW)
        live[s] = (fleet.owner_of(s), p.proposal_id)
    new_sid = fleet.add_shard()
    assert new_sid in fleet.shard_ids and fleet.n_shards == 5
    # Every LIVE scope still routes to the shard holding its sessions.
    for s, (sid, pid) in live.items():
        assert fleet.owner_of(s) == sid
        assert fleet.get_proposal(s, pid).proposal_id == pid
    # New scopes can land on the new shard (rendezvous steals ~1/5).
    stolen = [
        f"fresh{i}" for i in range(100)
        if fleet.owner_of(f"fresh{i}") == new_sid
    ]
    assert stolen, "new shard never wins placement"
    s = stolen[0]
    fleet.scope(s).with_threshold(1.0).initialize()
    p = fleet.create_proposal(s, request(), NOW)
    assert (
        fleet.shard(new_sid).engine.get_scope_stats(s).total_sessions == 1
    )
    # Removing a shard with live pinned scopes is refused without force.
    pinned_sid = next(iter(live.values()))[0]
    with pytest.raises(ValueError, match="live scopes"):
        fleet.remove_shard(pinned_sid)
    # delete_scope releases the pin; a drained shard removes cleanly.
    fleet.delete_scope(s)
    fleet.remove_shard(new_sid)
    assert fleet.n_shards == 4


# ── Crash / recovery isolation ─────────────────────────────────────────


def _build_wal_traffic(fleet, scope, n_votes=4):
    fleet.scope(scope).with_threshold(1.0).initialize()
    p = fleet.create_proposal(scope, request(n=n_votes + 2), NOW)
    ferry = fleet.get_proposal(scope, p.proposal_id)
    items = []
    for i in range(n_votes):
        v = build_vote(
            ferry, True, StubConsensusSigner(bytes([80 + i]) * 20), NOW + 1 + i
        )
        ferry.votes.append(v)
        items.append((scope, v))
    st = fleet.ingest_votes(items, NOW + 10, pre_validated=True)
    assert (st == int(StatusCode.OK)).all()
    return p.proposal_id


def test_recovery_does_not_stall_other_shards(tmp_path):
    """THE isolation contract: killing + WAL-replaying one shard's engine
    must not stall ingest on the other shards. The replay is held
    mid-record via the on_record hook while the test drives real traffic
    through every other shard and asserts it completes."""
    fleet = make_fleet(n_shards=3, wal_root=str(tmp_path))
    try:
        by_shard = scopes_covering_all_shards(fleet, prefix="r")
        victim_sid = fleet.shard_ids[0]
        victim_scope = by_shard[victim_sid][0]
        victim_pid = _build_wal_traffic(fleet, victim_scope)
        survivors = {
            sid: group[0]
            for sid, group in by_shard.items()
            if sid != victim_sid
        }
        ferries = {}
        for s in survivors.values():
            fleet.scope(s).with_threshold(1.0).initialize()
            p = fleet.create_proposal(s, request(n=8), NOW)
            ferries[s] = fleet.get_proposal(s, p.proposal_id)

        fleet.crash_shard(victim_sid)
        gate, release = threading.Event(), threading.Event()

        def on_record(lsn, kind):
            gate.set()
            assert release.wait(timeout=60), "test released the replay late"

        thread = fleet.recover_shard(
            victim_sid, background=True, on_record=on_record
        )
        try:
            assert gate.wait(timeout=60), "replay never reached a record"
            # Replay is BLOCKED mid-record. Other shards must serve, both
            # scalar and columnar:
            items = []
            for s, ferry in ferries.items():
                v = build_vote(
                    ferry, True, StubConsensusSigner(b"x" * 20), NOW + 20
                )
                ferry.votes.append(v)
                items.append((s, v))
            st = fleet.ingest_votes(items, NOW + 21, pre_validated=True)
            assert (st == int(StatusCode.OK)).all()
            # The recovering shard's scopes fail fast (no deadlock/stall)...
            with pytest.raises(ShardRecoveringError):
                fleet.get_scope_stats(victim_scope)
            # ...and batch routers either raise or mark rows NOT_FOUND.
            some_scope = next(iter(survivors.values()))
            with pytest.raises(ShardRecoveringError):
                fleet.ingest_columnar_multi(
                    [victim_scope, some_scope],
                    np.zeros(1, np.int64),
                    np.array([victim_pid], np.int64),
                    np.zeros(1, np.int64),
                    np.ones(1, bool),
                    NOW + 22,
                )
            st = fleet.ingest_columnar_multi(
                [victim_scope],
                np.zeros(1, np.int64),
                np.array([victim_pid], np.int64),
                np.zeros(1, np.int64),
                np.ones(1, bool),
                NOW + 22,
                unavailable_ok=True,
            )
            assert st.tolist() == [int(StatusCode.SESSION_NOT_FOUND)]
            # Fleet-wide readouts must keep working mid-recovery (host
            # fallback over the SERVING shards — no crash on the crashed
            # shard's dropped engine).
            counts = fleet.fleet_state_counts()
            assert sum(counts.values()) == 32 * 2  # two serving shards
            assert fleet.occupancy()[victim_sid]["recovering"] is True
        finally:
            release.set()
        thread.join(timeout=120)
        assert not thread.is_alive()
        # Recovered shard serves again with its pre-crash state intact.
        assert fleet.shard(victim_sid).available
        stats = fleet.get_scope_stats(victim_scope)
        assert stats.total_sessions == 1
        assert len(fleet.get_proposal(victim_scope, victim_pid).votes) == 4
    finally:
        fleet.close()


def test_recover_foreground_roundtrip(tmp_path):
    fleet = make_fleet(n_shards=2, wal_root=str(tmp_path))
    try:
        scope = scopes_covering_all_shards(fleet, prefix="f")[
            fleet.shard_ids[1]
        ][0]
        pid = _build_wal_traffic(fleet, scope, n_votes=3)
        before = fleet.get_scope_stats(scope).__dict__
        fleet.crash_shard(fleet.shard_ids[1])
        assert not fleet.shard(fleet.shard_ids[1]).available
        fleet.recover_shard(fleet.shard_ids[1])
        assert fleet.get_scope_stats(scope).__dict__ == before
        # Post-recovery the shard takes NEW traffic (watermark replay
        # left the chain extendable).
        ferry = fleet.get_proposal(scope, pid)
        v = build_vote(ferry, True, StubConsensusSigner(b"y" * 20), NOW + 30)
        st = fleet.ingest_votes([(scope, v)], NOW + 31, pre_validated=True)
        assert st.tolist() == [int(StatusCode.OK)]
    finally:
        fleet.close()


def test_close_releases_every_shard_wal(tmp_path):
    """fleet.close() must actually close each DurableEngine (flush +
    release the directory flock) — regression for the dead
    ``callable(wal)`` guard (``wal`` is a property returning a WalWriter,
    never callable): a new writer on the same directory must succeed
    immediately after close."""
    from hashgraph_tpu.wal import WalWriter

    fleet = make_fleet(n_shards=2, wal_root=str(tmp_path))
    scope = scopes_covering_all_shards(fleet, prefix="c")[fleet.shard_ids[0]][0]
    _build_wal_traffic(fleet, scope, n_votes=2)
    wal_dirs = [fleet.shard(sid).wal_dir for sid in fleet.shard_ids]
    fleet.close()
    for wal_dir in wal_dirs:
        with WalWriter(wal_dir) as wal:  # would raise on a held flock
            assert wal.directory == wal_dir


def test_delete_scope_evicts_placement_memo(fleet):
    scope = "churny"
    fleet.scope(scope).with_threshold(1.0).initialize()
    assert scope in fleet.placement._cache
    fleet.delete_scope(scope)
    assert scope not in fleet.placement._cache


def test_crash_without_wal_root_is_rejected(fleet):
    with pytest.raises(ValueError, match="wal_root"):
        fleet.crash_shard(fleet.shard_ids[0])


def test_recovery_rebuilds_pre_crash_identity_after_membership_change(
    tmp_path,
):
    """The recovery signer index is the shard's CONSTRUCTION index, not
    its current dict position: removing an earlier shard must not make a
    later shard recover with someone else's identity."""
    fleet = make_fleet(n_shards=3, wal_root=str(tmp_path))
    try:
        victim = fleet.shard_ids[2]
        identity_before = fleet.shard(victim).engine.signer().identity()
        assert identity_before == signer_factory(2).identity()
        fleet.remove_shard(fleet.shard_ids[0])  # reshuffles dict positions
        fleet.crash_shard(victim)
        fleet.recover_shard(victim)
        assert (
            fleet.shard(victim).engine.signer().identity() == identity_before
        )
        # add_shard after a removal mints a FRESH index (never reuses 0).
        new_sid = fleet.add_shard()
        new_identity = fleet.shard(new_sid).engine.signer().identity()
        taken = {
            fleet.shard(sid).engine.signer().identity()
            for sid in fleet.shard_ids
            if sid != new_sid
        }
        assert new_identity not in taken
    finally:
        fleet.close()


def test_failed_background_recovery_is_surfaced_and_retryable(tmp_path):
    fleet = make_fleet(n_shards=2, wal_root=str(tmp_path))
    try:
        victim = fleet.shard_ids[0]
        scope = scopes_covering_all_shards(fleet, prefix="fb")[victim][0]
        _build_wal_traffic(fleet, scope, n_votes=2)
        fleet.crash_shard(victim)

        def exploding(lsn, kind):
            raise RuntimeError("disk went away")

        thread = fleet.recover_shard(
            victim, background=True, on_record=exploding
        )
        thread.join(timeout=60)
        assert not thread.is_alive()
        shard = fleet.shard(victim)
        assert not shard.available  # still down, not half-recovered
        assert isinstance(shard.recovery_error, RuntimeError)
        assert "disk went away" in fleet.occupancy()[victim]["recovery_error"]
        assert (
            "disk went away" in fleet.health_report(NOW)[victim]["recovery_error"]
        )
        # Retry without the fault: recovers cleanly, error cleared.
        fleet.recover_shard(victim)
        assert shard.available and shard.recovery_error is None
        assert fleet.get_scope_stats(scope).total_sessions == 1
    finally:
        fleet.close()
