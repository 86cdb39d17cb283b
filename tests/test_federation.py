"""Federated multi-host fleet, in-process: two FleetGroups over real
TCP loopback — remote vote routing over the gossip fabric, cross-host
tallies on the fabric path, and live shard migration under traffic with
the typed retry-after window.

A :class:`FederationDriver` — the stateless front end with its own
placement view, as a separate process would hold — routes votes into
the same groups: zero loss across a migration whose in-window tail it
buffers and replays, and the merged scrape and SLO view it pulls.

(The multi-PROCESS topology — separate OS processes per host — is
``examples/federation_scrape_smoke.py``; these tests exercise the same
code with both groups in one process.)"""

import json
import os
import threading
import time

import pytest

from hashgraph_tpu import CreateProposalRequest, StubConsensusSigner, build_vote
from hashgraph_tpu.errors import StatusCode
from hashgraph_tpu.parallel.federation import (
    FederationDriver,
    FederationPlacement,
    FleetGroup,
    migrate_shard,
)
from hashgraph_tpu.parallel.fleet import ShardMigratingError

NOW = 1_700_000_000
OK = int(StatusCode.OK)
ALREADY = int(StatusCode.ALREADY_REACHED)


def _build_federation(wal_root):
    placement = FederationPlacement.uniform(["h0", "h1"], 2)
    groups = {}
    for host in ("h0", "h1"):
        groups[host] = FleetGroup(
            host,
            lambda k: StubConsensusSigner(bytes([k + 1]) * 20),
            placement=placement,
            wal_root=wal_root,
            capacity_per_shard=64,
            voter_capacity=8,
        )
        groups[host].start()
    for a in groups:
        for b in groups:
            if a != b:
                groups[a].connect(b, *groups[b].address, groups[b].peer_id)
    return placement, groups


# Module-scoped: building two FleetGroups compiles jax kernels, so the
# read-only / freeze-and-abort tests share one topology (distinct scope
# tags keep them independent). Tests that CHANGE the topology (a real
# migration) take the fresh fixture below.
@pytest.fixture(scope="module")
def federation(tmp_path_factory):
    placement, groups = _build_federation(
        str(tmp_path_factory.mktemp("federation"))
    )
    try:
        yield placement, groups
    finally:
        for group in groups.values():
            group.close()


@pytest.fixture()
def fresh_federation(tmp_path):
    placement, groups = _build_federation(str(tmp_path))
    try:
        yield placement, groups
    finally:
        for group in groups.values():
            group.close()


def scope_owned_by(placement, host, tag="s"):
    return next(
        f"{tag}{i}" for i in range(1000)
        if placement.owner(f"{tag}{i}")[0] == host
    )


def make_session(placement, groups, scope, voters=3):
    """Create a proposal on the owner host, pin, and return (proposal,
    ``voters`` chained signed votes)."""
    host, shard = placement.owner(scope)
    request = CreateProposalRequest(
        name="p", payload=b"", proposal_owner=b"o" * 20,
        expected_voters_count=voters, expiration_timestamp=3600,
        liveness_criteria_yes=True,
    )
    proposal = groups[host].adapter.create_proposal(scope, request, NOW)
    placement.pin(scope, shard)
    votes = []
    for i in range(voters):
        vote = build_vote(
            proposal, True, StubConsensusSigner(bytes([50 + i]) * 20), NOW + 1
        )
        proposal.votes.append(vote)
        votes.append(vote)
    return proposal, votes


def test_remote_votes_ride_the_fabric(federation):
    """Votes submitted on the NON-owning host land on the owner over a
    coalesced OP_VOTE_BATCH frame — not SESSION_NOT_FOUND."""
    placement, groups = federation
    scope = scope_owned_by(placement, "h1")
    proposal, votes = make_session(placement, groups, scope)
    statuses = groups["h0"].ingest_votes(
        [(scope, v) for v in votes[:2]], NOW + 2
    )
    assert (statuses == OK).all(), statuses
    # 2/3 quorum: decided on the owner.
    assert (
        groups["h1"].adapter.get_consensus_result(
            scope, proposal.proposal_id
        )
        is True
    )
    # Mixed local+remote batch in one call, statuses in input order.
    local_scope = scope_owned_by(placement, "h0", tag="loc")
    local_prop, local_votes = make_session(placement, groups, local_scope)
    mixed = [(scope, votes[2]), (local_scope, local_votes[0]),
             (local_scope, local_votes[1])]
    statuses = groups["h0"].ingest_votes(mixed, NOW + 3)
    assert statuses[0] == ALREADY  # decided session absorbs
    assert statuses[1] == OK and statuses[2] == OK, statuses


def test_remote_statuses_align_on_interleaved_scopes(federation):
    """Two remote scopes interleaved in one call: the frame groups rows
    per scope (reordering them), so statuses must map back through the
    frame order — each row's status describes ITS vote. A bad vote
    placed between good ones is the discriminator."""
    placement, groups = federation
    s_a = scope_owned_by(placement, "h1", tag="ila")
    s_b = scope_owned_by(placement, "h1", tag="ilb")
    _pa, votes_a = make_session(placement, groups, s_a)
    _pb, votes_b = make_session(placement, groups, s_b)
    # B's SECOND vote without its first: a dangling chain link the
    # engine rejects (RECEIVED_HASH_MISMATCH) — in input position 1,
    # but in frame position 2 (after both A rows).
    items = [(s_a, votes_a[0]), (s_b, votes_b[1]), (s_a, votes_a[1])]
    statuses = groups["h0"].ingest_votes(items, NOW + 2)
    assert statuses[0] == OK, statuses
    assert statuses[1] == int(StatusCode.RECEIVED_HASH_MISMATCH), statuses
    assert statuses[2] == OK, statuses


def test_deliver_proposals_routes_remotely(federation):
    placement, groups = federation
    scope = scope_owned_by(placement, "h1", tag="dlv")
    proposal, _votes = make_session(placement, groups, scope)
    # Deliver the full chain from the non-owner: extends the owner's
    # empty chain via the watermark path (one OP_DELIVER_PROPOSALS
    # frame over the fabric).
    codes = groups["h0"].deliver_proposals([(scope, proposal)], NOW + 2)
    assert codes[0] in (OK, int(StatusCode.PROPOSAL_ALREADY_EXIST)), codes
    assert (
        groups["h1"].adapter.get_consensus_result(
            scope, proposal.proposal_id
        )
        is True
    )


def test_federated_state_counts_fabric_path(federation):
    """Cross-host tallies on the OP_FLEET_TALLY fabric arm (this box has
    no cross-process collectives — tally_path() says so)."""
    from hashgraph_tpu.parallel.federation import tally_path

    placement, groups = federation
    assert tally_path() == "fabric"
    from hashgraph_tpu.ops.decide import STATE_ACTIVE

    before = groups["h0"].federated_state_counts()
    for host in ("h0", "h1"):
        scope = scope_owned_by(placement, host, tag=f"tly-{host}-")
        make_session(placement, groups, scope)
    counts0 = groups["h0"].federated_state_counts()
    counts1 = groups["h1"].federated_state_counts()
    assert counts0 == counts1  # both sum the same federation
    delta = counts0.get(STATE_ACTIVE, 0) - before.get(STATE_ACTIVE, 0)
    assert delta == 2, (before, counts0)
    # The federation's total slot space: 2 hosts x 2 shards x 64.
    assert sum(counts0.values()) == 4 * 64, counts0


def test_fleet_tally_opcode_over_bridge(federation):
    from hashgraph_tpu.bridge.client import BridgeClient

    placement, groups = federation
    with BridgeClient(*groups["h0"].address) as client:
        counts = client.fleet_tally(groups["h0"].peer_id)
    # One host's whole local fleet: 2 shards x 64 slots.
    assert sum(counts.values()) == 2 * 64, counts


def test_migrating_shard_raises_typed_with_retry_after(federation):
    placement, groups = federation
    scope = scope_owned_by(placement, "h1", tag="frz")
    _proposal, votes = make_session(placement, groups, scope)
    _host, shard = placement.owner(scope)
    # Freeze BOTH sides the orchestrator freezes: the placement (drivers
    # consult it) and the owning fleet (the wire refuses typed).
    placement.begin_migration(shard, retry_after=0.25)
    groups["h1"].fleet.begin_migration(shard, retry_after=0.25)
    try:
        with pytest.raises(ShardMigratingError) as excinfo:
            groups["h0"].ingest_votes([(scope, votes[0])], NOW + 2)
        assert excinfo.value.retry_after == 0.25
        assert excinfo.value.shard_id == shard
        # Local routes on the owner refuse the same way.
        with pytest.raises(ShardMigratingError):
            groups["h1"].ingest_votes([(scope, votes[0])], NOW + 2)
    finally:
        placement.abort_migration(shard)
        groups["h1"].fleet.end_migration(shard)
    # The freeze lifted: the held vote lands.
    statuses = groups["h0"].ingest_votes([(scope, votes[0])], NOW + 3)
    assert statuses[0] == OK, statuses


def test_wire_migrating_status_crosses_the_bridge(federation):
    """The typed refusal survives the wire: a remote sender's
    OP_VOTE_BATCH frame comes back STATUS_SHARD_MIGRATING (246) when
    the owner froze AFTER the sender's placement read."""
    from hashgraph_tpu.bridge import protocol as P
    from hashgraph_tpu.bridge.client import BridgeClient, BridgeError

    placement, groups = federation
    scope = scope_owned_by(placement, "h1", tag="wire")
    _proposal, votes = make_session(placement, groups, scope)
    _host, shard = placement.owner(scope)
    groups["h1"].fleet.begin_migration(shard, retry_after=0.5)
    try:
        with BridgeClient(*groups["h1"].address) as client:
            payload = P.encode_vote_batch(
                NOW + 2,
                [(groups["h1"].peer_id, scope, [votes[0].encode()])],
            )
            with pytest.raises(BridgeError) as excinfo:
                client._call(P.OP_VOTE_BATCH, payload)
            assert excinfo.value.status == P.STATUS_SHARD_MIGRATING
    finally:
        groups["h1"].fleet.end_migration(shard)


def test_live_migration_under_traffic(fresh_federation):
    """The tentpole end to end, in process: sustained ingest with a
    typed-retry loop while the scope's shard re-homes h1 -> h0.
    Zero lost votes, source==destination fingerprints (asserted inside
    migrate_shard), atomic flip, migration metrics + flight events, and
    the session keeps serving."""
    from hashgraph_tpu.obs import (
        FEDERATION_MIGRATION_SECONDS,
        FEDERATION_MIGRATIONS_TOTAL,
        registry,
    )

    placement, groups = fresh_federation
    migrations0 = registry.counter(FEDERATION_MIGRATIONS_TOTAL).value
    seconds0 = registry.histogram(FEDERATION_MIGRATION_SECONDS).count
    scope = scope_owned_by(placement, "h1", tag="live")
    # 24 chained votes against a quorum of EXACTLY 24 (ceil(2*36/3)):
    # the last vote is the deciding one, so `result is True` proves
    # every single vote survived the migration — and no vote ever links
    # past an absorbed post-decision vote (which would be a dangling
    # chain by protocol rule, not a migration artifact).
    host, shard = placement.owner(scope)
    request = CreateProposalRequest(
        name="p", payload=b"", proposal_owner=b"o" * 20,
        expected_voters_count=36, expiration_timestamp=3600,
        liveness_criteria_yes=True,
    )
    proposal = groups[host].adapter.create_proposal(scope, request, NOW)
    placement.pin(scope, shard)
    votes = []
    for i in range(24):
        vote = build_vote(
            proposal, True, StubConsensusSigner(bytes([50 + i]) * 20),
            NOW + 1,
        )
        proposal.votes.append(vote)
        votes.append(vote)

    applied = []
    errors = []

    def traffic():
        try:
            for vote in votes:
                while True:  # the retry-after loop the error prescribes
                    try:
                        statuses = groups["h0"].ingest_votes(
                            [(scope, vote)], NOW + 2
                        )
                        break
                    except ShardMigratingError as exc:
                        time.sleep(min(exc.retry_after, 0.05))
                assert statuses[0] in (OK, ALREADY), statuses
                applied.append(int(statuses[0]))
        except BaseException as exc:  # surfaced by the join below
            errors.append(exc)

    thread = threading.Thread(target=traffic)
    thread.start()
    time.sleep(0.05)  # let some votes land pre-migration
    report = migrate_shard(
        placement, groups, shard, "h0", retry_after=0.05
    )
    thread.join(timeout=60)
    assert not thread.is_alive()
    assert not errors, errors
    assert report["from"] == "h1" and report["to"] == "h0"
    assert report["sessions"] >= 1
    assert placement.owner(scope) == ("h0", shard)
    assert shard in groups["h0"].fleet.shard_ids
    assert shard not in groups["h1"].fleet.shard_ids
    # ZERO LOST VOTES: all 24 landed as plain acks across freeze+flip.
    assert len(applied) == 24 and all(s == OK for s in applied), applied
    # The migrated session decided on its new home AT THE LAST VOTE:
    # True iff nothing was lost across the migration.
    result = groups["h0"].adapter.get_consensus_result(
        scope, proposal.proposal_id
    )
    assert result is True, (result, applied)
    # One migration, counted and timed.
    assert (
        registry.counter(FEDERATION_MIGRATIONS_TOTAL).value
        == migrations0 + 1
    )
    assert (
        registry.histogram(FEDERATION_MIGRATION_SECONDS).count
        == seconds0 + 1
    )
    # Drain h1 COMPLETELY (its last shard migrates too — the
    # decommission flow): the emptied host keeps serving the wire, and
    # new scopes rendezvous only onto hosts that home shards.
    last = placement.shards_of("h1")[0]
    migrate_shard(placement, groups, last, "h0")
    assert placement.shards_of("h1") == []
    assert groups["h1"].fleet.n_shards == 0
    for i in range(16):
        assert placement.owner(f"post-drain-{i}")[0] == "h0"


def _driver(placement, groups):
    driver = FederationDriver(placement, flush_votes=10**6, flush_interval=60.0)
    for host, group in groups.items():
        driver.connect(host, *group.address, group.peer_id)
    return driver


def test_driver_migration_replays_the_tail_with_zero_loss(fresh_federation):
    """The driver's half of a live migration: votes still in its open
    coalescer window when the freeze begins, and votes submitted during
    the freeze, all buffer into the shard's tail and replay to the new
    owner after the flip. Nothing is lost or rejected, and the session
    decides on its new home at its last vote."""
    placement, groups = fresh_federation
    # The driver holds its own placement view, as a separate process would.
    view = FederationPlacement.uniform(["h0", "h1"], 2)
    scope = scope_owned_by(placement, "h1", tag="drv")
    host, shard = placement.owner(scope)
    request = CreateProposalRequest(
        name="p", payload=b"", proposal_owner=b"o" * 20,
        expected_voters_count=36, expiration_timestamp=3600,
        liveness_criteria_yes=True,
    )
    proposal = groups[host].adapter.create_proposal(scope, request, NOW)
    placement.pin(scope, shard)
    view.pin(scope, shard)
    votes = []
    for i in range(24):  # quorum of exactly 24: the last vote decides
        vote = build_vote(
            proposal, True, StubConsensusSigner(bytes([80 + i]) * 20),
            NOW + 1,
        )
        proposal.votes.append(vote)
        votes.append(vote.encode())
    driver = _driver(view, groups)
    try:
        assert driver.submit(scope, votes[:8], NOW + 2) == "sent"
        first = driver.drain()
        assert (first["acked"], first["rejected"]) == (8, 0), first
        assert driver.submit(scope, votes[8:16], NOW + 2) == "sent"  # windowed
        driver.begin_shard_migration(shard, retry_after=0.05)
        assert driver.submit(scope, votes[16:], NOW + 2) == "buffered"
        peer_id, src_fingerprint = groups["h1"].export_shard(shard, 0.05)
        adopted = groups["h0"].adopt_shard(
            shard, *groups["h1"].address, peer_id
        )
        assert adopted["fingerprint"] == src_fingerprint
        placement.complete_migration(shard, "h0")
        flip = driver.complete_shard_migration(shard, "h0")
        assert flip["tail_votes"] == 16
        groups["h1"].retire_shard(shard, peer_id)
        report = driver.drain()
    finally:
        driver.close()
    assert report["submitted"] == 16 and report["acked"] == 16, report
    assert report["rejected"] == 0 and report["buffered"] == 0, report
    assert view.owner(scope) == placement.owner(scope) == ("h0", shard)
    result = groups["h0"].adapter.get_consensus_result(
        scope, proposal.proposal_id
    )
    assert result is True


def test_driver_merged_scrape_and_slo_breach(federation, tmp_path):
    """Through the driver: one merged scrape carries both hosts'
    labelled families and the decision histogram, an impossible
    objective declared on a scope of each host fires that scope's alert
    in the merged SLO view, and the breach leaves a trace-linked,
    Perfetto-loadable incident dump."""
    from hashgraph_tpu import ScopeConfigBuilder
    from hashgraph_tpu.obs import slo_engine
    from hashgraph_tpu.obs.slo import IncidentCapture

    placement, groups = federation
    saved_capture = slo_engine.capture
    slo_engine.reset()
    slo_engine.capture = IncidentCapture(str(tmp_path))
    driver = _driver(placement, groups)
    try:
        probes = [
            scope_owned_by(placement, host, tag=f"slo-{host}-")
            for host in ("h0", "h1")
        ]
        for probe in probes:
            owner, _shard = placement.owner(probe)
            groups[owner].fleet.set_scope_config(
                probe, ScopeConfigBuilder().with_decide_p99_ms(0.001).build()
            )
            _proposal, votes = make_session(placement, groups, probe)
            driver.submit(probe, [v.encode() for v in votes], NOW + 2)
        report = driver.drain()
        assert report["acked"] == 6 and report["rejected"] == 0, report
        text = driver.merged_metrics_text()
        firing = driver.merged_slo()["alerts_firing"]
        incidents = slo_engine.capture.incidents()
    finally:
        driver.close()
        slo_engine.capture = saved_capture
        slo_engine.reset()
    assert 'host="h0"' in text and 'host="h1"' in text
    assert "hashgraph_decision_latency_seconds_bucket" in text
    for probe in probes:
        assert any(alert.endswith(f"/{probe}") for alert in firing), firing
    assert incidents
    linked = []
    for name in incidents:
        with open(os.path.join(tmp_path, name, "incident.json")) as fh:
            meta = json.load(fh)
        with open(os.path.join(tmp_path, name, "trace.json")) as fh:
            trace_doc = json.load(fh)
        linked.append(bool(meta.get("trace_id")) and "traceEvents" in trace_doc)
    assert any(linked), incidents


def test_migrate_shard_unknown_target_leaves_topology_intact(federation):
    placement, groups = federation
    scope = scope_owned_by(placement, "h1", tag="abrt")
    _proposal, votes = make_session(placement, groups, scope)
    _host, shard = placement.owner(scope)
    with pytest.raises(KeyError):
        migrate_shard(placement, groups, shard, "nope")
    # Rolled back: not migrating, still owned and serving on h1.
    assert not placement.migrating(shard)
    assert placement.host_of(shard) == "h1"
    statuses = groups["h0"].ingest_votes([(scope, votes[0])], NOW + 2)
    assert statuses[0] == OK, statuses


def test_adapter_columnar_wire_multi_scope(federation):
    """A multi-scope OP_VOTE_BATCH frame through the host's zero-copy
    columnar ingest: rows split per owning shard (columnar.pack_rows)
    and every status lands in flattened frame order."""
    from hashgraph_tpu.bridge import protocol as P
    from hashgraph_tpu.bridge.client import BridgeClient, parse_status_list

    placement, groups = federation
    sessions = []
    for i in range(4):
        scope = scope_owned_by(placement, "h0", tag=f"col{i}-")
        _proposal, votes = make_session(placement, groups, scope)
        sessions.append((scope, votes))
    frame_groups = [
        (groups["h0"].peer_id, scope, [v.encode() for v in votes[:2]])
        for scope, votes in sessions
    ]
    payload = P.encode_vote_batch(NOW + 2, frame_groups)
    with BridgeClient(*groups["h0"].address) as client:
        statuses = parse_status_list(client._call(P.OP_VOTE_BATCH, payload))
    assert statuses == [OK] * 8, statuses
    for scope, _votes in sessions:
        assert (
            groups["h0"].adapter.get_consensus_result(
                scope, _votes[0].proposal_id
            )
            is True
        )


def test_host_fingerprint_covers_all_shards(federation):
    """The adapter's state_fingerprint digests the union of the shards'
    canonical frames: adding a session on EITHER shard changes it."""
    placement, groups = federation
    before = groups["h0"].state_fingerprint()
    scope = scope_owned_by(placement, "h0", tag="fpr")
    make_session(placement, groups, scope)
    assert groups["h0"].state_fingerprint() != before
