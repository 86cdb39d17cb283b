#!/usr/bin/env python3
"""Bring the main path up on one TPU chip, through the library's own entry
points, at deployment size; or, with ``--chips 4``, the sharded pool on four.

One chip, three phases, one process:

1. engine: ``TpuConsensusEngine(capacity=16_384, voter_capacity=1_024)``
   (README's per-device geometry; BASELINE's 1,024-voter P2P north star),
   every slot allocated across a P2P and a Gossipsub scope, ~2.2M votes
   through ``ingest_columnar`` (2,048 sessions through their full 683-vote
   quorum), ``sweep_timeouts``; exact per-vote statuses and state
   histograms, a seeded sample of 256 sessions replayed through the plain
   reference (``ConsensusService`` over ``InMemoryConsensusStorage``), and
   the same trace once more through the compiled Pallas ingest.
2. signed: Ed25519 votes verified on the device (some tampered), verdicts
   and statuses equal to the host verifier's.
3. served: one ``BridgeServer`` with the apply reactor, driven over TCP by
   a client (proposals, one ``OP_VOTE_BATCH``, decisions read back); its
   state fingerprint equals an engine fed the same votes directly.

``--chips 4`` runs only the sharded path: ``ShardedPool`` over a 4-chip
mesh on the dry-run's proposal classes, against a single-device pool.

Each phase prints one line; any failure raises. The last stdout line is
``{"ok": true, "device": {...}}``. Without a TPU it exits nonzero and
prints no verdict.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

NOW = 1_700_000_000
CAPACITY = 16_384  # slots per device (README "Per-device geometry")
VOTERS = 1_024  # voter lanes: BASELINE's 1,024-voter P2P deployment
REFERENCE_SESSIONS = 256
CHUNK = 65_536  # votes per ingest_columnar call

_compile_seconds = [0.0]


def _count_compiles(name: str, seconds: float, **_kw) -> None:
    if name.startswith("/jax/core/compile/"):
        _compile_seconds[0] += seconds


def _report(phase: str, t0: float, c0: float, **fields) -> None:
    import jax

    dev = jax.devices()
    fields.update(
        platform=dev[0].platform,
        device_kind=dev[0].device_kind,
        device_count=len(dev),
        compile_s=round(_compile_seconds[0] - c0, 3),
        wall_s=round(time.perf_counter() - t0, 3),
    )
    print(f"phase {phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ── phase 1: the engine at deployment size ─────────────────────────────


def _classes(capacity: int, voters: int) -> "list[dict]":
    """Session classes filling every slot. Each has a closed-form expected
    per-vote status row and final state, so the run checks them exactly."""
    from hashgraph_tpu import StatusCode as S
    from hashgraph_tpu.ops import (
        STATE_ACTIVE as A,
        STATE_FAILED as F,
        STATE_REACHED_NO as N,
        STATE_REACHED_YES as Y,
    )

    q = (2 * voters + 2) // 3  # ceil(2n/3): quorum and P2P round cap
    late = max(1, voters // 64)
    short = voters * 25 // 64
    n_p2p = capacity // 16
    split, short_yes, short_tie = capacity // 128, capacity // 32, capacity // 64
    n_g = (capacity - 2 * n_p2p - split - short_yes - short_tie) // 4
    rest = capacity - 2 * n_p2p - split - short_yes - short_tie - 3 * n_g

    def row(*runs):
        return np.concatenate([np.full(k, int(c), np.int32) for c, k in runs])

    ok, ar, dup = S.OK, S.ALREADY_REACHED, S.DUPLICATE_VOTE
    # (name, scope, sessions, n, votes, values, liveness, dups, statuses,
    #  state after ingest, state after sweep)
    table = [
        ("yes", "p2p", n_p2p, voters, q + late, "yes", True, 0,
         row((ok, q), (ar, late)), Y, Y),
        ("no", "p2p", n_p2p, voters, q + late, "no", True, 0,
         row((ok, q), (ar, late)), N, N),
        # Alternating votes never decide; vote q+1 overruns the P2P round
        # cap (session FAILED mid-batch), later ones find it inactive.
        ("split", "p2p", split, voters, q + 7, "alternate", False, 0,
         row((ok, q), (S.MAX_ROUNDS_EXCEEDED, 1), (S.SESSION_NOT_ACTIVE, 6)),
         F, F),
        # Short of quorum with absent voters, plus re-votes: the sweep
        # counts the silent as YES.
        ("short_yes", "p2p", short_yes, voters, short, "mostly_yes", True, 8,
         row((ok, short), (dup, 8)), A, Y),
        # n/2 NO and liveness YES: a tie at timeout, so FAILED.
        ("short_tie", "p2p", short_tie, voters, voters // 2, "no", True, 8,
         row((ok, voters // 2), (dup, 8)), A, F),
        ("g_yes", "gossip", n_g, 16, 12, "yes", True, 0,
         row((ok, 11), (ar, 1)), Y, Y),
        ("g_no", "gossip", n_g, 64, 50, "no", True, 0,
         row((ok, 43), (ar, 7)), N, N),
        ("g_open", "gossip", n_g, 64, 30, "yes", True, 0,
         row((ok, 30)), A, Y),
        # n=2 with one vote: unanimity is undecidable at timeout.
        ("g_pair", "gossip", rest, 2, 1, "yes", True, 0, row((ok, 1)), A, F),
    ]
    keys = ("name", "scope", "sessions", "n", "votes", "values", "liveness",
            "dups", "statuses", "state", "final")
    return [dict(zip(keys, t)) for t in table]


def _build_trace(rng, classes) -> None:
    """Per class: voters [S, m+dups] (a random subset of the n voters in
    random order, absent ones never vote; re-votes repeat earlier voters)
    and values [S, m+dups]."""
    for c in classes:
        s, n, m, d = c["sessions"], c["n"], c["votes"], c["dups"]
        order = rng.permuted(np.tile(np.arange(n), (s, 1)), axis=1)[:, :m]
        again = order[np.arange(s)[:, None], rng.integers(0, m, (s, d))]
        c["voters"] = np.concatenate([order, again], axis=1)
        k = np.arange(m + d)
        values = {
            "yes": np.ones((s, m + d), bool),
            "no": np.zeros((s, m + d), bool),
            "alternate": np.broadcast_to(k % 2 == 0, (s, m + d)),
            # 3/4 YES among the first m votes, in random order per session.
            "mostly_yes": np.concatenate([
                rng.permuted(
                    np.broadcast_to(k[:m] < (3 * m) // 4, (s, m)), axis=1
                ),
                np.zeros((s, d), bool),
            ], axis=1),
        }[c["values"]]
        c["vals"] = np.ascontiguousarray(values)


def _run_engine(engine, classes, voters: int, rng_seed: int) -> np.ndarray:
    """Create every class's sessions, then ingest the whole trace in
    arrival order (vote k of every session before vote k+1, jittered),
    chunked per scope. Fills each class's status matrix and returns the
    statuses of the whole trace."""
    from hashgraph_tpu import CreateProposalRequest

    gid = np.array(
        [engine.voter_gid(_owner(v)) for v in range(voters)], np.int64
    )
    for c in classes:
        proposals = engine.create_proposals(
            c["scope"],
            [
                CreateProposalRequest(
                    name=f"{c['name']}{i}", payload=b"", proposal_owner=b"o",
                    expected_voters_count=c["n"], expiration_timestamp=60,
                    liveness_criteria_yes=c["liveness"],
                )
                for i in range(c["sessions"])
            ],
            NOW,
        )
        c["pids"] = np.array([p.proposal_id for p in proposals], np.int64)
    rng = np.random.default_rng(rng_seed)  # same arrival order per engine
    for c in classes:
        c["status"] = np.empty(c["voters"].shape, np.int32)
    calls = []
    for scope in ("p2p", "gossip"):
        members = [c for c in classes if c["scope"] == scope]
        pids = np.concatenate(
            [np.repeat(c["pids"], c["voters"].shape[1]) for c in members]
        )
        gids = np.concatenate([gid[c["voters"]].ravel() for c in members])
        vals = np.concatenate([c["vals"].ravel() for c in members])
        k = np.concatenate(
            [np.tile(np.arange(c["voters"].shape[1]), c["sessions"])
             for c in members]
        )
        arrival = np.argsort(k + rng.random(len(k)), kind="stable")
        calls.append((scope, members, pids, gids, vals, arrival))
    out = {}
    for start in range(0, max(len(a[-1]) for a in calls), CHUNK):
        for scope, _m, pids, gids, vals, arrival in calls:
            idx = arrival[start:start + CHUNK]
            if len(idx):
                out.setdefault(scope, []).append(
                    (idx, engine.ingest_columnar(
                        scope, pids[idx], gids[idx], vals[idx], NOW
                    ))
                )
    flat_all = []
    for scope, members, *_ in calls:
        flat = np.empty(sum(c["status"].size for c in members), np.int32)
        for idx, st in out[scope]:
            flat[idx] = st
        at = 0
        for c in members:
            c["status"][:] = flat[at:at + c["status"].size].reshape(
                c["status"].shape
            )
            at += c["status"].size
        flat_all.append(flat)
    return np.concatenate(flat_all)


def _owner(v: int) -> bytes:
    return b"voter-" + v.to_bytes(4, "big") + b"\x00" * 10


def _engine(pool=None, capacity=CAPACITY, voters=VOTERS, signer=None):
    from hashgraph_tpu import ScopeConfigBuilder, StubConsensusSigner
    from hashgraph_tpu.engine import TpuConsensusEngine

    kw = {"pool": pool} if pool is not None else {
        "capacity": capacity, "voter_capacity": voters,
    }
    engine = TpuConsensusEngine(
        signer or StubConsensusSigner(b"chip-smoke"),
        max_sessions_per_scope=capacity + 16, **kw,
    )
    engine.set_scope_config("p2p", ScopeConfigBuilder().p2p_preset().build())
    engine.set_scope_config(
        "gossip", ScopeConfigBuilder().gossipsub_preset().build()
    )
    return engine


def _state_hist(classes, key: str) -> "dict[int, int]":
    hist: "dict[int, int]" = {}
    for c in classes:
        hist[int(c[key])] = hist.get(int(c[key]), 0) + c["sessions"]
    return hist


def _outcome_engine(engine, scope, pid) -> str:
    from hashgraph_tpu.errors import ConsensusFailed

    try:
        result = engine.get_consensus_result(scope, pid)
    except ConsensusFailed:
        return "failed"
    return {True: "yes", False: "no", None: "active"}[result]


def _reference_replay(engine, classes, sample_rng, n_sessions: int):
    """Replay a seeded, class-stratified sample of sessions through the
    plain reference and compare per-vote statuses; return the sample and
    the reference service (for the post-sweep comparison)."""
    from hashgraph_tpu import (
        BroadcastEventBus,
        ConsensusService,
        InMemoryConsensusStorage,
        StatusCode as S,
        StubConsensusSigner,
        build_vote,
    )
    from hashgraph_tpu.errors import ConsensusError

    service = ConsensusService(
        InMemoryConsensusStorage(), BroadcastEventBus(),
        StubConsensusSigner(b"reference"), n_sessions + 16,
    )
    service.scope("p2p").p2p_preset().initialize()
    service.scope("gossip").gossipsub_preset().initialize()
    per_class = -(-n_sessions // len(classes))
    sample = []
    for c in classes:
        rows = sample_rng.choice(
            c["sessions"], min(per_class, c["sessions"]), replace=False
        )
        sample.extend((c, int(r)) for r in rows)
    signers = {}
    for c, r in sample:
        scope, pid = c["scope"], int(c["pids"][r])
        proposal = engine.get_proposal(scope, pid)
        service.process_incoming_proposal(scope, proposal.clone(), NOW)
        shadow = proposal.clone()
        got = []
        for v, val in zip(c["voters"][r], c["vals"][r]):
            signer = signers.setdefault(
                int(v), StubConsensusSigner(_owner(int(v)))
            )
            vote = build_vote(shadow, bool(val), signer, NOW)
            shadow.votes.append(vote)
            reached = service.storage().get_session(scope, pid).state.is_reached
            try:
                service.process_incoming_vote(scope, vote, NOW)
                got.append(int(S.ALREADY_REACHED if reached else S.OK))
            except ConsensusError as exc:
                got.append(int(exc.code))
        want = c["status"][r].tolist()
        assert got == want, (c["name"], r, got, want)
    return sample, service


def _outcome_reference(service, scope, pid, now) -> str:
    from hashgraph_tpu.errors import InsufficientVotesAtTimeout

    state = service.storage().get_session(scope, pid).state
    if state.is_active:
        try:
            service.handle_consensus_timeout(scope, pid, now)
        except InsufficientVotesAtTimeout:
            return "failed"
        state = service.storage().get_session(scope, pid).state
    if state.is_reached:
        return "yes" if state.result else "no"
    return "failed"


def phase_engine(seed: int, capacity=CAPACITY, voters=VOTERS,
                 reference_sessions=REFERENCE_SESSIONS) -> None:
    import jax

    from hashgraph_tpu.engine import ProposalPool
    from hashgraph_tpu.ops import STATE_ACTIVE

    t0, c0 = time.perf_counter(), _compile_seconds[0]
    rng = np.random.default_rng(seed)
    classes = _classes(capacity, voters)
    _build_trace(rng, classes)
    engine = _engine(capacity=capacity, voters=voters)
    t_in = time.perf_counter()
    statuses = _run_engine(engine, classes, voters, seed + 1)
    ingest_s = time.perf_counter() - t_in
    pool = engine.pool()
    assert pool.free_slots == 0
    for c in classes:  # exact per-vote statuses, every session
        bad = (c["status"] != c["statuses"][None, :]).any(axis=1)
        assert not bad.any(), (c["name"], c["status"][bad][:2], c["statuses"])
    counts = pool.state_counts()
    assert counts == _state_hist(classes, "state"), counts

    sample, service = _reference_replay(
        engine, classes, np.random.default_rng(seed + 2), reference_sessions
    )
    swept = engine.sweep_timeouts(NOW + 120)
    assert len(swept) == sum(
        c["sessions"] for c in classes if c["state"] == STATE_ACTIVE
    ), len(swept)
    final = pool.state_counts()
    assert final == _state_hist(classes, "final"), final
    for c, r in sample:
        scope, pid = c["scope"], int(c["pids"][r])
        mine = _outcome_engine(engine, scope, pid)
        ref = _outcome_reference(service, scope, pid, NOW + 120)
        assert mine == ref, (c["name"], r, mine, ref)

    # The same trace through the Pallas ingest: compiled on a TPU, the
    # Pallas interpreter elsewhere (ProposalPool picks by backend).
    pallas = _engine(
        pool=ProposalPool(capacity, voters, use_pallas=True),
        capacity=capacity, voters=voters,
    )
    t_pl = time.perf_counter()
    pallas_statuses = _run_engine(pallas, classes, voters, seed + 1)
    pallas_s = time.perf_counter() - t_pl
    assert np.array_equal(pallas_statuses, statuses)
    pallas.sweep_timeouts(NOW + 120)
    assert pallas.pool().state_counts() == final

    full = sum(c["sessions"] for c in classes if c["name"] in ("yes", "no"))
    _report(
        "engine", t0, c0,
        slots=capacity, lanes=voters, votes=len(statuses),
        full_quorum_sessions=full, p2p_quorum=(2 * voters + 2) // 3,
        ingest_s=round(ingest_s, 3), swept=len(swept),
        states=json.dumps(final, separators=(",", ":")),
        reference_sessions=len(sample), reference="identical",
        pallas="identical", pallas_ingest_s=round(pallas_s, 3),
        pallas_mode=(
            "compiled" if jax.default_backend() == "tpu" else "interpreted"
        ),
    )


# ── phase 2: signed votes, device verification ─────────────────────────


def phase_signed(seed: int, capacity=CAPACITY, voters=VOTERS,
                 proposals=4, batch=512, tamper_every=2) -> None:
    from hashgraph_tpu import (
        CreateProposalRequest,
        StatusCode,
        build_vote,
        native,
    )
    from hashgraph_tpu.obs import (
        DEVICE_VERIFY_BATCHES_TOTAL,
        DEVICE_VERIFY_FALLBACKS_TOTAL,
        DEVICE_VERIFY_SIGNATURES_TOTAL,
        registry,
    )
    from hashgraph_tpu.signing.ed25519 import (
        Ed25519ConsensusSigner,
        Ed25519DeviceConsensusSigner,
    )

    t0, c0 = time.perf_counter(), _compile_seconds[0]
    rng = np.random.default_rng(seed)
    seed_bytes = rng.bytes(32)
    device = Ed25519ConsensusSigner(seed_bytes, device_verify=True)
    assert isinstance(device, Ed25519DeviceConsensusSigner), type(device)
    host = Ed25519ConsensusSigner(seed_bytes, device_verify=False)
    assert type(host) is Ed25519ConsensusSigner
    dev_engine = _engine(capacity=capacity, voters=voters, signer=device)
    host_engine = _engine(capacity=capacity, voters=voters, signer=host)
    made = dev_engine.create_proposals(
        "p2p",
        [
            CreateProposalRequest(
                name=f"signed{i}", payload=b"", proposal_owner=b"o",
                expected_voters_count=voters, expiration_timestamp=60,
                liveness_criteria_yes=True,
            )
            for i in range(proposals)
        ],
        NOW,
    )
    for p in made:
        host_engine.process_incoming_proposal("p2p", p.clone(), NOW)
    signers = [Ed25519ConsensusSigner(rng.bytes(32)) for _ in range(voters)]
    votes = []  # round-robin over proposals: vote k of each, then k+1
    shadows = [p.clone() for p in made]
    for v in range(voters):
        for shadow in shadows:
            vote = build_vote(shadow, bool(rng.random() < 0.8), signers[v], NOW)
            shadow.votes.append(vote)
            votes.append(vote)
    batches = [votes[i:i + batch] for i in range(0, len(votes), batch)]
    tampered = 0
    for b, chunk in enumerate(batches):
        if b % tamper_every != 1:
            continue  # clean batches: the device MSM accepts them whole
        for i in rng.choice(len(chunk), max(1, len(chunk) // 32), replace=False):
            sig = bytearray(chunk[i].signature)
            sig[int(rng.integers(0, 2)) * 32] ^= 1  # R or S, low bit
            chunk[i] = chunk[i].clone()
            chunk[i].signature = bytes(sig)
            tampered += 1

    before = {
        name: registry.counter(name).value
        for name in (DEVICE_VERIFY_BATCHES_TOTAL,
                     DEVICE_VERIFY_SIGNATURES_TOTAL,
                     DEVICE_VERIFY_FALLBACKS_TOTAL)
    }
    rejected = 0
    for chunk in batches:
        idents = [v.vote_owner for v in chunk]
        payloads = [v.signing_payload() for v in chunk]
        sigs = [v.signature for v in chunk]
        got = Ed25519DeviceConsensusSigner.verify_batch(idents, payloads, sigs)
        want = Ed25519ConsensusSigner.verify_batch(idents, payloads, sigs)
        assert got == want, "device verdicts differ from the host's"
        items = [("p2p", v) for v in chunk]
        dev_status = dev_engine.ingest_votes(items, NOW)
        host_status = host_engine.ingest_votes(items, NOW)
        assert np.array_equal(dev_status, host_status), (dev_status, host_status)
        rejected += int(
            (np.asarray(dev_status) == StatusCode.INVALID_VOTE_SIGNATURE).sum()
        )
    assert rejected == tampered, (rejected, tampered)
    for p in made:
        assert dev_engine.get_consensus_result("p2p", p.proposal_id) == (
            host_engine.get_consensus_result("p2p", p.proposal_id)
        )
    counters = {
        name.removeprefix("hashgraph_device_verify_").removesuffix("_total"):
        int(registry.counter(name).value - before[name])
        for name in before
    }
    assert counters["fallbacks"] >= 1 and counters["batches"] >= len(batches)
    _report(
        "signed", t0, c0,
        votes=len(votes), batches=len(batches), batch=batch,
        tampered=tampered, rejected_invalid_signature=rejected,
        verdicts="identical", statuses="identical",
        device_verify=json.dumps(counters, separators=(",", ":")),
        native_runtime="loaded" if native.available() else "absent",
    )


# ── phase 3: the served path ───────────────────────────────────────────


def phase_served(seed: int, capacity=CAPACITY, voters=VOTERS,
                 proposals=8) -> None:
    from hashgraph_tpu import StubConsensusSigner, build_vote
    from hashgraph_tpu.bridge.client import BridgeClient, PipelinedBridgeClient
    from hashgraph_tpu.bridge.server import BridgeServer
    from hashgraph_tpu.engine import TpuConsensusEngine
    from hashgraph_tpu.sync.snapshot import state_fingerprint
    from hashgraph_tpu.wire import Proposal, Vote

    t0, c0 = time.perf_counter(), _compile_seconds[0]
    rng = np.random.default_rng(seed)
    per = (2 * voters + 2) // 3 + 17
    blobs, groups = [], []
    for i in range(proposals):
        proposal = Proposal(
            name=f"served-{i}", payload=b"x", proposal_id=1 + i,
            proposal_owner=b"\x11" * 20, expected_voters_count=voters,
            timestamp=NOW, expiration_timestamp=NOW + 3_600,
            liveness_criteria_yes=True,
        )
        blobs.append(proposal.encode())
        value = bool(i % 2 == 0)
        wire = []
        for v in rng.permutation(voters)[:per]:
            vote = build_vote(
                proposal, value, StubConsensusSigner(_owner(int(v))), NOW + 1
            )
            proposal.votes.append(vote)
            wire.append(vote.encode())
        groups.append(wire)
    key = rng.bytes(32)

    server = BridgeServer(
        capacity=capacity, voter_capacity=voters,
        signer_factory=StubConsensusSigner, wire_columnar=True,
        apply_reactor=True,
    )
    with server:
        host, port = server.address
        client = BridgeClient(host, port, timeout=120.0)
        fast = PipelinedBridgeClient(host, port, timeout=120.0)
        try:
            peer, _identity = client.add_peer(key)
            for blob in blobs:
                client.process_proposal(peer, "served", blob, NOW)
            served = fast.vote_batch_async(
                NOW + 1, [(peer, "served", g) for g in groups]
            ).result(120.0)
            decided = [
                client.get_result(peer, "served", 1 + i)
                for i in range(proposals)
            ]
            served_fp = client.state_fingerprint(peer)
        finally:
            fast.close()
            client.close()

    direct = TpuConsensusEngine(
        StubConsensusSigner(key), capacity=capacity, voter_capacity=voters
    )
    for blob in blobs:
        direct.process_incoming_proposal("served", Proposal.decode(blob), NOW)
    direct_status = direct.ingest_votes(
        [("served", Vote.decode(b)) for g in groups for b in g], NOW + 1
    )
    assert list(served) == [int(s) for s in direct_status]
    assert decided == [i % 2 == 0 for i in range(proposals)], decided
    assert decided == [
        direct.get_consensus_result("served", 1 + i) for i in range(proposals)
    ]
    assert served_fp == state_fingerprint(direct), "fingerprints differ"
    _report(
        "served", t0, c0,
        slots=capacity, lanes=voters, proposals=proposals,
        votes=sum(len(g) for g in groups), frame="OP_VOTE_BATCH",
        apply_reactor="on", decided=sum(d is not None for d in decided),
        statuses="identical", fingerprint="identical",
    )


# ── --chips 4: the sharded pool ────────────────────────────────────────


def phase_sharded(n_devices: int = 4, capacity_per_device=CAPACITY,
                  voters=VOTERS) -> None:
    from __graft_entry__ import mixed_class_scenario
    from hashgraph_tpu.engine import ProposalPool
    from hashgraph_tpu.parallel import ShardedPool, consensus_mesh

    t0, c0 = time.perf_counter(), _compile_seconds[0]
    sharded = ShardedPool(
        capacity_per_device=capacity_per_device, voter_capacity=voters,
        mesh=consensus_mesh(n_devices),
    )
    spans = {d for a in (sharded._state, sharded._vote_mask)
             for d in a.sharding.device_set}
    assert len(spans) == n_devices, spans
    got = mixed_class_scenario(sharded)
    single = ProposalPool(sharded.capacity, voters)
    want = mixed_class_scenario(single)
    assert np.array_equal(got["statuses"], want["statuses"])
    assert got["counts_ingest"] == want["counts_ingest"]
    assert got["counts"] == want["counts"]
    assert got["swept_results"] == want["swept_results"]
    _report(
        "sharded", t0, c0,
        mesh_devices=len(spans), slots=sharded.capacity,
        slots_per_device=capacity_per_device, lanes=voters,
        votes=len(got["statuses"]),
        occupancy=json.dumps(sharded.per_device_occupancy()),
        states=json.dumps(got["counts"], separators=(",", ":")),
        single_device="identical",
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=21)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    if not __debug__:
        parser.error("the checks are asserts: run without -O")

    from hashgraph_tpu.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    jax.monitoring.register_event_duration_secs_listener(_count_compiles)
    print(f"chip_smoke: seed={args.seed} compile_cache={cache}", flush=True)
    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_engine(args.seed)
        phase_signed(args.seed + 10)
        phase_served(args.seed + 20)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
