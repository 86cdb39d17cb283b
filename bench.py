"""Benchmarks for the BASELINE.md configs.

Default (bare ``python bench.py``) runs the full sweep and prints ONE JSON
line whose headline metric is **engine-level** vote-ingest throughput at
config-3 scale (10k concurrent proposals × 64 voters, single TPU core) —
the full TpuConsensusEngine service surface: proposal-id resolution, voter
lane resolution, per-vote status codes, round bookkeeping, event emission —
driven through the columnar batch API. ``detail`` carries every other
BASELINE shape:

  pool_level   raw ProposalPool throughput, same shape (no service layer)
  config2      1 proposal × 1024 voters, P2P: p50 finality latency
  config4      256 scopes × 1k proposals × 256 voters, 30% absent,
               liveness-timeout path (sharded when >1 device)
  config5      streaming mixed Gossipsub+P2P replay to 1M proposals
  lanes1024    12k proposals × 1024 voter lanes (per-chip slice of the
               100k-proposal north star)

Individual runs via argv: engine | pool (alias config3) | config2 |
config4 | config5 | lanes1024 | crypto | validated | redelivery | wal |
fleet | slo-overhead | default | all (``all`` prints newline-separated
JSON, one line
per section). ``wal`` measures the durability subsystem: append
throughput per fsync policy, DurableEngine ingest overhead vs a bare
engine, and recovery replay rate (host-only — not part of the BASELINE
sweep). ``redelivery`` measures amortized vote verification
(VerifiedVoteCache + validated-chain watermark) under gossip redelivery
and incremental chain growth, cache-on vs cache-off, with real ECDSA
signatures. ``fleet`` measures the scope-sharded fleet
(hashgraph_tpu.parallel.ConsensusFleet): an aggregate votes/sec headline
across all local devices with a per-shard breakdown, a paired fleet-vs-
single-shard A/B ``noise_verdict``, and a MULTICHIP-compatible record;
``fleet --smoke`` is the 2-shard CI short run. Decision-driving benches
(``fleet``, ``gossip``, ``churn``, ``fleet --hosts N``) add an ``slo``
block to their JSON — windowed p50/p95/p99 decide latency plus a
burn-rate verdict from :mod:`hashgraph_tpu.obs.slo`; ``slo-overhead``
is the paired A/B asserting always-on SLO tracking costs under 5%
throughput. The federated fleet bench additionally scrapes the merged
``/metrics`` + ``/slo`` views and induces one SLO breach to assert the
alert fires and an exemplar-linked Perfetto incident dump lands on the
owning host.

JAX's persistent compilation cache is ON BY DEFAULT, placed by
:mod:`hashgraph_tpu.compile_cache` (``JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``); ``--no-compile-cache`` disables it (e.g.
when compile time itself is measured).

``--metrics-out PATH`` additionally snapshots the always-on observability
registry (:mod:`hashgraph_tpu.obs` — counter totals, gauges, and histogram
quantiles such as ``wal_fsync_seconds`` p50/p90/p99) into the emitted JSON
and writes the full result object to PATH. ``--metrics-port N`` serves the
HTTP ``/metrics`` + ``/healthz`` sidecar for the run's duration so the
histograms can be scraped live while the bench executes. ``--trace-out
PATH`` runs the bench under one distributed trace context and exports the
context-tagged spans (device ingest, verify batches, WAL fsyncs, per-
proposal lifecycles) as a Chrome trace-event file for Perfetto.
``--health-out PATH`` writes the consensus-health snapshot (peer
scorecards with grades, equivocation/fork evidence, watchdog, firing
alert rules — :mod:`hashgraph_tpu.obs.health`) to PATH and folds the
alert counts into the emitted JSON under ``health``.

Traces are pre-validated replays (signature/hash verification is the
pluggable host stage — measured separately by ``python bench.py crypto``
and the validated end-to-end mode; the reference's own tests hand-deliver
already-validated votes the same way).
"""

from __future__ import annotations

import json
import time

import numpy as np


def spread_pct(vals: "list[float]") -> float:
    """Max-min spread of a rep list as % of the median — the shared
    denominator of every bench's noise_verdict separation bar."""
    vals = sorted(vals)
    mid = vals[len(vals) // 2]
    return round(100.0 * (vals[-1] - vals[0]) / mid, 1) if mid else 0.0


def _slo_block(objective_ms: "float | None" = None) -> dict:
    """Windowed decision-latency quantiles + an SLO verdict from the
    process-global SloEngine — the ``slo`` block the decision-driving
    benches (fleet / gossip / churn) append to their BENCH_*.json.

    ``objective_ms`` is the bench's declared decide-latency objective:
    the verdict passes when the fast-window global p99 meets it AND no
    burn-rate alert is firing at readout."""
    from hashgraph_tpu.obs import slo_engine

    state = slo_engine.state()
    window = state["global"]
    block = {
        "windowed_latency_ms": {
            "count": window["count"],
            "p50": round(window["p50"] * 1e3, 3),
            "p95": round(window["p95"] * 1e3, 3),
            "p99": round(window["p99"] * 1e3, 3),
        },
        "per_shard_p99_ms": {
            sid: round(s["p99"] * 1e3, 3)
            for sid, s in state["shards"].items()
        },
        "alerts_firing": state["alerts_firing"],
    }
    if objective_ms is not None:
        block["verdict"] = {
            "objective_ms": objective_ms,
            "p99_ms": block["windowed_latency_ms"]["p99"],
            "pass": bool(
                not state["alerts_firing"]
                and window["p99"] * 1e3 <= objective_ms
            ),
        }
    return block


def run_bench(
    p_count: int = 10_240,
    v_count: int = 64,
    votes_per_dispatch: int = 8,
    cycles: int = 5,
) -> dict:
    import jax

    from hashgraph_tpu.engine.pool import ProposalPool
    from hashgraph_tpu.ops.decide import required_votes_np

    rng = np.random.default_rng(7)
    now = 1_700_000_000

    pool = ProposalPool(p_count, v_count)

    def allocate(cycle: int) -> None:
        # Gossipsub mode, threshold 1.0: every vote is accepted (round cap 2
        # admits any count) and no session decides before its last voter, so
        # every dispatch carries only real, accepted votes.
        pool.allocate_batch(
            keys=[(f"bench{cycle}", i) for i in range(p_count)],
            n=np.full(p_count, v_count),
            req=required_votes_np(np.full(p_count, v_count), 1.0),
            cap=np.full(p_count, 2),
            gossip=np.ones(p_count, bool),
            liveness=np.ones(p_count, bool),
            expiry=np.full(p_count, now + 10_000),
            created_at=np.full(p_count, now),
        )

    L = votes_per_dispatch
    dispatches_per_cycle = v_count // L
    slots = np.repeat(np.arange(p_count, dtype=np.int64), L)

    def dispatch(d: int):
        # L votes per proposal per dispatch: lanes d*L..(d+1)*L-1.
        lanes = np.tile(np.arange(d * L, (d + 1) * L, dtype=np.int32), p_count)
        values = rng.random(p_count * L) < 0.5
        return pool.ingest_async(slots, lanes, values, now)

    def run_cycle(check: bool) -> None:
        pendings = [dispatch(d) for d in range(dispatches_per_cycle)]
        results = pool.complete_all(pendings)
        if check:
            for d, (statuses, _) in enumerate(results):
                assert int(statuses[0]) == 0, f"dispatch {d}: {statuses[0]}"

    # Warmup: compile every kernel the timed loop uses (allocate, ingest,
    # release) so the measured window is pure steady-state throughput.
    all_slots = list(range(p_count))
    allocate(0)
    run_cycle(check=True)
    pool.release(all_slots)
    allocate(0)
    run_cycle(check=True)

    jax.block_until_ready(pool._state)
    # Per-cycle timing with a median report: host timings jitter between
    # identical runs, and one slow cycle shouldn't define the engine's
    # throughput number.
    cycle_votes = p_count * v_count
    rates = []
    for cycle in range(1, cycles + 1):
        start = time.perf_counter()
        pool.release(all_slots)
        allocate(cycle)
        run_cycle(check=False)
        rates.append(cycle_votes / (time.perf_counter() - start))
    rates.sort()
    throughput = rates[len(rates) // 2]
    return {
        "metric": "vote_ingest_throughput",
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": round(throughput / 1_000_000, 4),
        "detail": {
            "proposals": p_count,
            "voters": v_count,
            "votes_per_cycle": cycle_votes,
            "cycles": cycles,
            "cycle_rates": [round(r, 1) for r in rates],
            "platform": jax.devices()[0].platform,
        },
    }


def run_engine_bench(
    p_count: int = 10_240, v_count: int = 64, cycles: int = 6
) -> dict:
    """Engine-level config 3: the full TpuConsensusEngine service surface —
    batch proposal creation, vectorized proposal-id + voter-lane resolution,
    per-vote status codes, round bookkeeping, event emission — via the
    columnar API. This is the honest north-star number (the service the
    embedder actually calls); ``run_bench`` measures the raw pool under it.
    """
    import jax

    from hashgraph_tpu import CreateProposalRequest, StubConsensusSigner
    from hashgraph_tpu.engine import TpuConsensusEngine

    now = 1_700_000_000
    engine = TpuConsensusEngine(
        StubConsensusSigner(b"\x01" * 20),
        capacity=p_count,
        voter_capacity=v_count,
        max_sessions_per_scope=p_count + 1,
    )
    engine.scope("s").with_threshold(1.0).initialize()
    requests = [
        CreateProposalRequest(
            name="p",
            payload=b"",
            proposal_owner=b"o",
            expected_voters_count=v_count,
            expiration_timestamp=10_000,
            liveness_criteria_yes=True,
        )
        for _ in range(p_count)
    ]
    owners = [
        bytes([1 + (i % 250), i // 250]) + b"\x00" * 18 for i in range(v_count)
    ]
    col_vals = (np.arange(p_count * v_count) % 2).astype(bool)

    ingest_rates, create_rates = [], []
    for cycle in range(cycles + 1):  # first is compile warmup
        engine.delete_scope("s")
        engine.scope("s").with_threshold(1.0).initialize()
        # Re-intern per cycle: delete_scope evicted the previous cycle's
        # gids (refcounted registry), so reusing them would measure the
        # EMPTY_VOTE_OWNER rejection fast path, not vote ingest — the
        # all-OK assert below guards every timed cycle against exactly
        # that regression.
        gids = np.array([engine.voter_gid(o) for o in owners], np.int64)
        col_gids = np.repeat(gids, p_count)
        t0 = time.perf_counter()
        proposals = engine.create_proposals("s", requests, now)
        t1 = time.perf_counter()
        pids = np.fromiter(
            (p.proposal_id for p in proposals), np.int64, p_count
        )
        col_pids = np.tile(pids, v_count)
        t2 = time.perf_counter()
        statuses = engine.ingest_columnar("s", col_pids, col_gids, col_vals, now)
        t3 = time.perf_counter()
        assert int(np.sum(statuses == 0)) == p_count * v_count, "not all OK"
        if cycle > 0:
            create_rates.append(p_count / (t1 - t0))
            ingest_rates.append(p_count * v_count / (t3 - t2))
    ingest_rates.sort()
    create_rates.sort()
    throughput = ingest_rates[len(ingest_rates) // 2]
    return {
        "metric": "engine_vote_ingest_throughput",
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": round(throughput / 1_000_000, 4),
        "detail": {
            "proposals": p_count,
            "voters": v_count,
            "cycles": cycles,
            "ingest_rates": [round(r, 1) for r in ingest_rates],
            "proposal_creation_rate": round(
                create_rates[len(create_rates) // 2], 1
            ),
            "platform": jax.devices()[0].platform,
        },
    }


def run_engine_lanes1024(
    p_count: int = 12_288, v_count: int = 1024, cycles: int = 3
) -> dict:
    """Engine-level north-star shape: 12k concurrent proposals × 1024 voter
    lanes under P2P round caps, driven through the FULL service surface
    (batch creation, pid resolution, lane resolution, round bookkeeping,
    statuses, events) via the columnar API — the per-chip slice of "100k
    concurrent 1024-voter proposals" measured at the layer embedders call,
    not the raw pool."""
    import jax

    from hashgraph_tpu import CreateProposalRequest, StubConsensusSigner
    from hashgraph_tpu import ScopeConfigBuilder
    from hashgraph_tpu.engine import TpuConsensusEngine

    rng = np.random.default_rng(13)
    now = 1_700_000_000
    engine = TpuConsensusEngine(
        StubConsensusSigner(b"\x01" * 20),
        capacity=p_count,
        voter_capacity=v_count,
        max_sessions_per_scope=p_count + 1,
    )
    fill = 672  # ceil(2n/3)=683 P2P vote cap; stay under mid-stream decisions
    requests = [
        CreateProposalRequest(
            name="p",
            payload=b"",
            proposal_owner=b"o",
            expected_voters_count=v_count,
            expiration_timestamp=10_000,
            liveness_criteria_yes=True,
        )
        for _ in range(p_count)
    ]
    owners = [
        bytes([1 + (i % 250), i // 250]) + b"\x00" * 18 for i in range(fill)
    ]
    col_vals = rng.random(p_count * fill) < 0.5

    ingest_rates, create_rates = [], []
    for cycle in range(cycles + 1):  # first is compile warmup
        engine.delete_scope("s")
        engine.set_scope_config("s", ScopeConfigBuilder().p2p_preset().build())
        # Re-intern per cycle (delete_scope evicted the previous cycle's
        # gids); the every-cycle all-OK assert guards against timing the
        # rejection path as throughput.
        cycle_gids = np.array([engine.voter_gid(o) for o in owners], np.int64)
        # One fresh (slot, gid) stream per cycle: proposal-major, arrival
        # order = lane order; every pair is first-occurrence so lane
        # resolution stays on the vectorized fresh-assignment path.
        col_gids = np.tile(cycle_gids, p_count)
        t0 = time.perf_counter()
        proposals = engine.create_proposals("s", requests, now)
        t1 = time.perf_counter()
        pids = np.fromiter((p.proposal_id for p in proposals), np.int64, p_count)
        col_pids = np.repeat(pids, fill)
        t2 = time.perf_counter()
        statuses = engine.ingest_columnar("s", col_pids, col_gids, col_vals, now)
        t3 = time.perf_counter()
        ok = int(np.sum(statuses == 0))
        assert ok == p_count * fill, (ok, p_count * fill)
        if cycle > 0:
            create_rates.append(p_count / (t1 - t0))
            ingest_rates.append(p_count * fill / (t3 - t2))
    ingest_rates.sort()
    create_rates.sort()
    throughput = ingest_rates[len(ingest_rates) // 2]
    return {
        "metric": "engine_lanes1024_ingest_throughput",
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": round(throughput / 1_000_000, 4),
        "detail": {
            "proposals": p_count,
            "voter_lanes": v_count,
            "network_type": "p2p",
            "votes_per_cycle": p_count * fill,
            "ingest_rates": [round(r, 1) for r in ingest_rates],
            "proposal_creation_rate": round(
                create_rates[len(create_rates) // 2], 1
            ),
            "platform": jax.devices()[0].platform,
        },
    }


def run_engine_config5(
    scopes: int = 256,
    proposals_per_scope: int = 128,
    v_count: int = 48,
    waves: int = 8,
    retain: bool = False,
) -> dict:
    """Engine-level config 5: mixed-scope streaming churn. Every wave
    registers 256 scopes' worth of fresh proposals (half gossipsub, half
    P2P scope configs), streams a shuffled mixed-scope vote batch through
    ingest_columnar_multi (one fused device pipeline, per-scope work =
    one table probe each), then deletes every scope — live-deployment
    session churn through the real service surface."""
    import jax

    from hashgraph_tpu import CreateProposalRequest, StubConsensusSigner
    from hashgraph_tpu import ScopeConfigBuilder
    from hashgraph_tpu.engine import TpuConsensusEngine

    rng = np.random.default_rng(29)
    now = 1_700_000_000
    p_count = scopes * proposals_per_scope
    engine = TpuConsensusEngine(
        StubConsensusSigner(b"\x01" * 20),
        capacity=p_count,
        voter_capacity=v_count,
        max_sessions_per_scope=proposals_per_scope + 1,
    )
    scope_names = [f"s{i}" for i in range(scopes)]

    def set_configs() -> None:
        # delete_scope drops the scope config with the sessions, so churn
        # waves must re-establish the mixed gossip/P2P split every wave.
        for i, scope in enumerate(scope_names):
            builder = ScopeConfigBuilder()
            builder = (
                builder.p2p_preset() if i % 2 else builder.gossipsub_preset()
            )
            engine.set_scope_config(scope, builder.build())

    owners = [bytes([1 + i]) * 20 for i in range(v_count)]
    requests = [
        CreateProposalRequest(
            name="p",
            payload=b"",
            proposal_owner=b"o",
            expected_voters_count=v_count,
            expiration_timestamp=10_000,
            liveness_criteria_yes=bool(rng.integers(2)),
        )
        for _ in range(proposals_per_scope)
    ]

    def run_wave(wave: int) -> tuple[int, int]:
        """Returns (votes_applied, proposals_registered)."""
        set_configs()
        # Re-intern per wave: the end-of-wave delete_scope sweep evicts
        # every gid (refcounted registry), so carrying gids across waves
        # would measure the EMPTY_VOTE_OWNER rejection path, not churn
        # (the every-wave applied-fraction assert below enforces this).
        gids = np.array([engine.voter_gid(o) for o in owners], np.int64)
        # One cross-scope allocate dispatch for the whole wave's population.
        batches = engine.create_proposals_multi(
            [(scope, requests) for scope in scope_names], now
        )
        all_pids = []
        scope_of = []
        for k, proposals in enumerate(batches):
            all_pids.extend(p.proposal_id for p in proposals)
            scope_of.extend([k] * len(proposals))
        pids = np.array(all_pids, np.int64)
        sidx = np.array(scope_of, np.int64)
        # 70% participation, proposal-major arrival order, scope-shuffled
        # at proposal granularity (within-proposal order must hold).
        present = int(v_count * 0.7)
        order = rng.permutation(p_count)
        col_pids = np.repeat(pids[order], present)
        col_sidx = np.repeat(sidx[order], present)
        col_gids = np.tile(gids[:present], p_count)
        col_vals = rng.random(p_count * present) < 0.55
        wire = None
        if retain:
            # Synthetic fixed-width vote bytes: retention stores verbatim
            # bytes without decoding (decode happens on export), so dummy
            # payloads price the retention machinery itself.
            width = 72
            wire = (
                np.zeros(p_count * present * width, np.uint8),
                np.arange(p_count * present + 1, dtype=np.int64) * width,
            )
        statuses = engine.ingest_columnar_multi(
            scope_names, col_sidx, col_pids, col_gids, col_vals, now,
            wire_votes=wire,
        )
        # Correctness gate on EVERY wave: a resolution or identity
        # regression must fail the bench, not get timed as throughput.
        # P2P round-cap overruns (24) and their followups (19) are
        # legitimate in this mixed workload; what must never appear is
        # an unresolved session (20) or a rejected voter identity (10),
        # and the bulk must apply.
        assert int(np.sum(statuses == 20)) == 0, "unresolved proposal ids"
        assert int(np.sum(statuses == 10)) == 0, "stale voter gids"
        applied = int(np.sum((statuses == 0) | (statuses == 28)))
        assert applied >= int(0.9 * len(statuses)), (applied, len(statuses))
        votes = len(statuses)
        engine.delete_scopes(scope_names)  # one release dispatch, not 256
        return votes, p_count

    run_wave(-1)  # warmup/compile
    total_votes = total_proposals = 0
    start = time.perf_counter()
    for wave in range(waves):
        votes, registered = run_wave(wave)
        total_votes += votes
        total_proposals += registered
    elapsed = time.perf_counter() - start
    throughput = total_votes / elapsed
    return {
        "metric": "engine_mixed_scope_churn_throughput"
        + ("_retained" if retain else ""),
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": round(throughput / 1_000_000, 4),
        "detail": {
            "scopes": scopes,
            "proposals_per_wave": p_count,
            "waves": waves,
            "proposals_churned": total_proposals,
            "votes": total_votes,
            "seconds": round(elapsed, 3),
            "proposals_per_sec": round(total_proposals / elapsed, 1),
            "platform": jax.devices()[0].platform,
        },
    }


def run_lanes1024(p_count: int = 12_288, v_count: int = 1024) -> dict:
    """1024-voter-lane pool run: ~the per-chip slice of 100k concurrent
    1024-voter proposals on a v5e-8 (BASELINE north-star shape)."""
    import jax

    from hashgraph_tpu.engine.pool import ProposalPool
    from hashgraph_tpu.ops.decide import required_votes_np

    rng = np.random.default_rng(3)
    now = 1_700_000_000
    pool = ProposalPool(p_count, v_count)
    L = 8
    fill = 672  # most of the ceil(2n/3)=683 quorum, no mid-stream decisions

    def allocate(cycle: int) -> None:
        pool.allocate_batch(
            keys=[(cycle, i) for i in range(p_count)],
            n=np.full(p_count, v_count),
            req=required_votes_np(np.full(p_count, v_count), 2.0 / 3.0),
            cap=np.full(p_count, 2),
            gossip=np.ones(p_count, bool),
            liveness=np.ones(p_count, bool),
            expiry=np.full(p_count, now + 10_000),
            created_at=np.full(p_count, now),
        )

    def run_cycle() -> int:
        pendings = []
        votes = 0
        for base in range(0, fill, L):
            slots = np.repeat(np.arange(p_count, dtype=np.int64), L)
            lanes = np.tile(np.arange(base, base + L, dtype=np.int32), p_count)
            values = rng.random(p_count * L) < 0.5
            pendings.append(pool.ingest_async(slots, lanes, values, now))
            votes += p_count * L
            if len(pendings) >= 16:
                pool.complete_all(pendings)
                pendings = []
        if pendings:
            pool.complete_all(pendings)
        return votes

    allocate(0)
    run_cycle()  # warmup/compile
    rates = []
    for cycle in range(1, 4):
        pool.release(list(range(p_count)))
        allocate(cycle)
        start = time.perf_counter()
        votes = run_cycle()
        rates.append(votes / (time.perf_counter() - start))
    rates.sort()
    throughput = rates[len(rates) // 2]
    return {
        "metric": "lanes1024_ingest_throughput",
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": round(throughput / 1_000_000, 4),
        "detail": {
            "proposals": p_count,
            "voter_lanes": v_count,
            "votes_per_cycle": p_count * fill,
            "platform": jax.devices()[0].platform,
        },
    }


def run_crypto(count: int = 4096) -> dict:
    """Host crypto stage: native threaded ECDSA recover throughput
    (the reference's dominant validate_vote cost,
    /root/reference/src/utils.rs:150-158)."""
    from hashgraph_tpu import EthereumConsensusSigner
    from hashgraph_tpu import native

    signers = [EthereumConsensusSigner.random() for _ in range(8)]
    payloads = [b"vote-payload-%d" % i for i in range(count)]
    t0 = time.perf_counter()
    sigs = [signers[i % 8].sign(p) for i, p in enumerate(payloads)]
    sign_rate = count / (time.perf_counter() - t0)
    idents = [signers[i % 8].identity() for i in range(count)]
    # Warmup (thread pool spinup) then timed run.
    EthereumConsensusSigner.verify_batch(idents[:64], payloads[:64], sigs[:64])
    t0 = time.perf_counter()
    verdicts = EthereumConsensusSigner.verify_batch(idents, payloads, sigs)
    verify_rate = count / (time.perf_counter() - t0)
    assert all(v is True for v in verdicts)
    return {
        "metric": "ecdsa_verify_throughput",
        "value": round(verify_rate, 1),
        "unit": "sigs/sec",
        "vs_baseline": None,
        "detail": {
            "signatures": count,
            "sign_rate": round(sign_rate, 1),
            "native_runtime": native.available(),
        },
    }


def run_validated(p_count: int = 1024, v_count: int = 16) -> dict:
    """End-to-end validated ingest: real EIP-191 ECDSA signatures through
    host validation (structural checks + hash recompute + native batched
    recover) into the columnar device path — the full
    process_incoming_vote pipeline at batch scale, nothing pre-validated.
    """
    from hashgraph_tpu import (
        CreateProposalRequest,
        EthereumConsensusSigner,
        StubConsensusSigner,
    )
    from hashgraph_tpu.engine import TpuConsensusEngine
    from hashgraph_tpu.protocol import compute_vote_hash
    from hashgraph_tpu.wire import Vote

    now = 1_700_000_000
    engine = TpuConsensusEngine(
        EthereumConsensusSigner.random(),
        capacity=p_count,
        voter_capacity=v_count,
        max_sessions_per_scope=p_count + 1,
    )
    engine.scope("s").with_threshold(1.0).initialize()
    # Device-path warmup on a throwaway scope (same grid shapes) so the
    # reported host/device split is steady-state, not compile time.
    warm = engine.create_proposals(
        "warm",
        [
            CreateProposalRequest(
                name="w",
                payload=b"",
                proposal_owner=b"o",
                expected_voters_count=v_count,
                expiration_timestamp=10_000,
                liveness_criteria_yes=True,
            )
            for _ in range(p_count)
        ],
        now,
    )
    warm_gids = np.array(
        [engine.voter_gid(bytes([1 + i]) * 20) for i in range(v_count)], np.int64
    )
    engine.ingest_columnar(
        "warm",
        np.tile(np.fromiter((p.proposal_id for p in warm), np.int64, p_count), v_count),
        np.repeat(warm_gids, p_count),
        np.zeros(p_count * v_count, bool),
        now,
    )
    engine.delete_scope("warm")

    proposals = engine.create_proposals(
        "s",
        [
            CreateProposalRequest(
                name="p",
                payload=b"",
                proposal_owner=b"o",
                expected_voters_count=v_count,
                expiration_timestamp=10_000,
                liveness_criteria_yes=True,
            )
            for _ in range(p_count)
        ],
        now,
    )
    signers = [EthereumConsensusSigner.random() for _ in range(v_count)]
    votes: list[Vote] = []
    for lane, signer in enumerate(signers):
        for p in proposals:
            vote = Vote(
                vote_id=lane + 1,
                vote_owner=signer.identity(),
                proposal_id=p.proposal_id,
                timestamp=now,
                vote=bool(lane % 2),
                parent_hash=b"",
                received_hash=b"",
                vote_hash=b"",
                signature=b"",
            )
            vote.vote_hash = compute_vote_hash(vote)
            vote.signature = signer.sign(vote.signing_payload())
            votes.append(vote)

    total = len(votes)
    gids = np.fromiter(
        (engine.voter_gid(v.vote_owner) for v in votes), np.int64, total
    )
    pids = np.fromiter((v.proposal_id for v in votes), np.int64, total)
    vals = np.fromiter((v.vote for v in votes), bool, total)

    start = time.perf_counter()
    # Host validation stage (reference: src/utils.rs:127-171 order):
    # structural + hash equality + batched signature recovery.
    hashes = [compute_vote_hash(v) for v in votes]
    hash_ok = all(h == v.vote_hash for h, v in zip(hashes, votes))
    t_hash = time.perf_counter()
    verdicts = EthereumConsensusSigner.verify_batch(
        [v.vote_owner for v in votes],
        [v.signing_payload() for v in votes],
        [v.signature for v in votes],
    )
    sig_ok = all(v is True for v in verdicts)
    t_verify = time.perf_counter()
    statuses = engine.ingest_columnar("s", pids, gids, vals, now)
    t_ingest = time.perf_counter()
    assert hash_ok and sig_ok
    assert int(np.sum(statuses == 0)) == total
    elapsed = t_ingest - start
    return {
        "metric": "validated_ingest_throughput",
        "value": round(total / elapsed, 1),
        "unit": "votes/sec",
        "vs_baseline": round(total / elapsed / 1_000_000, 4),
        "detail": {
            "votes": total,
            "hash_seconds": round(t_hash - start, 3),
            "verify_seconds": round(t_verify - t_hash, 3),
            "device_ingest_seconds": round(t_ingest - t_verify, 3),
            "host_share_pct": round(100 * (t_verify - start) / elapsed, 1),
        },
    }


def _sweep_build_batches(engine, scope, waves, p_count, v_count, signers,
                         batch_size, now):
    """Untimed setup for one cold validated rep: create fresh proposals,
    sign every vote (the sender's cost, excluded from ingest timing, as
    in run_validated), and pre-slice the columnar id arrays. Returns a
    list of (votes, pids, gids, vals) pipeline batches."""
    from hashgraph_tpu import CreateProposalRequest
    from hashgraph_tpu.protocol import compute_vote_hash
    from hashgraph_tpu.wire import Vote

    engine.scope(scope).with_threshold(1.0).initialize()
    votes: list[Vote] = []
    for _ in range(waves):
        proposals = engine.create_proposals(
            scope,
            [
                CreateProposalRequest(
                    name="p",
                    payload=b"",
                    proposal_owner=b"o",
                    expected_voters_count=v_count,
                    expiration_timestamp=10_000,
                    liveness_criteria_yes=True,
                )
                for _ in range(p_count)
            ],
            now,
        )
        for lane, signer in enumerate(signers):
            ident = signer.identity()
            for p in proposals:
                vote = Vote(
                    vote_id=lane + 1,
                    vote_owner=ident,
                    proposal_id=p.proposal_id,
                    timestamp=now,
                    vote=bool(lane % 2),
                    parent_hash=b"",
                    received_hash=b"",
                    vote_hash=b"",
                    signature=b"",
                )
                vote.vote_hash = compute_vote_hash(vote)
                vote.signature = signer.sign(vote.signing_payload())
                votes.append(vote)
    batches = []
    for lo in range(0, len(votes), batch_size):
        chunk = votes[lo : lo + batch_size]
        n = len(chunk)
        pids = np.fromiter((v.proposal_id for v in chunk), np.int64, n)
        gids = np.fromiter(
            (engine.voter_gid(v.vote_owner) for v in chunk), np.int64, n
        )
        vals = np.fromiter((v.vote for v in chunk), bool, n)
        batches.append((chunk, pids, gids, vals))
    return batches


def _sweep_timed_rep(engine, scope, batches, now, pipelined, scheme) -> dict:
    """ONE timed cold rep over pre-built batches: full host validation
    (hash recompute + equality + batched signature verify) feeding the
    columnar device path — run_validated's flow, restructured as
    double-buffered stages when ``pipelined`` (crypto for batch k+1 runs
    on the verify pool while batch k ingests on device)."""
    from hashgraph_tpu.protocol import compute_vote_hash

    total = sum(len(b[0]) for b in batches)
    applied = 0
    all_valid = True
    start = time.perf_counter()
    if pipelined:
        prev = None
        for batch in [*batches, None]:
            pend = (
                engine.verify_votes_async(batch[0])
                if batch is not None
                else None
            )
            if prev is not None:
                (votes, pids, gids, vals), prev_pend = prev
                verdicts, hashes = prev_pend.collect()
                all_valid &= all(v is True for v in verdicts)
                all_valid &= all(
                    h == v.vote_hash for h, v in zip(hashes, votes)
                )
                statuses = engine.ingest_columnar(scope, pids, gids, vals, now)
                applied += int(np.sum(statuses == 0))
            prev = (batch, pend) if batch is not None else None
    else:
        for votes, pids, gids, vals in batches:
            hashes = [compute_vote_hash(v) for v in votes]
            all_valid &= all(h == v.vote_hash for h, v in zip(hashes, votes))
            verdicts = scheme.verify_batch(
                [v.vote_owner for v in votes],
                [v.signing_payload() for v in votes],
                [v.signature for v in votes],
            )
            all_valid &= all(v is True for v in verdicts)
            statuses = engine.ingest_columnar(scope, pids, gids, vals, now)
            applied += int(np.sum(statuses == 0))
    elapsed = time.perf_counter() - start
    assert all_valid, "cold sweep produced an invalid verdict"
    assert applied == total, f"applied {applied} of {total}"
    return {"votes": total, "seconds": round(elapsed, 3),
            "votes_per_sec": round(total / elapsed, 1)}


def run_validated_sweep(p_count: int = 256, v_count: int = 64) -> dict:
    """Cold validated ingest sweep: batch-size × scheme × pool-threads,
    sequential vs pipelined, every vote carrying a REAL signature that is
    hashed and verified in the timed window (nothing cached, nothing
    redelivered — the admission cache cannot help cold traffic, so the
    sweep engines run verify_cache=None, today's uncached flow).

    Headline: Ed25519 batch-verified + pipelined throughput. Paired
    same-window A/B (ROADMAP 5b): the baseline arm re-measures BENCH_r05's
    exact validated flow (ECDSA, sequential) interleaved rep-for-rep with
    the headline arm inside ONE window, with a fixed-size host-crypto
    control (native ECDSA verify, the `crypto` metric's workload) timed
    between reps as a weather normalizer. The machine-readable
    ``noise_verdict`` refuses the claim unless the arms separate by more
    than the window's own spread — a speedup inside BENCHMARKS.md's
    documented ~26% weather band must not pass."""
    import os

    from hashgraph_tpu import Ed25519ConsensusSigner, EthereumConsensusSigner
    from hashgraph_tpu import native
    from hashgraph_tpu.engine import TpuConsensusEngine

    now = 1_700_000_000
    cores = os.cpu_count() or 1
    rng_scope = iter(range(10_000))

    def fresh_engine(scheme_cls, capacity):
        return TpuConsensusEngine(
            scheme_cls.random(),
            capacity=capacity,
            voter_capacity=v_count,
            max_sessions_per_scope=capacity + 1,
            verify_cache=None,
        )

    def run_cell(scheme_cls, waves, batch_size, pool_threads, pipelined,
                 warm=True) -> dict:
        if native.available():
            native.pool_configure(pool_threads)
        scheme_name = scheme_cls.__name__.replace("ConsensusSigner", "").lower()
        engine = fresh_engine(scheme_cls, waves * p_count + 8)
        scope = f"sweep-{next(rng_scope)}"
        signers = [scheme_cls.random() for _ in range(v_count)]
        batches = _sweep_build_batches(
            engine, scope, waves, p_count, v_count, signers, batch_size, now
        )
        if warm:
            # Columnar-path warmup at the same grid shapes (compile time
            # must not be billed to the first batch): a throwaway wave.
            warm_scope = f"warm-{next(rng_scope)}"
            warm_signers = [scheme_cls.random() for _ in range(v_count)]
            warm_batches = _sweep_build_batches(
                engine, warm_scope, 1, p_count, v_count, warm_signers,
                batch_size, now,
            )
            _sweep_timed_rep(engine, warm_scope, warm_batches, now,
                             pipelined, scheme_cls)
            engine.delete_scope(warm_scope)
        rep = _sweep_timed_rep(engine, scope, batches, now, pipelined,
                               scheme_cls)
        engine.delete_scope(scope)
        rep.update(
            scheme=scheme_name,
            batch_size=batch_size,
            pool_threads=pool_threads,
            mode="pipelined" if pipelined else "sequential",
        )
        return rep

    # ── Host-crypto control: fixed native ECDSA workload (the `crypto`
    # metric), timed between A/B reps as the weather normalizer. ──
    ctl_signers = [EthereumConsensusSigner.random() for _ in range(8)]
    ctl_payloads = [b"ctl-%d" % i for i in range(1024)]
    ctl_sigs = [ctl_signers[i % 8].sign(p) for i, p in enumerate(ctl_payloads)]
    ctl_ids = [ctl_signers[i % 8].identity() for i in range(1024)]
    EthereumConsensusSigner.verify_batch(ctl_ids[:64], ctl_payloads[:64],
                                         ctl_sigs[:64])  # pool warmup

    def control_rate() -> float:
        t0 = time.perf_counter()
        verdicts = EthereumConsensusSigner.verify_batch(
            ctl_ids, ctl_payloads, ctl_sigs
        )
        assert all(v is True for v in verdicts)
        return round(1024 / (time.perf_counter() - t0), 1)

    # ── Sweep cells (single rep each; the A/B below carries the noise
    # statistics for the headline claim). ──
    sweep: list[dict] = []
    for batch_size in (4096, 16384):
        for pool_threads in (1, 0):
            sweep.append(
                run_cell(Ed25519ConsensusSigner, 2, batch_size,
                         pool_threads, True)
            )
    sweep.append(run_cell(Ed25519ConsensusSigner, 2, 16384, 0, False))
    sweep.append(run_cell(EthereumConsensusSigner, 1, 16384, 0, True))

    # ── Paired same-window A/B: headline arm (Ed25519 batch, pipelined)
    # interleaved with the BENCH_r05 baseline arm (ECDSA, sequential),
    # control timed around every rep. ──
    if native.available():
        native.pool_configure(0)
    headline_reps: list[float] = []
    baseline_reps: list[float] = []
    controls: list[float] = []
    controls.append(control_rate())
    for _ in range(3):
        rep = run_cell(Ed25519ConsensusSigner, 8, 16384, 0, True, warm=False)
        headline_reps.append(rep["votes_per_sec"])
        controls.append(control_rate())
        rep = run_cell(EthereumConsensusSigner, 1, 16384, 0, False,
                       warm=False)
        baseline_reps.append(rep["votes_per_sec"])
        controls.append(control_rate())

    headline = sorted(headline_reps)[1]
    baseline = sorted(baseline_reps)[1]
    speedup = round(headline / baseline, 2)
    max_spread = max(
        spread_pct(headline_reps),
        spread_pct(baseline_reps),
        spread_pct(controls),
    )
    # The claim must clear the window's own weather: the slowest headline
    # rep has to beat the fastest baseline rep, and the speedup has to
    # exceed twice the worst observed spread.
    separated = min(headline_reps) > max(baseline_reps)
    outside_noise = speedup > 1.0 + 2.0 * max_spread / 100.0
    noise_verdict = {
        "pass": bool(separated and outside_noise),
        "criterion": (
            "min(headline reps) > max(baseline reps) AND "
            "speedup > 1 + 2*max_spread"
        ),
        "headline_votes_per_sec": headline,
        "baseline_votes_per_sec": baseline,
        "speedup": speedup,
        "vs_bench_r05_8632": round(headline / 8632.5, 2),
        "headline_reps": headline_reps,
        "baseline_reps": baseline_reps,
        "control_sigs_per_sec": controls,
        "spread_pct": {
            "headline": spread_pct(headline_reps),
            "baseline": spread_pct(baseline_reps),
            "control": spread_pct(controls),
        },
    }
    # Device-vs-host-pool verify arm (ROADMAP item 2): the same paired
    # same-window A/B discipline, batch sizes 256/1k/4k/16k, per-phase
    # device timings, winner named honestly (on CPU backends the native
    # pool wins; the wall-clock budget skips — and records — sizes the
    # backend cannot afford).
    device_arm = run_device_verify()

    return {
        "metric": "cold_validated_ingest_throughput",
        "value": headline,
        "unit": "votes/sec",
        "vs_baseline": round(headline / 8632.5, 2),
        "detail": {
            "cores": cores,
            "native_runtime": native.available(),
            "pool_size": native.pool_size(),
            "scheme_headline": "ed25519 (randomized-linear-combination "
                               "batch verify, pipelined)",
            "sweep": sweep,
            "noise_verdict": noise_verdict,
            "device_verify": device_arm,
        },
    }


def run_device_verify(smoke: bool = False, budget_seconds: float = 45.0) -> dict:
    """Device-vs-host-pool Ed25519 batch verify: paired same-window A/B.

    Arms verify the SAME signed corpus through the same
    ``verify_batch`` contract — ``Ed25519DeviceConsensusSigner`` (the
    JAX pipeline: decompression, vectorized SHA-512, Straus MSM) vs
    ``Ed25519ConsensusSigner`` (the native verify pool, or the
    pure-Python twin without the runtime) — interleaved rep for rep at
    batch sizes 256/1k/4k/16k. Each size reports both medians, the
    device pipeline's per-phase seconds (decompress / hash / MSM from
    the backend's own clocks), and a machine-readable ``noise_verdict``
    that names the WINNER honestly: on the CPU backend the device arm
    is expected to lose to the native pool by orders of magnitude (the
    u32-limb field core exists for accelerators, not host cores), and
    the verdict says so rather than hiding the direction. A wall-clock
    budget bounds every size at the warm rep: a blown warm rep skips
    later sizes outright and degrades the FIRST size to one timed rep
    per arm (at least one paired cell always ships, flagged as
    degraded) — skips are recorded, not silent."""
    from hashgraph_tpu import crypto_device, native
    from hashgraph_tpu.signing import (
        Ed25519ConsensusSigner,
        Ed25519DeviceConsensusSigner,
    )

    if not crypto_device.available():
        return {
            "metric": "device_verify_throughput",
            "value": 0.0,
            "unit": "sigs/sec",
            "detail": {"skipped": "crypto_device backend unavailable"},
        }
    import jax

    platform = jax.devices()[0].platform
    sizes = (256, 1024) if smoke else (256, 1024, 4096, 16384)
    reps = 2 if smoke else 3
    if native.available():
        native.pool_configure(0)  # affinity-sized: the pool's best foot

    # One shared corpus (vote-sized payloads, real signatures), sliced
    # per batch size so both arms always see identical bytes.
    signers = [Ed25519ConsensusSigner.random() for _ in range(64)]
    top = max(sizes)
    payloads = [b"device-verify-%6d:" % i + b"p" * 73 for i in range(top)]
    idents = [signers[i % 64].identity() for i in range(top)]
    sigs = [signers[i % 64].sign(p) for i, p in enumerate(payloads)]

    def time_arm(scheme_cls, n: int) -> float:
        t0 = time.perf_counter()
        verdicts = scheme_cls.verify_batch(
            idents[:n], payloads[:n], sigs[:n]
        )
        elapsed = time.perf_counter() - t0
        assert all(v is True for v in verdicts), "A/B corpus must verify"
        return elapsed

    cells: list[dict] = []
    skipped: list[dict] = []
    over_budget = False
    for n in sizes:
        if over_budget:
            skipped.append({
                "batch_size": n,
                "reason": "previous size exceeded the device budget; "
                          "honest skip instead of a stalled driver",
            })
            continue
        # Warm both arms at this shape (device: XLA compile for the
        # size's lane/block buckets; host: pool threads) off the clock.
        warm = time_arm(Ed25519DeviceConsensusSigner, n)
        time_arm(Ed25519ConsensusSigner, n)
        # The warm rep is the budget's first honest look at this size:
        # past it, skip (later sizes) or degrade to ONE timed rep per
        # arm (the smallest size — the sweep always emits at least one
        # paired cell, and a 1-rep cell says so in its verdict).
        size_reps = reps
        if warm > budget_seconds:
            over_budget = True
            if cells:
                skipped.append({
                    "batch_size": n,
                    "reason": "warm rep %.1fs exceeded the %.0fs budget"
                              % (warm, budget_seconds),
                })
                continue
            size_reps = 1
        device_reps: list[float] = []
        host_reps: list[float] = []
        for _ in range(size_reps):
            device_reps.append(time_arm(Ed25519DeviceConsensusSigner, n))
            host_reps.append(time_arm(Ed25519ConsensusSigner, n))
        phases = crypto_device.last_phase_seconds()
        dev = sorted(device_reps)[len(device_reps) // 2]
        host = sorted(host_reps)[len(host_reps) // 2]
        device_sps = round(n / dev, 1)
        host_sps = round(n / host, 1)
        device_faster = dev < host
        speedup = round((host / dev) if device_faster else (dev / host), 2)
        max_spread = max(spread_pct(device_reps), spread_pct(host_reps))
        separated = (
            max(device_reps) < min(host_reps)
            if device_faster
            else max(host_reps) < min(device_reps)
        )
        cells.append({
            "batch_size": n,
            "reps": size_reps,
            "budget_degraded_to_single_rep": size_reps < reps,
            "device_sigs_per_sec": device_sps,
            "host_pool_sigs_per_sec": host_sps,
            "device_phase_seconds": {
                k: round(v, 4) for k, v in phases.items()
            },
            "device_reps_seconds": [round(t, 4) for t in device_reps],
            "host_reps_seconds": [round(t, 4) for t in host_reps],
            "noise_verdict": {
                "winner": "device" if device_faster else "host_pool",
                "speedup": speedup,
                "pass": bool(
                    separated and speedup > 1.0 + 2.0 * max_spread / 100.0
                ),
                "criterion": (
                    "winner's every rep beats loser's every rep AND "
                    "speedup > 1 + 2*max_spread"
                ),
                "max_spread_pct": max_spread,
            },
        })
        if max(device_reps) + warm > budget_seconds:
            over_budget = True

    headline = cells[-1] if cells else {}
    return {
        "metric": "device_verify_throughput",
        "value": headline.get("device_sigs_per_sec", 0.0),
        "unit": "sigs/sec",
        "detail": {
            "platform": platform,
            "native_runtime": native.available(),
            "pool_size": native.pool_size(),
            "smoke": smoke,
            "cells": cells,
            "skipped_sizes": skipped,
            "honest_summary": (
                "device arm wins" if headline.get("noise_verdict", {}).get(
                    "winner") == "device"
                else "host pool wins on this backend — the device path "
                     "pays off on accelerator hardware, not host cores"
            ),
        },
    }


def run_config2(voters: int = 1024, repeats: int = 9) -> dict:
    """1 proposal × 1024 voters, P2P dynamic rounds: p50 finality latency.

    The P2P cap is ceil(2n/3) votes; a unanimous YES replay decides at
    req = ceil(2n/3) = 683 votes. The whole chain arrives as one dispatch
    (scan depth = 683), timing first-vote-to-decision wall clock.
    """
    import jax

    from hashgraph_tpu.engine.pool import ProposalPool
    from hashgraph_tpu.ops.decide import STATE_REACHED_YES, required_votes_np

    now = 1_700_000_000
    cap = (2 * voters + 2) // 3
    pool = ProposalPool(40, voters)  # headroom for the 32-chain slope below
    latencies = []
    for rep in range(repeats + 1):  # first is compile warmup
        pool.allocate_batch(
            keys=[(rep, 0)],
            n=np.array([voters]),
            req=required_votes_np(np.array([voters]), 2.0 / 3.0),
            cap=np.array([cap]),
            gossip=np.array([False]),
            liveness=np.array([True]),
            expiry=np.array([now + 1000]),
            created_at=np.array([now]),
        )
        slots = np.zeros(cap, np.int64)
        lanes = np.arange(cap, dtype=np.int32)
        values = np.ones(cap, bool)
        start = time.perf_counter()
        statuses, transitions = pool.ingest(slots, lanes, values, now)
        latency = time.perf_counter() - start
        assert transitions and transitions[0][1] == STATE_REACHED_YES
        if rep > 0:
            latencies.append(latency)
        pool.release([0])
    latencies.sort()
    p50 = latencies[len(latencies) // 2]

    # Decouple device execution from the link: K chained dispatches on
    # distinct slots pay the host<->device round-trip ONCE (async queue +
    # one blocking readback), so wall(K) ≈ link + K*device and the slope
    # (wall(K) - wall(1)) / (K - 1) is the on-device decision time. K=32
    # makes the slope signal (~tens of ms) far larger than the link's
    # same-day jitter band, and three paired samples are reported so the
    # spread is visible. The p50 above includes the host's dispatch and
    # readback round trips; BASELINE's finality metric wants the
    # device-side figure.
    def chain_wall(n_chains: int, fresh: bool) -> float:
        slot_ids = pool.allocate_batch(
            keys=[("lat", i) for i in range(n_chains)],
            n=np.full(n_chains, voters),
            req=required_votes_np(np.full(n_chains, voters), 2.0 / 3.0),
            cap=np.full(n_chains, cap),
            gossip=np.zeros(n_chains, bool),
            liveness=np.ones(n_chains, bool),
            expiry=np.full(n_chains, now + 1000),
            created_at=np.full(n_chains, now),
        )
        lanes_l = np.arange(cap, dtype=np.int32)
        values_l = np.ones(cap, bool)
        t0 = time.perf_counter()
        if fresh:
            # The closed-form kernel the engine fast path dispatches:
            # whole chains, no sequential scan.
            pendings = [
                pool.ingest_async_grouped(
                    np.array([s], np.int64),
                    np.zeros(cap, np.int64),
                    np.arange(cap, dtype=np.int64),
                    cap,
                    lanes_l,
                    values_l,
                    now,
                    fresh=True,
                )
                for s in slot_ids
            ]
        else:
            pendings = [
                pool.ingest_async(
                    np.full(cap, s, np.int64), lanes_l, values_l, now
                )
                for s in slot_ids
            ]
        results = pool.complete_all(pendings)
        wall = time.perf_counter() - t0
        for _, transitions in results:
            assert transitions and transitions[0][1] == STATE_REACHED_YES
        pool.release(slot_ids)
        return wall

    K = 32

    def slope(fresh: bool) -> tuple[float, list[float]]:
        chain_wall(K, fresh)  # warmup (bucket + stack-kernel compiles)
        samples = []
        for _ in range(3):
            w1 = chain_wall(1, fresh)
            wk = chain_wall(K, fresh)
            samples.append(max(wk - w1, 0.0) / (K - 1) * 1000)
        return sorted(samples)[1], samples

    device_ms, samples_ms = slope(fresh=False)
    fresh_ms, fresh_samples = slope(fresh=True)
    return {
        "metric": "p2p_finality_latency_p50",
        "value": round(p50 * 1000, 3),
        "unit": "ms",
        "vs_baseline": None,
        "detail": {
            "voters": voters,
            "votes_to_quorum": cap,
            "latencies_ms": [round(l * 1000, 2) for l in latencies],
            "device_exec_ms_per_decision": round(device_ms, 3),
            "device_exec_samples_ms": [round(s, 3) for s in samples_ms],
            # Closed-form (scan-free) kernel — the engine fast path's
            # dispatch for fresh chains.
            "device_exec_fresh_ms_per_decision": round(fresh_ms, 3),
            "device_exec_fresh_samples_ms": [round(s, 3) for s in fresh_samples],
            "platform": jax.devices()[0].platform,
        },
    }


def run_config4(
    scopes: int = 256, proposals_per_scope: int = 1000, voters: int = 256
) -> dict:
    """Byzantine/absent liveness path: 30% of voters never vote; sessions
    finalize via the timeout sweep. Sharded over all available devices."""
    import jax

    from hashgraph_tpu.engine.pool import ProposalPool
    from hashgraph_tpu.ops.decide import (
        STATE_ACTIVE,
        required_votes_np,
    )
    from hashgraph_tpu.parallel import ShardedPool, consensus_mesh

    rng = np.random.default_rng(11)
    now = 1_700_000_000
    p_count = scopes * proposals_per_scope
    n_dev = len(jax.devices())
    if n_dev > 1:
        per_dev = -(-p_count // n_dev)
        pool = ShardedPool(per_dev, voters, consensus_mesh())
    else:
        pool = ProposalPool(p_count, voters)

    pool.allocate_batch(
        keys=[(f"s{i % scopes}", i) for i in range(p_count)],
        n=np.full(p_count, voters),
        req=required_votes_np(np.full(p_count, voters), 2.0 / 3.0),
        cap=np.full(p_count, 2),
        gossip=np.ones(p_count, bool),
        liveness=rng.random(p_count) < 0.5,
        expiry=np.full(p_count, now + 100),
        created_at=np.full(p_count, now),
    )

    # 70% participation, random yes/no, streamed in lane-rounds.
    present = int(voters * 0.7)
    slots = np.repeat(np.arange(p_count, dtype=np.int64), 8)
    start = time.perf_counter()
    total_votes = 0
    pendings = []
    for base_lane in range(0, present, 8):
        width = min(8, present - base_lane)
        sl = np.repeat(np.arange(p_count, dtype=np.int64), width)
        lanes = np.tile(
            np.arange(base_lane, base_lane + width, dtype=np.int32), p_count
        )
        values = rng.random(p_count * width) < 0.5
        pendings.append(pool.ingest_async(sl, lanes, values, now))
        total_votes += p_count * width
    pool.complete_all(pendings)
    # Liveness sweep finalizes everything still active.
    active = [s for s in range(p_count) if pool.state_of(s) == STATE_ACTIVE]
    swept = pool.timeout(active)
    elapsed = time.perf_counter() - start

    undecided = sum(1 for _, st in swept if st == STATE_ACTIVE)
    throughput = total_votes / elapsed
    return {
        "metric": "byzantine_timeout_throughput",
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": round(throughput / 1_000_000, 4),
        "detail": {
            "scopes": scopes,
            "proposals": p_count,
            "voters": voters,
            "absent_pct": 30,
            "votes": total_votes,
            "timeout_decisions": len(swept),
            "undecided_after_sweep": undecided,
            "seconds": round(elapsed, 3),
            "devices": n_dev,
        },
    }


def run_engine_config4(
    scopes: int = 256, proposals_per_scope: int = 500, voters: int = 256
) -> dict:
    """Engine-level config 4: 256 scopes × 500 proposals × 256 voters, 30%
    absent, mixed liveness, finalized by the engine's timeout sweep — the
    Byzantine/absent path through the FULL service surface (registration,
    multi-scope columnar ingest, sweep with events), not the raw pool.
    (Half the BASELINE population by default to bound sweep wall time; the
    full 256×1000 shape runs at the same votes/sec — measured 0.48M/s
    end-to-end incl. compile, vs the raw pool's ~1M/s at that shape.)"""
    import jax

    from hashgraph_tpu import CreateProposalRequest, StubConsensusSigner
    from hashgraph_tpu.engine import TpuConsensusEngine

    rng = np.random.default_rng(17)
    now = 1_700_000_000
    p_count = scopes * proposals_per_scope
    engine = TpuConsensusEngine(
        StubConsensusSigner(b"\x01" * 20),
        capacity=p_count,
        voter_capacity=voters,
        max_sessions_per_scope=proposals_per_scope + 1,
    )
    present = int(voters * 0.7)
    owners = [
        bytes([1 + (i % 250), i // 250]) + b"\x00" * 18 for i in range(present)
    ]

    def requests_for(scope_idx: int) -> list[CreateProposalRequest]:
        return [
            CreateProposalRequest(
                name="p",
                payload=b"",
                proposal_owner=b"o",
                expected_voters_count=voters,
                expiration_timestamp=100,
                liveness_criteria_yes=bool((scope_idx + k) % 2),
            )
            for k in range(proposals_per_scope)
        ]

    def run_round(round_idx: int) -> dict:
        """One full registration -> ingest -> sweep pass. Round 0 is the
        compile warmup at the EXACT production shapes (allocate, ingest,
        timeout, readback-stack programs all compile there); the timed
        round measures steady-state service throughput — the same warmup
        discipline as the other engine benches' cycle 0 and the pool-level
        config4, which allocates before its clock starts."""
        scope_names = [f"r{round_idx}-s{i}" for i in range(scopes)]
        gids = np.array([engine.voter_gid(o) for o in owners], np.int64)
        start = time.perf_counter()
        batches = engine.create_proposals_multi(
            [(scope, requests_for(i)) for i, scope in enumerate(scope_names)],
            now,
        )
        t_create = time.perf_counter()

        pids = np.array(
            [p.proposal_id for batch in batches for p in batch], np.int64
        )
        sidx = np.repeat(np.arange(scopes, dtype=np.int64), proposals_per_scope)
        # Chunked by PROPOSAL block (each chunk carries all its proposals'
        # votes), bounding host memory and keeping lane resolution on the
        # vectorized fresh-assignment path.
        total_votes = 0
        chunk = max(1, p_count // 8)
        for base in range(0, p_count, chunk):
            sel = slice(base, min(base + chunk, p_count))
            n_sel = sel.stop - sel.start
            col_pids = np.repeat(pids[sel], present)
            col_sidx = np.repeat(sidx[sel], present)
            col_gids = np.tile(gids, n_sel)
            col_vals = rng.random(n_sel * present) < 0.5
            statuses = engine.ingest_columnar_multi(
                scope_names, col_sidx, col_pids, col_gids, col_vals, now
            )
            # Correctness gate on every round (see run_engine_config5): a
            # resolution or identity regression must fail the bench, not
            # get timed as throughput.
            assert int(np.sum(statuses == 20)) == 0, "unresolved proposal ids"
            assert int(np.sum(statuses == 10)) == 0, "stale voter gids"
            applied = int(np.sum((statuses == 0) | (statuses == 28)))
            assert applied >= int(0.9 * len(statuses)), (applied, len(statuses))
            total_votes += n_sel * present
        t_ingest = time.perf_counter()

        swept = engine.sweep_timeouts(now + 200)
        elapsed = time.perf_counter() - start
        return {
            "votes": total_votes,
            "seconds": elapsed,
            "create_seconds": t_create - start,
            "ingest_seconds": t_ingest - t_create,
            "sweep_seconds": elapsed - (t_ingest - start),
            "timeout_decisions": len(swept),
            "scope_names": scope_names,
        }

    warm = run_round(0)
    engine.delete_scopes(warm["scope_names"])
    timed = run_round(1)

    throughput = timed["votes"] / timed["seconds"]
    return {
        "metric": "engine_byzantine_timeout_throughput",
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": round(throughput / 1_000_000, 4),
        "detail": {
            "scopes": scopes,
            "proposals": p_count,
            "voters": voters,
            "absent_pct": 30,
            "votes": timed["votes"],
            "create_seconds": round(timed["create_seconds"], 3),
            "ingest_seconds": round(timed["ingest_seconds"], 3),
            "sweep_seconds": round(timed["sweep_seconds"], 3),
            "timeout_decisions": timed["timeout_decisions"],
            "seconds": round(timed["seconds"], 3),
            "warmup_seconds": round(warm["seconds"], 3),
            "platform": jax.devices()[0].platform,
        },
    }


def run_config5(
    p_count: int = 65_536, v_count: int = 48, waves: int = 16
) -> dict:
    """Streaming mixed Gossipsub+P2P replay to 1M proposals: ``waves``
    arrival-ordered populations (16 × 65536 ≈ 1.05M) streamed through the
    pipelined ingest path, each wave recycling the pool like a live
    deployment churns sessions."""
    import jax

    from hashgraph_tpu.engine.pool import ProposalPool
    from hashgraph_tpu.ops.decide import required_votes_np

    rng = np.random.default_rng(23)
    now = 1_700_000_000
    pool = ProposalPool(p_count, v_count)
    all_slots = list(range(p_count))

    def allocate(wave: int) -> None:
        gossip = rng.random(p_count) < 0.5
        caps = np.where(gossip, 2, (2 * v_count + 2) // 3)
        pool.allocate_batch(
            keys=[(wave, i) for i in range(p_count)],
            n=np.full(p_count, v_count),
            req=required_votes_np(np.full(p_count, v_count), 2.0 / 3.0),
            cap=caps,
            gossip=gossip,
            liveness=rng.random(p_count) < 0.5,
            expiry=np.full(p_count, now + 10_000),
            created_at=np.full(p_count, now),
        )

    def stream_wave() -> int:
        # Rounds of one-vote-per-proposal through the full voter set:
        # gossip sessions decide once quorum lands (~vote 32 of 48), P2P
        # sessions hit their ceil(2n/3) caps, and later rounds exercise the
        # ALREADY_REACHED / SESSION_NOT_ACTIVE absorption paths — exactly
        # like a replayed gossip trace.
        votes = 0
        pendings = []
        slots = np.arange(p_count, dtype=np.int64)
        for r in range(v_count):
            lanes = np.full(p_count, r, np.int32)
            values = rng.random(p_count) < 0.55
            pendings.append(pool.ingest_async(slots, lanes, values, now))
            votes += p_count
            if len(pendings) >= 8:
                pool.complete_all(pendings)
                pendings = []
        if pendings:
            pool.complete_all(pendings)
        return votes

    allocate(0)
    stream_wave()  # warmup/compile wave (uncounted)
    total_votes = 0
    start = time.perf_counter()
    for wave in range(waves):
        pool.release(all_slots)
        allocate(wave + 1)
        total_votes += stream_wave()
    elapsed = time.perf_counter() - start

    counts = pool.state_counts()
    throughput = total_votes / elapsed
    return {
        "metric": "streaming_mixed_replay_throughput",
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": round(throughput / 1_000_000, 4),
        "detail": {
            "proposals_replayed": p_count * waves,
            "pool_slots": p_count,
            "voters": v_count,
            "votes": total_votes,
            "seconds": round(elapsed, 3),
            "proposals_per_sec": round(p_count * waves / elapsed, 1),
            "final_wave_state_counts": {str(k): v for k, v in counts.items()},
            "platform": jax.devices()[0].platform,
        },
    }


def run_deepchain(
    p_count: int = 64, depth: int = 2048, reps: int = 3
) -> dict:
    """Deep-chain replay: 64 fresh sessions × 2048-vote chains, scan kernel
    vs closed-form kernel on identical batches. The scan pays `depth`
    sequential steps; the closed form is log-depth (cumsum + reductions) —
    this mode makes that design win directly measurable on hardware."""
    import jax

    from hashgraph_tpu.engine.pool import ProposalPool
    from hashgraph_tpu.ops.decide import required_votes_np

    rng = np.random.default_rng(41)
    now = 1_700_000_000
    waves = 8  # pipelined dispatches per timing: device work dominates RTT
    votes = p_count * depth * waves
    pool = ProposalPool(p_count * waves, depth)
    lanes = np.tile(np.arange(depth, dtype=np.int64), p_count)
    rows = np.repeat(np.arange(p_count, dtype=np.int64), depth)
    cols = np.tile(np.arange(depth, dtype=np.int64), p_count)
    vals = rng.random(p_count * depth) < 0.5  # threshold 1.0: never decides

    def run_once(fresh: bool) -> float:
        n_slots = p_count * waves
        slot_ids = pool.allocate_batch(
            keys=[("d", i) for i in range(n_slots)],
            n=np.full(n_slots, depth),
            req=required_votes_np(np.full(n_slots, depth), 1.0),
            cap=np.full(n_slots, depth + 1),
            gossip=np.zeros(n_slots, bool),
            liveness=np.ones(n_slots, bool),
            expiry=np.full(n_slots, now + 1000),
            created_at=np.full(n_slots, now),
        )
        groups = np.asarray(slot_ids, np.int64).reshape(waves, p_count)
        t0 = time.perf_counter()
        pendings = [
            pool.ingest_async_grouped(
                groups[w], rows, cols, depth, lanes, vals, now, fresh=fresh
            )
            for w in range(waves)
        ]
        results = pool.complete_all(pendings)
        dt = time.perf_counter() - t0
        for statuses, _ in results:
            assert int(np.sum(statuses == 0)) == p_count * depth
        pool.release(slot_ids)
        return dt

    for fresh in (False, True):
        run_once(fresh)  # compile warmup
    scan_s = sorted(run_once(False) for _ in range(reps))[reps // 2]
    fresh_s = sorted(run_once(True) for _ in range(reps))[reps // 2]
    return {
        "metric": "deepchain_fresh_vs_scan",
        "value": round(votes / fresh_s, 1),
        "unit": "votes/sec",
        "vs_baseline": round(votes / fresh_s / 1_000_000, 4),
        "detail": {
            "sessions": p_count,
            "chain_depth": depth,
            "votes": votes,
            "scan_seconds": round(scan_s, 3),
            "fresh_seconds": round(fresh_s, 3),
            "scan_votes_per_sec": round(votes / scan_s, 1),
            "speedup": round(scan_s / fresh_s, 2),
            "platform": jax.devices()[0].platform,
        },
    }


def run_redelivery(
    chain_len: int = 48,
    expected_voters: int = 64,
    redelivery_waves: int = 8,
) -> dict:
    """Amortized vote verification under gossip redelivery and incremental
    chain growth — the workload ISSUE 4 targets: the reference protocol
    gossips *growing vote chains*, so a chain of length L delivered one
    extension at a time costs O(L²) signature checks without memoization.
    Real EIP-191 ECDSA signatures throughout (the honest host-crypto-bound
    envelope, same convention as ``validated``).

    Three sub-workloads, each measured cache-on (engine default) vs
    cache-off (``verify_cache=None``):

    - ``growth``: a fresh receiver is handed the chain at every length
      1..L via ``process_incoming_proposal`` (session dropped between
      deliveries — the new-peer-per-delivery shape). Cache-off verifies
      L(L+1)/2 signatures; cache-on verifies L. This is the headline.
    - ``watermark``: the same growth delivered to ONE persistent session
      via ``deliver_proposals`` — the validated-chain watermark applies
      just the suffix, so even cache-off is O(L); shows the structural
      (non-cache) half of the amortization.
    - ``waves``: the full chain redelivered ``redelivery_waves`` times
      through ``ingest_votes`` (the embedder fallback pattern); duplicate
      rejection happens *after* admission validation, so cache-off pays
      waves×L ECDSA recovers.

    The headline ``value`` is cache-on growth throughput; ``speedup`` in
    detail is cache-off/cache-on wall time on that same workload.

    Sessions run on the HOST substrate (``expected_voters_count`` above
    the engine's lane capacity spills them, exactly the graceful-degrade
    path oversized proposals take): admission verification is a pure host
    stage, and the per-delivery host↔device round trip would otherwise
    swamp the quantity under test. The device ingest path is measured by
    the other modes; its cost is identical cache-on and cache-off.
    """
    from hashgraph_tpu import CreateProposalRequest, EthereumConsensusSigner
    from hashgraph_tpu.engine import TpuConsensusEngine

    now = 1_700_000_000
    L = chain_len

    def fresh_engine(cache) -> TpuConsensusEngine:
        engine = TpuConsensusEngine(
            EthereumConsensusSigner.random(),
            capacity=16,
            voter_capacity=16,  # < expected_voters: sessions host-spill
            verify_cache=cache,
        )
        engine.scope("s").with_threshold(1.0).initialize()
        return engine

    # One signed chain, reused verbatim by every mode/engine (the bytes a
    # gossip network would redeliver). threshold 1.0 with L < n keeps every
    # session undecided, so no wave short-circuits on ALREADY_REACHED
    # before validating.
    sender = fresh_engine(None)
    base = sender.create_proposal(
        "s",
        CreateProposalRequest(
            name="p",
            payload=b"",
            proposal_owner=b"o",
            expected_voters_count=expected_voters,
            expiration_timestamp=10_000,
            liveness_criteria_yes=True,
        ),
        now,
    )
    from hashgraph_tpu import build_vote

    signers = [EthereumConsensusSigner.random() for _ in range(L)]
    chain = base.clone()
    for k, signer in enumerate(signers):
        chain.votes.append(build_vote(chain, bool(k % 2), signer, now + 1 + k))
    grown = [chain.clone() for _ in range(L)]
    for k in range(L):
        grown[k].votes = [v.clone() for v in chain.votes[: k + 1]]

    def run_growth(engine) -> float:
        t0 = time.perf_counter()
        for k in range(L):
            engine.process_incoming_proposal("s", grown[k].clone(), now + 50)
            engine.delete_scope("s")
            engine.scope("s").with_threshold(1.0).initialize()
        return time.perf_counter() - t0

    def run_watermark(engine) -> float:
        t0 = time.perf_counter()
        for k in range(L):
            [code] = engine.deliver_proposals(
                [("s", grown[k].clone())], now + 50
            )
            assert code == 0, code
        return time.perf_counter() - t0

    def run_waves(engine) -> float:
        engine.process_incoming_proposal("s", grown[-1].clone(), now + 50)
        batch = [("s", v.clone()) for v in chain.votes]
        t0 = time.perf_counter()
        for _ in range(redelivery_waves):
            engine.ingest_votes(batch, now + 60)
        return time.perf_counter() - t0

    # Compile warmup: the pool kernels are module-level jits, so one
    # throwaway engine pass compiles every shape the timed runs dispatch.
    for fn in (run_growth, run_watermark, run_waves):
        fn(fresh_engine(None))

    growth_votes = L * (L + 1) // 2
    wave_votes = redelivery_waves * L
    t_growth_off = run_growth(fresh_engine(None))
    t_growth_on = run_growth(fresh_engine("default"))
    t_mark_off = run_watermark(fresh_engine(None))
    t_mark_on = run_watermark(fresh_engine("default"))
    t_waves_off = run_waves(fresh_engine(None))
    t_waves_on = run_waves(fresh_engine("default"))

    throughput = growth_votes / t_growth_on
    return {
        "metric": "redelivery_amortized_ingest_throughput",
        "value": round(throughput, 1),
        "unit": "votes/sec",
        "vs_baseline": None,
        "detail": {
            "chain_len": L,
            "growth_votes_delivered": growth_votes,
            "speedup": round(t_growth_off / t_growth_on, 2),
            "growth_cached_votes_per_sec": round(growth_votes / t_growth_on, 1),
            "growth_uncached_votes_per_sec": round(
                growth_votes / t_growth_off, 1
            ),
            "watermark_speedup_vs_uncached_growth": round(
                t_growth_off / t_mark_on, 2
            ),
            "watermark_cached_votes_per_sec": round(
                growth_votes / t_mark_on, 1
            ),
            "watermark_uncached_votes_per_sec": round(
                growth_votes / t_mark_off, 1
            ),
            "waves": redelivery_waves,
            "waves_votes_redelivered": wave_votes,
            "waves_speedup": round(t_waves_off / t_waves_on, 2),
            "waves_cached_votes_per_sec": round(wave_votes / t_waves_on, 1),
            "waves_uncached_votes_per_sec": round(wave_votes / t_waves_off, 1),
        },
    }


def run_wal(
    p_count: int = 256,
    voters_per_proposal: int = 12,
    wave: int = 512,
    raw_records: int = 2_000,
) -> dict:
    """Durability subsystem overhead: WAL append throughput per fsync
    policy, engine vote-ingest bare vs DurableEngine-wrapped, and crash
    recovery replay rate. Host-only (filesystem + engine scalar surface);
    runs identically under JAX_PLATFORMS=cpu.

    The headline is the wrapped/bare ingest ratio at the "batch" policy —
    the number an embedder pays for durability on the hot path. "always"
    appends are fsync-bound and measured on a smaller count.
    """
    import os
    import tempfile

    from hashgraph_tpu import CreateProposalRequest, StubConsensusSigner, build_vote
    from hashgraph_tpu.engine import TpuConsensusEngine
    from hashgraph_tpu.wal import DurableEngine, WalWriter, replay
    from hashgraph_tpu.wal import format as WF

    def fresh_engine(identity: bytes) -> TpuConsensusEngine:
        return TpuConsensusEngine(
            StubConsensusSigner(identity),
            capacity=max(512, p_count * 2),
            voter_capacity=64,
        )

    now = 1_700_000_000
    identity = os.urandom(20)
    scope = "bench"

    # Workload: p_count proposals, each voted on by voters_per_proposal
    # distinct remote voters (pre-validated replay, same convention as the
    # BASELINE configs), delivered in waves through ingest_votes.
    requests = [
        CreateProposalRequest(
            name=f"b{i}",
            payload=b"x",
            proposal_owner=b"owner",
            expected_voters_count=voters_per_proposal + 1,
            expiration_timestamp=10_000,
            liveness_criteria_yes=True,
        )
        for i in range(p_count)
    ]
    signers = [StubConsensusSigner(os.urandom(20)) for _ in range(voters_per_proposal)]

    def build_workload(engine):
        proposals = engine.create_proposals(scope, requests, now)
        votes = [
            (scope, build_vote(p, True, s, now + 1))
            for p in proposals
            for s in signers
        ]
        return [votes[i : i + wave] for i in range(0, len(votes), wave)]

    def timed_ingest(engine):
        waves = build_workload(engine)
        total = sum(len(w) for w in waves)
        t0 = time.perf_counter()
        for batch in waves:
            engine.ingest_votes(batch, now + 2, pre_validated=True)
        return total, time.perf_counter() - t0

    # Warm the jit cache on a throwaway engine so neither timed side pays
    # first-call compilation (the workload shapes are identical).
    timed_ingest(fresh_engine(identity))

    detail = {}
    with tempfile.TemporaryDirectory() as root:
        # Raw append throughput per policy (vote-record-sized payloads).
        sample = build_vote(
            fresh_engine(identity).create_proposal(scope, requests[0], now),
            True,
            signers[0],
            now,
        ).encode()
        payload = WF.encode_votes(now, True, [(scope, sample)] * 4)
        for policy, count in (
            ("off", raw_records),
            ("batch", raw_records),
            ("always", max(64, raw_records // 20)),
        ):
            with WalWriter(
                os.path.join(root, f"raw-{policy}"), fsync_policy=policy
            ) as wal:
                t0 = time.perf_counter()
                for _ in range(count):
                    wal.append(WF.KIND_VOTES, payload)
                dt = time.perf_counter() - t0
            detail[f"append_{policy}_records_per_sec"] = round(count / dt)
            detail[f"append_{policy}_mb_per_sec"] = round(
                count * (len(payload) + WF.HEADER_BYTES + WF.BODY_LEAD_BYTES)
                / dt
                / 1e6,
                1,
            )

        # Engine ingest: bare vs wrapped (batch policy — the default).
        bare_votes, bare_dt = timed_ingest(fresh_engine(identity))
        wal_dir = os.path.join(root, "engine")
        durable = DurableEngine(
            fresh_engine(identity), wal_dir, fsync_policy="batch"
        )
        wrapped_votes, wrapped_dt = timed_ingest(durable)
        durable.close()
        bare_rate = bare_votes / bare_dt
        wrapped_rate = wrapped_votes / wrapped_dt
        detail["ingest_bare_votes_per_sec"] = round(bare_rate)
        detail["ingest_durable_votes_per_sec"] = round(wrapped_rate)

        # Recovery: replay the log just written into a fresh engine.
        recovered = fresh_engine(identity)
        t0 = time.perf_counter()
        stats = replay(wal_dir, recovered)
        dt = time.perf_counter() - t0
        detail["recover_records_per_sec"] = round(stats.records_applied / dt)
        detail["recover_votes_per_sec"] = round(stats.votes_replayed / dt)
        detail["recover_records"] = stats.records_applied

    return {
        "metric": "wal_durable_vs_bare_ingest",
        "value": round(wrapped_rate / bare_rate, 3),
        "unit": "ratio",
        "detail": detail,
    }


def run_catchup(
    history_votes: "tuple[int, ...]" = (256, 1024, 4096),
    v_count: int = 16,
    wave: int = 8,
    reps: int = 3,
    smoke: bool = False,
) -> dict:
    """State-sync catch-up: snapshot+tail vs full WAL replay, paired
    same-window A/B at several history lengths (ROADMAP 4 + 5b).

    A source peer on a real BridgeServer accumulates a signed vote
    history in gossip-sized waves (``wave`` votes per record — the
    realistic replay granularity: full replay re-verifies at that batch
    size, while the snapshot path verifies the whole history in ONE
    batched pool pass). Per history length, ``reps`` interleaved rep
    pairs each catch a FRESH joiner up twice over the wire:

    - **A (baseline)**: ``CatchUpClient.full_replay`` — stream the whole
      WAL, per-record validation (O(history) crypto);
    - **B (headline)**: ``CatchUpClient.catch_up`` — manifest + chunks
      (digest-checked), one batched chain/signature verify, atomic
      install, then tail the post-snapshot suffix. The source's snapshot
      is invalidated between reps (a sweep record moves the watermark)
      so every B rep pays the full snapshot build + transfer + verify,
      not a cached manifest.

    Every rep asserts byte-identical convergence
    (``sync.state_fingerprint`` equality of joiner vs source) before its
    time counts. The ``noise_verdict`` (at the largest history) refuses
    the claim unless the arms separate beyond the window's own spread,
    with a fixed host-crypto control timed between reps as the weather
    normalizer. Headline: catch-up seconds + verified votes/sec at the
    largest history.
    """
    import os
    import tempfile

    from hashgraph_tpu import build_vote
    from hashgraph_tpu import native
    from hashgraph_tpu.bridge.client import BridgeClient
    from hashgraph_tpu.bridge.server import BridgeServer
    from hashgraph_tpu.engine import TpuConsensusEngine
    from hashgraph_tpu.signing.ed25519 import Ed25519ConsensusSigner
    from hashgraph_tpu.sync import CatchUpClient, state_fingerprint
    from hashgraph_tpu.wire import Proposal

    if smoke:
        history_votes = (64,)
        reps = 2
    now = 1_700_000_000
    scheme = Ed25519ConsensusSigner

    # Host-crypto control: fixed batch-verify workload timed between A/B
    # reps — the shared-host weather normalizer (BENCHMARKS.md).
    ctl_signers = [scheme.random() for _ in range(8)]
    ctl_payloads = [b"ctl-%d" % i for i in range(1024)]
    ctl_sigs = [ctl_signers[i % 8].sign(p) for i, p in enumerate(ctl_payloads)]
    ctl_ids = [ctl_signers[i % 8].identity() for i in range(1024)]

    def control_rate() -> float:
        """Median of three back-to-back runs: one control point should
        track the window's crypto weather, not a single scheduler
        preemption (isolated runs show rare 2x dips on shared hosts)."""
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            verdicts = scheme.verify_batch(ctl_ids, ctl_payloads, ctl_sigs)
            assert all(v is True for v in verdicts)
            rates.append(1024 / (time.perf_counter() - t0))
        return round(sorted(rates)[1], 1)

    def fresh_joiner(capacity: int) -> TpuConsensusEngine:
        return TpuConsensusEngine(
            scheme.random(),
            capacity=capacity,
            voter_capacity=v_count + 2,
        )

    def build_history(client, peer, total: int) -> int:
        """Drive ``total`` chained signed votes (spread over
        total/v_count proposals) through the bridge; returns the
        proposal count."""
        p_count = max(1, total // v_count)
        signers = [scheme.random() for _ in range(v_count)]
        for p in range(p_count):
            # One scope per proposal: the history must be RETAINED (the
            # engine's per-scope session cap would otherwise evict early
            # sessions, shrinking the very state catch-up ships).
            scope = f"scope-{p}"
            pid, blob = client.create_proposal(
                peer, scope, now, f"p{p}", b"payload", v_count + 1, 3_600
            )
            proposal = Proposal.decode(blob)
            batch: list[bytes] = []
            for signer in signers:
                vote = build_vote(proposal, True, signer, now + 1)
                proposal.votes.append(vote)
                batch.append(vote.encode())
                if len(batch) == wave:
                    client.process_votes(peer, scope, batch, now + 1)
                    batch = []
            if batch:
                client.process_votes(peer, scope, batch, now + 1)
        return p_count

    lengths: list[dict] = []
    with tempfile.TemporaryDirectory() as root:
        for total in history_votes:
            server = BridgeServer(
                capacity=max(64, total // v_count + 8),
                voter_capacity=v_count + 2,
                wal_dir=os.path.join(root, f"wal-{total}"),
                wal_fsync="off",  # catch-up reads the log; fsync is not under test
                signer_factory=scheme,  # peers verify the Ed25519 votes
            )
            with server:
                host, port = server.address
                with BridgeClient(host, port) as client:
                    key = os.urandom(32)
                    peer, identity = client.add_peer(key)
                    p_count = build_history(client, peer, total)
                    source = server.durable_engine(identity)
                    src_fp = state_fingerprint(source)
                    capacity = max(64, p_count + 8)

                    # Untimed warmup pair: both arms' one-time costs (jit
                    # at these shapes, Ed25519 table builds, snapshot
                    # build) land here, not on the first timed rep.
                    with CatchUpClient(host, port, peer) as cu:
                        cu.full_replay(fresh_joiner(capacity))
                    with CatchUpClient(host, port, peer) as cu:
                        cu.catch_up(fresh_joiner(capacity))

                    a_seconds: list[float] = []
                    b_seconds: list[float] = []
                    b_votes_verified = 0
                    # Per-length control window: the verdict compares the
                    # weather DURING these reps, not across the whole
                    # sweep (earlier lengths' samples would inflate the
                    # spread without describing this window).
                    controls: list[float] = [control_rate()]
                    for _ in range(reps):
                        joiner = fresh_joiner(capacity)
                        with CatchUpClient(host, port, peer) as cu:
                            rep = cu.full_replay(joiner)
                        assert state_fingerprint(joiner) == src_fp, (
                            "full replay diverged"
                        )
                        a_seconds.append(rep.seconds)
                        controls.append(control_rate())

                        # Move the watermark so THIS rep's manifest is a
                        # fresh snapshot build, not a cached artifact.
                        source.sweep_timeouts(now + 2)
                        src_fp = state_fingerprint(source)
                        joiner = fresh_joiner(capacity)
                        with CatchUpClient(host, port, peer) as cu:
                            rep = cu.catch_up(joiner)
                        assert state_fingerprint(joiner) == src_fp, (
                            "snapshot+tail diverged"
                        )
                        b_seconds.append(rep.seconds)
                        b_votes_verified = rep.votes_verified + rep.tail_votes
                        controls.append(control_rate())

                    med_a = sorted(a_seconds)[len(a_seconds) // 2]
                    med_b = sorted(b_seconds)[len(b_seconds) // 2]
                    lengths.append({
                        "history_votes": total,
                        "proposals": p_count,
                        "wal_last_lsn": source.wal.last_lsn,
                        "replay_seconds": a_seconds,
                        "catchup_seconds": b_seconds,
                        "replay_votes_per_sec": round(total / med_a, 1),
                        "catchup_votes_per_sec": round(total / med_b, 1),
                        "votes_verified": b_votes_verified,
                        "speedup": round(med_a / med_b, 2),
                        "control_sigs_per_sec": controls,
                    })

    largest = lengths[-1]
    a_reps = largest["replay_seconds"]
    b_reps = largest["catchup_seconds"]
    controls = largest["control_sigs_per_sec"]
    med_a = sorted(a_reps)[len(a_reps) // 2]
    med_b = sorted(b_reps)[len(b_reps) // 2]
    speedup = round(med_a / med_b, 2)
    max_spread = max(spread_pct(a_reps), spread_pct(b_reps), spread_pct(controls))
    separated = max(b_reps) < min(a_reps)
    outside_noise = speedup > 1.0 + 2.0 * max_spread / 100.0
    noise_verdict = {
        "pass": bool(separated and outside_noise),
        "criterion": (
            "max(catchup reps) < min(replay reps) AND "
            "speedup > 1 + 2*max_spread (largest history)"
        ),
        "history_votes": largest["history_votes"],
        "catchup_seconds": med_b,
        "replay_seconds": med_a,
        "speedup": speedup,
        "catchup_reps": b_reps,
        "replay_reps": a_reps,
        "control_sigs_per_sec": controls,
        "spread_pct": {
            "catchup": spread_pct(b_reps),
            "replay": spread_pct(a_reps),
            "control": spread_pct(controls),
        },
    }
    return {
        "metric": "catchup_verified_votes_per_sec",
        "value": largest["catchup_votes_per_sec"],
        "unit": "votes/sec",
        "detail": {
            "scheme": "ed25519",
            "native_runtime": native.available(),
            "wave_votes_per_record": wave,
            "catchup_seconds_headline": med_b,
            "lengths": lengths,
            "noise_verdict": noise_verdict,
        },
    }


def run_chaos(smoke: bool = False, seeds: "list[int] | None" = None) -> dict:
    """Deterministic chaos harness: the full scenario corpus
    (hashgraph_tpu.sim) at pinned seeds, plus the blindness self-test.

    Every scenario must pass all four machine-checked verdicts —
    convergence (honest state-fingerprint equality), accountability
    (exactly the injected culprits convicted, offline-verifiable
    evidence, zero honest convictions), safety (no divergent honest
    decisions), liveness (decisions propagate everywhere within a fixed
    tick bound, zero honest peers left under a stale watchdog
    conviction) — and a run is a pure function of its seed, so a failure
    here is a deterministic regression, never a flake. ``--smoke`` is
    the CI shape (3 pinned seeds); the full mode adds two more. The
    ``scenarios: {passed, failed, seeds}`` block is the machine-readable
    summary downstream tooling keys on."""
    import time as _time

    from hashgraph_tpu.sim import SCENARIOS, run_corpus, run_scenario

    if seeds is None:
        seeds = [7, 99, 1234] if smoke else [7, 99, 1234, 31337, 777]
    t0 = _time.perf_counter()
    corpus = run_corpus(seeds)
    # The harness must be able to detect its own blindness: a run with
    # the evidence layer disabled HAS to fail accountability, or every
    # green corpus above is meaningless.
    blind = run_scenario("equivocator", seeds[0], blind=True)
    blind_ok = (
        not blind["passed"]
        and not blind["verdicts"]["accountability"]["ok"]
        and bool(blind["verdicts"]["accountability"]["missed_culprits"])
    )
    seconds = round(_time.perf_counter() - t0, 3)
    total = corpus["scenarios"]["passed"] + corpus["scenarios"]["failed"]
    # Gate hard, like every other smoke bench: a failed scenario or a
    # blindness self-test that passes (i.e. fails to fail) must exit the
    # runner non-zero or the CI job cannot hold the line. The assert
    # message names the (scenario, seed) pairs — each reproduces
    # byte-for-byte from its seed.
    assert not corpus["failures"], (
        "chaos scenarios FAILED (deterministic — rerun these seeds): "
        + ", ".join(
            f"{f['scenario']}@{f['seed']}" for f in corpus["failures"]
        )
    )
    assert blind_ok, (
        "blindness self-test failed: a run with the evidence layer "
        "disabled did NOT fail the accountability verdict — the harness "
        "cannot detect its own blindness"
    )
    return {
        "metric": "chaos_scenarios_passed",
        "value": corpus["scenarios"]["passed"],
        "unit": f"of {total} scenario-runs",
        "detail": {
            "scenarios": corpus["scenarios"],
            "corpus": sorted(SCENARIOS),
            "results": corpus["results"],
            "failures": corpus["failures"],
            "blind_selftest_detects_disabled_evidence": blind_ok,
            "seconds": seconds,
        },
    }


def run_liveness(smoke: bool = False, seeds: "list[int] | None" = None) -> dict:
    """Liveness battery: the three liveness scenarios at pinned seeds,
    each run TWICE per seed — the adaptive (φ-accrual) watchdog arm and
    a paired binary-floor-only baseline (``overrides={"phi_threshold":
    None}``, same seed, same traffic) — with the A/B claims hard-gated:

    - the adaptive arm SEES every flapping-links flap (``phi`` crosses
      the threshold on every survivor) while the static arm is blind to
      the identical silence (130 ticks, far under the 500 000-tick
      binary floor) — strictly more detections, same zero stale
      convictions after heal in BOTH arms;
    - slow-never-dead's counterfactual is the conviction half: a static
      bar tuned tight enough to catch that flap (the scenario computes
      ``phi_from_deviation`` for the equivalent deviation) WOULD convict
      the slow-but-alive peer (1 stale conviction) where the variance-
      aware φ keeps it healthy (0) — adaptive strictly fewer stale
      convictions under jitter;
    - stale-partial-synchrony closes the loop: when silence really does
      blow past every bound, BOTH detectors convict, and both clear
      after GST.

    Deterministic like run_chaos: every line reproduces byte-for-byte
    from its (scenario, seed) pair, so the asserts are regression gates,
    not weather reports."""
    import time as _time

    from hashgraph_tpu.sim import run_scenario

    if seeds is None:
        seeds = [7, 99, 1234] if smoke else [7, 99, 1234, 31337, 777]
    battery = ("flapping-links", "slow-never-dead", "stale-partial-synchrony")
    t0 = _time.perf_counter()
    results: dict = {}
    failures: list[str] = []
    adaptive_detections = 0
    static_detections = 0
    adaptive_stale = 0
    static_stale = 0
    counterfactual_static_convictions = 0
    for name in battery:
        for seed in seeds:
            run = run_scenario(name, seed)
            if not run["passed"]:
                failures.append(f"{name}@{seed}")
            entry = {
                "passed": run["passed"],
                "checks": run["checks"],
                "max_decide_ticks": run["verdicts"]["liveness"][
                    "max_decide_ticks"
                ],
                "stale_convictions": run["verdicts"]["liveness"][
                    "stale_convictions"
                ],
            }
            adaptive_stale += len(entry["stale_convictions"])
            if name == "flapping-links":
                # Paired baseline arm: identical seed + traffic, binary
                # silence floor only (phi_threshold=None). Its four
                # verdicts must STILL pass — the floor is a correct
                # detector, just a blind one at sub-floor silences.
                base = run_scenario(name, seed, overrides={"phi_threshold": None})
                # ``passed`` gates scenario CHECKS too, and the φ-
                # detection checks legitimately read False here — that
                # blindness IS the baseline. The bar for this arm is the
                # four verdicts.
                base_ok = all(v["ok"] for v in base["verdicts"].values())
                if not base_ok:
                    failures.append(f"{name}@{seed}(static-arm)")
                adaptive_detections += int(
                    run["checks"]["phi_suspected_during_flap"]
                )
                static_detections += int(
                    base["checks"]["phi_suspected_during_flap"]
                )
                static_stale += len(
                    base["verdicts"]["liveness"]["stale_convictions"]
                )
                entry["static_arm"] = {
                    "verdicts_ok": base_ok,
                    "checks": base["checks"],
                    "stale_convictions": base["verdicts"]["liveness"][
                        "stale_convictions"
                    ],
                }
            if name == "slow-never-dead":
                # The counterfactual static bar (tuned tight enough to
                # catch the flap) convicts the slow-but-alive peer; the
                # deployed φ detector does not.
                counterfactual_static_convictions += int(
                    run["checks"]["metronome_counterfactual_convicts"]
                )
            results[f"{name}@{seed}"] = entry
    seconds = round(_time.perf_counter() - t0, 3)
    assert not failures, (
        "liveness scenarios FAILED (deterministic — rerun these seeds): "
        + ", ".join(failures)
    )
    # A/B gates, all hard: adaptive sees every flap the static floor
    # misses, neither arm leaves a stale conviction after heal, and the
    # tight-static counterfactual convicts where φ does not.
    assert adaptive_detections == len(seeds) and static_detections == 0, (
        adaptive_detections,
        static_detections,
    )
    assert adaptive_stale == 0 and static_stale == 0, (
        adaptive_stale,
        static_stale,
    )
    assert counterfactual_static_convictions == len(seeds), (
        counterfactual_static_convictions
    )
    total = len(battery) * len(seeds)
    return {
        "metric": "liveness_scenarios_passed",
        "value": total - len(failures),
        "unit": f"of {total} scenario-runs",
        "detail": {
            "battery": list(battery),
            "seeds": seeds,
            "results": results,
            "ab": {
                "flap_detections": {
                    "adaptive": adaptive_detections,
                    "static_floor": static_detections,
                },
                "stale_convictions_after_heal": {
                    "adaptive": adaptive_stale,
                    "static_floor": static_stale,
                },
                "tight_static_counterfactual_convictions": (
                    counterfactual_static_convictions
                ),
                "adaptive_phi_convictions_same_traffic": 0,
            },
            "seconds": seconds,
        },
    }


def _rss_bytes() -> int:
    """Resident set size of this process (Linux /proc, no psutil dep)."""
    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("VmRSS not found in /proc/self/status")


def run_churn(
    smoke: bool = False,
    target_sessions: "int | None" = None,
    scopes: int = 16,
    per_scope: int = 256,
    v_count: int = 4,
) -> dict:
    """Tiered session-lifecycle churn: 10M+ CUMULATIVE sessions through a
    fixed-size engine under a HARD, asserted RSS + device-slot ceiling.

    Every wave creates ``scopes × per_scope`` fresh sessions
    (create_proposals_multi), decides them all with exactly the quorum's
    worth of columnar votes, advances the logical clock one tick, and
    runs the engine's ``sweep_timeouts`` — whose lifecycle hook demotes
    decided sessions to the serialized tier after ``demote_after`` ticks
    and garbage-collects them ``evict_decided_after`` ticks after their
    deciding activity. The working set (live sessions + tier population
    + RSS) is asserted bounded on EVERY wave, so the 10M headline is a
    held ceiling, not an observation.

    The throughput claim rides the repo's paired same-window A/B: the
    tiered lifecycle arm vs an untier'd arm running the identical
    create/vote traffic with the reference's only lifecycle
    (delete_scope after every wave), interleaved T/U within one window,
    with a machine-readable ``noise_verdict`` gating "steady-state
    ingest within 2x of the untier'd arm".
    """
    import jax

    from hashgraph_tpu import CreateProposalRequest, ScopeConfig, StubConsensusSigner
    from hashgraph_tpu.engine import TpuConsensusEngine
    from hashgraph_tpu.obs import slo_engine

    slo_engine.reset()

    now0 = 1_700_000_000
    wave_sessions = scopes * per_scope
    if target_sessions is None:
        target_sessions = 60_000 if smoke else 10_000_000
    demote_after, evict_after = 2.0, 4.0
    # Ceilings (hard asserts, not observations). Live: waves still inside
    # the demotion window plus the in-flight wave. Tier: waves between
    # demotion and GC. RSS: growth budget over the post-warmup baseline.
    live_ceiling = wave_sessions * (int(demote_after) + 2)
    tier_ceiling = wave_sessions * (int(evict_after - demote_after) + 2)
    capacity = live_ceiling  # the device-slot ceiling: pool cannot exceed it
    rss_budget = (512 if not smoke else 384) * 1024 * 1024
    scope_names = [f"s{i}" for i in range(scopes)]
    owners = [bytes([1 + i]) * 20 for i in range(v_count)]
    # Exactly the quorum's worth of YES votes per session (div_ceil(2n,3)
    # under the gossipsub default): every vote is an accept, every session
    # decides on its last vote — no ALREADY_REACHED extras in the timing.
    present = -(-2 * v_count // 3)
    requests = [
        CreateProposalRequest(
            name="p",
            payload=b"",
            proposal_owner=b"o",
            expected_voters_count=v_count,
            expiration_timestamp=now0 + 100_000_000,
            liveness_criteria_yes=True,
        )
        for _ in range(per_scope)
    ]

    def make_engine(tiered: bool) -> TpuConsensusEngine:
        engine = TpuConsensusEngine(
            StubConsensusSigner(b"\x01" * 20),
            capacity=capacity,
            voter_capacity=v_count,
            max_sessions_per_scope=live_ceiling + tier_ceiling,
        )
        config = ScopeConfig(
            demote_after=demote_after if tiered else None,
            evict_decided_after=evict_after if tiered else None,
        )
        for scope in scope_names:
            engine.set_scope_config(scope, config.clone())
        return engine

    def run_wave(engine, now: int, tiered: bool) -> int:
        """One churn wave; returns votes applied."""
        gids = np.array([engine.voter_gid(o) for o in owners], np.int64)
        batches = engine.create_proposals_multi(
            [(scope, requests) for scope in scope_names], now
        )
        all_pids = []
        scope_of = []
        for k, proposals in enumerate(batches):
            all_pids.extend(p.proposal_id for p in proposals)
            scope_of.extend([k] * len(proposals))
        pids = np.array(all_pids, np.int64)
        sidx = np.array(scope_of, np.int64)
        col_pids = np.repeat(pids, present)
        col_sidx = np.repeat(sidx, present)
        col_gids = np.tile(gids[:present], wave_sessions)
        col_vals = np.ones(wave_sessions * present, bool)
        statuses = engine.ingest_columnar_multi(
            scope_names, col_sidx, col_pids, col_gids, col_vals, now
        )
        # Correctness gate every wave: an unresolved session (20) or a
        # stale voter identity (10) is a lifecycle bug, not throughput.
        assert int(np.sum(statuses != 0)) == 0, (
            "churn wave rejected votes: "
            + str(np.unique(statuses[statuses != 0]))
        )
        if tiered:
            engine.sweep_timeouts(now)  # lifecycle hook: demote + GC
        else:
            engine.delete_scopes(scope_names)  # the reference's lifecycle
            config = ScopeConfig()
            for scope in scope_names:
                engine.set_scope_config(scope, config.clone())
        return len(statuses)

    # ── Paired same-window A/B (steady-state rate, small windows) ──────
    window_waves = 3 if smoke else 6
    reps = 3 if smoke else 5
    arm_t = make_engine(tiered=True)
    arm_u = make_engine(tiered=False)
    # Warmup both arms through the full lifecycle (compile + steady tier).
    warm = int(demote_after + evict_after) + 1
    now_t = now_u = now0
    for _ in range(warm):
        run_wave(arm_t, now_t, True)
        now_t += 1
        run_wave(arm_u, now_u, False)
        now_u += 1
    t_rates: list[float] = []
    u_rates: list[float] = []
    for _ in range(reps):
        t0 = time.perf_counter()
        votes = 0
        for _ in range(window_waves):
            votes += run_wave(arm_t, now_t, True)
            now_t += 1
        t_rates.append(votes / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        votes = 0
        for _ in range(window_waves):
            votes += run_wave(arm_u, now_u, False)
            now_u += 1
        u_rates.append(votes / (time.perf_counter() - t0))
    med_t = sorted(t_rates)[len(t_rates) // 2]
    med_u = sorted(u_rates)[len(u_rates) // 2]
    slowdown = med_u / med_t if med_t else float("inf")
    max_spread = max(spread_pct(t_rates), spread_pct(u_rates))
    within_2x = slowdown <= 2.0
    noise_verdict = {
        "pass": bool(within_2x),
        "criterion": (
            "median tiered-arm ingest rate within 2x of the untier'd "
            "paired arm, same window, interleaved reps"
        ),
        "tiered_votes_per_sec": round(med_t, 1),
        "untiered_votes_per_sec": round(med_u, 1),
        "slowdown_vs_untiered": round(slowdown, 3),
        "tiered_reps": [round(r, 1) for r in t_rates],
        "untiered_reps": [round(r, 1) for r in u_rates],
        "spread_pct": {
            "tiered": spread_pct(t_rates),
            "untiered": spread_pct(u_rates),
        },
        "max_spread_pct": max_spread,
    }
    assert within_2x, (
        f"tiered steady-state ingest {med_t:.0f}/s is more than 2x below "
        f"the untier'd arm {med_u:.0f}/s"
    )
    del arm_u

    # ── Headline: cumulative sessions under the asserted ceilings ──────
    engine = arm_t  # continue the warmed tiered engine
    cumulative = warm * wave_sessions + reps * window_waves * wave_sessions
    # The headline loop must actually run (and sample its ceilings) even
    # at smoke scale, on top of whatever the warmup + A/B consumed.
    target_sessions = max(target_sessions, cumulative + 20 * wave_sessions)
    votes_total = cumulative * present
    import gc as _gc

    _gc.collect()
    rss0 = _rss_bytes()
    rss_peak = 0
    occ_peak = {"live_sessions": 0, "tier_sessions": 0, "tier_bytes": 0}
    start = time.perf_counter()
    while cumulative < target_sessions:
        votes_total += run_wave(engine, now_t, True)
        now_t += 1
        cumulative += wave_sessions
        # EVERY ceiling asserts on EVERY wave — the headline is a held
        # bound, not an average that can hide a transient overshoot.
        rss = _rss_bytes()
        rss_peak = max(rss_peak, rss)
        assert rss - rss0 <= rss_budget, (
            f"RSS ceiling broken at {cumulative} cumulative sessions: "
            f"{(rss - rss0) / 1e6:.1f} MB over a "
            f"{rss_budget / 1e6:.0f} MB budget"
        )
        occ = engine.occupancy()
        for key in occ_peak:
            occ_peak[key] = max(occ_peak[key], occ[key])
        assert occ["device_slots_used"] <= capacity
        assert occ["live_sessions"] <= live_ceiling, occ
        assert occ["tier_sessions"] <= tier_ceiling, occ
    elapsed = time.perf_counter() - start
    occ = engine.occupancy()
    return {
        "metric": "churn_cumulative_sessions",
        "value": cumulative,
        "unit": "sessions",
        "detail": {
            "wave_sessions": wave_sessions,
            "scopes": scopes,
            "voters_per_session": v_count,
            "votes_per_session": present,
            "votes_total": votes_total,
            "headline_seconds": round(elapsed, 3),
            "sessions_per_sec": round(
                (cumulative - warm * wave_sessions
                 - reps * window_waves * wave_sessions) / elapsed, 1
            ),
            "ceilings": {
                "device_slots": capacity,
                "live_sessions": live_ceiling,
                "tier_sessions": tier_ceiling,
                "rss_budget_bytes": rss_budget,
                "asserted_every_wave": True,
            },
            "observed_peaks": {
                "rss_over_baseline_bytes": max(rss_peak - rss0, 0),
                **occ_peak,
            },
            "final_occupancy": occ,
            "policy": {
                "demote_after_ticks": demote_after,
                "evict_decided_after_ticks": evict_after,
            },
            "noise_verdict": noise_verdict,
            "slo": _slo_block(objective_ms=5_000.0),
            "platform": jax.devices()[0].platform,
        },
    }


def run_gossip(
    n_peers: int = 4,
    p_count: int = 8,
    v_count: int = 128,
    chunk: int = 16,
    reps: int = 3,
    smoke: bool = False,
    stages: bool = True,
    reactor_ab: bool = True,
    reactor_only: bool = False,
) -> dict:
    """Networked gossip fabric: aggregate votes/sec ACROSS A SOCKET.

    ``n_peers`` bridge servers each host one consensus peer over real TCP
    (loopback). A driver distributes proposals to every peer (untimed
    setup), then delivers every proposal's signed vote chain in
    gossip-sized ``chunk``-vote units, timed, in two paired arms per rep:

    - **A (baseline)**: the serial ``BridgeClient`` loop — today's
      embedder: one ``OP_PROCESS_VOTES`` frame per (peer, chunk), each
      blocking a full round trip + a per-frame engine dispatch;
    - **B (headline)**: the gossip fabric — the same chunks submitted to
      a :class:`~hashgraph_tpu.gossip.GossipNode` driver, coalesced into
      columnar ``OP_VOTE_BATCH`` frames (many chunks per frame), many
      frames in flight per connection (pipelining), landed via
      ``ingest_votes_pipelined`` on the receiving side.

    The workload is stub-signed: the transport and dispatch path is
    under test, not host crypto (the validated-sweep bench owns that
    wall; real schemes pay the same crypto in both arms and would only
    compress the ratio). Aggregate networked votes/sec counts every vote
    crossing a socket: ``p_count * v_count * n_peers / wall``.

    Every rep asserts ``sync.state_fingerprint`` EQUALITY across all
    peers for both arms before its time counts. The ``noise_verdict``
    refuses the claim unless the arms separate beyond the window's own
    spread (serial-ping control as the loopback/scheduler weather
    normalizer); ``target_5x`` reports the ISSUE acceptance bar.

    ``reactor_ab`` appends a SECOND paired A/B — reactor-off vs
    reactor-on fabric arms on dedicated peer sets with the apply
    reactor pinned per arm — reporting its own ``noise_verdict``,
    ``votes_per_dispatch`` per arm, and each arm's device-apply share
    of server busy time against the r06 66.8% attribution.
    ``reactor_only`` runs just that pair (``make bench-reactor``).

    ``smoke`` (CI): 3 IN-PROCESS peers, tiny shapes, one A/B pair, plus
    a sampled-fanout + one-anti-entropy-round convergence phase
    asserting fingerprint-identical state across peers. The full bench
    spawns each peer as its OWN PROCESS (``examples/gossip_peer.py``):
    in-process "peers" share one GIL, so an aggregate networked number
    measured there is really one interpreter's ceiling, not a fabric's.
    """
    import os
    import subprocess

    from hashgraph_tpu import build_vote
    from hashgraph_tpu.bridge.client import BridgeClient
    from hashgraph_tpu.bridge.server import BridgeServer
    from hashgraph_tpu.gossip import GossipNode
    from hashgraph_tpu.signing.stub import StubConsensusSigner
    from hashgraph_tpu.wire import Proposal

    if smoke:
        n_peers, p_count, v_count, reps = 3, 2, 16, 1
    now = 1_700_000_000
    total_votes = p_count * v_count
    networked = total_votes * n_peers
    # +1 warmup pair, +1 smoke convergence phase; one scope per proposal
    # so every session is retained for the fingerprint comparison.
    capacity = (2 * (reps + 1) + 2) * p_count + 8

    servers: list[BridgeServer] = []  # in-process (smoke) only
    procs: "list[subprocess.Popen]" = []  # one per peer (full bench)
    clients: list[BridgeClient] = []
    peer_ids: list[int] = []
    if smoke:
        for _ in range(n_peers):
            server = BridgeServer(
                capacity=capacity,
                voter_capacity=v_count + 2,
                signer_factory=StubConsensusSigner,
            )
            server.start()
            servers.append(server)
        addresses = [server.address for server in servers]
        import jax

        peer_platform = jax.devices()[0].platform  # in-process peers
    else:
        # Peers on CPU regardless of the driver's backend: four small
        # engines contending for one accelerator would measure device
        # queueing, and a chip belongs to one process at a time. The
        # detail records the platform the peers report, not this
        # parent's (which never initializes a backend here).
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        runner = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "examples", "gossip_peer.py",
        )
        addresses = []
        for _ in range(n_peers):
            proc = subprocess.Popen(
                [sys.executable, runner,
                 "--capacity", str(capacity),
                 "--voter-capacity", str(v_count + 2)],
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            procs.append(proc)
        for proc in procs:  # jax init per process; generous but parallel
            line = proc.stdout.readline().decode()
            assert line.startswith("PORT "), f"peer runner said: {line!r}"
            _, port_s, peer_platform = line.split()
            addresses.append(("127.0.0.1", int(port_s)))
    for address in addresses:
        client = BridgeClient(*address, timeout=60.0)
        pid, _identity = client.add_peer(os.urandom(32))
        clients.append(client)
        peer_ids.append(pid)

    def build_epoch(
        tag: str, cs=None, pids=None, expected_voters=None
    ) -> "list[tuple[str, int, list[bytes]]]":
        """Create + distribute p_count proposals (untimed), return
        (scope, proposal_id, chained signed votes as wire bytes).
        ``cs``/``pids`` target an alternate peer set (the reactor A/B
        arms); default is the main one. ``expected_voters`` above
        2*v_count keeps quorum unreachable: a decided session freezes
        its chain, so votes landing in frames AFTER the decide frame
        answer RECEIVED_HASH_MISMATCH — benign with the main arm's
        512-vote windows (every late row shares the decide frame and
        settles ALREADY_REACHED) but surfaced by gossip-frame-sized
        windows, which would make acked != networked without any vote
        actually dropping."""
        cs = clients if cs is None else cs
        pids = peer_ids if pids is None else pids
        out = []
        signers = [StubConsensusSigner(os.urandom(20)) for _ in range(v_count)]
        for p in range(p_count):
            scope = f"{tag}-{p}"
            pid, blob = cs[0].create_proposal(
                pids[0], scope, now, f"p{p}", b"payload",
                v_count + 1 if expected_voters is None else expected_voters,
                3_600,
            )
            for i in range(1, len(cs)):
                cs[i].process_proposal(pids[i], scope, blob, now)
            proposal = Proposal.decode(blob)
            votes: list[bytes] = []
            for signer in signers:
                vote = build_vote(proposal, True, signer, now + 1)
                proposal.votes.append(vote)  # chain each vote on the last
                votes.append(vote.encode())
            out.append((scope, pid, votes))
        return out

    def chunks(votes: "list[bytes]") -> "list[list[bytes]]":
        return [votes[i : i + chunk] for i in range(0, len(votes), chunk)]

    def assert_converged(tag: str) -> str:
        fps = {
            client.state_fingerprint(pid)
            for client, pid in zip(clients, peer_ids)
        }
        assert len(fps) == 1, f"{tag}: peers diverged ({len(fps)} states)"
        return next(iter(fps))

    def run_serial(epoch) -> float:
        t0 = time.perf_counter()
        for scope, _pid, votes in epoch:
            for part in chunks(votes):
                for client, pid in zip(clients, peer_ids):
                    client.process_votes(pid, scope, part, now + 1)
        wall = time.perf_counter() - t0
        assert_converged("serial")
        return wall

    fabric_node: "list[GossipNode]" = []  # lazily built, reused across reps

    def run_fabric(epoch) -> float:
        if not fabric_node:
            # Full bench: peers are co-located OS processes — attach the
            # shared-memory ring lane (FEATURE_SHM_RING; TCP fallback is
            # automatic when a peer can't map the rings). The smoke's
            # in-process peers keep TCP so CI covers both lanes.
            node = GossipNode(
                "bench-driver", fanout=None, flush_votes=512,
                shm_ring_bytes=None if smoke else 8 * 1024 * 1024,
            )
            for i, address in enumerate(addresses):
                node.add_peer(f"peer{i}", *address, peer_ids[i])
            fabric_node.append(node)
        node = fabric_node[0]
        t0 = time.perf_counter()
        for scope, pid, votes in epoch:
            for part in chunks(votes):
                node.submit_votes(scope, pid, part, now + 1, local=False)
        report = node.drain()
        wall = time.perf_counter() - t0
        assert report["acked"] == networked, (
            f"fabric dropped votes: {report}"
        )
        assert_converged("fabric")
        return wall

    # Control: serial ping round trips on peer 0 — the loopback +
    # scheduler weather normalizer (median of 3 runs of 200).
    def control_rate() -> float:
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(200):
                clients[0].ping()
            rates.append(200 / (time.perf_counter() - t0))
        return round(sorted(rates)[1], 1)

    # Stage attribution: the servers' wire-path counters (decode /
    # crypto / device-apply wall seconds + frames per path) scraped over
    # GET_METRICS, summed across peer processes. In-process smoke peers
    # share one registry, so scrape exactly one client there.
    _STAGE_FAMILIES = {
        "hashgraph_bridge_wire_decode_seconds_total": "wire_decode_s",
        "hashgraph_bridge_wire_crypto_seconds_total": "crypto_s",
        "hashgraph_bridge_wire_apply_seconds_total": "device_apply_s",
        "hashgraph_bridge_wire_columnar_frames_total": "columnar_frames",
        "hashgraph_bridge_wire_fallback_frames_total": "fallback_frames",
        "hashgraph_bridge_shm_rings_attached_total": "shm_rings",
        # Dispatch amortization (ISSUE 19): fused device calls and the
        # rows they carried — votes_per_dispatch = apply_rows /
        # device_dispatches is the measured amortization factor.
        "hashgraph_bridge_wire_device_dispatches_total": "device_dispatches",
        "hashgraph_bridge_wire_apply_rows_total": "apply_rows",
    }

    def scrape_stages(cs=None) -> "dict[str, float]":
        cs = clients if cs is None else cs
        out = {name: 0.0 for name in _STAGE_FAMILIES.values()}
        for client in cs[:1] if smoke else cs:
            for line in client.get_metrics().splitlines():
                if line.startswith("#") or " " not in line:
                    continue
                family, _, value = line.partition(" ")
                key = _STAGE_FAMILIES.get(family)
                if key is not None:
                    out[key] += float(value)
        return out

    def stage_delta(before: dict, after: dict) -> dict:
        return {
            key: round(after[key] - before[key], 4)
            for key in before
        }

    def spawn_peer_set(pin: str):
        """A dedicated peer set with the apply reactor PINNED on/off —
        the A/B arms must not inherit HASHGRAPH_TPU_APPLY_REACTOR from
        the environment (the main arms deliberately do, so the CI
        reactor smoke leg exercises the reactor on the headline path)."""
        r_servers: list = []
        r_procs: list = []
        r_clients: list = []
        r_pids: list = []
        r_capacity = (reps + 2) * p_count + 8
        if smoke:
            for _ in range(n_peers):
                server = BridgeServer(
                    capacity=r_capacity,
                    voter_capacity=v_count + 2,
                    signer_factory=StubConsensusSigner,
                    apply_reactor=(pin == "on"),
                )
                server.start()
                r_servers.append(server)
            addrs = [server.address for server in r_servers]
        else:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            runner = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "examples", "gossip_peer.py",
            )
            addrs = []
            for _ in range(n_peers):
                proc = subprocess.Popen(
                    [sys.executable, runner,
                     "--capacity", str(r_capacity),
                     "--voter-capacity", str(v_count + 2),
                     "--reactor", pin],
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE,
                    env=env,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                )
                r_procs.append(proc)
            for proc in r_procs:
                line = proc.stdout.readline().decode()
                assert line.startswith("PORT "), f"peer runner said: {line!r}"
                addrs.append(("127.0.0.1", int(line.split()[1])))
        for address in addrs:
            client = BridgeClient(*address, timeout=60.0)
            pid, _identity = client.add_peer(os.urandom(32))
            r_clients.append(client)
            r_pids.append(pid)
        return r_servers, r_procs, r_clients, r_pids, addrs

    def run_reactor_pair() -> dict:
        """Paired reactor-off/on A/B on DEDICATED pinned peer sets.

        Both arms run the identical fabric workload, but with
        gossip-frame-sized coalescer windows (``flush_votes=chunk``):
        many small pipelined OP_VOTE_BATCH frames per connection — the
        exact per-dispatch-amortization regime the reactor exists for.
        The off arm pays one device dispatch per frame; the on arm's
        per-engine windows merge in-flight frames into fused dispatches.
        Reps interleave off/on so scheduler weather hits both arms;
        per-arm metric scrapes around each timed run attribute stage
        seconds and ``votes_per_dispatch`` to the right arm even in
        smoke mode, where every in-process server shares one registry."""
        arms: dict = {}
        try:
            for pin in ("off", "on"):
                servers_, procs_, clients_, pids_, addrs_ = spawn_peer_set(pin)
                node = GossipNode(
                    f"reactor-{pin}-driver", fanout=None, flush_votes=chunk,
                )
                for i, address in enumerate(addrs_):
                    node.add_peer(f"peer{i}", *address, pids_[i])
                arms[pin] = {
                    "servers": servers_, "procs": procs_,
                    "clients": clients_, "pids": pids_, "node": node,
                }

            def run_arm(arm, tag: str) -> float:
                node, cs, pids = arm["node"], arm["clients"], arm["pids"]
                # Quorum unreachable (see build_epoch): every row must
                # ack, so the arms measure pure dispatch amortization
                # with an exact acked == networked accounting even at
                # chunk-sized flush windows.
                epoch = build_epoch(tag, cs, pids, expected_voters=2 * v_count + 2)
                t0 = time.perf_counter()
                for scope, pid, votes in epoch:
                    for part in chunks(votes):
                        node.submit_votes(
                            scope, pid, part, now + 1, local=False
                        )
                report = node.drain()
                wall = time.perf_counter() - t0
                assert report["acked"] == networked, (
                    f"reactor arm {tag} dropped votes: {report}"
                )
                fps = {
                    client.state_fingerprint(pid)
                    for client, pid in zip(cs, pids)
                }
                assert len(fps) == 1, f"reactor arm {tag}: peers diverged"
                return wall

            # Untimed warmup per arm: jit at these shapes.
            run_arm(arms["off"], "rw-off")
            run_arm(arms["on"], "rw-on")
            rates: dict = {"off": [], "on": []}
            stage_totals = {
                pin: {name: 0.0 for name in _STAGE_FAMILIES.values()}
                for pin in ("off", "on")
            }
            for rep in range(reps):
                for pin in ("off", "on"):
                    before = scrape_stages(arms[pin]["clients"])
                    rates[pin].append(
                        networked / run_arm(arms[pin], f"rr{rep}-{pin}")
                    )
                    delta = stage_delta(
                        before, scrape_stages(arms[pin]["clients"])
                    )
                    for key, value in delta.items():
                        stage_totals[pin][key] += value
        finally:
            for arm in arms.values():
                node = arm.get("node")
                if node is not None:
                    node.close()
                for client in arm.get("clients", ()):
                    client.close()
                for server in arm.get("servers", ()):
                    server.stop()
                for proc in arm.get("procs", ()):
                    try:
                        proc.stdin.close()
                        proc.wait(timeout=15)
                    except Exception:
                        proc.kill()

        def med(values):
            return sorted(values)[len(values) // 2]

        def vpd(totals) -> float:
            dispatches = totals.get("device_dispatches", 0.0)
            if not dispatches:
                return 0.0
            return round(totals.get("apply_rows", 0.0) / dispatches, 2)

        def apply_share(totals) -> float:
            busy = sum(
                totals[key]
                for key in ("wire_decode_s", "crypto_s", "device_apply_s")
            )
            return round(totals["device_apply_s"] / busy, 3) if busy else 0.0

        med_off, med_on = med(rates["off"]), med(rates["on"])
        speedup = round(med_on / med_off, 3) if med_off else 0.0
        max_spread = max(spread_pct(rates["off"]), spread_pct(rates["on"]))
        separated = min(rates["on"]) > max(rates["off"])
        outside_noise = speedup > 1.0 + 2.0 * max_spread / 100.0
        return {
            "noise_verdict": {
                "pass": bool(separated and outside_noise),
                "criterion": (
                    "min(reactor-on reps) > max(reactor-off reps) AND "
                    "speedup > 1 + 2*max_spread"
                ),
                "speedup": speedup,
                "reactor_on_votes_per_sec": round(med_on, 1),
                "reactor_off_votes_per_sec": round(med_off, 1),
                "on_reps": [round(r, 1) for r in rates["on"]],
                "off_reps": [round(r, 1) for r in rates["off"]],
                "spread_pct": {
                    "on": spread_pct(rates["on"]),
                    "off": spread_pct(rates["off"]),
                },
            },
            "votes_per_dispatch": {
                "off": vpd(stage_totals["off"]),
                "on": vpd(stage_totals["on"]),
            },
            "device_apply_share": {
                "off": apply_share(stage_totals["off"]),
                "on": apply_share(stage_totals["on"]),
                "r06_baseline": 0.668,
            },
            "stage_totals": {
                pin: {key: round(value, 4) for key, value in totals.items()}
                for pin, totals in stage_totals.items()
            },
            "coalescer_flush_votes": chunk,
        }

    reactor_block = None
    a_rates: list[float] = []
    b_rates: list[float] = []
    stage_reps: list[dict] = []
    controls: list[float] = []
    final_stages = None
    slo_frames: list = []
    profile_frames: list = []
    convergence = None
    try:
        if not reactor_only:
            # Untimed warmup pair: jit at these shapes, connection setup.
            run_serial(build_epoch("w-a"))
            run_fabric(build_epoch("w-b"))

            controls.append(control_rate())
            for rep in range(reps):
                a_rates.append(
                    networked / run_serial(build_epoch(f"r{rep}-a"))
                )
                controls.append(control_rate())
                before = scrape_stages() if stages else None
                b_rates.append(
                    networked / run_fabric(build_epoch(f"r{rep}-b"))
                )
                if stages:
                    stage_reps.append(stage_delta(before, scrape_stages()))
                controls.append(control_rate())
            final_stages = scrape_stages() if stages else None
            # One OP_METRICS_PULL frame per peer: each process's windowed
            # SLO state rides home with the bench (the peers decided the
            # sessions, so THEIR SloEngines hold the latency windows).
            slo_frames = [client.metrics_pull() for client in clients]
            # Continuous-profiling readout (round 20): when the
            # always-on sampler is armed (HASHGRAPH_TPU_PROFILE=1 — the
            # profile-smoke CI leg) pull one OP_PROFILE attribution
            # frame per peer. Old peers answer UNKNOWN_OPCODE and the
            # client returns None — filtered, not fatal.
            from hashgraph_tpu.obs.profiler import profiler_enabled

            if profiler_enabled():
                profile_frames = [
                    frame
                    for frame in (client.profile() for client in clients)
                    if frame is not None
                ]

        # Smoke convergence phase: sampled fanout misses peers on
        # purpose; ONE anti-entropy round (same logical now) repairs
        # them to fingerprint-identical state.
        if smoke and not reactor_only:
            node = GossipNode(
                "smoke-node",
                engine=servers[0].peer_engine(peer_ids[0]),
                fanout=1,
                seed=1234,
            )
            for i in range(1, n_peers):
                node.add_peer(f"peer{i}", *addresses[i], peer_ids[i])
            try:
                epoch = build_epoch("ae")
                for scope, pid, votes in epoch:
                    # local=False: peer 0 already holds these votes if
                    # sampled; it gets them via anti-entropy otherwise —
                    # no, peer 0 IS the node's engine: apply locally so
                    # it can serve the repair push.
                    node.submit_votes(scope, pid, votes, now + 1, local=True)
                node.drain()
                diverged = len({
                    client.state_fingerprint(pid)
                    for client, pid in zip(clients, peer_ids)
                }) > 1
                round_report = node.anti_entropy(now + 1)
                fingerprint = assert_converged("anti-entropy")
                convergence = {
                    "sampled_fanout": 1,
                    "diverged_before_round": diverged,
                    "anti_entropy": round_report,
                    "fingerprint": fingerprint,
                }
            finally:
                node.close()

        if reactor_ab or reactor_only:
            reactor_block = run_reactor_pair()
    finally:
        for node in fabric_node:
            node.close()
        for client in clients:
            client.close()
        for server in servers:
            server.stop()
        for proc in procs:
            try:
                proc.stdin.close()  # EOF = the runner's shutdown signal
                proc.wait(timeout=15)
            except Exception:
                proc.kill()

    if reactor_only:
        verdict = reactor_block["noise_verdict"]
        return {
            "metric": "gossip_reactor_votes_per_sec",
            "value": verdict["reactor_on_votes_per_sec"],
            "unit": "votes/sec",
            "detail": {
                "n_peers": n_peers,
                "peer_platform": peer_platform,
                "proposals": p_count,
                "votes_per_proposal": v_count,
                "chunk_votes": chunk,
                "votes_networked_per_rep": networked,
                "reactor_ab": reactor_block,
            },
        }

    med_a = sorted(a_rates)[len(a_rates) // 2]
    med_b = sorted(b_rates)[len(b_rates) // 2]
    speedup = round(med_b / med_a, 2) if med_a else 0.0
    max_spread = max(spread_pct(a_rates), spread_pct(b_rates),
                     spread_pct(controls))
    separated = min(b_rates) > max(a_rates)
    outside_noise = speedup > 1.0 + 2.0 * max_spread / 100.0
    noise_verdict = {
        "pass": bool(separated and outside_noise),
        "criterion": (
            "min(fabric reps) > max(serial reps) AND "
            "speedup > 1 + 2*max_spread"
        ),
        "speedup": speedup,
        "target_5x": bool(speedup >= 5.0),
        "fabric_votes_per_sec": round(med_b, 1),
        "serial_votes_per_sec": round(med_a, 1),
        "fabric_reps": [round(r, 1) for r in b_rates],
        "serial_reps": [round(r, 1) for r in a_rates],
        "control_pings_per_sec": controls,
        "spread_pct": {
            "fabric": spread_pct(b_rates),
            "serial": spread_pct(a_rates),
            "control": spread_pct(controls),
        },
    }
    from hashgraph_tpu.parallel.rollup import merge_slo_states

    merged_slo = merge_slo_states(slo_frames)
    slo_objective_ms = 5_000.0
    worst_p99_ms = round(merged_slo["global"]["worst_p99"] * 1e3, 3)
    detail = {
        "n_peers": n_peers,
        "peer_platform": peer_platform,
        "proposals": p_count,
        "votes_per_proposal": v_count,
        "chunk_votes": chunk,
        "votes_networked_per_rep": networked,
        "fingerprints_identical": True,  # asserted every rep, both arms
        "noise_verdict": noise_verdict,
        "slo": {
            "windowed_decisions": merged_slo["global"]["count"],
            "worst_peer_p99_ms": worst_p99_ms,
            "per_peer_latency_ms": {
                host: {
                    "p50": round(s["global"]["p50"] * 1e3, 3),
                    "p95": round(s["global"]["p95"] * 1e3, 3),
                    "p99": round(s["global"]["p99"] * 1e3, 3),
                }
                for host, s in merged_slo["hosts"].items()
            },
            "alerts_firing": merged_slo["alerts_firing"],
            "verdict": {
                "objective_ms": slo_objective_ms,
                "p99_ms": worst_p99_ms,
                "pass": bool(
                    not merged_slo["alerts_firing"]
                    and worst_p99_ms <= slo_objective_ms
                ),
            },
        },
    }
    if stages and stage_reps:
        # Per-rep wall seconds inside the fabric arm's server path (wire
        # decode / crypto / device apply) plus frames per path: the
        # residual gap to the in-process number is attributable stage by
        # stage, and a regression in any one stage is visible in the
        # BENCH json without re-profiling. shm_rings reports the
        # ABSOLUTE attach count (attachment happens once at warmup, so a
        # per-rep delta would always read 0).
        totals = {
            key: round(sum(rep[key] for rep in stage_reps), 4)
            for key in stage_reps[0]
        }
        totals["shm_rings"] = final_stages["shm_rings"]
        busy = sum(
            totals[key]
            for key in ("wire_decode_s", "crypto_s", "device_apply_s")
        )
        dispatches = totals.get("device_dispatches", 0.0)
        detail["stage_attribution"] = {
            "per_rep": stage_reps,
            "totals": totals,
            "stage_share": {
                key: round(totals[key] / busy, 3) if busy else 0.0
                for key in ("wire_decode_s", "crypto_s", "device_apply_s")
            },
            # Amortization factor: rows landed per fused device call.
            "votes_per_dispatch": (
                round(totals.get("apply_rows", 0.0) / dispatches, 2)
                if dispatches else 0.0
            ),
        }
    if profile_frames:
        # Fleet attribution via the ONE merge (rollup discipline), held
        # to its contract in-bench: only known stage names, and shares
        # that sum to a probability mass — a broken denominator fails
        # the profile-smoke CI leg here, not in a dashboard later.
        from hashgraph_tpu.obs.attribution import STAGE_KEYS
        from hashgraph_tpu.parallel.rollup import merge_profile_states

        merged_profile = merge_profile_states(profile_frames)
        shares = {
            key: stage["share"]
            for key, stage in merged_profile["stages"].items()
        }
        assert set(shares) == set(STAGE_KEYS), shares
        assert sum(shares.values()) <= 1.0 + 1e-6, shares
        samples = merged_profile["samples"]
        detail["profile"] = {
            "hosts": sorted(merged_profile["hosts"]),
            "stage_shares": shares,
            "busy_seconds": merged_profile["busy_seconds"],
            "votes_per_dispatch": (
                merged_profile["device"]["votes_per_dispatch"]
            ),
            "samples": samples["total"],
            "samples_dropped": samples["dropped"],
            "sample_roles": samples["roles"],
            "profiler_overhead_s": samples["overhead_seconds"],
        }
    if reactor_block is not None:
        detail["reactor_ab"] = reactor_block
    if smoke:
        detail["convergence"] = convergence
    return {
        "metric": "gossip_networked_votes_per_sec",
        "value": round(med_b, 1),
        "unit": "votes/sec",
        "detail": detail,
    }


def run_fleet(
    n_shards: int | None = None,
    scopes_per_shard: int = 2,
    p_count: int = 256,
    v_count: int = 64,
    reps: int = 3,
    smoke: bool = False,
) -> dict:
    """Scope-sharded fleet throughput: one engine per local device, scopes
    rendezvous-placed across them, a sustained mixed gossip+P2P columnar
    workload routed by :class:`hashgraph_tpu.parallel.ConsensusFleet`, and
    an AGGREGATE fleet votes/sec headline with a per-shard breakdown.

    Paired same-window A/B (the PR-6 methodology): the fleet arm (all
    shards) interleaves rep-for-rep with a single-shard arm (the same
    per-shard workload confined to one shard) inside one window, and the
    machine-readable ``noise_verdict`` refuses the scaling claim unless
    the arms separate beyond the window's own spread. ``scaling`` is
    aggregate-fleet / best-single-shard from the same window; on >= 4
    distinct-device shards, near-linear means >= 3x (ISSUE 7 acceptance).

    ``smoke`` shrinks to 2 shards x tiny shapes for the CI job: routing,
    the psum tally path, and the sweep are exercised; the verdict is
    reported but not asserted (2 CPU "devices" share one core).

    Emits a ``MULTICHIP_*``-compatible record (``multichip_record``) so
    the multichip artifact finally carries throughput, per-device slot
    occupancy, and sweep seconds instead of just ``ok``/``tail``.
    """
    import jax

    from hashgraph_tpu import (
        CreateProposalRequest,
        ScopeConfigBuilder,
        StubConsensusSigner,
    )
    from hashgraph_tpu.parallel import ConsensusFleet

    from hashgraph_tpu.obs import slo_engine

    slo_engine.reset()
    rng = np.random.default_rng(31)
    now = 1_700_000_000
    if smoke:
        scopes_per_shard, p_count, v_count, reps = 1, 32, 16, 1
        n_shards = 2 if n_shards is None else n_shards
    n_devices = len(jax.devices())
    if n_shards is None:
        n_shards = n_devices
    present = max(2, min(int(v_count * 0.7), (2 * v_count + 2) // 3 - 3))
    capacity_per_shard = scopes_per_shard * p_count

    fleet = ConsensusFleet(
        lambda k: StubConsensusSigner(bytes([k + 1]) * 20),
        n_shards=n_shards,
        capacity_per_shard=capacity_per_shard,
        voter_capacity=v_count,
        max_sessions_per_scope=p_count + 1,
    )
    distinct_devices = len({str(fleet.shard(s).device) for s in fleet.shard_ids})

    # Deterministically pick scopes_per_shard scope names per shard per
    # rep epoch (rendezvous placement decides ownership; we just probe
    # names until every shard's quota fills).
    def pick_scopes(epoch: int, shard_ids) -> "dict[str, list[str]]":
        got = {sid: [] for sid in shard_ids}
        i = 0
        while any(len(v) < scopes_per_shard for v in got.values()):
            scope = f"e{epoch}-s{i}"
            i += 1
            sid = fleet.owner_of(scope)
            if sid in got and len(got[sid]) < scopes_per_shard:
                got[sid].append(scope)
        return got

    owners = [
        bytes([1 + (i % 250), i // 250]) + b"\x00" * 18 for i in range(present)
    ]
    requests = [
        CreateProposalRequest(
            name="p",
            payload=b"",
            proposal_owner=b"o",
            expected_voters_count=v_count,
            expiration_timestamp=100,
            liveness_criteria_yes=bool(rng.integers(2)),
        )
        for _ in range(p_count)
    ]

    def run_arm(epoch: int, shard_ids, adaptive: bool = False) -> dict:
        """One rep of the sustained workload over ``shard_ids``' scopes:
        register, columnar-ingest via the fleet router (mixed gossip/P2P
        scopes, shuffled at proposal granularity), sweep, verify. Only
        the ingest window feeds votes/sec (create/sweep timed apart).
        ``adaptive=True`` declares consensus-timeout bounds on every
        scope so the per-scope timeout learner rides the hot path — the
        liveness A/B's treatment arm."""
        by_shard = pick_scopes(epoch, shard_ids)
        scopes = [s for group in by_shard.values() for s in group]
        scope_shard = {
            s: sid for sid, group in by_shard.items() for s in group
        }
        for i, scope in enumerate(scopes):
            builder = ScopeConfigBuilder()
            builder = (
                builder.p2p_preset() if i % 2 else builder.gossipsub_preset()
            )
            # A declared decide-latency objective on every bench scope:
            # the SLO plane tracks the run end to end (per-scope burn
            # windows, alert machinery live) and the BENCH json carries
            # a windowed-p99 verdict against it. Generous on purpose —
            # a CI box breaching 5s would be a real regression.
            builder = builder.with_decide_p99_ms(5_000.0)
            if adaptive:
                # Liveness A/B treatment arm: identical workload, but
                # every scope opts into adaptive consensus timeouts
                # (engine/adaptive.py) so each decision feeds the
                # learner. Advisory-only by design — the decide path is
                # byte-identical, which is exactly what the within-noise
                # gate below verifies.
                builder = builder.with_timeout_bounds(0.5, 30.0)
            fleet.set_scope_config(scope, builder.build())
        t0 = time.perf_counter()
        pids = {}
        for scope in scopes:
            pids[scope] = np.fromiter(
                (
                    p.proposal_id
                    for p in fleet.create_proposals(scope, requests, now)
                ),
                np.int64,
                p_count,
            )
        t_create = time.perf_counter()
        gids = {
            scope: np.array(
                [fleet.voter_gid(scope, o) for o in owners], np.int64
            )
            for scope in scopes
        }
        # Proposal-major rows, scope-shuffled at proposal granularity.
        all_pids = np.concatenate([pids[s] for s in scopes])
        all_sidx = np.repeat(np.arange(len(scopes), dtype=np.int64), p_count)
        order = rng.permutation(len(all_pids))
        col_pids = np.repeat(all_pids[order], present)
        col_sidx = np.repeat(all_sidx[order], present)
        col_gids = np.concatenate(
            [gids[scopes[k]] for k in all_sidx[order]]
        )
        col_vals = rng.random(len(col_pids)) < 0.5
        t1 = time.perf_counter()
        statuses = fleet.ingest_columnar_multi(
            scopes, col_sidx, col_pids, col_gids, col_vals, now
        )
        t2 = time.perf_counter()
        # Correctness gate every rep (run_engine_config5 discipline): a
        # resolution/identity regression fails the bench, not the timer.
        assert int(np.sum(statuses == 20)) == 0, "unresolved proposal ids"
        assert int(np.sum(statuses == 10)) == 0, "stale voter gids"
        applied = int(np.sum((statuses == 0) | (statuses == 28)))
        assert applied >= int(0.9 * len(statuses)), (applied, len(statuses))
        # Per-shard slice of the SAME concurrent window.
        per_shard_votes = {sid: 0 for sid in shard_ids}
        for k, scope in enumerate(scopes):
            per_shard_votes[scope_shard[scope]] += int(
                np.sum(all_sidx[order] == k)
            ) * present
        occupancy = fleet.occupancy()
        t3 = time.perf_counter()
        swept = fleet.sweep_timeouts(now + 200)
        counts = fleet.fleet_state_counts()  # ONE psum (device path)
        t4 = time.perf_counter()
        for scope in scopes:
            fleet.delete_scope(scope)
        wall = t2 - t1
        return {
            "votes": len(statuses),
            "votes_per_sec": round(len(statuses) / wall, 1),
            "ingest_seconds": round(wall, 3),
            "create_seconds": round(t_create - t0, 3),
            "sweep_seconds": round(t4 - t3, 3),
            "swept": len(swept),
            "per_shard_votes_per_sec": {
                sid: round(v / wall, 1) for sid, v in per_shard_votes.items()
            },
            "state_counts": {str(k): v for k, v in counts.items()},
            "occupancy": occupancy,
        }

    all_shards = fleet.shard_ids
    single = all_shards[:1]
    # The single-shard arm repeats its scope-set workload ``single_waves``
    # times per rep so both arms' timing windows are comparable in wall
    # length (a 20 ms window is timer-jitter-bound; the fleet arm's window
    # is naturally ~n_shards longer).
    single_waves = max(1, min(n_shards, 4))

    def run_single_rep(epoch_base: int) -> dict:
        waves = [
            run_arm(epoch_base + w, single) for w in range(single_waves)
        ]
        votes = sum(w["votes"] for w in waves)
        seconds = sum(w["ingest_seconds"] for w in waves)
        out = dict(waves[0])
        out.update(
            votes=votes,
            ingest_seconds=round(seconds, 3),
            votes_per_sec=round(votes / seconds, 1),
        )
        return out

    # Warmup epoch (uncounted): compiles every shard's kernels at the
    # production shapes for BOTH arms.
    run_arm(0, all_shards)
    run_arm(1, single)

    fleet_reps: list[dict] = []
    single_reps: list[dict] = []
    epoch = 2
    for _ in range(reps):
        single_reps.append(run_single_rep(epoch))
        epoch += single_waves
        fleet_reps.append(run_arm(epoch, all_shards))
        epoch += 1

    fleet_rates = [r["votes_per_sec"] for r in fleet_reps]
    single_rates = [r["votes_per_sec"] for r in single_reps]
    headline_rep = sorted(fleet_reps, key=lambda r: r["votes_per_sec"])[
        len(fleet_reps) // 2
    ]
    headline = headline_rep["votes_per_sec"]
    best_single = max(single_rates)
    scaling = round(headline / best_single, 2) if best_single else None
    max_spread = max(spread_pct(fleet_rates), spread_pct(single_rates))
    separated = min(fleet_rates) > max(single_rates)
    outside_noise = (
        scaling is not None and scaling > 1.0 + 2.0 * max_spread / 100.0
    )
    # The scaling CLAIM is only made on real parallel hardware: >= 4
    # shards on >= 4 distinct non-CPU devices. Virtual CPU "devices"
    # share the host's cores, so a single shard already saturates the
    # substrate and aggregate/single is physically capped near 1x there —
    # the bench still runs the A/B and reports the ratio, it just doesn't
    # pretend shared cores are a scaling testbed.
    shared_substrate = jax.devices()[0].platform == "cpu"
    scaling_target = (
        3.0
        if (n_shards >= 4 and distinct_devices >= 4 and not shared_substrate)
        else None
    )
    if scaling_target is not None:
        # Real parallel hardware: the headline is trustworthy when the
        # arms separate beyond the window's own weather (PR-6 criterion).
        verdict_pass = bool(separated and outside_noise)
        criterion = (
            "min(fleet reps) > max(single-shard reps) AND "
            "scaling > 1 + 2*max_spread"
        )
    else:
        # No parallel-scaling claim to defend (shared CPU substrate, or
        # too few shards/devices for the near-linear bar); the verdict
        # gates the aggregate number's own reproducibility against
        # BENCHMARKS.md's documented weather band.
        reason = (
            "shared substrate"
            if shared_substrate
            else "fewer than 4 shards on distinct devices"
        )
        verdict_pass = spread_pct(fleet_rates) < 33.3
        criterion = f"no scaling claim ({reason}): fleet rep spread < 33%"
    noise_verdict = {
        "pass": verdict_pass,
        "criterion": criterion,
        "aggregate_votes_per_sec": headline,
        "best_single_shard_votes_per_sec": best_single,
        "scaling": scaling,
        "scaling_target": scaling_target,
        "scaling_pass": (
            None if scaling_target is None else bool(scaling >= scaling_target)
        ),
        "shared_substrate": shared_substrate,
        "fleet_reps": fleet_rates,
        "single_shard_reps": single_rates,
        "spread_pct": {
            "fleet": spread_pct(fleet_rates),
            "single": spread_pct(single_rates),
        },
    }
    per_device_occupancy = [
        occ
        for sid in all_shards
        for occ in headline_rep["occupancy"][sid]["per_device_slots_used"]
    ]
    multichip_record = {
        "n_devices": n_devices,
        "n_shards": n_shards,
        "ok": True,
        "votes_per_sec": headline,
        "per_device_slot_occupancy": per_device_occupancy,
        "sweep_seconds": headline_rep["sweep_seconds"],
        "votes": headline_rep["votes"],
        "tally_path": "psum" if fleet._tally() is not None else "host-sum",
    }
    # ── Liveness block: adaptive-timeout learner ON vs OFF, paired ────
    # Interleaved same-window arms over the identical workload; the
    # treatment arm declares [0.5s, 30s] bounds on every scope. The
    # learner is ADVISORY (Engine.adaptive_timeout(); timers stay
    # embedder-owned, reference src/lib.rs:15-34), so the machine check
    # is two-sided: enabling it on a healthy network costs nothing the
    # window's own weather can't explain (ingest within noise of
    # static), and it actually LEARNED (book updates land only in
    # adaptive arms, every learned value inside the declared bounds).
    # The conviction half of the liveness story — adaptive strictly
    # fewer stale convictions under flapping links — is seed-
    # deterministic and gated by `python bench.py liveness`, not by
    # wall-clock arms.
    def _book_updates() -> int:
        total = 0
        for sid in all_shards:
            snap = fleet.shard(sid).engine.adaptive_timeout_snapshot()
            total += snap["decays_total"] + snap["backoffs_total"]
        return total

    ab_pairs = 1 if smoke else 2
    static_ab: list[float] = []
    adaptive_ab: list[float] = []
    static_updates = adaptive_updates = 0
    last_updates = _book_updates()
    for _ in range(ab_pairs):
        static_ab.append(run_arm(epoch, all_shards)["votes_per_sec"])
        epoch += 1
        cur = _book_updates()
        static_updates += cur - last_updates
        last_updates = cur
        adaptive_ab.append(
            run_arm(epoch, all_shards, adaptive=True)["votes_per_sec"]
        )
        epoch += 1
        cur = _book_updates()
        adaptive_updates += cur - last_updates
        last_updates = cur
    learned_values = [
        v
        for sid in all_shards
        for v in fleet.shard(sid)
        .engine.adaptive_timeout_snapshot()["scopes"]
        .values()
    ]
    bounds_held = all(0.5 <= v <= 30.0 for v in learned_values)
    med_static = sorted(static_ab)[len(static_ab) // 2]
    med_adaptive = sorted(adaptive_ab)[len(adaptive_ab) // 2]
    ab_spread = max(spread_pct(static_ab), spread_pct(adaptive_ab))
    ratio = round(med_adaptive / med_static, 4) if med_static else None
    within_noise = ratio is not None and abs(ratio - 1.0) <= max(
        0.10, 2.0 * ab_spread / 100.0
    )
    slo = _slo_block(objective_ms=5_000.0)
    liveness_block = {
        "pass": bool(
            within_noise
            and adaptive_updates > 0
            and static_updates == 0
            and bounds_held
        ),
        "criterion": (
            "adaptive-timeout arm within max(10%, 2*max_spread) of static "
            "AND learner updates land only in adaptive arms AND every "
            "learned timeout inside declared [0.5s, 30s] bounds"
        ),
        "decide_p99_ms": slo["windowed_latency_ms"]["p99"],
        "adaptive_vs_static_ratio": ratio,
        "within_noise": bool(within_noise),
        "static_reps": static_ab,
        "adaptive_reps": adaptive_ab,
        "spread_pct": {
            "static": spread_pct(static_ab),
            "adaptive": spread_pct(adaptive_ab),
        },
        "learner": {
            "adaptive_arm_updates": adaptive_updates,
            "static_arm_updates": static_updates,
            "learned_timeouts_sampled": len(learned_values),
            "bounds_held": bool(bounds_held),
        },
        "stale_conviction_ab": (
            "seed-deterministic; gated by `python bench.py liveness` "
            "(flapping-links adaptive-vs-static arms)"
        ),
    }
    fleet.close()
    return {
        "metric": "fleet_aggregate_ingest_throughput",
        "value": headline,
        "unit": "votes/sec",
        "vs_baseline": round(headline / 1_000_000, 4),
        "detail": {
            "n_shards": n_shards,
            "n_devices": n_devices,
            "distinct_devices": distinct_devices,
            "scopes_per_shard": scopes_per_shard,
            "proposals_per_scope": p_count,
            "voters": v_count,
            "present": present,
            "smoke": smoke,
            "per_shard": headline_rep["per_shard_votes_per_sec"],
            "sweep_seconds": headline_rep["sweep_seconds"],
            "swept": headline_rep["swept"],
            "state_counts": headline_rep["state_counts"],
            "noise_verdict": noise_verdict,
            "multichip_record": multichip_record,
            "slo": slo,
            "liveness": liveness_block,
            "platform": jax.devices()[0].platform,
        },
    }


def run_federation(
    n_hosts: int = 2,
    shards_per_host: int = 2,
    p_count: int = 48,
    v_count: int = 64,
    chunk: int = 32,
    reps: int = 3,
    smoke: bool = False,
) -> dict:
    """Federated multi-host fleet: aggregate votes/sec across N OS
    processes, plus a LIVE SHARD MIGRATION under sustained traffic.

    ``n_hosts`` federation hosts (``examples/federation_host.py`` — each
    a full FleetGroup: scope-sharded ConsensusFleet behind a bridge
    server) run as separate processes over real TCP. A
    :class:`~hashgraph_tpu.parallel.federation.FederationDriver` routes
    every scope's signed vote chain to its two-level-rendezvous owner
    (host, then shard) as coalesced pipelined ``OP_VOTE_BATCH`` frames —
    each vote crosses the wire ONCE, to the host that owns it.

    Paired same-window A/B: the federated arm (scopes spread over all
    hosts) interleaves rep-for-rep with a single-host arm (the same
    workload confined to host 0), and the machine-readable
    ``noise_verdict`` applies the fleet bench's criterion — a scaling
    claim only on real parallel hardware; on a shared-substrate CPU box
    the verdict gates the aggregate number's reproducibility instead.

    The **migration rep** then re-homes one of host 0's shards onto
    host 1 while the driver keeps submitting: freeze (in-flight frames
    for the shard come back ``STATUS_SHARD_MIGRATING`` and re-route;
    new submits buffer into the shard's tail), snapshot at the frozen
    WAL watermark + tail catch-up on the adopter, SOURCE == DESTINATION
    ``state_fingerprint`` asserted, atomic placement flip on every
    participant, tail replay, retire. Asserts ZERO lost votes
    (``acked == submitted``, nothing buffered or rejected) and ZERO
    lost decisions (every session decided True on its current owner),
    and reports the throughput dip + recovery as per-window rates.

    ``smoke`` (CI): 2 hosts x tiny shapes, one A/B pair, one migration.
    """
    import os
    import subprocess
    import threading as _threading

    from hashgraph_tpu import build_vote
    from hashgraph_tpu.bridge.client import BridgeClient
    from hashgraph_tpu.parallel.federation import (
        FederationDriver,
        FederationPlacement,
    )
    from hashgraph_tpu.signing.stub import StubConsensusSigner
    from hashgraph_tpu.wire import Proposal

    if smoke:
        p_count, v_count, chunk, reps = 8, 12, 8, 1
    now = 1_700_000_000
    total_votes = p_count * v_count
    host_ids = [f"h{i}" for i in range(n_hosts)]
    placement = FederationPlacement.uniform(host_ids, shards_per_host)
    single = FederationPlacement(
        {"h0": [f"h0:{k}" for k in range(shards_per_host)]}
    )

    import shutil
    import tempfile

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.abspath(__file__))
    runner = os.path.join(repo, "examples", "federation_host.py")
    # Each host process gets its own incident directory: the induced
    # breach below must produce an exemplar-linked Perfetto dump ON THE
    # OWNING HOST, and the parent asserts on it from the outside.
    incident_root = tempfile.mkdtemp(prefix="bench-federation-incidents-")
    # Containers declared before the try so the finally can clean up
    # whatever a PARTIAL startup managed to spawn (a runner dying before
    # READY must not leak its siblings' processes or WAL flocks).
    procs: "dict[str, subprocess.Popen]" = {}
    clients: "dict[str, BridgeClient]" = {}
    ports: "dict[str, int]" = {}
    peer_ids: "dict[str, int]" = {}
    drivers: list = []

    def command(host_id: str, line: str) -> str:
        proc = procs[host_id]
        proc.stdin.write((line + "\n").encode())
        proc.stdin.flush()
        resp = proc.stdout.readline().decode().strip()
        if not resp or resp.startswith("ERROR"):
            raise RuntimeError(f"{host_id}: {line!r} -> {resp!r}")
        return resp

    def build_epoch(tag: str, plc) -> list:
        """Create + pin p_count proposals on their owning hosts
        (untimed); return (scope, pid, owner_host, chained vote bytes)."""
        out = []
        signers = [StubConsensusSigner(os.urandom(20)) for _ in range(v_count)]
        for p in range(p_count):
            scope = f"{tag}-p{p}"
            host, shard = plc.owner(scope)
            pid, blob = clients[host].create_proposal(
                peer_ids[host], scope, now, f"p{p}", b"payload",
                v_count, 3_600,
            )
            plc.pin(scope, shard)
            proposal = Proposal.decode(blob)
            votes: list[bytes] = []
            for signer in signers:
                vote = build_vote(proposal, True, signer, now + 1)
                proposal.votes.append(vote)
                votes.append(vote.encode())
            out.append((scope, pid, host, votes))
        return out

    def chunks(votes: "list[bytes]") -> "list[list[bytes]]":
        return [votes[i : i + chunk] for i in range(0, len(votes), chunk)]

    def run_arm(driver, epoch) -> float:
        t0 = time.perf_counter()
        for scope, _pid, _host, votes in epoch:
            for part in chunks(votes):
                driver.submit(scope, part, now + 1)
            driver.pump()
        report = driver.drain()
        wall = time.perf_counter() - t0
        assert report["rejected"] == 0 and report["buffered"] == 0, report
        assert report["acked"] == total_votes, report
        return wall

    def control_rate() -> float:
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(200):
                clients["h0"].ping()
            rates.append(200 / (time.perf_counter() - t0))
        return round(sorted(rates)[1], 1)

    migration: "dict | None" = None
    slo_detail: "dict | None" = None
    try:
        for host_id in host_ids:
            procs[host_id] = subprocess.Popen(
                [sys.executable, runner,
                 "--host-id", host_id,
                 "--hosts", ",".join(host_ids),
                 "--shards-per-host", str(shards_per_host),
                 "--capacity", "512",
                 "--voter-capacity", str(v_count + 2)],
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                env=dict(
                    env,
                    HASHGRAPH_INCIDENT_DIR=os.path.join(
                        incident_root, host_id
                    ),
                ),
                cwd=repo,
            )
        for host_id, proc in procs.items():
            line = proc.stdout.readline().decode()
            assert line.startswith("READY "), f"host runner said: {line!r}"
            _, port_s, peer_s, peer_platform = line.split()
            ports[host_id] = int(port_s)
            peer_ids[host_id] = int(peer_s)
            clients[host_id] = BridgeClient(
                "127.0.0.1", int(port_s), timeout=60.0
            )

        driver_fed = FederationDriver(placement)
        drivers.append(driver_fed)
        driver_single = FederationDriver(single)
        drivers.append(driver_single)
        for host_id in host_ids:
            driver_fed.connect(
                host_id, "127.0.0.1", ports[host_id], peer_ids[host_id]
            )
        driver_single.connect(
            "h0", "127.0.0.1", ports["h0"], peer_ids["h0"]
        )

        # Untimed warmup pair: jit at these shapes on every host.
        run_arm(driver_fed, build_epoch("w-fed", placement))
        run_arm(driver_single, build_epoch("w-one", single))

        fed_rates: list[float] = []
        single_rates: list[float] = []
        controls: list[float] = [control_rate()]
        per_host = {h: 0 for h in host_ids}
        for rep in range(reps):
            single_rates.append(
                total_votes / run_arm(
                    driver_single, build_epoch(f"r{rep}-one", single)
                )
            )
            controls.append(control_rate())
            epoch_fed = build_epoch(f"r{rep}-fed", placement)
            if rep == 0:
                # Attribution captured AT BUILD TIME: the later
                # migration rep re-homes a shard and would otherwise
                # rewrite rep 0's ownership history.
                for _scope, _pid, owner_host, owner_votes in epoch_fed:
                    per_host[owner_host] += len(owner_votes)
            fed_rates.append(total_votes / run_arm(driver_fed, epoch_fed))
            controls.append(control_rate())

        # ── The live-migration rep (under sustained traffic) ───────────
        epoch = build_epoch("mig", placement)
        h0_scopes = [e for e in epoch if e[2] == "h0"]
        assert h0_scopes, "no scope landed on h0 (placement bug)"
        shard = placement.pinned(h0_scopes[0][0])
        dst_host = host_ids[1]
        # Proposal-major interleave so every shard sees traffic across
        # the whole window.
        stream = [
            (scope, part)
            for parts in zip(*(
                [(scope, part) for part in chunks(votes)]
                for scope, _pid, _host, votes in epoch
            ))
            for scope, part in parts
        ]
        trigger = max(1, int(len(stream) * 0.4))
        mig_out: dict = {}
        mig_err: list = []

        def do_migration() -> None:
            try:
                t0 = time.perf_counter()
                driver_fed.begin_shard_migration(shard, retry_after=0.25)
                resp = command("h0", f"EXPORT {shard} 0.25")
                _, export_peer, src_fp = resp.split()
                resp = command(
                    dst_host,
                    f"ADOPT {shard} 127.0.0.1 {ports['h0']} {export_peer}",
                )
                _, sessions_s, dst_fp = resp.split()
                assert src_fp == dst_fp, (
                    f"migration fingerprint mismatch: {src_fp[:16]} != "
                    f"{dst_fp[:16]}"
                )
                for host_id in host_ids:
                    command(host_id, f"FLIP {shard} {dst_host}")
                flip = driver_fed.complete_shard_migration(shard, dst_host)
                command("h0", f"RETIRE {shard} {export_peer}")
                mig_out.update(
                    shard=shard,
                    to=dst_host,
                    sessions_moved=int(sessions_s),
                    fingerprint_equal=True,
                    fingerprint=src_fp,
                    tail_votes_replayed=flip["tail_votes"],
                    seconds=round(time.perf_counter() - t0, 3),
                )
            except BaseException as exc:  # surfaced after the join
                mig_err.append(exc)

        # Pace the stream so the submission window is LONGER than the
        # migration: the dip (the frozen shard's votes buffering instead
        # of flowing) and the recovery (tail replay + resumed routing)
        # are then visible as per-window rates instead of one spike.
        target_window = 2.5 if smoke else 4.0
        pace = target_window / len(stream)
        marks: list[tuple[float, int]] = []  # (t, votes flowing)
        mig_thread = None
        t0 = time.perf_counter()
        mig_t = [None, None]
        for k, (scope, part) in enumerate(stream):
            if k == trigger:
                mig_t[0] = time.perf_counter() - t0
                mig_thread = _threading.Thread(
                    target=do_migration, name="migration"
                )
                mig_thread.start()
            outcome = driver_fed.submit(scope, part, now + 1)
            driver_fed.pump()
            if outcome == "sent":
                marks.append((time.perf_counter() - t0, len(part)))
            deadline = t0 + pace * (k + 1)
            while time.perf_counter() < deadline:
                driver_fed.pump()
                time.sleep(0.002)
        assert mig_thread is not None
        mig_thread.join(timeout=120)
        assert not mig_thread.is_alive(), "migration thread hung"
        if mig_err:
            raise mig_err[0]
        mig_t[1] = mig_t[0] + mig_out["seconds"]
        # The drained tail replayed at the flip: its votes re-enter the
        # flow there — the recovery half of the dip.
        marks.append(
            (mig_t[1], mig_out["tail_votes_replayed"])
        )
        report = driver_fed.drain()
        wall = time.perf_counter() - t0
        # ZERO LOST VOTES: everything submitted (incl. the frozen-window
        # tail, replayed after the flip) was acked by an owner.
        assert report["buffered"] == 0 and report["rejected"] == 0, report
        assert report["acked"] == total_votes, report
        # ZERO LOST DECISIONS: every session decided on its CURRENT
        # owner (migrated scopes now answer from the adopting host).
        for scope, pid, _host, _votes in epoch:
            owner_host, _shard = placement.owner(scope)
            result = clients[owner_host].get_result(
                peer_ids[owner_host], scope, pid
            )
            assert result is True, (scope, pid, owner_host, result)
        # Dip + recovery: votes/s in ~10 equal windows across the rep.
        n_windows = 10
        window_rates = []
        for w in range(n_windows):
            lo, hi = wall * w / n_windows, wall * (w + 1) / n_windows
            votes_in = sum(v for t, v in marks if lo <= t < hi)
            window_rates.append(round(votes_in / (wall / n_windows), 1))
        migration = dict(mig_out)
        migration.update(
            rep_votes_per_sec=round(total_votes / wall, 1),
            window_votes_per_sec=window_rates,
            migration_window=[round(mig_t[0], 3), round(mig_t[1], 3)],
            decisions_verified=len(epoch),
            zero_lost_votes=True,
            zero_lost_decisions=True,
        )

        # ── SLO plane: merged federated scrape + induced breach ────────
        # Healthy picture first: every decision so far was best-effort
        # (no declared objective), so the per-host windowed quantiles
        # describe the bench traffic and nothing alerts.
        healthy_slo = driver_fed.merged_slo()
        per_host_p99_ms = {
            h: round(
                (healthy_slo["hosts"].get(h, {}).get("global") or {})
                .get("p99", 0.0) * 1_000.0, 3,
            )
            for h in host_ids
        }
        worst_p99_ms = max(per_host_p99_ms.values())
        slo_objective_ms = 5_000.0
        healthy_verdict = {
            "objective_ms": slo_objective_ms,
            "worst_host_p99_ms": worst_p99_ms,
            "pass": bool(
                not healthy_slo["alerts_firing"]
                and worst_p99_ms <= slo_objective_ms
            ),
        }

        # Induced breach: declare an impossible objective (1us) on a few
        # fresh scopes, each ON ITS OWNING HOST, then decide them — every
        # decide breaches, the multi-window burn rate saturates, and the
        # owning host's SLO engine fires + dumps an incident linking the
        # breaching decision's trace id.
        breach_scopes = [f"slo-probe-{i}" for i in range(3)]
        probe_signers = [
            StubConsensusSigner(os.urandom(20)) for _ in range(v_count)
        ]
        for probe in breach_scopes:
            owner_host, owner_shard = placement.owner(probe)
            command(owner_host, f"SLOCFG {probe} 0.001")
            pid, blob = clients[owner_host].create_proposal(
                peer_ids[owner_host], probe, now, "slo", b"payload",
                v_count, 3_600,
            )
            placement.pin(probe, owner_shard)
            proposal = Proposal.decode(blob)
            probe_votes: "list[bytes]" = []
            for signer in probe_signers:
                vote = build_vote(proposal, True, signer, now + 1)
                proposal.votes.append(vote)
                probe_votes.append(vote.encode())
            for part in chunks(probe_votes):
                driver_fed.submit(probe, part, now + 1)
            driver_fed.pump()
        probe_report = driver_fed.drain()
        assert probe_report["acked"] == len(breach_scopes) * v_count, (
            probe_report
        )

        merged_text = driver_fed.merged_metrics_text()
        merged_slo = driver_fed.merged_slo()
        hosts_labelled = all(
            f'host="{h}"' in merged_text for h in host_ids
        )
        decision_histogram = (
            "hashgraph_decision_latency_seconds_bucket" in merged_text
        )
        assert hosts_labelled, "merged scrape missing a host label"
        assert decision_histogram, "merged scrape missing decide histogram"
        firing = sorted(merged_slo["alerts_firing"])
        for probe in breach_scopes:
            assert any(a.endswith(f"/{probe}") for a in firing), (
                probe, firing,
            )

        incidents = []
        for host_id in host_ids:
            host_dir = os.path.join(incident_root, host_id)
            if not os.path.isdir(host_dir):
                continue
            for name in sorted(os.listdir(host_dir)):
                inc_dir = os.path.join(host_dir, name)
                with open(os.path.join(inc_dir, "incident.json")) as fh:
                    meta = json.load(fh)
                with open(os.path.join(inc_dir, "trace.json")) as fh:
                    trace_doc = json.load(fh)
                incidents.append({
                    "host": host_id,
                    "name": name,
                    "reason": meta["reason"],
                    "scope": meta["scope"],
                    "trace_linked": bool(meta.get("trace_id")),
                    "perfetto_loadable": "traceEvents" in trace_doc,
                })
        assert incidents, "induced breach produced no incident dump"
        assert any(
            i["perfetto_loadable"] and i["trace_linked"] for i in incidents
        ), incidents

        slo_detail = {
            "windowed_per_host_p99_ms": per_host_p99_ms,
            "windowed_decisions": healthy_slo["global"]["count"],
            "verdict": healthy_verdict,
            "merged_scrape": {
                "hosts_labelled": hosts_labelled,
                "decision_histogram": decision_histogram,
            },
            "induced_breach": {
                "scopes": breach_scopes,
                "alerts_firing": firing,
                "incidents": incidents,
            },
        }
    finally:
        for driver in drivers:
            driver.close()
        for client in clients.values():
            client.close()
        for proc in procs.values():
            try:
                proc.stdin.close()  # EOF = the runner's shutdown signal
                proc.wait(timeout=15)
            except Exception:
                proc.kill()
        shutil.rmtree(incident_root, ignore_errors=True)

    med_fed = sorted(fed_rates)[len(fed_rates) // 2]
    med_single = sorted(single_rates)[len(single_rates) // 2]
    scaling = round(med_fed / med_single, 2) if med_single else None
    max_spread = max(
        spread_pct(fed_rates), spread_pct(single_rates), spread_pct(controls)
    )
    separated = min(fed_rates) > max(single_rates)
    outside_noise = (
        scaling is not None and scaling > 1.0 + 2.0 * max_spread / 100.0
    )
    # The scaling CLAIM needs real parallel hardware (run_fleet's
    # criterion): N host processes on a shared-core CPU box contend for
    # the same substrate, so the verdict gates reproducibility there.
    shared_substrate = (os.cpu_count() or 2) < 2 * n_hosts
    if not shared_substrate:
        verdict_pass = bool(separated and outside_noise)
        criterion = (
            "min(federated reps) > max(single-host reps) AND "
            "scaling > 1 + 2*max_spread"
        )
    else:
        verdict_pass = spread_pct(fed_rates) < 33.3
        criterion = (
            f"no scaling claim ({os.cpu_count()} cores for {n_hosts} "
            "host processes + driver): federated rep spread < 33%"
        )
    noise_verdict = {
        "pass": verdict_pass,
        "criterion": criterion,
        "federated_votes_per_sec": round(med_fed, 1),
        "single_host_votes_per_sec": round(med_single, 1),
        "scaling": scaling,
        "shared_substrate": shared_substrate,
        "federated_reps": [round(r, 1) for r in fed_rates],
        "single_host_reps": [round(r, 1) for r in single_rates],
        "control_pings_per_sec": controls,
        "spread_pct": {
            "federated": spread_pct(fed_rates),
            "single_host": spread_pct(single_rates),
            "control": spread_pct(controls),
        },
    }
    return {
        "metric": "federation_aggregate_votes_per_sec",
        "value": round(med_fed, 1),
        "unit": "votes/sec",
        "detail": {
            "hosts": n_hosts,
            "host_platform": peer_platform,
            "shards_per_host": shards_per_host,
            "proposals": p_count,
            "votes_per_proposal": v_count,
            "chunk_votes": chunk,
            "votes_per_rep": total_votes,
            "per_host_votes_r0": per_host,
            "tally_path": "fabric",  # CPU backend: no cross-process psum
            "noise_verdict": noise_verdict,
            "migration": migration,
            "slo": slo_detail,
            "smoke": smoke,
        },
    }


def run_slo_overhead(
    p_count: int = 192,
    v_count: int = 32,
    reps: int = 5,
    smoke: bool = False,
) -> dict:
    """Always-on SLO tracking cost: paired A/B of the same decision-heavy
    workload with the process-global SloEngine enabled vs disabled.

    Each rep runs one engine through ``p_count`` proposals x ``v_count``
    voters to decision with ``decide_p99_ms`` declared on every scope —
    the WORST case for the SLO plane, since every decide walks the full
    observe path (windowed histogram + burn-rate evaluation + labelled
    gauge upkeep). Arms interleave on-off-on-off in the same window so
    drift hits both; the verdict asserts the median overhead stays under
    the 5% acceptance bar, noise-aware (an overhead claim smaller than
    the rep spread is reported but not failed on).

    ``smoke`` (CI): tiny shapes, 3 paired reps.
    """
    from hashgraph_tpu import (
        CreateProposalRequest,
        ScopeConfigBuilder,
        StubConsensusSigner,
        build_vote,
    )
    from hashgraph_tpu.engine import TpuConsensusEngine
    from hashgraph_tpu.obs import slo_engine

    if smoke:
        p_count, v_count, reps = 48, 16, 3
    now = 1_700_000_000
    total_votes = p_count * v_count
    scope_cfg = ScopeConfigBuilder().with_decide_p99_ms(5_000.0).build()
    signers = [StubConsensusSigner(bytes([k + 1]) * 20) for k in range(v_count)]
    engine = TpuConsensusEngine(
        StubConsensusSigner(b"\x09" * 20),
        capacity=p_count + 8,
        voter_capacity=v_count + 2,
    )

    slo_engine.reset()

    def run_arm(tag: str) -> float:
        # One proposal per scope, every scope carrying a declared
        # objective: each rep is p_count decisions walking the full SLO
        # observe path (all built untimed; only the ingest is timed).
        batch: "list[tuple[str, object]]" = []
        scopes = []
        for p in range(p_count):
            scope = f"{tag}-p{p}"
            scopes.append(scope)
            engine.set_scope_config(scope, scope_cfg)
            request = CreateProposalRequest(
                f"p{p}", b"payload", b"o", v_count, 3_600, True
            )
            pid = engine.create_proposal(scope, request, now).proposal_id
            proposal = engine.get_proposal(scope, pid)
            for signer in signers:
                vote = build_vote(proposal, True, signer, now + 1)
                proposal.votes.append(vote)
                batch.append((scope, vote))
            scopes[-1] = (scope, pid)
        t0 = time.perf_counter()
        engine.ingest_votes(batch, now + 1)
        wall = time.perf_counter() - t0
        for scope, pid in scopes:
            assert engine.get_consensus_result(scope, pid) is True, scope
        engine.delete_scopes([scope for scope, _pid in scopes])
        return wall

    # Untimed warmups compile at these shapes AND pre-install the
    # per-scope labelled gauge families before either arm is timed.
    # Scope names are FIXED per arm (reps recreate the same scopes), so
    # the registry stays bounded and no timed rep pays a gauge install.
    slo_engine.enabled = True
    run_arm("on")
    slo_engine.enabled = False
    run_arm("off")

    on_rates: list[float] = []
    off_rates: list[float] = []
    try:
        for _rep in range(reps):
            slo_engine.enabled = True
            on_rates.append(total_votes / run_arm("on"))
            slo_engine.enabled = False
            off_rates.append(total_votes / run_arm("off"))
    finally:
        slo_engine.enabled = True  # never leave the plane off

    med_on = sorted(on_rates)[len(on_rates) // 2]
    med_off = sorted(off_rates)[len(off_rates) // 2]
    overhead_pct = round(100.0 * (med_off - med_on) / med_off, 2)
    max_spread = max(spread_pct(on_rates), spread_pct(off_rates))
    # Noise-aware bar: an apparent overhead smaller than the rep-to-rep
    # spread is indistinguishable from measurement noise, so it cannot
    # fail the 5% ceiling on its own.
    within_noise = bool(abs(overhead_pct) <= max_spread)
    verdict = {
        "pass": bool(overhead_pct < 5.0 or within_noise),
        "criterion": (
            "median SLO-on throughput within 5% of SLO-off, or the gap "
            "is inside the rep spread (noise)"
        ),
        "overhead_pct": overhead_pct,
        "within_noise": within_noise,
        "spread_pct": {
            "slo_on": spread_pct(on_rates),
            "slo_off": spread_pct(off_rates),
        },
    }
    state = slo_engine.state()
    return {
        "metric": "slo_tracking_overhead_pct",
        "value": overhead_pct,
        "unit": "%",
        "detail": {
            "proposals": p_count,
            "votes_per_proposal": v_count,
            "reps": reps,
            "slo_on_votes_per_sec": [round(r, 1) for r in on_rates],
            "slo_off_votes_per_sec": [round(r, 1) for r in off_rates],
            "median_on": round(med_on, 1),
            "median_off": round(med_off, 1),
            "windowed_decisions_tracked": state["global"]["count"],
            "alerts_firing": state["alerts_firing"],
            "verdict": verdict,
            "smoke": smoke,
        },
    }


def run_profile_overhead(
    p_count: int = 192,
    v_count: int = 32,
    reps: int = 5,
    smoke: bool = False,
) -> dict:
    """Always-on stack-sampling cost: paired A/B of the same
    decision-heavy workload with the continuous profiler sampling vs
    parked — the round-20 analogue of ``run_slo_overhead``.

    The profiler THREAD stays alive in both arms (that is how it ships:
    started once at server start, never joined per-request); only
    ``enabled`` toggles, so the A/B isolates exactly the cost the kill
    switch can remove — ``sys._current_frames()`` walks plus aggregate
    upkeep at the adaptive rate. Arms interleave on-off-on-off in one
    window so drift hits both; the verdict asserts the median overhead
    stays under the 2% acceptance bar, noise-aware (a gap smaller than
    the rep spread is reported, not failed on).

    ``smoke`` (CI): tiny shapes, 3 paired reps.
    """
    from hashgraph_tpu import (
        CreateProposalRequest,
        ScopeConfigBuilder,
        StubConsensusSigner,
        build_vote,
    )
    from hashgraph_tpu.engine import TpuConsensusEngine
    from hashgraph_tpu.obs import default_profiler

    if smoke:
        p_count, v_count, reps = 48, 16, 3
    now = 1_700_000_000
    total_votes = p_count * v_count
    scope_cfg = ScopeConfigBuilder().build()
    signers = [StubConsensusSigner(bytes([k + 1]) * 20) for k in range(v_count)]
    engine = TpuConsensusEngine(
        StubConsensusSigner(b"\x0a" * 20),
        capacity=p_count + 8,
        voter_capacity=v_count + 2,
    )

    def run_arm(tag: str) -> float:
        batch: "list[tuple[str, object]]" = []
        scopes = []
        for p in range(p_count):
            scope = f"{tag}-p{p}"
            engine.set_scope_config(scope, scope_cfg)
            request = CreateProposalRequest(
                f"p{p}", b"payload", b"o", v_count, 3_600, True
            )
            pid = engine.create_proposal(scope, request, now).proposal_id
            proposal = engine.get_proposal(scope, pid)
            for signer in signers:
                vote = build_vote(proposal, True, signer, now + 1)
                proposal.votes.append(vote)
                batch.append((scope, vote))
            scopes.append((scope, pid))
        t0 = time.perf_counter()
        engine.ingest_votes(batch, now + 1)
        wall = time.perf_counter() - t0
        for scope, pid in scopes:
            assert engine.get_consensus_result(scope, pid) is True, scope
        engine.delete_scopes([scope for scope, _pid in scopes])
        return wall

    was_running = default_profiler.running
    was_enabled = default_profiler.enabled
    default_profiler.reset()
    default_profiler.enabled = True
    default_profiler.start()

    # Untimed warmup pair compiles at these shapes before either arm.
    run_arm("on")
    default_profiler.enabled = False
    run_arm("off")

    on_rates: list[float] = []
    off_rates: list[float] = []
    try:
        for _rep in range(reps):
            default_profiler.enabled = True
            on_rates.append(total_votes / run_arm("on"))
            default_profiler.enabled = False
            off_rates.append(total_votes / run_arm("off"))
    finally:
        default_profiler.enabled = was_enabled
        if not was_running:
            default_profiler.stop()

    med_on = sorted(on_rates)[len(on_rates) // 2]
    med_off = sorted(off_rates)[len(off_rates) // 2]
    overhead_pct = round(100.0 * (med_off - med_on) / med_off, 2)
    max_spread = max(spread_pct(on_rates), spread_pct(off_rates))
    within_noise = bool(abs(overhead_pct) <= max_spread)
    snap = default_profiler.snapshot()
    verdict = {
        "pass": bool(overhead_pct < 2.0 or within_noise),
        "criterion": (
            "median profiler-on throughput within 2% of profiler-off, "
            "or the gap is inside the rep spread (noise)"
        ),
        "overhead_pct": overhead_pct,
        "within_noise": within_noise,
        "spread_pct": {
            "profiler_on": spread_pct(on_rates),
            "profiler_off": spread_pct(off_rates),
        },
    }
    return {
        "metric": "profiler_overhead_pct",
        "value": overhead_pct,
        "unit": "%",
        "detail": {
            "proposals": p_count,
            "votes_per_proposal": v_count,
            "reps": reps,
            "profiler_on_votes_per_sec": [round(r, 1) for r in on_rates],
            "profiler_off_votes_per_sec": [round(r, 1) for r in off_rates],
            "median_on": round(med_on, 1),
            "median_off": round(med_off, 1),
            "samples": snap["samples"],
            "sample_roles": snap["roles"],
            "rate_hz": snap["rate_hz"],
            "self_measured_overhead_s": snap["overhead_seconds"],
            "verdict": verdict,
            "smoke": smoke,
        },
    }


def run_regress() -> dict:
    """Perf-regression sentry over the checked-in BENCH_*.json
    trajectory (``tools/bench_regress.py`` as a bench runner, so the
    verdict lands in the same artifact stream it audits). Host-only: no
    engine, no device — it reads the corpus next to this file."""
    import pathlib

    from tools.bench_regress import build_verdict

    verdict = build_verdict(pathlib.Path(__file__).resolve().parent)
    return {
        "metric": "bench_regressions",
        "value": len(verdict["regressions"]),
        "unit": "regressions",
        "detail": verdict,
    }


def run_default() -> dict:
    """The driver-visible sweep: engine-level config 3 as the headline,
    every other BASELINE shape in ``detail`` (one JSON line total).

    The headline is the MEDIAN of three full engine-bench repetitions
    (each itself a median over per-cycle rates), with the cross-repetition
    spread reported alongside: a claim that does not survive the spread
    between identical runs isn't a claim (BENCHMARKS.md)."""
    reps = [run_engine_bench() for _ in range(3)]
    values = sorted(r["value"] for r in reps)
    engine = next(r for r in reps if r["value"] == values[1])
    spread_pct = 100.0 * (values[-1] - values[0]) / values[1]
    sections = {
        "pool_level": run_bench(),
        "config2": run_config2(),
        "lanes1024": run_lanes1024(),
        "engine_lanes1024": run_engine_lanes1024(),
        "validated": run_validated(),
        "validated_sweep": run_validated_sweep(),
        "crypto": run_crypto(),
        "config4": run_config4(),
        "engine_config4": run_engine_config4(),
        "config5": run_config5(),
        "engine_config5": run_engine_config5(),
    }
    detail = dict(engine["detail"])
    for name, result in sections.items():
        detail[name] = {
            "metric": result["metric"],
            "value": result["value"],
            "unit": result["unit"],
            "detail": result["detail"],
        }
    # Key order is deliberate: the driver's artifact stores only the TAIL
    # of this (long) line, so the headline fields and the compact per-
    # section summary go LAST — the captured artifact then always carries
    # the headline, vs_baseline, the repetition evidence, and one number
    # per BASELINE shape even when the full detail is truncated away.
    return {
        "metric": engine["metric"],
        "unit": engine["unit"],
        "detail": detail,
        "summary": {name: result["value"] for name, result in sections.items()},
        "headline_repetitions": values,
        "headline_spread_pct": round(spread_pct, 1),
        "value": engine["value"],
        "vs_baseline": engine["vs_baseline"],
    }


if __name__ == "__main__":
    import sys

    # --metrics-out PATH: after the run, snapshot the always-on metrics
    # registry (counters, gauges, histogram count/sum/p50/p90/p99 — e.g.
    # wal_fsync_seconds quantiles, hashgraph_decision_latency_seconds)
    # into the BENCH json alongside the throughput numbers, and also write
    # the full result to PATH (one JSON object).
    args = sys.argv[1:]

    def _pop_flag(name: str) -> str | None:
        """Extract `NAME VALUE` from args; None when absent."""
        if name not in args:
            return None
        flag = args.index(name)
        if flag + 1 >= len(args):
            raise SystemExit(f"{name} requires a value")
        value = args[flag + 1]
        del args[flag : flag + 2]
        return value

    metrics_out = _pop_flag("--metrics-out")

    # --health-out PATH: after the run, snapshot the process-wide health
    # monitor (peer scorecards with grades, equivocation/fork evidence,
    # watchdog state, firing alert rules) to PATH, and fold the alert
    # counts into the BENCH json line — a bench run that tripped an
    # anomaly rule should say so in the artifact, not just in a side file.
    health_out = _pop_flag("--health-out")

    # fleet --hosts N: N > 1 switches the fleet bench to the FEDERATED
    # topology — N OS processes (examples/federation_host.py), two-level
    # (host, shard) placement, cross-host vote routing over the gossip
    # fabric, and a live shard migration under sustained traffic.
    fleet_hosts = _pop_flag("--hosts")

    # fleet --smoke: the CI topology — 2 simulated shards on virtual CPU
    # devices (the conftest trick), exercising routing + the psum tally on
    # boxes with one physical device. Must run before anything initializes
    # the jax backend; if it already initialized, the fleet falls back to
    # shards sharing a device and says so in ``tally_path``.
    # gossip --stages: force the wire-path stage-attribution block into
    # the BENCH json (decode / crypto / device-apply seconds per rep).
    # Attribution is on by default; the flag exists so `make
    # bench-gossip STAGES=1` has an explicit, stable spelling and so it
    # can be turned OFF (--no-stages) for minimal artifacts.
    gossip_stages = True
    if "--stages" in args:
        args.remove("--stages")
    if "--no-stages" in args:
        args.remove("--no-stages")
        gossip_stages = False
    # gossip --reactor-only: run ONLY the paired reactor-off/on A/B
    # (dedicated pinned peer sets) — `make bench-reactor`'s spelling.
    # --no-reactor-ab drops the reactor pair from the full gossip bench
    # for minimal artifacts.
    gossip_reactor_ab = True
    gossip_reactor_only = False
    if "--no-reactor-ab" in args:
        args.remove("--no-reactor-ab")
        gossip_reactor_ab = False
    if "--reactor-only" in args:
        args.remove("--reactor-only")
        gossip_reactor_only = True

    fleet_smoke = "--smoke" in args
    if fleet_smoke:
        args.remove("--smoke")
        import os as _os

        _flags = _os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in _flags:
            _os.environ["XLA_FLAGS"] = (
                _flags + " --xla_force_host_platform_device_count=2"
            ).strip()
        try:
            import jax

            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass

    # JAX's persistent compilation cache, ON BY DEFAULT (BENCH_r05
    # measured 147.7 s of compile warmup in engine_config4 alone; a re-run
    # at the same geometry should never pay it twice), placed by the one
    # rule in hashgraph_tpu.compile_cache. --no-compile-cache opts out.
    # Enabling it touches jax.config only: no backend initializes in this
    # parent, so modes that spawn chip-holding children stay legal.
    no_compile_cache = "--no-compile-cache" in args
    if no_compile_cache:
        args.remove("--no-compile-cache")

    # --trace-out PATH: run the whole bench under one distributed trace
    # context (so every observed_span — device ingest, verify batches,
    # WAL fsyncs — lands context-tagged in the trace store) and export a
    # Chrome trace-event file Perfetto opens directly. Pair with a
    # jax.profiler capture over the same window to correlate host spans
    # with device timelines on one wall-clock axis.
    trace_out = _pop_flag("--trace-out")
    _trace_cm = None
    if trace_out is not None:
        from hashgraph_tpu.obs.trace import (
            TraceContext,
            trace_store,
            use_context,
        )

        _root_ctx = TraceContext.generate()
        _trace_cm = use_context(_root_ctx)
        _trace_cm.__enter__()
        print(
            f"trace context {_root_ctx.to_traceparent()} -> {trace_out}",
            file=sys.stderr,
        )

    # --metrics-port N: serve /metrics + /healthz for the duration of the
    # run (0 = ephemeral; the bound address is printed to stderr so stdout
    # stays one JSON line), so `curl` can watch histograms fill live.
    sidecar = None
    sidecar_port = _pop_flag("--metrics-port")
    if sidecar_port is not None:
        from hashgraph_tpu.obs import MetricsSidecar, registry

        sidecar = MetricsSidecar(registry, port=int(sidecar_port))
        host, port = sidecar.start()
        print(f"metrics sidecar listening on http://{host}:{port}/metrics",
              file=sys.stderr)

    which = args[0] if args else "default"
    if not no_compile_cache and which not in ("wal", "crypto", "regress"):
        from hashgraph_tpu.compile_cache import enable_compile_cache

        print(f"persistent compilation cache at {enable_compile_cache()}",
              file=sys.stderr)
    runners = {
        "engine": run_engine_bench,
        "pool": run_bench,
        "config3": run_bench,  # historical alias
        "config2": run_config2,
        "config4": run_config4,
        "engine_config4": run_engine_config4,
        "config5": run_config5,
        "engine_config5": run_engine_config5,
        "engine_config5_retained": lambda: run_engine_config5(retain=True),
        "lanes1024": run_lanes1024,
        "engine_lanes1024": run_engine_lanes1024,
        "deepchain": run_deepchain,
        "crypto": run_crypto,
        "validated": run_validated,
        "validated-sweep": run_validated_sweep,
        "validated_sweep": run_validated_sweep,  # shell-friendly alias
        "device-verify": lambda: run_device_verify(smoke=fleet_smoke),
        "device_verify": lambda: run_device_verify(smoke=fleet_smoke),
        "redelivery": run_redelivery,
        "wal": run_wal,
        "fleet": lambda: (
            run_federation(
                n_hosts=int(fleet_hosts), smoke=fleet_smoke
            )
            if fleet_hosts is not None and int(fleet_hosts) > 1
            else run_fleet(smoke=fleet_smoke)
        ),
        "catchup": lambda: run_catchup(smoke=fleet_smoke),
        "gossip": lambda: run_gossip(
            smoke=fleet_smoke,
            stages=gossip_stages,
            reactor_ab=gossip_reactor_ab,
            reactor_only=gossip_reactor_only,
        ),
        "chaos": lambda: run_chaos(smoke=fleet_smoke),
        "liveness": lambda: run_liveness(smoke=fleet_smoke),
        "churn": lambda: run_churn(smoke=fleet_smoke),
        "slo-overhead": lambda: run_slo_overhead(smoke=fleet_smoke),
        "slo_overhead": lambda: run_slo_overhead(smoke=fleet_smoke),
        "profile-overhead": lambda: run_profile_overhead(smoke=fleet_smoke),
        "profile_overhead": lambda: run_profile_overhead(smoke=fleet_smoke),
        "regress": run_regress,
        "default": run_default,
    }
    def _registry_snapshot() -> dict:
        from hashgraph_tpu.obs import registry

        return registry.snapshot()

    def _health_snapshot() -> dict:
        from hashgraph_tpu.obs import health_monitor

        return health_monitor.snapshot()

    def _health_summary(snap: dict) -> dict:
        """Compact alert view for the BENCH json line (the full
        scorecard/evidence snapshot lives in --health-out's file)."""
        firing = snap["alerts"]["firing"]
        grades: dict[str, int] = {}
        for card in snap["peers"].values():
            grades[card["grade"]] = grades.get(card["grade"], 0) + 1
        return {
            "alert_events_total": snap["alerts"]["events_total"],
            "alerts_firing": [a["rule"] for a in firing],
            "evidence_records": len(snap["evidence"]),
            "peer_grades": grades,
        }

    # finally: a run that RAISES is exactly the one whose trace matters —
    # the export (and sidecar shutdown) must survive runner failures.
    try:
        if which == "all":
            results = {}
            for name in (
                "engine",
                "pool",
                "config2",
                "lanes1024",
                "engine_lanes1024",
                "validated",
                "crypto",
                "config4",
                "engine_config4",
                "config5",
                "engine_config5",
                "engine_config5_retained",
            ):
                results[name] = runners[name]()
                print(json.dumps(results[name]))
            if metrics_out is not None:
                with open(metrics_out, "w") as fh:
                    json.dump(
                        {"results": results, "metrics": _registry_snapshot()}, fh
                    )
            if health_out is not None:
                snap = _health_snapshot()
                with open(health_out, "w") as fh:
                    json.dump(snap, fh)
                print(json.dumps({"health": _health_summary(snap)}))
        else:
            result = runners[which]()
            if health_out is not None:
                snap = _health_snapshot()
                with open(health_out, "w") as fh:
                    json.dump(snap, fh)
                result["health"] = _health_summary(snap)
            if metrics_out is not None:
                result["metrics"] = _registry_snapshot()
                with open(metrics_out, "w") as fh:
                    json.dump(result, fh)
            print(json.dumps(result))
    finally:
        # Cleanup steps are independent: a failing trace export must not
        # mask the runner's real exception or skip the sidecar shutdown.
        try:
            if _trace_cm is not None:
                _trace_cm.__exit__(None, None, None)
                from hashgraph_tpu.obs.trace import trace_store

                events = trace_store.export_chrome(trace_out)
                dropped = (
                    f" ({trace_store.dropped} spans dropped at the store cap)"
                    if trace_store.dropped
                    else ""
                )
                print(
                    f"wrote {events} trace events to {trace_out}{dropped}",
                    file=sys.stderr,
                )
        except Exception as exc:
            print(f"trace export failed: {exc!r}", file=sys.stderr)
        finally:
            if sidecar is not None:
                sidecar.stop()
