"""Continuous profiling plane: always-on stack sampling and wall-clock
attribution reports (round 20).

Covers the acceptance surface: per-role hot-function dominance in the
sampled aggregate, the adaptive-rate backoff/speed-up contract, the
collapsed-stack round trip, the bounded-aggregate drop accounting, the
``/profile`` sidecar endpoint and ``OP_PROFILE`` opcode serving the
same schema (with the old-peer UNKNOWN_OPCODE tolerance), the
attribution report's share arithmetic over a registry state, fleet
federation via ``merge_profile_states`` (synthetic frames, and live
ones from a served peer armed by the environment variable),
``IncidentCapture``'s ``profile.json``, and sim-corpus byte-identity
with the sampler live.
"""

import json
import os
import threading
import time
import urllib.request

import pytest

from hashgraph_tpu.bridge import protocol as P
from hashgraph_tpu.bridge.client import BridgeClient, BridgeError
from hashgraph_tpu.bridge.server import BridgeServer
from hashgraph_tpu.obs.attribution import (
    ATTRIBUTION_SCHEMA,
    STAGE_KEYS,
    attribution_report,
)
from hashgraph_tpu.obs.profiler import (
    PROFILE_SCHEMA,
    ContinuousProfiler,
    parse_collapsed,
    profiler_enabled,
    thread_role,
)

NOW = 1_700_000_000


# ── role labelling ─────────────────────────────────────────────────────


class TestThreadRole:
    def test_prefix_table(self):
        assert thread_role("bridge-reader-3") == "reader"
        assert thread_role("bridge-shm-0") == "reader"
        assert thread_role("bridge-pipeline-1") == "serial-lane"
        assert thread_role("apply-reactor") == "reactor"
        assert thread_role("reactor-flusher") == "reactor"
        assert thread_role("gossip-loop-peer1") == "gossip-loop"
        assert thread_role("wal-writer") == "wal-fsync"
        assert thread_role("MainThread") == "main"
        assert thread_role("ThreadPoolExecutor-0_0") == "other"
        assert thread_role("") == "other"


# ── the sampling fold ──────────────────────────────────────────────────


def _hot_spin(stop: threading.Event) -> None:
    """A recognizable leaf frame for the dominance assertion."""
    while not stop.is_set():
        sum(range(64))


@pytest.fixture()
def hot_thread():
    """A running thread named like the serial-lane pool, pinned inside
    ``_hot_spin`` so every sample of it has a known hottest leaf."""
    stop = threading.Event()
    thread = threading.Thread(
        target=_hot_spin, args=(stop,), name="bridge-pipeline-0", daemon=True
    )
    thread.start()
    try:
        yield thread
    finally:
        stop.set()
        thread.join(timeout=5)


class TestSampling:
    def test_hot_function_dominates_its_role(self, hot_thread):
        prof = ContinuousProfiler()
        for _ in range(25):
            prof.sample_once()
        snap = prof.snapshot()
        assert snap["schema"] == PROFILE_SCHEMA
        assert snap["roles"].get("serial-lane", 0) >= 25
        lane = [s for s in snap["stacks"] if s["role"] == "serial-lane"]
        assert lane, "no serial-lane stacks sampled"
        # Hottest-first ordering + the pinned leaf: the spin function
        # must dominate the role's aggregate.
        hottest = lane[0]
        assert any("_hot_spin" in frame for frame in hottest["frames"])
        spin = sum(
            s["samples"]
            for s in lane
            if any("_hot_spin" in f for f in s["frames"])
        )
        total = sum(s["samples"] for s in lane)
        assert spin / total > 0.9

    def test_sampler_excludes_its_own_thread(self):
        prof = ContinuousProfiler(min_hz=50.0, max_hz=50.0)
        prof.start()
        try:
            time.sleep(0.3)
            snap = prof.snapshot()
            assert snap["samples"] > 0
            for entry in snap["stacks"]:
                assert not any(
                    "profiler._loop" in frame for frame in entry["frames"]
                )
        finally:
            prof.stop()

    def test_kill_switch_stops_sampling(self):
        prof = ContinuousProfiler(min_hz=50.0, max_hz=50.0)
        prof.enabled = False
        prof.start()
        try:
            time.sleep(0.25)
            assert prof.snapshot()["samples"] == 0
        finally:
            prof.stop()

    def test_bounded_aggregate_counts_drops(self, hot_thread):
        # Cap of 1 distinct stack: with >= 2 live threads (main + the
        # hot one) every tick lands at least one novel-stack drop after
        # the first key is admitted.
        prof = ContinuousProfiler(max_stacks=1)
        for _ in range(10):
            prof.sample_once()
        snap = prof.snapshot()
        assert len(snap["stacks"]) == 1
        assert snap["dropped"] > 0
        # Total accounting: admitted + dropped == every sample taken.
        admitted = sum(s["samples"] for s in snap["stacks"])
        assert admitted + snap["dropped"] == snap["samples"]

    def test_registry_counters_advance(self):
        from hashgraph_tpu.obs import MetricsRegistry
        from hashgraph_tpu.obs.profiler import (
            PROFILE_OVERHEAD_SECONDS_TOTAL,
            PROFILE_SAMPLES_TOTAL,
        )

        reg = MetricsRegistry()
        prof = ContinuousProfiler(reg)
        prof.sample_once()
        prof._adapt(0.001)
        snap = reg.snapshot()
        assert snap["counters"][PROFILE_SAMPLES_TOTAL] > 0
        assert snap["counters"][PROFILE_OVERHEAD_SECONDS_TOTAL] > 0


class TestAdaptiveRate:
    def test_backoff_to_floor_when_over_budget(self):
        prof = ContinuousProfiler(min_hz=19.0, max_hz=97.0)
        start_hz = prof.rate_hz
        # Every tick costs more than the whole interval: the EWMA blows
        # through the budget and the rate must walk down to the floor.
        for _ in range(50):
            prof._adapt(2.0 / prof.rate_hz)
        assert prof.rate_hz < start_hz
        assert prof.rate_hz == pytest.approx(19.0)

    def test_speedup_to_ceiling_when_cheap(self):
        prof = ContinuousProfiler(min_hz=19.0, max_hz=97.0)
        for _ in range(50):
            prof._adapt(2.0 / prof.rate_hz)  # drive to the floor first
        for _ in range(80):
            prof._adapt(0.0)  # free ticks: well under half the budget
        assert prof.rate_hz == pytest.approx(97.0)

    def test_rate_never_leaves_the_band(self):
        prof = ContinuousProfiler(min_hz=19.0, max_hz=97.0)
        for k in range(200):
            prof._adapt(0.0 if k % 3 else 1.0)
            assert 19.0 <= prof.rate_hz <= 97.0 + 1e-9


class TestCollapsedRoundTrip:
    def test_collapsed_parses_back_exactly(self, hot_thread):
        prof = ContinuousProfiler()
        for _ in range(10):
            prof.sample_once()
        snap = prof.snapshot()
        parsed = parse_collapsed(prof.collapsed(snap))
        expect = {
            (s["role"], tuple(s["frames"])): s["samples"]
            for s in snap["stacks"]
        }
        assert parsed == expect
        assert any(role == "serial-lane" for role, _frames in parsed)

    def test_empty_profile_collapses_to_empty_text(self):
        prof = ContinuousProfiler()
        assert prof.collapsed() == ""
        assert parse_collapsed("") == {}


class TestChromeExport:
    def test_samples_ride_pid_zero_with_role_threads(self, hot_thread):
        prof = ContinuousProfiler()
        for _ in range(5):
            prof.sample_once()
        doc = prof.export_chrome()
        events = doc["traceEvents"]
        # export_chrome merges the shared trace ring, so other suites'
        # consensus instants may ride along on their own pids — the
        # pid-0 contract covers the profiler's sample instants only.
        instants = [
            e
            for e in events
            if e.get("ph") == "i" and "role" in e.get("args", {})
        ]
        assert instants and all(e["pid"] == 0 for e in instants)
        names = [
            e["args"]["name"]
            for e in events
            if e.get("ph") == "M" and e["name"] == "thread_name"
        ]
        assert any("serial-lane" in n for n in names)
        assert doc["otherData"]["profile"]["samples"] == prof.snapshot()[
            "samples"
        ]

    def test_export_writes_loadable_json(self, tmp_path, hot_thread):
        prof = ContinuousProfiler()
        prof.sample_once()
        path = tmp_path / "trace.json"
        prof.export_chrome(str(path))
        doc = json.loads(path.read_text())
        assert "traceEvents" in doc


class TestEnvGate:
    def test_profiler_enabled_contract(self, monkeypatch):
        monkeypatch.delenv("HASHGRAPH_TPU_PROFILE", raising=False)
        assert profiler_enabled(None) is False  # default OFF
        assert profiler_enabled(True) is True
        monkeypatch.setenv("HASHGRAPH_TPU_PROFILE", "1")
        assert profiler_enabled(None) is True
        assert profiler_enabled(False) is False  # explicit wins

    def test_server_start_arms_default_profiler(self, monkeypatch):
        from hashgraph_tpu.obs import default_profiler

        monkeypatch.setenv("HASHGRAPH_TPU_PROFILE", "1")
        assert not default_profiler.running
        try:
            with BridgeServer(capacity=8, voter_capacity=4):
                assert default_profiler.running
        finally:
            default_profiler.stop()
            default_profiler.reset()

    def test_server_start_respects_default_off(self, monkeypatch):
        from hashgraph_tpu.obs import default_profiler

        monkeypatch.delenv("HASHGRAPH_TPU_PROFILE", raising=False)
        with BridgeServer(capacity=8, voter_capacity=4):
            assert not default_profiler.running


# ── the attribution report and its three surfaces ──────────────────────


class TestAttributionReport:
    def test_shares_sum_to_one_over_busy_time(self):
        report = attribution_report(
            state={
                "counters": {
                    "hashgraph_bridge_wire_decode_seconds_total": 1.0,
                    "hashgraph_bridge_wire_crypto_seconds_total": 2.0,
                    "hashgraph_bridge_wire_apply_seconds_total": 5.0,
                    "hashgraph_bridge_wire_device_dispatches_total": 10.0,
                    "hashgraph_bridge_wire_apply_rows_total": 320.0,
                },
                "histograms": {"wal_fsync_seconds": {"sum": 2.0, "count": 4}},
            },
            profiler=ContinuousProfiler(),
        )
        assert report["schema"] == ATTRIBUTION_SCHEMA
        assert set(report["stages"]) == set(STAGE_KEYS)
        assert sum(s["share"] for s in report["stages"].values()) == (
            pytest.approx(1.0, abs=1e-3)
        )
        assert report["stages"]["device_apply"]["share"] == 0.5
        assert report["device"]["votes_per_dispatch"] == 32.0
        assert report["wal"]["fsyncs"] == 4

    def test_empty_totals_do_not_divide_by_zero(self):
        report = attribution_report(state={}, profiler=ContinuousProfiler())
        assert report["busy_seconds"] == 0.0
        assert all(s["share"] == 0.0 for s in report["stages"].values())
        assert all(
            s["share"] == 0.0 for s in report["apply_breakdown"].values()
        )
        assert report["waits"]["lane"]["mean_ms"] == 0.0

    def test_live_report_fuses_profiler_samples(self, hot_thread):
        prof = ContinuousProfiler()
        for _ in range(5):
            prof.sample_once()
        report = attribution_report(
            state={"counters": {}, "histograms": {}}, profiler=prof
        )
        assert report["samples"]["total"] == prof.snapshot()["samples"]
        assert "serial-lane" in report["samples"]["roles"]

    def test_idle_profiler_contributes_no_samples_block(self):
        report = attribution_report(
            state={"counters": {}, "histograms": {}},
            profiler=ContinuousProfiler(),
        )
        assert "samples" not in report


class TestProfileSurfaces:
    def test_sidecar_and_opcode_serve_the_same_schema(self):
        from hashgraph_tpu.obs import registry

        # Both surfaces read the LIVE process registry: advance a stage
        # counter and the pulled reports must see a non-zero busy time.
        registry.counter(
            "hashgraph_bridge_wire_apply_seconds_total"
        ).inc(0.25)
        with BridgeServer(
            capacity=16, voter_capacity=8, metrics_port=0
        ) as server:
            host, port = server.metrics_address
            with BridgeClient(*server.address) as client:
                alice, _ = client.add_peer()
                bob, _ = client.add_peer()
                pid, _ = client.create_proposal(
                    alice, "prof", NOW, "p", b"", 4, 600
                )
                proposal = client.get_proposal(alice, "prof", pid)
                client.process_proposal(bob, "prof", proposal, NOW + 1)
                vote = client.cast_vote(bob, "prof", pid, True, NOW + 2)
                client.process_votes(alice, "prof", [vote], NOW + 3)
                with urllib.request.urlopen(
                    f"http://{host}:{port}/profile", timeout=5
                ) as response:
                    http_body = json.loads(response.read())
                frame = client.profile()
        assert http_body["schema"] == ATTRIBUTION_SCHEMA
        assert set(http_body["stages"]) == set(STAGE_KEYS)
        assert http_body["busy_seconds"] > 0  # the vote above was applied
        assert frame is not None
        assert frame["profile"]["schema"] == ATTRIBUTION_SCHEMA
        assert set(frame["profile"]["stages"]) == set(STAGE_KEYS)
        assert frame["host"], "OP_PROFILE frame must carry the host label"

    def test_old_peer_unknown_opcode_returns_none(self, monkeypatch):
        with BridgeServer(capacity=8, voter_capacity=4) as server:
            with BridgeClient(*server.address) as client:
                def refuse(opcode, payload=b"", *a, **kw):
                    raise BridgeError(
                        P.STATUS_UNKNOWN_OPCODE, "old peer"
                    )

                monkeypatch.setattr(client, "_call", refuse)
                assert client.profile() is None

    def test_other_bridge_errors_still_raise(self, monkeypatch):
        with BridgeServer(capacity=8, voter_capacity=4) as server:
            with BridgeClient(*server.address) as client:
                def explode(opcode, payload=b"", *a, **kw):
                    raise BridgeError(P.STATUS_INTERNAL, "boom")

                monkeypatch.setattr(client, "_call", explode)
                with pytest.raises(BridgeError):
                    client.profile()

    def test_incident_capture_writes_profile_json(self, tmp_path):
        from hashgraph_tpu.obs.slo import IncidentCapture

        cap = IncidentCapture(str(tmp_path))
        path = cap.capture("slo_breach", scope="s")
        assert path is not None
        body = json.load(open(os.path.join(path, "profile.json")))
        assert body["schema"] == ATTRIBUTION_SCHEMA
        assert set(body["stages"]) == set(STAGE_KEYS)


class TestFleetMerge:
    def _frame(self, host, decode, crypto, apply_s, samples):
        return {
            "host": host,
            "profile": {
                "schema": ATTRIBUTION_SCHEMA,
                "stages": {
                    "wire_decode": {"seconds": decode, "share": 0.0},
                    "crypto": {"seconds": crypto, "share": 0.0},
                    "device_apply": {"seconds": apply_s, "share": 0.0},
                    "wal_fsync": {"seconds": 0.0, "share": 0.0},
                },
                "device": {"dispatches": 4.0, "apply_rows": 64.0},
                "wal": {"fsyncs": 2},
                "samples": {
                    "total": samples,
                    "dropped": 1,
                    "overhead_seconds": 0.01,
                    "roles": {"reader": samples},
                },
            },
        }

    def test_shares_recomputed_over_fleet_denominator(self):
        from hashgraph_tpu.parallel.rollup import merge_profile_states

        merged = merge_profile_states(
            [
                self._frame("h1", 1.0, 1.0, 6.0, 10),
                self._frame("h2", 1.0, 1.0, 2.0, 30),
            ]
        )
        assert set(merged["hosts"]) == {"h1", "h2"}
        assert merged["busy_seconds"] == pytest.approx(12.0)
        # 8/12 device-apply fleet-wide — NOT the mean of per-host shares.
        assert merged["stages"]["device_apply"]["share"] == (
            pytest.approx(8.0 / 12.0, abs=1e-3)
        )
        assert merged["device"]["votes_per_dispatch"] == 16.0
        assert merged["wal"]["fsyncs"] == 4
        assert merged["samples"]["total"] == 40
        assert merged["samples"]["roles"] == {"reader": 40}

    def test_live_frames_merge_to_known_stages(self, monkeypatch):
        """A served peer with the sampler armed by the environment: its
        OP_PROFILE frames, after a columnar OP_VOTE_BATCH was applied,
        merge to the known stage names only, with shares that sum to at
        most one over the summed fleet busy time."""
        from hashgraph_tpu import build_vote
        from hashgraph_tpu.bridge.client import PipelinedBridgeClient
        from hashgraph_tpu.obs import default_profiler
        from hashgraph_tpu.parallel.rollup import merge_profile_states
        from hashgraph_tpu.signing.stub import StubConsensusSigner
        from hashgraph_tpu.wire import Proposal

        monkeypatch.setenv("HASHGRAPH_TPU_PROFILE", "1")
        try:
            with BridgeServer(
                capacity=16, voter_capacity=8,
                signer_factory=StubConsensusSigner,
            ) as server:
                assert default_profiler.running
                with PipelinedBridgeClient(*server.address) as client:
                    peer, _ = client.add_peer(os.urandom(32))
                    _pid, blob = client.create_proposal(
                        peer, "live", NOW, "p", b"", 6, 600
                    )
                    proposal = Proposal.decode(blob)
                    votes = []
                    for k in range(4):
                        signer = StubConsensusSigner(bytes([k + 1]) * 20)
                        vote = build_vote(proposal, True, signer, NOW + 1)
                        proposal.votes.append(vote)
                        votes.append(vote.encode())
                    statuses = client.vote_batch_async(
                        NOW + 1, [(peer, "live", votes)]
                    ).result(30)
                    assert list(statuses) == [0] * 4
                with BridgeClient(*server.address) as client:
                    frames = [client.profile(), client.profile()]
        finally:
            default_profiler.stop()
            default_profiler.reset()
        assert all(frame is not None for frame in frames)
        merged = merge_profile_states(frames)
        shares = {
            key: stage["share"] for key, stage in merged["stages"].items()
        }
        assert set(shares) == set(STAGE_KEYS)
        # Each share is rounded to 4 places, so their sum may exceed one
        # by at most half a unit in that place per stage.
        assert 0.0 < sum(shares.values()) <= 1.0 + len(STAGE_KEYS) * 5e-5
        busy = [frame["profile"]["busy_seconds"] for frame in frames]
        assert min(busy) > 0
        assert merged["busy_seconds"] == pytest.approx(sum(busy), abs=1e-5)

    def test_empty_and_degenerate_frames_merge_clean(self):
        from hashgraph_tpu.parallel.rollup import merge_profile_states

        merged = merge_profile_states([{"host": "h1"}, {}])
        assert merged["busy_seconds"] == 0.0
        assert all(
            s["share"] == 0.0 for s in merged["stages"].values()
        )


# ── determinism: the sampler must be protocol-invisible ────────────────


class TestDeterminism:
    def test_sim_verdict_byte_identical_with_profiler_on(self):
        """Acceptance: the chaos harness's verdict JSON is byte-for-byte
        identical with the always-on sampler live — sampling reads
        interpreter frames, never protocol state."""
        from hashgraph_tpu.sim.scenarios import run_scenario

        baseline = run_scenario("partition-heal", 7)
        prof = ContinuousProfiler(min_hz=50.0, max_hz=97.0)
        prof.start()
        try:
            sampled = run_scenario("partition-heal", 7)
        finally:
            prof.stop()
        assert prof.snapshot()["samples"] > 0, "sampler never fired"
        assert json.dumps(baseline, sort_keys=True) == json.dumps(
            sampled, sort_keys=True
        )
