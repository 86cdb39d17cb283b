"""Liveness observatory: φ-accrual suspicion, adaptive consensus
timeouts, overload admission control, and the machine-checked liveness
verdict.

Covers the full stack ISSUE 18 added: the accrual math (obs/accrual),
its integration into the health watchdog (stale-OR-phi flagging, read-
time grading so convictions clear on heal), the per-scope adaptive
timeout learner and its engine wiring, the ScopeConfig/WAL plumbing
that persists timeout bounds, RETRY_AFTER shedding on the bridge plus
the gossip node's deferral window, and the sim-layer liveness verdict
with its A/B override seam.
"""

import math

import pytest

from hashgraph_tpu.engine import TpuConsensusEngine
from hashgraph_tpu.engine.adaptive import AdaptiveTimeoutBook
from hashgraph_tpu.obs.accrual import (
    DEFAULT_MAX_PHI,
    PhiAccrual,
    phi_from_deviation,
)
from hashgraph_tpu.obs.health import DEFAULT_PHI_THRESHOLD, HealthMonitor
from hashgraph_tpu.obs.registry import MetricsRegistry
from hashgraph_tpu.scope_config import ScopeConfig, ScopeConfigBuilder

from common import NOW, random_stub_signer


# ── φ-accrual math ─────────────────────────────────────────────────────


def test_phi_from_deviation_shape():
    assert phi_from_deviation(0.0) == 0.0
    assert phi_from_deviation(-3.0) == 0.0
    # Monotone non-decreasing across the erfc/asymptotic switch at x=8.
    xs = [0.5, 1.0, 2.0, 4.0, 7.9, 8.0, 8.1, 20.0, 37.0, 40.0, 100.0]
    phis = [phi_from_deviation(x) for x in xs]
    assert phis == sorted(phis)
    assert all(math.isfinite(p) for p in phis)
    # phi=1 means "~10% of intervals run this late": Q(x)=0.1 at x≈1.2816.
    assert phi_from_deviation(1.2816) == pytest.approx(1.0, abs=1e-3)
    # Clamped: a silence 100 sigmas out is operationally identical to 64.
    assert phi_from_deviation(100.0) == DEFAULT_MAX_PHI
    assert phi_from_deviation(100.0, max_phi=10.0) == 10.0


def test_phi_accrual_min_samples_gate():
    acc = PhiAccrual(min_samples=8)
    now = 0.0
    for _ in range(8):  # 8 heartbeats = 7 intervals < min_samples
        now += 10.0
        acc.heartbeat(now)
    assert acc.sample_count == 7
    assert acc.phi(now + 1_000.0) == 0.0
    acc.heartbeat(now + 10.0)  # 8th interval: distribution trusted
    assert acc.phi(now + 1_010.0) > 0.0


def test_phi_accrual_monotone_in_silence_and_resets_on_heartbeat():
    acc = PhiAccrual()
    now = 0.0
    for _ in range(16):
        now += 10.0
        acc.heartbeat(now)
    prev = -1.0
    for silence in range(0, 200, 5):
        cur = acc.phi(now + silence)
        assert cur >= prev
        prev = cur
    assert prev > DEFAULT_PHI_THRESHOLD  # long silence convicts
    acc.heartbeat(now + 200.0)
    assert acc.phi(now + 200.0) == 0.0  # suspicion revised instantly


def test_phi_accrual_same_tick_coalesces_and_window_bounds():
    acc = PhiAccrual(window=4)
    acc.heartbeat(5.0)
    for _ in range(10):  # a burst in one batch is ONE observation
        acc.heartbeat(5.0)
    assert acc.sample_count == 0
    for i in range(50):
        acc.heartbeat(5.0 + (i + 1) * 3.0)
    assert acc.sample_count == 4  # bounded history
    assert acc.mean() == pytest.approx(3.0)


def test_phi_accrual_jitter_earns_wider_tolerance():
    """A peer with jittery arrivals must be suspected LESS at the same
    silence than a metronome-regular peer with the same mean — the whole
    point of replacing one fixed bar with per-peer distributions."""
    regular, jittery = PhiAccrual(), PhiAccrual()
    now_r = now_j = 0.0
    for i in range(32):
        now_r += 10.0
        regular.heartbeat(now_r)
        now_j += 10.0 + (6.0 if i % 2 else -6.0)  # mean 10, wide spread
        jittery.heartbeat(now_j)
    silence = 40.0
    assert jittery.phi(now_j + silence) < regular.phi(now_r + silence)
    # The metronome still gets the variance floor: one tick late is not
    # certain death.
    assert regular.phi(now_r + 10.5) < DEFAULT_PHI_THRESHOLD


def test_phi_accrual_silence_at_mean_is_not_suspicious():
    """Silence equal to the mean is x = 0 in exact arithmetic. Clock
    rounding that puts it a few ulps past the mean must not step phi from
    0 to -log10(1/2), on a jittery history or on a regular one."""
    mean = 51.99997810579886
    d = 0.75 * mean
    regular, jittery = PhiAccrual(), PhiAccrual()
    now_r = now_j = 0.0
    regular.heartbeat(now_r)
    jittery.heartbeat(now_j)
    for _ in range(20):
        for gap in (mean, mean):
            now_r += gap
            regular.heartbeat(now_r)
        for gap in (mean - d, mean + d):
            now_j += gap
            jittery.heartbeat(now_j)
    # now_j + mean - now_j rounds to a silence 2.3e-13 past the mean.
    assert (now_j + mean) - now_j > jittery.mean()
    assert jittery.phi(now_j + mean) == 0.0
    assert regular.phi(now_r + mean) == 0.0
    # One tick past the mean is a real excess, and phi rises above 0.
    assert jittery.phi(now_j + mean + 1.0) > 0.0


# ── watchdog integration (stale OR phi, read-time grading) ─────────────


def _monitor(**kw) -> HealthMonitor:
    kw.setdefault("registry", MetricsRegistry())
    return HealthMonitor(**kw)


def test_watchdog_flags_phi_before_binary_floor():
    mon = _monitor(stale_after=10_000.0)
    peer = b"\x01" * 32
    now = 0
    for _ in range(16):
        now += 10
        mon.note_admitted({peer: 1}, now)
    # Silence far past the peer's own cadence but far under the binary
    # floor: only the φ detector can see it.
    probe = now + 500
    assert peer.hex() in mon.watchdog(now=probe)
    card = mon.snapshot(now=probe)["peers"][peer.hex()]
    assert card["phi"] >= card["phi_threshold"]
    # The binary floor itself is untouched — the silence is well inside
    # stale_after, so the conviction is the φ detector's alone.
    assert probe - card["last_seen"] <= card["stale_after"]
    # Read-time grading: a heartbeat clears the conviction with no
    # explicit reset call anywhere.
    mon.note_admitted({peer: 1}, probe)
    assert peer.hex() not in mon.watchdog(now=probe)


def test_phi_threshold_none_disables_accrual_convictions():
    mon = _monitor(stale_after=10_000.0, phi_threshold=None)
    peer = b"\x02" * 32
    now = 0
    for _ in range(16):
        now += 10
        mon.note_admitted({peer: 1}, now)
    assert mon.watchdog(now=now + 500) == []  # binary floor only
    assert peer.hex() in mon.watchdog(now=now + 20_000)


# ── adaptive timeout learner ───────────────────────────────────────────


def _adaptive_config(lo=1.0, hi=60.0, default=30.0) -> ScopeConfig:
    return (
        ScopeConfigBuilder()
        .p2p_preset()
        .with_timeout(default)
        .with_timeout_bounds(lo, hi)
        .build()
    )


def test_book_noop_without_bounds():
    book = AdaptiveTimeoutBook()
    static = ScopeConfigBuilder().p2p_preset().build()
    assert book.current("s", static) is None
    assert book.on_timeout("s", static) is None
    assert book.on_decided("s", static, 1.0) is None
    assert book.current("s", None) is None
    assert book.snapshot()["scopes"] == {}


def test_book_backoff_and_decay():
    book = AdaptiveTimeoutBook()
    cfg = _adaptive_config(lo=1.0, hi=60.0, default=4.0)
    assert book.current("s", cfg) == 4.0  # seeds at the static default
    assert book.on_timeout("s", cfg) == 8.0  # geometric backoff
    assert book.on_timeout("s", cfg) == 16.0
    for _ in range(10):
        book.on_timeout("s", cfg)
    assert book.current("s", cfg) == 60.0  # clamped at timeout_max
    # Successes decay toward observed_p99 * headroom from above.
    target = 2.0 * book.headroom
    prev = book.current("s", cfg)
    for _ in range(50):
        cur = book.on_decided("s", cfg, 2.0)
        assert cur <= prev
        prev = cur
    assert prev == pytest.approx(target, rel=0.05)
    # A zero observation (empty SLO window) must never drag the value.
    assert book.on_decided("s", cfg, 0.0) == prev
    snap = book.snapshot()
    assert snap["backoffs_total"] == 12 and snap["decays_total"] == 50


def test_book_lru_bound():
    book = AdaptiveTimeoutBook(max_scopes=4)
    cfg = _adaptive_config()
    for i in range(32):
        book.on_timeout(f"scope-{i}", cfg)
    assert len(book.snapshot()["scopes"]) == 4
    assert "scope-31" in book.snapshot()["scopes"]


def test_book_ctor_validation():
    with pytest.raises(ValueError):
        AdaptiveTimeoutBook(backoff=1.0)
    with pytest.raises(ValueError):
        AdaptiveTimeoutBook(decay=0.0)
    with pytest.raises(ValueError):
        AdaptiveTimeoutBook(headroom=0.9)


# ── ScopeConfig bounds + WAL persistence ───────────────────────────────


def test_scope_config_bounds_validation():
    with pytest.raises(ValueError):
        ScopeConfigBuilder().p2p_preset().with_timeout_bounds(
            1.0, None
        ).build()
    with pytest.raises(ValueError):
        ScopeConfigBuilder().p2p_preset().with_timeout_bounds(
            None, 30.0
        ).build()
    with pytest.raises(ValueError):
        ScopeConfigBuilder().p2p_preset().with_timeout_bounds(
            30.0, 1.0
        ).build()
    assert not ScopeConfigBuilder().p2p_preset().build().adaptive_timeout_enabled()
    assert _adaptive_config().adaptive_timeout_enabled()


def test_wal_codec_round_trips_timeout_bounds():
    from hashgraph_tpu.wal.format import (
        Reader,
        decode_scope_config,
        encode_scope_config,
    )

    for cfg in (
        _adaptive_config(lo=0.25, hi=12.5),
        ScopeConfigBuilder().gossipsub_preset().build(),
    ):
        blob = encode_scope_config(cfg)
        out = decode_scope_config(Reader(blob))
        assert out.timeout_min == cfg.timeout_min
        assert out.timeout_max == cfg.timeout_max
        assert out.adaptive_timeout_enabled() == cfg.adaptive_timeout_enabled()
        # Canonical: fingerprints hash these bytes.
        assert encode_scope_config(out) == blob


# ── engine wiring ──────────────────────────────────────────────────────


def test_engine_adaptive_timeout_learns_from_fired_timeouts():
    from hashgraph_tpu import CreateProposalRequest

    engine = TpuConsensusEngine(
        random_stub_signer(), capacity=16, voter_capacity=8
    )
    scope = "adaptive-scope"
    engine.set_scope_config(scope, _adaptive_config(lo=1.0, hi=60.0, default=5.0))
    assert engine.adaptive_timeout(scope) == 5.0
    # Static scope: the advisory readout is the config default, always.
    engine.set_scope_config("static", ScopeConfigBuilder().p2p_preset().build())
    static_default = engine.get_scope_config("static").default_timeout
    assert engine.adaptive_timeout("static") == static_default

    proposal = engine.create_proposal(
        scope,
        CreateProposalRequest(
            name="p",
            payload=b"",
            proposal_owner=b"o",
            expected_voters_count=4,
            expiration_timestamp=100,
            liveness_criteria_yes=False,
        ),
        NOW,
    )
    # 0 of 4 votes, liveness False: the timeout decides False
    # (silent-as-no), and the FIRED timeout is the learning signal.
    assert (
        engine.handle_consensus_timeout(scope, proposal.proposal_id, NOW + 60)
        is False
    )
    # The fired timeout backed the scope's learned value off.
    assert engine.adaptive_timeout(scope) == 10.0
    snap = engine.adaptive_timeout_snapshot()
    assert snap["backoffs_total"] == 1
    assert snap["scopes"][scope] == 10.0


# ── overload admission: bridge shed + gossip deferral ──────────────────


class _FakeConn:
    def __init__(self):
        self.sent = b""

    def sendall(self, data: bytes) -> None:
        self.sent += data


def test_bridge_sheds_retry_after_past_admission_limit():
    import threading

    from hashgraph_tpu.bridge import protocol as P
    from hashgraph_tpu.bridge.server import BridgeServer

    server = BridgeServer(
        capacity=4, voter_capacity=4, ordered_admission_limit=2
    )

    class _Lane:
        def __init__(self, depth: int):
            self._depth = depth

        def depth(self) -> int:
            return self._depth

    class _State:
        def __init__(self, depth: int):
            self.write_lock = threading.Lock()
            self.ordered = _Lane(depth)

    mutating = next(iter(P.MUTATING_OPCODES))
    read_only = next(
        op for op in range(64) if op not in P.MUTATING_OPCODES
    )
    # Below the limit, and for read-only frames at ANY depth: admitted.
    conn = _FakeConn()
    assert not server._shed_retry_after(conn, _State(1), mutating, 7)
    assert not server._shed_retry_after(conn, _State(500), read_only, 7)
    assert conn.sent == b""
    # At the limit: shed with a typed, depth-scaled hint.
    assert server._shed_retry_after(conn, _State(2), mutating, 7)
    status, corr, cursor = P.parse_frame(conn.sent[4:], tagged=True)
    assert status == P.STATUS_RETRY_AFTER
    assert corr == 7
    hint = float(cursor.string())
    assert 0.0 < hint <= 1.0


def test_gossip_node_defers_during_retry_after_window():
    from hashgraph_tpu.bridge import protocol as P
    from hashgraph_tpu.bridge.client import BridgeError
    from hashgraph_tpu.gossip.node import GossipNode

    class _Transport:
        def __init__(self):
            self.requests = 0

        def try_request(self, name, opcode, payload):
            self.requests += 1
            return None  # backpressure-shed; irrelevant to this test

        def stats(self):
            return {}

        def close(self):
            pass

    class _RetryAfterFuture:
        def result(self, timeout=None):
            raise BridgeError(P.STATUS_RETRY_AFTER, "0.5")

    transport = _Transport()
    node = GossipNode("n0", transport=transport)
    meta = [(1, "scope-a", 3)]
    # A typed shed opens the peer's backoff window and books the frame
    # as deferred (not failed) with its scopes dirty for anti-entropy.
    node._harvest("peer-1", meta, _RetryAfterFuture(), None)
    assert node._retry_after["peer-1"] > 0
    assert node._deferred_frames == 1
    assert node._failed_frames == 0
    assert node._dirty["peer-1"] == {"scope-a"}
    # While the window is open, hot-path frames defer WITHOUT touching
    # the wire — the node must not re-offer load the peer just shed.
    node._send_frame("peer-1", b"payload", meta)
    assert transport.requests == 0
    assert node._deferred_frames == 2
    # A garbled hint falls back to a short fixed window, never a crash.
    class _GarbledFuture:
        def result(self, timeout=None):
            raise BridgeError(P.STATUS_RETRY_AFTER, "not-a-float")

    node._harvest("peer-2", meta, _GarbledFuture(), None)
    assert node._retry_after["peer-2"] > 0


# ── sim layer: liveness verdict + A/B override seam ────────────────────


def test_flapping_links_scenario_and_static_baseline_arm():
    from hashgraph_tpu.sim import run_scenario

    run = run_scenario("flapping-links", 7)
    assert run["passed"], run["checks"]
    live = run["verdicts"]["liveness"]
    assert live["ok"]
    assert live["stale_convictions"] == {}
    assert live["undecidable_sessions"] == 0
    assert 0 < live["max_decide_ticks"] <= live["decide_bound_ticks"]
    assert run["checks"]["phi_suspected_during_flap"]

    # The A/B seam bench.py liveness rides: same scenario, binary-floor-
    # only watchdog. All four verdicts still hold — the arm is blind to
    # the flap (sub-floor silence), not broken.
    base = run_scenario(
        "flapping-links", 7, overrides={"phi_threshold": None}
    )
    assert all(v["ok"] for v in base["verdicts"].values())
    assert not base["checks"]["phi_suspected_during_flap"]
    assert base["verdicts"]["liveness"]["stale_convictions"] == {}


def test_slow_never_dead_scenario_counterfactual():
    from hashgraph_tpu.sim import run_scenario

    run = run_scenario("slow-never-dead", 7)
    assert run["passed"], run["checks"]
    # The variance-aware detector tolerates the slow-but-alive peer; the
    # tight-static counterfactual (computed inside the scenario) would
    # have convicted it.
    assert run["checks"]["slow_peer_never_suspected"]
    assert run["checks"]["metronome_counterfactual_convicts"]
