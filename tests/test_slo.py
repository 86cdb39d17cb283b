"""The SLO plane: windowed quantile sketches, the multi-window
burn-rate alert state machine, bounded incident capture, exemplar text
round-trips, and torn-free concurrent sidecar scrapes under ingest.

Every clock in these tests is injected (a mutable float), so alert
trajectories are exact — no sleeps, no wall-clock flakes."""

import json
import os
import threading
import urllib.request

import pytest

from hashgraph_tpu.obs import MetricsSidecar
from hashgraph_tpu.obs.prometheus import parse_exemplars, render
from hashgraph_tpu.obs.registry import (
    DEFAULT_TIME_BUCKETS,
    MetricsRegistry,
    quantile_from,
)
from hashgraph_tpu.obs.slo import (
    DEFAULT_BURN_THRESHOLD,
    IncidentCapture,
    SloEngine,
    WindowedHistogram,
)


class Clock:
    """An injectable monotonic clock the tests advance explicitly."""

    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


# ── WindowedHistogram ──────────────────────────────────────────────────


class TestWindowedHistogram:
    def test_window_counts_and_quantile(self):
        wh = WindowedHistogram(slice_seconds=10.0, max_age=100.0)
        for k in range(10):
            wh.observe(0.004, 1000.0 + 10 * k)
        counts, total, breaching = wh.window_counts(100.0, 1100.0)
        assert total == 10 and breaching == 0
        q = wh.quantile(0.99, 100.0, 1100.0)
        assert 0.002 < q <= 0.008  # inside the 4ms log bucket's bounds

    def test_old_slices_age_out(self):
        wh = WindowedHistogram(slice_seconds=10.0, max_age=50.0)
        wh.observe(0.001, 1000.0)
        wh.observe(0.001, 1100.0)  # prunes the first slice (>max_age)
        _, total, _ = wh.window_counts(1000.0, 1100.0)
        assert total == 1

    def test_narrow_window_excludes_older_slices(self):
        wh = WindowedHistogram(slice_seconds=10.0, max_age=1000.0)
        wh.observe(0.001, 1000.0, breaching=False)
        wh.observe(0.5, 1200.0, breaching=True)
        _, total_fast, breach_fast = wh.window_counts(50.0, 1200.0)
        assert (total_fast, breach_fast) == (1, 1)
        _, total_all, breach_all = wh.window_counts(1000.0, 1200.0)
        assert (total_all, breach_all) == (2, 1)

    def test_summary_shape(self):
        wh = WindowedHistogram()
        wh.observe(0.01, 1000.0)
        s = wh.summary(300.0, 1000.0)
        assert s["count"] == 1
        assert set(s) >= {"count", "p50", "p95", "p99"}

    def test_quantile_from_interpolates(self):
        bounds = DEFAULT_TIME_BUCKETS
        counts = [0] * (len(bounds) + 1)
        idx = next(i for i, b in enumerate(bounds) if 0.01 <= b)
        counts[idx] = 100
        q50 = quantile_from(bounds, counts, 100, 0.50)
        lo = bounds[idx - 1] if idx else 0.0
        assert lo < q50 <= bounds[idx]

    def test_empty_quantile_is_zero(self):
        wh = WindowedHistogram()
        assert wh.quantile(0.99, 300.0, 1000.0) == 0.0


# ── Burn-rate alert state machine ──────────────────────────────────────


class TestBurnRateAlerts:
    def _engine(self, clock, **kw):
        return SloEngine(clock=clock, **kw)

    def test_alert_fires_only_when_both_windows_burn(self, tmp_path):
        clock = Clock()
        slo = self._engine(clock)
        # An hour of healthy traffic fills the slow window.
        for _ in range(30):
            slo.observe("s", 0.005, objective_s=0.05, now=clock())
            clock.tick(30.0)
        assert slo.state(now=clock())["alerts_firing"] == []
        # Sustained breaches push BOTH windows over the threshold.
        for _ in range(10):
            slo.observe("s", 0.5, objective_s=0.05, now=clock())
            clock.tick(10.0)
        state = slo.state(now=clock())
        assert state["alerts_firing"] == ["s"]
        scope = state["scopes"]["s"]
        assert scope["burn_fast"] >= DEFAULT_BURN_THRESHOLD
        assert scope["burn_slow"] >= DEFAULT_BURN_THRESHOLD
        assert scope["alerts_total"] == 1

    def test_alert_clears_when_fast_window_recovers(self):
        clock = Clock()
        slo = self._engine(clock)
        for _ in range(30):
            slo.observe("s", 0.005, objective_s=0.05, now=clock())
            clock.tick(30.0)
        for _ in range(10):
            slo.observe("s", 0.5, objective_s=0.05, now=clock())
            clock.tick(10.0)
        assert slo.state(now=clock())["alerts_firing"] == ["s"]
        clock.tick(400.0)  # breaches age out of the fast window
        slo.observe("s", 0.005, objective_s=0.05, now=clock())
        state = slo.state(now=clock())
        assert state["alerts_firing"] == []
        # One firing EPISODE, not one per breaching observation.
        assert state["scopes"]["s"]["alerts_total"] == 1

    def test_short_blip_does_not_fire(self):
        clock = Clock()
        slo = self._engine(clock)
        for _ in range(200):
            slo.observe("s", 0.005, objective_s=0.05, now=clock())
            clock.tick(15.0)
        # One breach in 200: the slow-window burn stays far under 14.4.
        slo.observe("s", 0.5, objective_s=0.05, now=clock())
        assert slo.state(now=clock())["alerts_firing"] == []

    def test_best_effort_scopes_never_alert(self):
        clock = Clock()
        slo = self._engine(clock)
        for _ in range(50):
            slo.observe("free", 10.0, now=clock())  # no objective
            clock.tick(5.0)
        state = slo.state(now=clock())
        assert state["alerts_firing"] == []
        assert state["scopes"]["free"]["objective_s"] is None

    def test_disabled_kill_switch_skips_everything(self):
        clock = Clock()
        slo = self._engine(clock)
        slo.enabled = False
        slo.observe("s", 9.9, objective_s=0.01, now=clock())
        state = slo.state(now=clock())
        assert state["scopes"] == {} and state["global"]["count"] == 0
        slo.enabled = True
        slo.observe("s", 9.9, objective_s=0.01, now=clock())
        assert slo.state(now=clock())["global"]["count"] == 1

    def test_scope_lru_pins_objective_scopes(self):
        clock = Clock()
        slo = self._engine(clock, max_scopes=4)
        slo.observe("pinned", 0.1, objective_s=0.05, now=clock())
        for k in range(32):
            slo.observe(f"churn-{k}", 0.001, now=clock())
        state = slo.state(now=clock())
        assert len(state["scopes"]) <= 4
        assert "pinned" in state["scopes"]

    def test_per_shard_windows_tracked(self):
        clock = Clock()
        slo = self._engine(clock)
        slo.observe("a", 0.001, shard="s0", now=clock())
        slo.observe("b", 0.2, shard="s1", now=clock())
        shards = slo.state(now=clock())["shards"]
        assert set(shards) == {"s0", "s1"}
        assert shards["s1"]["p99"] > shards["s0"]["p99"]

    def test_registry_families_installed(self):
        from hashgraph_tpu.obs.slo import (
            SLO_ALERTS_FIRING,
            SLO_BREACHES_TOTAL,
            SLO_DECISION_P99_SECONDS,
        )

        clock = Clock()
        reg = MetricsRegistry()
        slo = SloEngine(registry=reg, clock=clock)
        for _ in range(30):
            slo.observe("s", 0.005, shard="sh0", objective_s=0.05, now=clock())
            clock.tick(30.0)
        for _ in range(10):
            slo.observe("s", 0.5, shard="sh0", objective_s=0.05, now=clock())
            clock.tick(10.0)
        text = reg.render_prometheus()
        assert f"{SLO_BREACHES_TOTAL} 10" in text
        assert f"{SLO_ALERTS_FIRING} 1" in text
        assert f'{SLO_DECISION_P99_SECONDS}{{shard="sh0"}}' in text
        assert f'{SLO_DECISION_P99_SECONDS}{{scope="s"}}' in text


# ── Incident capture ───────────────────────────────────────────────────


class TestIncidentCapture:
    def test_capture_writes_linked_artifacts(self, tmp_path):
        clock = Clock()
        cap = IncidentCapture(str(tmp_path), clock=clock)
        path = cap.capture(
            "slo_breach",
            scope="s",
            shard="sh0",
            trace_hex="ab" * 16,
            latency_s=0.5,
            objective_s=0.05,
        )
        assert path is not None
        meta = json.load(open(os.path.join(path, "incident.json")))
        assert meta["trace_id"] == "ab" * 16
        assert meta["latency_s"] == 0.5 and meta["objective_s"] == 0.05
        doc = json.load(open(os.path.join(path, "trace.json")))
        assert "traceEvents" in doc  # Perfetto/chrome://tracing loadable
        assert os.path.exists(os.path.join(path, "flight.jsonl"))

    def test_cooldown_collapses_breach_storm(self, tmp_path):
        clock = Clock()
        cap = IncidentCapture(str(tmp_path), cooldown_s=60.0, clock=clock)
        assert cap.capture("slo_breach", scope="s") is not None
        assert cap.capture("slo_breach", scope="s") is None  # cooled down
        clock.tick(61.0)
        assert cap.capture("slo_breach", scope="s") is not None
        assert len(cap.incidents()) == 2

    def test_max_incidents_gc_keeps_newest(self, tmp_path):
        clock = Clock()
        cap = IncidentCapture(
            str(tmp_path), max_incidents=3, cooldown_s=0.0, clock=clock
        )
        for k in range(6):
            clock.tick(1.0)
            cap.capture("slo_breach", scope=f"s{k}")
        names = cap.incidents()
        assert len(names) == 3
        assert names[-1].startswith("incident-000006")

    def test_disabled_without_root(self, monkeypatch):
        monkeypatch.delenv("HASHGRAPH_INCIDENT_DIR", raising=False)
        cap = IncidentCapture(None)
        assert not cap.enabled
        assert cap.capture("slo_breach", scope="s") is None

    def test_engine_captures_exactly_once_per_cooldown(self, tmp_path):
        clock = Clock()
        cap = IncidentCapture(str(tmp_path), cooldown_s=10**9, clock=clock)
        slo = SloEngine(clock=clock, capture=cap)
        for _ in range(20):
            slo.observe("s", 0.5, objective_s=0.05, now=clock())
            clock.tick(10.0)
        assert len(cap.incidents()) == 1


# ── Engine wiring: ScopeConfig objective -> decided() -> slo_engine ────


class TestEngineWiring:
    def test_decide_p99_ms_config_field(self):
        from hashgraph_tpu.scope_config import ScopeConfig, ScopeConfigBuilder

        cfg = ScopeConfigBuilder().with_decide_p99_ms(50.0).build()
        assert cfg.decide_p99_ms == 50.0
        assert cfg.clone().decide_p99_ms == 50.0
        with pytest.raises(ValueError):
            ScopeConfig(decide_p99_ms=-1.0).validate()

    def test_decision_feeds_global_slo_engine(self):
        from hashgraph_tpu import (
            CreateProposalRequest,
            build_vote,
        )
        from hashgraph_tpu.engine import TpuConsensusEngine
        from hashgraph_tpu.obs import slo_engine
        from hashgraph_tpu.scope_config import ScopeConfigBuilder

        from common import NOW, random_stub_signer

        slo_engine.reset()
        engine = TpuConsensusEngine(
            random_stub_signer(), capacity=8, voter_capacity=8
        )
        engine.set_scope_config(
            "slo-scope", ScopeConfigBuilder().with_decide_p99_ms(50.0).build()
        )
        request = CreateProposalRequest("p", b"", b"o", 2, 100, True)
        pid = engine.create_proposal("slo-scope", request, NOW).proposal_id
        for _ in range(2):
            vote = build_vote(
                engine.get_proposal("slo-scope", pid),
                True,
                random_stub_signer(),
                NOW + 1,
            )
            engine.ingest_votes([("slo-scope", vote)], NOW + 1)
        state = slo_engine.state()
        entry = state["scopes"].get("slo-scope")
        assert entry is not None and entry["count"] >= 1
        # The declared objective arrived in seconds, and the decision's
        # trace id landed as the latency histogram's exemplar.
        assert entry["objective_s"] == pytest.approx(0.05)
        from hashgraph_tpu.obs import DECISION_LATENCY

        exemplars = engine.metrics.histogram(DECISION_LATENCY).exemplars()
        assert any(
            entry_[1] is not None and len(entry_[1]) == 32
            for entry_ in exemplars.values()
        )
        slo_engine.reset()

    def test_kill_switch_leaves_decisions_untouched(self):
        """The global plane switched off: sessions on objective scopes
        still decide, and the plane records none of it."""
        from hashgraph_tpu import CreateProposalRequest, build_vote
        from hashgraph_tpu.engine import TpuConsensusEngine
        from hashgraph_tpu.obs import slo_engine
        from hashgraph_tpu.scope_config import ScopeConfigBuilder

        from common import NOW, random_stub_signer

        slo_engine.reset()
        engine = TpuConsensusEngine(
            random_stub_signer(), capacity=8, voter_capacity=8
        )
        config = ScopeConfigBuilder().with_decide_p99_ms(0.001).build()
        signers = [random_stub_signer() for _ in range(3)]
        slo_engine.enabled = False
        try:
            for k in range(2):
                scope = f"off-{k}"
                engine.set_scope_config(scope, config)
                request = CreateProposalRequest("p", b"", b"o", 3, 100, True)
                pid = engine.create_proposal(scope, request, NOW).proposal_id
                proposal = engine.get_proposal(scope, pid)
                batch = []
                for signer in signers:
                    vote = build_vote(proposal, True, signer, NOW + 1)
                    proposal.votes.append(vote)
                    batch.append((scope, vote))
                engine.ingest_votes(batch, NOW + 1)
                assert engine.get_consensus_result(scope, pid) is True
            state = slo_engine.state()
        finally:
            slo_engine.enabled = True
            slo_engine.reset()
        assert state["scopes"] == {} and state["global"]["count"] == 0
        assert state["alerts_firing"] == []


# ── Exemplars: render + text round-trip ────────────────────────────────


class TestExemplars:
    def test_exemplar_round_trip(self):
        reg = MetricsRegistry()
        hist = reg.histogram("rt_seconds")
        hist.observe(0.004, exemplar="fe" * 16)
        hist.observe(0.004)  # no exemplar: the recorded one sticks
        text = render(reg)
        found = parse_exemplars(text)
        assert "rt_seconds_bucket" in found
        (ex,) = found["rt_seconds_bucket"]
        assert ex["trace_id"] == "fe" * 16
        assert ex["value"] == pytest.approx(0.004)
        assert ex["le"] is not None

    def test_exemplar_per_bucket_latest_wins(self):
        reg = MetricsRegistry()
        hist = reg.histogram("latest_seconds")
        hist.observe(0.004, exemplar="aa" * 16)
        hist.observe(0.004, exemplar="bb" * 16)
        exemplars = hist.exemplars()
        (entry,) = exemplars.values()
        assert entry[1] == "bb" * 16

    def test_no_exemplar_no_suffix(self):
        reg = MetricsRegistry()
        reg.histogram("plain_seconds").observe(0.004)
        assert parse_exemplars(render(reg)) == {}


# ── Concurrent sidecar scrapes during ingest ───────────────────────────


class TestConcurrentScrapes:
    def test_scrapes_never_tear_during_ingest(self):
        reg = MetricsRegistry()
        counter = reg.counter("ingest_total")
        hist = reg.histogram("ingest_seconds")
        clock = Clock()
        slo = SloEngine(registry=reg, clock=clock)
        sidecar = MetricsSidecar(reg, slo_fn=lambda: slo.state(now=clock()))
        host, port = sidecar.start()
        stop = threading.Event()
        errors: list = []

        def ingest():
            k = 0
            while not stop.is_set():
                counter.inc()
                hist.observe(0.001 * (k % 7 + 1), exemplar=f"{k:032x}")
                slo.observe(
                    f"s{k % 3}", 0.002, objective_s=0.05, now=clock()
                )
                k += 1

        def scrape():
            try:
                for _ in range(25):
                    with urllib.request.urlopen(
                        f"http://{host}:{port}/metrics", timeout=10
                    ) as rsp:
                        text = rsp.read().decode()
                    # Torn text would break these invariants: complete
                    # final line, TYPE before samples, and a histogram's
                    # +Inf bucket equal to its _count (single-moment
                    # snapshot per histogram).
                    assert text.endswith("\n")
                    assert text.index(
                        "# TYPE ingest_seconds histogram"
                    ) < text.index("ingest_seconds_bucket")
                    inf = count = None
                    for line in text.splitlines():
                        if line.startswith('ingest_seconds_bucket{le="+Inf"'):
                            inf = int(line.split(" # ")[0].rsplit(" ", 1)[-1])
                        elif line.startswith("ingest_seconds_count"):
                            count = int(line.rsplit(" ", 1)[-1])
                    assert inf is not None and inf == count
                    with urllib.request.urlopen(
                        f"http://{host}:{port}/slo", timeout=10
                    ) as rsp:
                        body = json.loads(rsp.read())
                    # /slo and /metrics stay mutually consistent: both
                    # surfaces exist and agree the plane is enabled.
                    assert body["enabled"] is True
                    assert set(body["scopes"]) <= {"s0", "s1", "s2"}
            except Exception as exc:  # pragma: no cover - failure detail
                errors.append(exc)

        writer = threading.Thread(target=ingest, daemon=True)
        scrapers = [
            threading.Thread(target=scrape, daemon=True) for _ in range(4)
        ]
        writer.start()
        for t in scrapers:
            t.start()
        for t in scrapers:
            t.join(timeout=60)
        stop.set()
        writer.join(timeout=10)
        sidecar.stop()
        assert not errors, errors[0]

    def test_slo_endpoint_serves_engine_state(self):
        clock = Clock()
        reg = MetricsRegistry()
        slo = SloEngine(registry=reg, clock=clock)
        slo.observe("s", 0.005, objective_s=0.05, now=clock())
        sidecar = MetricsSidecar(reg, slo_fn=lambda: slo.state(now=clock()))
        host, port = sidecar.start()
        try:
            with urllib.request.urlopen(
                f"http://{host}:{port}/slo", timeout=5
            ) as rsp:
                body = json.loads(rsp.read())
        finally:
            sidecar.stop()
        assert body["scopes"]["s"]["objective_s"] == 0.05
        assert body["alerts_firing"] == []
