"""The four-chip cell ``p2p1024-x4-closed`` on the CPU: its residency
arithmetic, and whole tiny runs of the harness on four virtual devices
(the sharded served path correct, with its per-layer metrics, and the
control fault caught).

Run with ``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import plugins, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "p2p1024-x4-closed"
TINY_CFG = json.dumps({"expected_voters": 16, "voter_lanes": 16,
                       "slots": 256, "scopes": 4,
                       "max_sessions_per_scope": 64})
TINY_MIX = json.dumps({"connections": 2, "warmup_votes_per_connection": 1500,
                       "window_votes_per_connection": 8000,
                       "frame": {"dist": "loguniform", "min": 8, "max": 64},
                       "active_per_scope": 4, "reference_share": 0.5,
                       "setup_margin_per_scope": 8})


def test_resident_sessions_follow_from_the_mix():
    """The configuration's ``resident_sessions`` (its cut, in ``reduced``)
    is what the mix makes resident at set-up, and fits the pool."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    cfg = plugins.load_json("configs", cell["config"])
    mix = plugins.load_json("traffic", cell["traffic"])
    resident = sum(
        sum(traffic.Plan(1, cfg, mix, conn).setup.values())
        for conn in range(mix["connections"]))
    assert resident == cfg["resident_sessions"] == 19_456
    assert cfg["reduced"] == ["resident_sessions"]
    assert cell["chips"] == cfg["chips"] == 4
    assert cfg["slots"] // cfg["chips"] == 16_384
    assert resident <= cfg["scopes"] * cfg["max_sessions_per_scope"] <= cfg["slots"]


def _tiny(trace: int, fault: "str | None" = None) -> dict:
    args = [sys.executable, "-m", "benchmark.run", "--workload", CELL,
            "--seed", str(2**31 + 29), "--seconds", "3", "--trace",
            str(trace), "--allow-cpu", "--config-override", TINY_CFG,
            "--mix-override", TINY_MIX]
    if fault:
        args += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(args, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_tiny_sharded_run_is_correct_and_reads_the_pool():
    out = _tiny(trace=1)
    assert out["correct"] is True, out
    assert out["device"]["count"] == 4
    metrics = out["metrics"]
    assert metrics["shard_route_us_per_vote.closed"]["value"] > 0
    assert 0 < metrics["shard_pad_share.closed"]["value"] < 100
    assert metrics["activate_us_per_session.closed"]["value"] > 0


def test_control_fault_on_the_sharded_path_is_not_correct():
    out = _tiny(trace=0, fault="control_quorum")
    assert out["correct"] is False, out
