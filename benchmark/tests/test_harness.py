"""The benchmark's own tests, all on the CPU: the trace reduction on a
hand-built trace, the generator's determinism, the reference's rules,
the contract's naming rules, and whole runs of the harness at a tiny size
(the timed path correct, and each planted fault caught).

Run with ``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests``.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark import loadgen, plugins, reference, traffic, wire
from benchmark.trace_reduce import reduce_profile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _event(meta, offset_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {offset_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _plane(pid, name, lines, names):
    body = "".join(
        f"lines {{ id: {i + 1} name: \"{ln}\" timestamp_ns: 0 "
        + " ".join(_event(*e) for e in events) + " }\n"
        for i, (ln, events) in enumerate(lines))
    meta = "".join(f"event_metadata {{ key: {k} value {{ id: {k} name: "
                   f"\"{v}\" }} }}\n" for k, v in names.items())
    return f"planes {{ id: {pid} name: \"{name}\"\n{body}{meta}}}\n"


SYNTHETIC = (
    _plane(1, "/host:CPU", [("main", [(1, 1000, 0), (2, 11000, 0),
                                      (3, 5000, 2000)])],
           {1: "bench.window_start", 2: "bench.window_end",
            3: "PjitFunction(ingest)"})
    + _plane(2, "/device:TPU:0", [
        ("XLA Ops", [(1, 0, 3000), (2, 2000, 2000), (3, 8000, 1000),
                     (4, 10500, 1500)]),
        ("XLA Modules", [(5, 0, 4000), (6, 8000, 1000)])],
        {1: "fusion.1", 2: "fusion.2", 3: "all-reduce.3", 4: "fusion.4",
         5: "jit_ingest(11)", 6: "jit_decide(12)"})
    + _plane(3, "/device:TPU:1", [("XLA Ops", [(1, 1000, 1000)])],
             {1: "fusion.1"})
)


def test_trace_reduction_by_hand():
    from jax.profiler import ProfileData

    s = reduce_profile(ProfileData.from_text_proto(SYNTHETIC), n_devices=2)
    # Window [1000, 11000) ns. Chip 0: [1000,4000) + [8000,9000) +
    # [10500,11000) = 4,500 ns busy; chip 1: 1,000 ns.
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_by_device == pytest.approx([4.5e-6, 1.0e-6])
    assert s.busy_s == pytest.approx(2.75e-6)
    assert s.collective_s == pytest.approx(1.0e-6)
    # Programs clipped to the window: ingest [1000,4000), decide 1,000 ns.
    assert s.module_s == pytest.approx({"jit_ingest": 3e-6,
                                        "jit_decide": 1e-6})
    # Idle gaps on chip 0, longest first: [4000,8000) while the host ran
    # the ingest call, then [9000,10500).
    assert s.gaps[0] == ("PjitFunction(ingest)", pytest.approx(4e-6))
    assert s.gaps[1] == ("host idle", pytest.approx(1.5e-6))
    bd = s.breakdown()
    # Operations named by their program; fusion.1 ran 2,000 ns inside
    # jit_ingest on chip 0 (its chip-1 run has no program around it).
    assert bd["device_ops"][0] == ["jit_ingest/fusion.1", pytest.approx(1e-6)]
    assert dict(bd["device_ops"])["fusion.1"] == pytest.approx(0.5e-6)
    load = __import__("benchmark.run", fromlist=["load_reader"]).load_reader
    ctx = {"trace": s, "votes": 10, "counters": {}}
    assert load("device_idle_share.closed")(ctx) == pytest.approx(72.5)
    assert load("kernel_us_per_vote.closed")(ctx) == pytest.approx(0.4)


RECORDED = os.path.join(ROOT, "benchmark", "tests", "v5e_window.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A short window recorded on one v5e: the reduction finds the device
    plane, its programs and a busy time inside the window."""
    from jax.profiler import ProfileData

    s = reduce_profile(ProfileData.from_file(RECORDED), n_devices=1)
    assert s.n_devices == 1 and 0 < s.busy_s <= s.window_s
    assert any("ingest" in k for k in s.module_s)


@pytest.mark.parametrize("mix_name", ["p2p-ed25519-closed",
                                      "p2p-validated-closed"])
def test_generator_is_deterministic_in_seed(mix_name):
    cfg = dict(plugins.load_json("configs", "p2p-1024"), expected_voters=64)
    mix = dict(plugins.load_json("traffic", mix_name),
               warmup_votes_per_connection=300,
               window_votes_per_connection=600)

    def frames(seed):
        plan = traffic.Plan(seed, cfg, mix, conn=1, peer=1)
        out = plan.setup_frames() + plan.warm_frames()
        window = plan.window_frames()
        out += [next(window) for _ in range(40)]  # past the pregenerated
        return [(f.kind, f.now, f.payload) for f in out]

    a, b, c = frames(2**31 + 5), frames(2**31 + 5), frames(2**31 + 6)
    assert a == b and a != c


class _Runner:
    """Stands in for the connection: records what was sent, and when."""

    def __init__(self):
        self.sent = []

    def send(self, corr, frame, block=True):
        import time

        self.sent.append((corr, frame.due, block, time.monotonic()))
        return self.sent[-1][3]


def test_send_loop_keeps_due_times_and_stop():
    """Frames with a ``due`` time go out at it, without waiting for a free
    in-flight slot (open loop); others as slots allow (closed loop); none
    after ``stop``."""
    import time

    job = loadgen.Job.__new__(loadgen.Job)
    job.frames, job.sent = [], []
    made = [traffic.Frame("votes", 0, b"", due=d)
            for d in (0.05, 0.10, 0.15, 5.0)]
    made.append(traffic.Frame("votes", 0, b""))
    runner, start = _Runner(), time.monotonic()
    late = job._send_all(runner, made, start, stop=start + 0.3)
    assert [s[1] for s in runner.sent] == [0.05, 0.10, 0.15]
    assert [s[2] for s in runner.sent] == [False] * 3
    for (_, due, _, t), lag in zip(runner.sent, late):
        assert t >= start + due and lag == pytest.approx(t - start - due)
    runner = _Runner()
    job._send_all(runner, [traffic.Frame("votes", 0, b"")] * 3,
                  time.monotonic(), stop=time.monotonic() + 10)
    assert [s[2] for s in runner.sent] == [True] * 3


def test_parts_found_by_name(tmp_path, monkeypatch):
    """Schemes, servers, metric readers and a mix's own generator are files
    found by name; a new one is a new file."""
    for c in BENCH["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert hasattr(plugins.load_module(
            "servers", cfg.get("server", "bridge")), "build")
    for w in BENCH["workloads"]:
        mix = plugins.load_json("traffic", w["traffic"])
        scheme = plugins.load_module("schemes", mix["signature"])
        assert {"keys", "sign", "verify", "PROGRAM_SIGNER"} <= set(dir(scheme))
    cfg = dict(plugins.load_json("configs", "p2p-1024"), expected_voters=16)
    mix = dict(plugins.load_json("traffic", "p2p-validated-closed"),
               generator="one_frame_for_test")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "one_frame_for_test.py").write_text(
        "from benchmark import traffic\n"
        "class Plan(traffic.Plan):\n"
        "    def window_frames(self):\n"
        "        return iter([traffic.Frame('votes', 0, b'', due=0.0)])\n")
    monkeypatch.setattr(plugins, "HERE", str(tmp_path))
    job = loadgen.Job({"config": cfg, "mix": mix, "seed": 3, "seconds": 1,
                       "conn": 0, "peer": 1})
    assert type(job.plan).__module__ != "benchmark.traffic"
    assert [f.due for f in job.plan.window_frames()] == [0.0]


def test_reference_rules():
    assert reference.threshold_value(1024, 2 / 3) == 683
    assert reference.threshold_value(64, 2 / 3) == 43
    # P2P: 683 yes decide; the 684th undecided vote exceeds the round cap.
    assert reference.deciding_vote([True] * 700, [True] * 700, 1024, 2 / 3,
                                   "p2p", True) == (682, "yes")
    vals = [i % 2 == 0 for i in range(700)]
    assert reference.deciding_vote(vals, [True] * 700, 1024, 2 / 3, "p2p",
                                   False) == (683, "failed")
    verify = plugins.load_module("schemes", "validated").verify
    node = reference.Node("gossipsub", 2 / 3, 2, verify)
    for k, now in ((1, 10), (2, 10), (3, 11)):
        assert node.deliver("s", k, 64, True, 0, 100, now) == reference.OK
    assert ("s", 1) in node.sessions  # tie at now=10: the later one goes
    assert ("s", 2) not in node.sessions and ("s", 3) in node.sessions


def test_reference_vote_checks():
    ident = b"\x01" * 20
    node = reference.Node("p2p", 2 / 3, 10, plugins.load_module(
        "schemes", "validated").verify)
    node.deliver("s", 9, 4, True, 0, 100, 1)
    h = wire.vote_hash(5, ident, 9, 0, True, b"", b"")
    fields = wire.vote_signed_fields(5, ident, 9, 0, True, b"", b"", h)
    import hashlib

    sig = hashlib.sha256(ident + fields).digest()
    assert node.vote("s", 9, ident, True, 0, 5, b"", b"", h, sig, 1) == 0
    assert node.vote("s", 9, ident, True, 0, 5, b"", b"", h, sig, 1) == \
        reference.DUPLICATE_VOTE
    bad = bytes([sig[0] ^ 1]) + sig[1:]
    assert node.vote("s", 9, ident, True, 0, 5, b"", b"", h, bad, 1) == \
        reference.INVALID_VOTE_SIGNATURE
    assert node.vote("s", 8, ident, True, 0, 5, b"", b"", h, sig, 1) == \
        reference.SESSION_NOT_FOUND


def test_names_units_and_sources():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert len(cfg["source"]) <= 200 and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "configs", w["config"] + ".json"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py"))


def _run(*args, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-m", "benchmark.run", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_no_tpu_means_no_result():
    r = _run("--workload", "p2p1024-validated-closed", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "correct" not in r.stdout


TINY_CFG = json.dumps({"expected_voters": 16, "voter_lanes": 16,
                       "slots": 256, "scopes": 4,
                       "max_sessions_per_scope": 64})
TINY_MIX = json.dumps({"connections": 2, "warmup_votes_per_connection": 1500,
                       "window_votes_per_connection": 8000,
                       "frame": {"dist": "loguniform", "min": 8, "max": 64},
                       "active_per_scope": 4, "reference_share": 0.5,
                       "setup_margin_per_scope": 8})


def _tiny(workload, fault=None, seed=2**31 + 17):
    args = ["--workload", workload, "--seed", str(seed), "--seconds", "1.5",
            "--trace", "0", "--allow-cpu", "--config-override", TINY_CFG,
            "--mix-override", TINY_MIX]
    if fault:
        args += ["--fault", fault]
    r = _run(*args)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_tiny_run_is_correct():
    out = _tiny("p2p1024-ed25519-closed")
    assert out["correct"] is True, out
    assert set(out["metrics"]) == {"votes_per_s", "decide_p50_ms",
                                   "decide_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["control_quorum", "answer_altered",
                                   "half_dropped", "state_unchanged"])
def test_planted_fault_is_not_correct(fault):
    out = _tiny("p2p1024-validated-closed", fault)
    assert out["correct"] is False, out
