"""Run one benchmark cell once and print the contract's result line.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

This process holds the chip and serves: it builds the server that the
cell's configuration names (``benchmark/servers/<server>.py``) with the
configuration's deployment parameters and the mix's signature scheme, and
nothing else. Load comes from ``connections`` child processes
(``benchmark.loadgen``) that never import JAX. Everything that belongs to
one configuration, traffic mix, scheme, server or per-layer metric is a
file of its own, found by name (``benchmark.plugins``).

Set-up (process start to the first timed vote) covers: JAX and server
start, the resident proposals (while the children make their votes), and
warm-up traffic that brings the mix to its steady state and runs every
frame shape and decision path of it. Then every child runs
the window, the end state is read back, ``memory_peak_bytes`` is read, the
server is stopped and freed, and each child replays its traffic through
the plain reference (``benchmark.reference``) for ``correct``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

from benchmark import plugins  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")

COUNTERS = {
    "decode_s": "hashgraph_bridge_wire_decode_seconds_total",
    "crypto_s": "hashgraph_bridge_wire_crypto_seconds_total",
    "apply_s": "hashgraph_bridge_wire_apply_seconds_total",
    "dispatches": "hashgraph_bridge_wire_device_dispatches_total",
    "apply_rows": "hashgraph_bridge_wire_apply_rows_total",
    "verified_sigs": "hashgraph_verified_signatures_total",
}
MIN_VOTES_COMPARED = 1000
MIN_DECISIONS = 200  # a p95 needs some hundreds of samples


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str) -> "tuple[dict, dict, dict, dict]":
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = plugins.load_json("configs", cell["config"])
    mix = plugins.load_json("traffic", cell["traffic"])
    return bench, cell, cfg, mix


class Child:
    def __init__(self, spec: dict):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen"], cwd=ROOT,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items()
                 if not k.startswith("XLA_")},
        )
        self.say(json.dumps(spec))

    def say(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def wait(self, event: str) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"load generator exited before {event!r}")
        got = json.loads(line)
        if got.get("event") != event:
            raise RuntimeError(f"load generator sent {got!r}, not {event!r}")
        return got

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
            try:
                self.proc.wait(30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def all_wait(children, event: str) -> "list[dict]":
    return [c.wait(event) for c in children]


def load_reader(name: str):
    return plugins.load_module("metrics", name).read


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # Test and control hooks; the benchmark's own runs never pass these.
    p.add_argument("--fault", default=None, help=argparse.SUPPRESS)
    p.add_argument("--allow-cpu", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--config-override", default=None, help=argparse.SUPPRESS)
    p.add_argument("--mix-override", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    bench, cell, cfg, mix = find_cell(args.workload)
    cfg.update(json.loads(args.config_override or "{}"))
    mix.update(json.loads(args.mix_override or "{}"))
    seed = args.seed & ((1 << 63) - 1)

    # The children generate their traffic while this process starts JAX
    # and the server.
    children = [
        Child({"seed": seed, "conn": c, "peer": 1, "config": cfg, "mix": mix,
               "seconds": args.seconds})
        for c in range(mix["connections"])
    ]
    try:
        return serve(args, bench, cell, cfg, mix, seed, children)
    finally:
        for c in children:
            c.stop()


def serve(args, bench, cell, cfg, mix, seed, children) -> int:
    import jax

    os.makedirs(os.path.join(CACHE_DIR, "jax"), exist_ok=True)
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CACHE_DIR, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.allow_cpu:
        if platform != "tpu":
            log(f"no TPU found (JAX platform {platform!r}); no result")
            return 2
        if len(devices) < cell["chips"]:
            log(f"cell needs {cell['chips']} chips, JAX sees {len(devices)}")
            return 2
        peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
        if kind not in peaks:
            log(f"device kind {kind!r} is not in benchmark/peaks.json")
            return 2
    used = devices[:cell["chips"]]

    # Every compilation is logged (jax_log_compiles) to a handler that
    # keeps the program names; none may fall inside the window.
    import logging

    compiled: "list[str]" = []

    class Compiles(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith("Compiling "):
                compiled.append(msg[len("Compiling "):][:300])

    jax_log = logging.getLogger("jax")
    jax_log.addHandler(Compiles())
    jax_log.propagate = False
    jax.config.update("jax_log_compiles", True)
    log(f"JAX up on {len(devices)} {kind} at {time.monotonic() - T_START:.3f} s")

    from hashgraph_tpu.obs import registry

    from . import wire

    server = plugins.load_module("servers", cfg.get("server", "bridge")).build(
        cfg, mix, plugins.load_module("schemes", mix["signature"]), args.fault)
    server.start()
    port = server.address[1]
    control = wire.Connection("127.0.0.1", port)
    key = seed.to_bytes(8, "little") * 4
    peer = wire.add_peer(control, key)
    control.close()
    if peer != 1:
        raise RuntimeError(f"first peer got id {peer}")

    log(f"server up at {time.monotonic() - T_START:.3f} s")
    all_wait(children, "built")
    for c in children:
        c.say(f"connect {port}")
    setup = all_wait(children, "setup")
    log(f"resident proposals in at {time.monotonic() - T_START:.3f} s")
    for c in children:
        c.say("warm")
    warm = all_wait(children, "warm")
    log(f"warm-up done at {time.monotonic() - T_START:.3f} s; "
        f"{len(compiled)} programs compiled in set-up")
    if not all(e["ok"] for e in setup + warm):
        raise RuntimeError("set-up or warm-up traffic got no reply")
    log(f"vote generation s per child: {[round(w['gen_s'], 3) for w in warm]}"
        f"; pregenerated window votes: {sum(w['window_votes'] for w in warm)}")

    def counters() -> dict:
        return {k: registry.counter(v).value for k, v in COUNTERS.items()}

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(CACHE_DIR, "trace", args.workload)
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    c0, n_compiles0 = counters(), len(compiled)
    t0 = time.monotonic() + 0.1
    t1 = t0 + args.seconds
    for c in children:
        c.say(f"go {t0!r}")
    if args.trace:
        from .trace_reduce import WINDOW_END, WINDOW_START

        for mark, at in ((WINDOW_START, t0), (WINDOW_END, t1)):
            time.sleep(max(0.0, at - time.monotonic()))
            with jax.profiler.TraceAnnotation(mark):
                pass
    window = all_wait(children, "window")
    c1, window_compiles = counters(), compiled[n_compiles0:]
    trace_s = 0.0
    if args.trace:
        t_tr = time.monotonic()
        jax.profiler.stop_trace()
        trace_s = time.monotonic() - t_tr
    setup_s = t0 - T_START

    for c in children:
        c.say("query")
    all_wait(children, "queried")
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    server.stop()
    del server
    gc.collect()

    for c in children:
        c.say("reference")
    refs = all_wait(children, "reference")
    for c in children:
        c.say("close")

    votes = sum(w["votes_in_window"] for w in window)
    lat = sorted(x for w in window for x in w["decide_latencies_s"])
    attempted = sum(w["attempted"] for w in window)
    failed = sum(w["failed"] for w in window)
    sum_ref = {k: sum(r[k] for r in refs) for k in (
        "status_mismatches", "proposal_mismatches", "result_mismatches",
        "unanswered_frames", "votes_compared", "tampered_rejected",
        "sessions_compared", "sessions_decided")}
    log(f"window: {votes} votes replied in {args.seconds} s over "
        f"{sum(w['frames_in_window'] for w in window)} frames; "
        f"{len(lat)} decisions; compilations in window: "
        f"{len(window_compiles)} {window_compiles}; "
        f"attempted {attempted}, failed {failed}")
    log("load generators: late p50/p99/max s per child: "
        + json.dumps([[round(w['late_p50_s'], 6), round(w['late_p99_s'], 6),
                       round(w['late_max_s'], 6)] for w in window])
        + f"; frames made during the window (pregenerated ran out): "
          f"{sum(w['lazy_frames'] for w in window)}")
    log(f"reference: {json.dumps(sum_ref)}; seconds per child "
        f"{[round(r['reference_s'], 3) for r in refs]}; first mismatches "
        f"{[r['first_mismatches'] for r in refs if r['first_mismatches']]}")

    e2e = {m["name"]: m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    metrics = {}
    if not args.trace:
        values = {"setup_s": setup_s}
        if votes:
            values["votes_per_s"] = votes / args.seconds
        if lat:
            q = statistics.quantiles(lat, n=100, method="inclusive")
            values["decide_p50_ms"] = statistics.median(lat) * 1e3
            values["decide_p95_ms"] = q[94] * 1e3
        for name, m in e2e.items():
            if name in values:
                metrics[name] = {"value": values[name], "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        from .trace_reduce import reduce_trace

        summary = reduce_trace(trace_dir, n_devices=len(used))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        ctx = {"counters": {k: c1[k] - c0[k] for k in c0}, "votes": votes,
               "seconds": args.seconds, "trace": summary,
               "chips": len(used)}
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"trace: {summary.describe()}; stop_trace took {trace_s:.3f} s")
        log("counters over the window: " + json.dumps(ctx["counters"]))

    checks = {
        "status_mismatches": {"value": sum_ref["status_mismatches"],
                              "limit": 0},
        "proposal_mismatches": {"value": sum_ref["proposal_mismatches"],
                                "limit": 0},
        "end_state_mismatches": {"value": sum_ref["result_mismatches"],
                                 "limit": 0},
        "unanswered_frames": {"value": sum_ref["unanswered_frames"],
                              "limit": 0},
        "votes_compared": {"value": sum_ref["votes_compared"],
                           "limit": f">={MIN_VOTES_COMPARED}"},
        "decisions_timed": {"value": len(lat),
                            "limit": f">={MIN_DECISIONS}"},
    }
    correct = (
        all(checks[k]["value"] == 0 for k in (
            "status_mismatches", "proposal_mismatches",
            "end_state_mismatches", "unanswered_frames"))
        and sum_ref["votes_compared"] >= MIN_VOTES_COMPARED
        and len(lat) >= MIN_DECISIONS
    )
    for name, chk in checks.items():
        print(f"check {name}: {chk['value']} (limit {chk['limit']})",
              file=sys.stderr)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
