"""From a profiler trace (``.xplane.pb``) of the measured window to the
device numbers: busy time (the union of the intervals in which an
operation ran), time per program and per operation, time in collectives,
and the longest idle gaps with what the host was doing in each.

The window is marked on the host by two annotations the harness records,
``bench.window_start`` and ``bench.window_end``; without them the whole
trace is the window. Device planes are those named ``/device:<kind>:<n>``;
their ``XLA Ops`` line gives operations and their ``XLA Modules`` line the
programs (jitted functions) that ran.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"psum|allreduce|allgather", re.IGNORECASE)
WINDOW_START, WINDOW_END = "bench.window_start", "bench.window_end"


def _union(intervals) -> "list[tuple[float, float]]":
    out: "list[list[float]]" = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


class Summary:
    """Device numbers of one traced window; times in seconds, averaged over
    the chips used where the name says per chip."""

    def __init__(self, window_s, busy_by_device, op_s, module_s,
                 collective_s, gaps):
        self.window_s = window_s
        self.busy_by_device = busy_by_device
        self.busy_s = (sum(busy_by_device) / len(busy_by_device)
                       if busy_by_device else 0.0)
        self.op_s = op_s  # name -> seconds, summed over chips
        self.module_s = module_s  # program name -> seconds, summed
        self.collective_s = collective_s  # summed over chips
        self.gaps = gaps  # [(label, seconds)], longest first, chip 0
        self.n_devices = len(busy_by_device)

    def breakdown(self) -> dict:
        n = max(self.n_devices, 1)
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v / n] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}

    def describe(self) -> str:
        top = sorted(self.module_s.items(), key=lambda kv: -kv[1])[:8]
        return (f"window {self.window_s:.6f} s, busy per chip "
                f"{[round(b, 6) for b in self.busy_by_device]}, programs "
                f"{[[k, round(v, 6)] for k, v in top]}, collectives "
                f"{self.collective_s:.6f} s")


def device_planes(profile, n_devices: "int | None" = None) -> list:
    planes = [p for p in profile.planes
              if re.match(r"^/device:(?!CUSTOM)[A-Za-z]+:\d+$", p.name)]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return planes[:n_devices] if n_devices else planes


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def _host_events(profile):
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events)
    return out


def reduce_profile(profile, n_devices: "int | None" = None) -> Summary:
    host = _host_events(profile)
    marks = {name: start for name, start, _end in host
             if name in (WINDOW_START, WINDOW_END)}
    planes = device_planes(profile, n_devices)
    if WINDOW_START in marks and WINDOW_END in marks:
        lo, hi = marks[WINDOW_START], marks[WINDOW_END]
    else:
        spans = [(a, b) for p in planes for _n, a, b in
                 _events(p, "XLA Ops") + _events(p, "XLA Modules")]
        lo = min((a for a, _ in spans), default=0.0)
        hi = max((b for _, b in spans), default=0.0)
    busy, op_s, module_s, coll = [], {}, {}, 0.0
    first_busy = []
    for i, plane in enumerate(planes):
        modules = sorted((a, b, re.sub(r"\(\d+\)$", "", name))
                         for name, a, b in _events(plane, "XLA Modules"))
        for a, b, key in modules:
            c = _clip(a, b, lo, hi)
            if c is not None:
                module_s[key] = module_s.get(key, 0.0) + (c[1] - c[0]) * 1e-9
        ops = sorted(_events(plane, "XLA Ops") or _events(plane, "XLA Modules"),
                     key=lambda e: e[1])
        clipped, m = [], 0
        for name, a, b in ops:
            c = _clip(a, b, lo, hi)
            if c is None:
                continue
            clipped.append(c)
            d = (c[1] - c[0]) * 1e-9
            if COLLECTIVE.search(name):
                coll += d
            while m < len(modules) and modules[m][1] <= a:
                m += 1
            short = name.split(" = ", 1)[0].lstrip("%")[:80]
            if m < len(modules) and modules[m][0] <= a:
                short = f"{modules[m][2]}/{short}"
            op_s[short] = op_s.get(short, 0.0) + d
        merged = _union(clipped)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if i == 0:
            first_busy = merged
    gaps = []
    edges = [lo] + [x for ab in first_busy for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for a, b in gaps[:10]:
        best, best_overlap = "host idle", 0.0
        for name, ha, hb in host:
            if name.startswith("bench."):
                continue
            overlap = min(b, hb) - max(a, ha)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        labelled.append((best, (b - a) * 1e-9))
    return Summary((hi - lo) * 1e-9, busy, op_s, module_s, coll, labelled)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(trace_dir: str, n_devices: "int | None" = None) -> Summary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)),
                          n_devices)
