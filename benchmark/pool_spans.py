"""The proposal pool's spans in a traced window: ``pool.activate`` (stat
``slots``), around each slot activation, and on the sharded pool
``pool.shard_route`` (stats ``rows`` and ``grid_rows``), around the host
routing and placement of each routed batch. The program writes them as
``jax.profiler.TraceAnnotation``s, as it writes the served path's spans.

``span_reduce`` reads the ``bridge.``, ``engine.`` and ``python.`` spans;
the readers of the pool's metrics call :func:`pool_spans`, which reads the
same trace (the running cell's own, checked against the window that
``ctx["trace"]`` measured) for the ``pool.`` spans. It returns None where
there is nothing to read: no trace, or a program that writes no such span.
"""

from __future__ import annotations

import functools
import os
import sys
import time

from .span_reduce import TRACE_ROOT, _workload
from .trace_reduce import WINDOW_END, WINDOW_START, find_xplane

PREFIX = "pool."


class PoolSpans:
    """Pool spans of one window: seconds clipped to the window; durations
    and stat sums of the spans that start in it."""

    def __init__(self, window_s, totals, stat_sums):
        self.window_s = window_s
        self.totals = totals  # name -> seconds clipped to the window
        self.stat_sums = stat_sums  # (name, stat) -> sum; "duration_s" too

    def total(self, name: str) -> "float | None":
        return self.totals.get(name)

    def stat(self, name: str, stat: str) -> float:
        return self.stat_sums.get((name, stat), 0.0)


def reduce_pool_spans(profile) -> "PoolSpans | None":
    marks, events = {}, []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    events.append((e.start_ns, e.start_ns + e.duration_ns,
                                   name, e.stats))
                elif name in (WINDOW_START, WINDOW_END):
                    marks[name] = e.start_ns
    if len(marks) != 2:
        return None
    lo, hi = marks[WINDOW_START], marks[WINDOW_END]
    totals: "dict[str, float]" = {}
    stat_sums: "dict[tuple[str, str], float]" = {}
    for a, b, name, stats in events:
        if b <= lo or a >= hi:
            continue
        totals[name] = totals.get(name, 0.0) + (min(b, hi) - max(a, lo)) * 1e-9
        if a < lo:
            continue
        key = (name, "duration_s")
        stat_sums[key] = stat_sums.get(key, 0.0) + (b - a) * 1e-9
        for stat, value in stats:
            if isinstance(value, (int, float)):
                stat_sums[(name, stat)] = stat_sums.get((name, stat), 0.0) + value
    if not totals:
        return None
    return PoolSpans((hi - lo) * 1e-9, totals, stat_sums)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> "PoolSpans | None":
    from jax.profiler import ProfileData

    t0 = time.monotonic()
    found = reduce_pool_spans(ProfileData.from_file(path))
    took = time.monotonic() - t0
    if found is None:
        print(f"pool spans: none in {path} ({took:.3f} s)", file=sys.stderr)
    else:
        print("pool spans: seconds in the window " + repr(
            {k: round(v, 6) for k, v in found.totals.items()})
            + f", stats {sorted(found.stat_sums.items())} ({took:.3f} s)",
            file=sys.stderr)
    return found


def pool_spans(ctx, argv=None) -> "PoolSpans | None":
    """The window's pool spans, or None where there are none to read."""
    trace = ctx.get("trace")
    workload = _workload(sys.argv if argv is None else argv)
    if trace is None or workload is None:
        return None
    try:
        path = find_xplane(os.path.join(TRACE_ROOT, workload))
    except FileNotFoundError:
        return None
    found = _load(path, os.path.getmtime(path))
    if found is None or abs(found.window_s - trace.window_s) > 1e-6:
        return None
    return found
