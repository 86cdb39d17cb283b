"""Kernels: device seconds of the programs the served path runs (every
jitted program in the trace whose name is one of the engine's apply
programs, APPLY below), summed over chips, per vote replied in the window,
in microseconds.

Found by name: metric ``kernel_us_per_vote.closed`` in BENCHMARK.json."""

import re

# Program names as the trace shows them (PERF.md, "Layers").
APPLY = re.compile(r"ingest|decide|timeout|activate|load|release|chain|apply")


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["votes"]:
        return None
    s = sum(v for k, v in t.module_s.items() if APPLY.search(k))
    return s / ctx["votes"] * 1e6 if s else None
