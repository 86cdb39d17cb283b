"""Engine and device apply, host side: seconds in ingest_wire_columnar's
apply stage (counter hashgraph_bridge_wire_apply_seconds_total) per vote
replied in the window, in microseconds.

Found by name: metric ``apply_us_per_vote.closed`` in BENCHMARK.json."""


def _per_vote(ctx, key):
    v = ctx["votes"]
    return ctx["counters"][key] / v * 1e6 if v else None


def read(ctx):
    return _per_vote(ctx, "apply_s")
