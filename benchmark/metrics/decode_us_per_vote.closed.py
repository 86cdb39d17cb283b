"""Wire decode: host seconds the bridge spent decoding vote frames
(counter hashgraph_bridge_wire_decode_seconds_total) per vote replied in
the window, in microseconds.

Found by name: metric ``decode_us_per_vote.closed`` in BENCHMARK.json."""


def _per_vote(ctx, key):
    v = ctx["votes"]
    return ctx["counters"][key] / v * 1e6 if v else None


def read(ctx):
    return _per_vote(ctx, "decode_s")
