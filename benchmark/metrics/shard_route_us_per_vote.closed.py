"""Sharded pool, host routing and placement: seconds inside the
``pool.shard_route`` spans of the traced window (each routed batch's
grouping by owning device, padding to the [D*B] grid and sharded
``device_put``s, for ingest, activation and release alike) per vote
replied in the window, in microseconds. Under ingest the routing runs
inside the planning and device-wait stages of the apply stage.

Found by name: metric ``shard_route_us_per_vote.closed`` in BENCHMARK.json."""

from benchmark.pool_spans import pool_spans


def read(ctx):
    found, votes = pool_spans(ctx), ctx["votes"]
    seconds = None if found is None else found.total("pool.shard_route")
    return None if seconds is None or not votes else seconds / votes * 1e6
