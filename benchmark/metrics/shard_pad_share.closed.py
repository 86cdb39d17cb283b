"""Sharded pool, padding of the routed grid: percent of the [D*B] grid
rows placed in the traced window that carry no real row, from the
``rows`` and ``grid_rows`` stats of the ``pool.shard_route`` spans that
start in it (the program counts the same in
``hashgraph_pool_shard_{rows,grid_rows}_total``).

Found by name: metric ``shard_pad_share.closed`` in BENCHMARK.json."""

from benchmark.pool_spans import pool_spans


def read(ctx):
    found = pool_spans(ctx)
    grid = 0.0 if found is None else found.stat("pool.shard_route", "grid_rows")
    if not grid:
        return None
    return 100.0 * (1.0 - found.stat("pool.shard_route", "rows") / grid)
