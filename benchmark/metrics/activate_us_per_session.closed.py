"""Pool, slot activation: seconds inside the ``pool.activate`` spans that
start in the traced window per slot they activated (their ``slots``
stat), in microseconds. In the window each retired session's successor
arrives in a delivery frame of its own and is activated alone, under the
engine lock that the vote path waits on, so the metric moves
``votes_per_s``. Set-up's activations fall before the trace starts and
are not read: batching them would leave this number as it is.

Found by name: metric ``activate_us_per_session.closed`` in BENCHMARK.json."""

from benchmark.pool_spans import pool_spans


def read(ctx):
    found = pool_spans(ctx)
    slots = 0.0 if found is None else found.stat("pool.activate", "slots")
    if not slots:
        return None
    return found.stat("pool.activate", "duration_s") / slots * 1e6
