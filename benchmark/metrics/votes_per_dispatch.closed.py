"""Batching: rows applied per device dispatch over the window (counters
hashgraph_bridge_wire_apply_rows_total / ..._wire_device_dispatches_total).

Found by name: metric ``votes_per_dispatch.closed`` in BENCHMARK.json."""


def read(ctx):
    d = ctx["counters"]["dispatches"]
    return ctx["counters"]["apply_rows"] / d if d else None
