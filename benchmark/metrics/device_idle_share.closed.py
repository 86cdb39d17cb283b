"""Device: percent of the traced window in which no operation ran on the
device, averaged over the chips used (1 - busy union / window).

Found by name: metric ``device_idle_share.closed`` in BENCHMARK.json."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.window_s <= 0 or not t.busy_by_device:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
