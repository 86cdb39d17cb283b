"""AttributionReport — the round-11/19 busy-share math as a readout.

This module makes the per-component wall-clock attribution of the
served path a first-class, machine-readable report any operator (or
incident bundle) can pull:

- the wire-path stage counters
  (``hashgraph_bridge_wire_{decode,crypto,apply}_seconds_total``),
- the WAL fsync histogram (``wal_fsync_seconds`` sum/count),
- the reactor window/dispatch counters (fused dispatches, rows,
  flush-reason breakdown),
- the continuous profiler's sampled per-role stack counts,

fused into one ``{"stages": {name: {"seconds", "share"}}}`` body whose
shares sum to 1.0 over the instrumented busy time. Beside the stages:

- ``apply_breakdown`` — the ``device_apply`` stage opened up: the sums of
  the per-frame sub-stage histograms that tile it (host validation,
  health accounting, dispatch planning, the device round trip, post-apply
  bookkeeping), each with its share of ``device_apply``;
- ``waits`` — time a frame spent not being served: queued on its
  connection's serial lane, waiting for the engine lock, and the Python
  GC's pauses (which stall every thread).

The report is served three ways (same body each time): the
``OP_PROFILE`` bridge opcode, the sidecar's ``/profile`` endpoint, and
``IncidentCapture``'s ``profile.json``; ``parallel.rollup.
merge_profile_states`` federates host-labelled reports into one fleet
view.
"""

from __future__ import annotations

ATTRIBUTION_SCHEMA = "hashgraph.attribution.v1"

# Instrumented busy-time components, in pipeline order. ``wal_fsync``
# rides the histogram rather than a *_seconds_total counter; everything
# shares one denominator so the shares are comparable across rounds.
STAGE_KEYS = ("wire_decode", "crypto", "device_apply", "wal_fsync")

_STAGE_COUNTERS = {
    "hashgraph_bridge_wire_decode_seconds_total": "wire_decode",
    "hashgraph_bridge_wire_crypto_seconds_total": "crypto",
    "hashgraph_bridge_wire_apply_seconds_total": "device_apply",
}
_WAL_FSYNC_HISTOGRAM = "wal_fsync_seconds"
_DISPATCHES = "hashgraph_bridge_wire_device_dispatches_total"
_APPLY_ROWS = "hashgraph_bridge_wire_apply_rows_total"
_REACTOR_COUNTERS = {
    "hashgraph_reactor_windows_total": "windows",
    "hashgraph_reactor_rows_total": "rows",
    "hashgraph_reactor_flush_rows_total": "flush_rows",
    "hashgraph_reactor_flush_bytes_total": "flush_bytes",
    "hashgraph_reactor_flush_deadline_total": "flush_deadline",
    "hashgraph_reactor_flush_now_change_total": "flush_now_change",
    "hashgraph_reactor_flush_forced_total": "flush_forced",
}


# device_apply's sub-stages (obs families, one observed_span per frame
# each), in pipeline order.
_APPLY_HISTOGRAMS = {
    "validate": "hashgraph_engine_validate_seconds",
    "health": "hashgraph_engine_health_seconds",
    "plan": "hashgraph_engine_plan_seconds",
    "device_wait": "hashgraph_device_ingest_seconds",
    "post_apply": "hashgraph_engine_post_apply_seconds",
}
_WAIT_HISTOGRAMS = {
    "lane": "hashgraph_bridge_lane_wait_seconds",
    "lock": "hashgraph_engine_lock_wait_seconds",
}
_GC_COLLECTIONS = "hashgraph_gc_collections_total"
_GC_PAUSE_SECONDS = "hashgraph_gc_pause_seconds_total"


def attribution_report(state: dict | None = None, profiler=None) -> dict:
    """The live process's attribution report. ``state`` defaults to the
    process registry's ``export_state()``; ``profiler`` defaults to the
    process-wide :data:`~hashgraph_tpu.obs.default_profiler` (its sample
    summary is included only when it has actually sampled — an idle
    profiler must not imply an empty profile means an idle process)."""
    if state is None:
        from hashgraph_tpu.obs import registry

        state = registry.export_state()
    counters = state.get("counters") or {}
    gauges = state.get("gauges") or {}
    histograms = state.get("histograms") or {}

    seconds = {key: 0.0 for key in STAGE_KEYS}
    for family, key in _STAGE_COUNTERS.items():
        seconds[key] = float(counters.get(family, 0.0))
    wal_fsyncs = 0
    wal = histograms.get(_WAL_FSYNC_HISTOGRAM)
    if wal:
        seconds["wal_fsync"] = float(wal.get("sum", 0.0))
        wal_fsyncs = int(wal.get("count", 0))
    busy = sum(seconds.values())
    apply_s = seconds["device_apply"]

    def _sum(family: str) -> float:
        return float((histograms.get(family) or {}).get("sum", 0.0))

    apply_breakdown = {}
    for key, family in _APPLY_HISTOGRAMS.items():
        part = _sum(family)
        apply_breakdown[key] = {
            "seconds": round(part, 6),
            "share": round(part / apply_s, 4) if apply_s else 0.0,
        }
    waits = {}
    for key, family in _WAIT_HISTOGRAMS.items():
        hist = histograms.get(family) or {}
        total, n = float(hist.get("sum", 0.0)), int(hist.get("count", 0))
        waits[key] = {
            "seconds": round(total, 6),
            "count": n,
            "mean_ms": round(total / n * 1e3, 4) if n else 0.0,
        }
    waits["gc"] = {
        "seconds": round(float(gauges.get(_GC_PAUSE_SECONDS, 0.0)), 6),
        "count": int(gauges.get(_GC_COLLECTIONS, 0)),
    }

    dispatches = float(counters.get(_DISPATCHES, 0.0))
    apply_rows = float(counters.get(_APPLY_ROWS, 0.0))
    report = {
        "schema": ATTRIBUTION_SCHEMA,
        "busy_seconds": round(busy, 6),
        "stages": {
            key: {
                "seconds": round(seconds[key], 6),
                "share": round(seconds[key] / busy, 4) if busy else 0.0,
            }
            for key in STAGE_KEYS
        },
        "apply_breakdown": apply_breakdown,
        "waits": waits,
        "device": {
            "dispatches": dispatches,
            "apply_rows": apply_rows,
            # The round-19 amortization factor, measured not asserted.
            "votes_per_dispatch": (
                round(apply_rows / dispatches, 2) if dispatches else 0.0
            ),
        },
        "wal": {"fsyncs": wal_fsyncs},
        "reactor": {
            key: float(counters.get(family, 0.0))
            for family, key in _REACTOR_COUNTERS.items()
        },
    }

    if profiler is None:
        from hashgraph_tpu.obs import default_profiler

        profiler = default_profiler
    snap = profiler.snapshot() if profiler is not None else None
    if snap is not None and snap["samples"]:
        report["samples"] = {
            "total": snap["samples"],
            "dropped": snap["dropped"],
            "rate_hz": snap["rate_hz"],
            "overhead_seconds": snap["overhead_seconds"],
            "roles": snap["roles"],
        }
    return report
