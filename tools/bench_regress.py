"""Perf-regression sentry over the checked-in BENCH_*.json trajectory.

Every bench round in this repo ships a machine-readable artifact
(BENCH_rNN.json) carrying its headline number and — since round 6 —
its own paired-A/B rep spread. This tool reconstructs the per-metric
trajectory across those artifacts and issues NOISE-AWARE verdicts: a
drop between two rounds is a regression only when it exceeds the sum of
both rounds' recorded spreads (a claim the rounds themselves could not
have distinguished from noise cannot convict a later round).

Corpus archaeology the loader handles (see the BENCHMARKS.md
"Bench round ↔ BENCH file" table):

- **r06**: driver-wrapped ``{n, cmd, rc, tail, parsed}`` records
  whose ``parsed`` object is the flat bench line;
- **r02–r05**: the same wrapper but ``parsed: null`` and a
  FRONT-truncated ``tail`` — the artifact keeps only the line's end.
  Where the run_default key order preserved the trailing
  ``headline_spread_pct`` / ``value`` pair (r05) the headline is
  regex-recovered and the entry marked ``recovered``; otherwise the
  file is listed under ``skipped`` with the reason;
- **r07+**: flat ``{metric, value, unit, detail}`` lines.

Confidence discipline: only entries that are neither recovered nor
spread-less participate in hard regression verdicts; everything else
still appears in the trajectory but its comparisons are ``advisory``
(reported, never failing). That is what keeps the existing trajectory
free of FALSE regressions: a drop between artifacts from different
hardware is a hardware story, not a code regression, and such points do
not carry the evidence to say otherwise.

The device-apply busy-share trajectory (round 11's 66.8% → round 19's
50.9%) is reconstructed alongside, so the attribution the continuous
profiler now serves live (``obs/attribution.py``) is checkable against
its own history.

Usage: ``python tools/bench_regress.py [repo_root]`` (also reachable as
``python bench.py regress`` / ``make bench-regress``). Prints one JSON
verdict block; exit status 1 iff a non-advisory regression was found.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

REGRESS_SCHEMA = "hashgraph.bench_regress.v1"

# Artifact file ↔ bench round. r01–r05 were numbered by sequential
# driver run; r06–r09 kept that sequence while the ROUNDS jumped with
# the issue numbers (r06 records round 11's gossip+attribution run, r07
# round 13's federation, r08 round 14's churn, r09 round 18's
# liveness). From BENCH_r19 on the artifact number IS the round number,
# which `_round_for` assumes for any file not pinned here.
ROUND_FOR_FILE = {
    "BENCH_r02.json": 2,
    "BENCH_r03.json": 3,
    "BENCH_r04.json": 4,
    "BENCH_r05.json": 5,
    "BENCH_r06.json": 11,
    "BENCH_r07.json": 13,
    "BENCH_r08.json": 14,
    "BENCH_r09.json": 18,
}

# Metric implied by the driver command line for recovered (truncated)
# wrapped artifacts, whose leading "metric" key did not survive.
_DEFAULT_SWEEP_METRIC = ("vote_ingest_throughput", "votes/sec")


def _round_for(name: str) -> int | None:
    if name in ROUND_FOR_FILE:
        return ROUND_FOR_FILE[name]
    m = re.match(r"BENCH_r(\d+)\.json$", name)
    return int(m.group(1)) if m else None


def _recorded_spreads(body) -> list[float]:
    """Every rep-spread percentage the artifact recorded about itself
    (``headline_spread_pct`` and any ``spread_pct`` scalar or per-arm
    dict, wherever they appear). The MAX becomes the entry's noise
    figure — conservative by construction."""
    out: list[float] = []

    def walk(node) -> None:
        if isinstance(node, dict):
            for key, value in node.items():
                if key == "headline_spread_pct" and isinstance(
                    value, (int, float)
                ):
                    out.append(float(value))
                elif key == "spread_pct":
                    if isinstance(value, dict):
                        out.extend(
                            float(v)
                            for v in value.values()
                            if isinstance(v, (int, float))
                        )
                    elif isinstance(value, (int, float)):
                        out.append(float(value))
                else:
                    walk(value)
        elif isinstance(node, list):
            for value in node:
                walk(value)

    walk(body)
    return out


def _device_apply_shares(body) -> list[dict]:
    """Device-apply busy-share readings in an artifact: round 11's
    ``stage_attribution.stage_share`` block and round 19's per-arm
    ``device_apply_share`` (its ``r06_baseline`` echo excluded — the
    r06 artifact speaks for itself)."""
    found: list[dict] = []

    def walk(node) -> None:
        if isinstance(node, dict):
            share = node.get("stage_share")
            if isinstance(share, dict) and "device_apply_s" in share:
                found.append(
                    {"arm": "headline", "share": float(share["device_apply_s"])}
                )
            share = node.get("device_apply_share")
            if isinstance(share, dict):
                for arm, value in share.items():
                    if arm != "r06_baseline" and isinstance(
                        value, (int, float)
                    ):
                        found.append({"arm": arm, "share": float(value)})
            for value in node.values():
                walk(value)
        elif isinstance(node, list):
            for value in node:
                walk(value)

    walk(body)
    return found


def _recover_from_tail(tail: str) -> tuple[float, float] | None:
    """(value, headline_spread_pct) regex-recovered from a
    front-truncated run_default line — possible exactly because that
    line puts the headline fields LAST (a deliberate choice documented
    in bench.py). None when the trailing pair did not survive."""
    m = re.search(
        r'"headline_spread_pct":\s*([0-9.]+).*?"value":\s*([0-9.eE+-]+)',
        tail[-800:],
        re.DOTALL,
    )
    if m is None:
        return None
    return float(m.group(2)), float(m.group(1))


def load_corpus(root: str) -> tuple[list[dict], list[dict]]:
    """(entries, skipped) from every BENCH_r*.json under ``root``."""
    entries: list[dict] = []
    skipped: list[dict] = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        name = os.path.basename(path)
        try:
            with open(path) as fh:
                raw = json.load(fh)
        except (OSError, ValueError) as exc:
            skipped.append({"file": name, "reason": f"unreadable: {exc}"})
            continue
        round_no = _round_for(name)
        body = None
        recovered = False
        if isinstance(raw.get("metric"), str) and "value" in raw:
            body = raw
        elif isinstance(raw.get("parsed"), dict):
            body = raw["parsed"]
        elif isinstance(raw.get("tail"), str):
            got = _recover_from_tail(raw["tail"])
            if got is None:
                skipped.append(
                    {
                        "file": name,
                        "reason": (
                            "truncated artifact: headline fields did not "
                            "survive the tail"
                        ),
                    }
                )
                continue
            value, spread = got
            metric, unit = _DEFAULT_SWEEP_METRIC
            entries.append(
                {
                    "file": name,
                    "round": round_no,
                    "metric": metric,
                    "value": value,
                    "unit": unit,
                    "spread_pct": spread,
                    "recovered": True,
                    "confident": False,
                    "device_apply_shares": [],
                }
            )
            continue
        else:
            skipped.append(
                {"file": name, "reason": "unrecognized artifact shape"}
            )
            continue
        spreads = _recorded_spreads(body)
        spread = max(spreads) if spreads else None
        try:
            value = float(body["value"])
        except (KeyError, TypeError, ValueError):
            skipped.append(
                {"file": name, "reason": "no numeric headline value"}
            )
            continue
        entries.append(
            {
                "file": name,
                "round": round_no,
                "metric": str(body.get("metric", "unknown")),
                "value": value,
                "unit": str(body.get("unit", "")),
                "spread_pct": spread,
                "recovered": recovered,
                "confident": bool(spread is not None and not recovered),
                "device_apply_shares": _device_apply_shares(body),
            }
        )
    return entries, skipped


def _compare(older: dict, newer: dict) -> dict:
    """Noise-aware verdict for one consecutive same-metric pair. All
    headline metrics in this corpus are higher-is-better rates/counts."""
    delta_pct = (
        round(100.0 * (newer["value"] - older["value"]) / older["value"], 2)
        if older["value"]
        else 0.0
    )
    comparison = {
        "metric": older["metric"],
        "from": {"file": older["file"], "round": older["round"]},
        "to": {"file": newer["file"], "round": newer["round"]},
        "delta_pct": delta_pct,
    }
    if not (older["confident"] and newer["confident"]):
        reasons = [
            f"{e['file']}: "
            + ("recovered from truncated tail" if e["recovered"] else "no recorded spread")
            for e in (older, newer)
            if not e["confident"]
        ]
        comparison["verdict"] = "advisory"
        comparison["reason"] = "; ".join(reasons)
        return comparison
    allowance = float(older["spread_pct"]) + float(newer["spread_pct"])
    comparison["allowance_pct"] = round(allowance, 2)
    if delta_pct < -allowance:
        comparison["verdict"] = "regression"
    elif delta_pct > allowance:
        comparison["verdict"] = "improvement"
    else:
        comparison["verdict"] = "stable"
    return comparison


def build_verdict(root: str) -> dict:
    """The machine-readable verdict block: trajectory, per-pair
    comparisons, the device-apply share history, and the hard
    ``regressions`` list (empty == pass)."""
    entries, skipped = load_corpus(root)
    series: dict[str, dict] = {}
    for entry in sorted(
        entries, key=lambda e: (e["round"] is None, e["round"], e["file"])
    ):
        key = entry["metric"]
        if key in series and series[key]["unit"] != entry["unit"]:
            # Same name, different unit = a different measurement; a
            # cross-unit delta would be meaningless.
            key = f"{key} ({entry['unit']})"
        bucket = series.setdefault(
            key, {"unit": entry["unit"], "points": []}
        )
        bucket["points"].append(
            {
                key: entry[key]
                for key in (
                    "file",
                    "round",
                    "value",
                    "spread_pct",
                    "recovered",
                    "confident",
                )
            }
        )
    comparisons: list[dict] = []
    for metric, bucket in series.items():
        points = bucket["points"]
        bucket["comparisons"] = []
        for older, newer in zip(points, points[1:]):
            pair = _compare(
                {**older, "metric": metric}, {**newer, "metric": metric}
            )
            bucket["comparisons"].append(pair)
            comparisons.append(pair)
    shares = [
        {
            "file": entry["file"],
            "round": entry["round"],
            "arm": reading["arm"],
            "share": reading["share"],
        }
        for entry in sorted(
            entries, key=lambda e: (e["round"] is None, e["round"], e["file"])
        )
        for reading in entry["device_apply_shares"]
    ]
    regressions = [c for c in comparisons if c["verdict"] == "regression"]
    return {
        "schema": REGRESS_SCHEMA,
        "files": sorted(e["file"] for e in entries)
        + sorted(s["file"] for s in skipped),
        "entries": len(entries),
        "skipped": skipped,
        "series": series,
        "stage_shares": {"device_apply": shares},
        "regressions": regressions,
        "pass": not regressions,
    }


def main(argv: list[str]) -> int:
    root = argv[1] if len(argv) > 1 else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    verdict = build_verdict(root)
    print(json.dumps(verdict, indent=2))
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
