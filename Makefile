# Canonical entry points for builders and CI. `make verify` is THE
# command a checker runs: it installs the dev extras (pytest +
# hypothesis — the property suites importorskip cleanly when absent,
# but a verified build should run them) and then executes the exact
# tier-1 command from ROADMAP.md, byte for byte, so local runs and CI
# never drift from what the roadmap promises.

SHELL := /bin/bash

.PHONY: verify tier1 dev-install test catchup-smoke metrics-smoke trace-smoke federation-scrape-smoke smoke obs-smoke

dev-install:
	python -m pip install -e '.[dev]'

# The exact ROADMAP.md "Tier-1 verify" command (keep in sync — that file
# is the source of truth; this target only gives it a stable name).
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

verify: dev-install tier1

# Fast local loop (no install, no log artifact).
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow'

# State-sync check: two in-process peers over a real bridge, small signed
# history, snapshot+tail AND full-replay joiners both asserted
# byte-identical to the source, interrupted-transfer resume included.
catchup-smoke:
	JAX_PLATFORMS=cpu python examples/catchup_smoke.py

# End-to-end observability check: start a bridge server (WAL + HTTP
# sidecar), drive a proposal to decision, scrape /metrics + /healthz and
# the GET_METRICS opcode, and assert the well-known metric families are
# present. See examples/metrics_smoke.py.
metrics-smoke:
	JAX_PLATFORMS=cpu python examples/metrics_smoke.py

# End-to-end distributed-tracing check: two bridge peers decide one
# proposal with trace context on the wire; per-peer span dumps stitch
# into one Chrome/Perfetto trace (shared trace_id, causal order) and
# EXPLAIN reports the quorum arithmetic. See examples/trace_smoke.py.
trace-smoke:
	JAX_PLATFORMS=cpu python examples/trace_smoke.py

# Metric-federation check: 2 federation hosts as OS processes, one
# decision each, then OP_METRICS_PULL frames merged into ONE scrape —
# both hosts' families labelled host="...", fleet-total bare series,
# merged /slo rollup — served over a real HTTP sidecar. See
# examples/federation_scrape_smoke.py.
federation-scrape-smoke:
	JAX_PLATFORMS=cpu python examples/federation_scrape_smoke.py

# Aggregate observability smoke: single-process scrape + trace paths.
smoke: metrics-smoke trace-smoke

# Fleet-wide observability plane smoke: everything `smoke` covers plus
# the federated merged scrape across two host processes — the CI
# `obs-smoke` job's target.
obs-smoke: smoke federation-scrape-smoke
