"""The device batch-verify pipeline behind the ``verify_batch_submit`` seam.

Orchestrates the three device stages over one signature batch:

1. **decompress** — A and R encodings for every lane, stacked into one
   dispatch (curve.decompress: the shared inverse-sqrt chain);
2. **hash** — vectorized SHA-512 challenge hashes k_i over R||A||M;
3. **msm** — the randomized-linear-combination check, one Straus MSM
   across all lanes (msm._msm_is_identity).

Host work between stages is O(n) bookkeeping: canonical-scalar checks
(s < L), mod-L scalar algebra for the randomizers (Python ints are the
host's native 256-bit ALU), and window decomposition. Lane counts and
SHA block counts pad to power-of-two buckets so the set of compiled
shapes — and therefore XLA compile time, amortized further by the
persistent compile cache — stays tiny.

Failure semantics (the part that makes this a *backend*, not a fork):
the RLC accepting proves every lane verifies under the cofactored
criterion; the RLC failing says only "at least one lane is bad", so the
batch drops to the host verifier (the native pool's own batch path, or
the pure-Python RFC 8032 twin) for **exact per-item blame** — the same
escalation the native runtime performs internally when a chunk's
combination fails. Verdicts are therefore decision-identical to
``signing/_ed25519.py`` on every input, which the fuzz battery asserts.

Every batch increments ``hashgraph_device_verify_{batches,signatures}_
total``; blame escalations increment ``..._fallbacks_total``; verify
work lands in the ``hashgraph_device_verify_seconds`` histogram and the
per-phase split is exported via :func:`last_phase_seconds` for callers
that time the phases. The clocks measure WORK, not the wall
window: ``submit`` is host pack + device dispatch inside
``verify_batch_begin``; ``decompress``/``hash``/``msm``/``fallback``
are time spent inside ``collect``; any overlap gap an async caller
opens between begin and collect is deliberately attributed to NOTHING
(the whole point of the submit/collect seam is that the gap is free).
"""

from __future__ import annotations

import secrets
import time

import numpy as np

from ..obs import (
    DEVICE_VERIFY_BATCHES_TOTAL,
    DEVICE_VERIFY_FALLBACKS_TOTAL,
    DEVICE_VERIFY_SECONDS,
    DEVICE_VERIFY_SIGNATURES_TOTAL,
    registry,
)
from ..signing._ed25519 import L  # ONE home for the group order

# The identity's encoding (y=1): the inert pad for unused lanes.
_PAD_ENC = b"\x01" + b"\x00" * 31

_last_phases: "dict[str, float]" = {}

_jax_state: "dict[str, object]" = {"checked": False, "ok": False}


def available() -> bool:
    """True when JAX (any backend, CPU included) can serve the pipeline."""
    if not _jax_state["checked"]:
        try:
            import jax

            jax.devices()
            _jax_state["ok"] = True
        except Exception:
            _jax_state["ok"] = False
        _jax_state["checked"] = True
    return bool(_jax_state["ok"])


def last_phase_seconds() -> "dict[str, float]":
    """Per-phase wall seconds of the most recent batch."""
    return dict(_last_phases)


def _bucket(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _decompress_jit():
    import jax

    from . import curve

    if "decompress" not in _jax_state:
        _jax_state["decompress"] = jax.jit(curve.decompress)
    return _jax_state["decompress"]


def verify_batch_begin(
    identities: "list[bytes]",
    payloads: "list[bytes]",
    signatures: "list[bytes]",
):
    """Start the device pipeline NOW (decompress + challenge hashes are
    in flight when this returns); the returned zero-arg collect yields
    one bool per item. Lengths must be pre-checked by the seam."""
    import jax.numpy as jnp

    from . import curve, msm, sha512

    n = len(identities)
    verdicts = [False] * n
    t0 = time.perf_counter()
    phases = {
        "submit": 0.0, "decompress": 0.0, "hash": 0.0, "msm": 0.0,
        "fallback": 0.0,
    }
    registry.counter(DEVICE_VERIFY_BATCHES_TOTAL).inc()
    registry.counter(DEVICE_VERIFY_SIGNATURES_TOTAL).inc(n)

    # Host precheck: non-canonical scalars (s >= L) are False without
    # touching the device — same short-circuit as the host verifiers.
    live = [
        i for i in range(n)
        if int.from_bytes(signatures[i][32:], "little") < L
    ]
    if not live:
        phases["submit"] = time.perf_counter() - t0
        _finish_phases(phases)
        return lambda: verdicts

    k = len(live)
    lanes = _bucket(2 * k)
    enc = np.zeros((lanes, 32), np.uint8)
    enc[2 * k:] = np.frombuffer(_PAD_ENC, np.uint8)
    for j, i in enumerate(live):
        enc[j] = np.frombuffer(identities[i], np.uint8)
        enc[k + j] = np.frombuffer(signatures[i][:32], np.uint8)
    points_dev, ok_dev = _decompress_jit()(jnp.asarray(enc))

    # Challenge hashes k_i = SHA-512(R || A || M), bucketed on lanes
    # AND block count (two axes of shape variation, both bounded).
    msgs = [
        signatures[i][:32] + identities[i] + payloads[i] for i in live
    ]
    blocks = _bucket(max(sha512.blocks_needed(len(m)) for m in msgs), 1)
    hash_lanes = _bucket(k)
    digests_dev = sha512.sha512_batch_dispatch(
        msgs + [b""] * (hash_lanes - k), blocks
    )
    phases["submit"] = time.perf_counter() - t0

    def _collect() -> "list[bool]":
        tc = time.perf_counter()
        points = np.asarray(points_dev)
        ok = np.asarray(ok_dev)
        phases["decompress"] = time.perf_counter() - tc
        t1 = time.perf_counter()
        digests = sha512.digest_bytes(digests_dev)[:k]
        phases["hash"] = time.perf_counter() - t1
        t2 = time.perf_counter()

        ok_a, ok_r = ok[:k], ok[k:2 * k]
        surv = [j for j in range(k) if ok_a[j] and ok_r[j]]
        if not surv:
            phases["msm"] = time.perf_counter() - t2
            _finish_phases(phases)
            return verdicts

        # Randomized linear combination (fresh nonzero 128-bit z per
        # item per batch): accept iff
        # 8*(S*B + sum -z_i h_i A_i + sum -z_i R_i) == O.
        h = [int.from_bytes(bytes(digests[j]), "little") % L for j in surv]
        z = [1 + secrets.randbelow((1 << 128) - 1) for _ in surv]
        m = len(surv)
        msm_lanes = _bucket(2 * m + 1)
        pts = np.broadcast_to(curve.IDENTITY, (msm_lanes, 4, 16)).copy()
        s_total = 0
        for row, j in enumerate(surv):
            i = live[j]
            s_total = (
                s_total
                + z[row] * int.from_bytes(signatures[i][32:], "little")
            ) % L
            pts[row] = points[j]                  # A_i
            pts[m + row] = points[k + j]          # R_i
        scalars = [(-(z[r] * h[r])) % L for r in range(m)]
        scalars += [(-z[r]) % L for r in range(m)]
        scalars.append(s_total)
        pts[2 * m] = curve.BASE_AFFINE
        nibbles = np.zeros((msm_lanes, msm.WINDOWS), np.int32)
        nibbles[:2 * m + 1] = msm.scalars_to_nibbles(scalars)
        accepted = msm.msm_accepts(jnp.asarray(pts), jnp.asarray(nibbles))
        phases["msm"] = time.perf_counter() - t2

        if accepted:
            for j in surv:
                verdicts[live[j]] = True
        else:
            t3 = time.perf_counter()
            registry.counter(DEVICE_VERIFY_FALLBACKS_TOTAL).inc()
            rows = [live[j] for j in surv]
            host = _host_blame(
                [identities[i] for i in rows],
                [payloads[i] for i in rows],
                [signatures[i] for i in rows],
            )
            for i, verdict in zip(rows, host):
                verdicts[i] = bool(verdict)
            phases["fallback"] = time.perf_counter() - t3
        _finish_phases(phases)
        return verdicts

    return _collect


def _finish_phases(phases: "dict[str, float]") -> None:
    # Work, not wall: total = what begin+collect actually spent, so an
    # async caller's overlap gap never inflates the histogram.
    phases["total"] = sum(phases.values())
    registry.histogram(DEVICE_VERIFY_SECONDS).observe(phases["total"])
    _last_phases.clear()
    _last_phases.update(phases)


def _host_blame(identities, payloads, signatures) -> "list[bool]":
    """Exact per-item verdicts from the host verifier hierarchy (native
    pool batch if present, else the pure-Python twin) — the blame pass
    after a failed linear combination."""
    from .. import native
    from ..signing import _ed25519 as _py

    results = native.ed25519_verify_batch(
        [bytes(i) for i in identities],
        list(payloads),
        [bytes(s) for s in signatures],
    )
    if results is not None:
        return [code == 1 for code in results]
    return [
        _py.verify(bytes(i), p, bytes(s))
        for i, p, s in zip(identities, payloads, signatures)
    ]


def verify_batch(identities, payloads, signatures) -> "list[bool]":
    """Synchronous wrapper: begin + collect."""
    return verify_batch_begin(identities, payloads, signatures)()
