"""hashgraph_tpu.crypto_device — device-resident Ed25519 batch verify.

The accelerator-side counterpart of ``native/consensus_native.cpp``'s
batch verifier (ROADMAP item 2): the whole randomized-linear-combination
check — batched point decompression, vectorized SHA-512 challenge
hashes, and one Straus multi-scalar multiply across every signature
lane — runs in JAX, so validated ingest stops being bounded by host
cores. The same code compiles for TPU, GPU, and CPU (CI runs it on the
CPU backend).

Layering:

- :mod:`.field`   — radix-2^16 u32-limb GF(2^255-19) core (lazy carries)
- :mod:`.sha512`  — vectorized SHA-512 in uint32 pairs, ragged batches
- :mod:`.curve`   — extended-Edwards point ops + batched decompression
- :mod:`.msm`     — the Straus MSM + cofactored identity test, one jit
- :mod:`.backend` — pipeline orchestration, buckets, metrics, blame

The public seam is NOT here: engines select the backend through
``Ed25519ConsensusSigner(device_verify=True)`` (or the
``HASHGRAPH_TPU_DEVICE_VERIFY`` env), and every caller keeps speaking
``SignatureScheme.verify_batch_submit`` / ``PendingVerdicts``. This
package only exposes the backend entry points that seam calls, plus
bench/test hooks.

Import note: submodules import JAX; this ``__init__`` defers those
imports so ``hashgraph_tpu.signing`` (and the jax-free obs/WAL layers
under it) can probe availability without initializing a backend.
"""

from __future__ import annotations

__all__ = [
    "available",
    "verify_batch",
    "verify_batch_begin",
    "last_phase_seconds",
]


def available() -> bool:
    from . import backend

    return backend.available()


def verify_batch(identities, payloads, signatures):
    from . import backend

    return backend.verify_batch(identities, payloads, signatures)


def verify_batch_begin(identities, payloads, signatures):
    from . import backend

    return backend.verify_batch_begin(identities, payloads, signatures)


def last_phase_seconds():
    from . import backend

    return backend.last_phase_seconds()
