"""Ed25519 signing scheme: the batch-verification-native alternative.

A second production scheme alongside :class:`EthereumConsensusSigner`,
added under the reference's pluggable-scheme contract (reference:
src/signing.rs:46-74) — identity is the 32-byte public key, signatures
are 64-byte ``R || S`` over the raw payload (RFC 8032, no EIP-191-style
envelope; the payload is already the canonical signed-fields encoding).

Why a second scheme: recover-and-compare ECDSA verification is
inherently scalar — each signature costs a full double-scalar multiply
and there is no sound way to merge checks — while Ed25519 verification
equations combine algebraically: a random linear combination verifies a
whole batch with one multi-scalar multiply (Bernstein et al., "Batch
binary Edwards" lineage). The native core
(``native/consensus_native.cpp``) implements that batch path over the
persistent verify pool; this module falls back to the pure-Python RFC
8032 code in :mod:`._ed25519` when the native runtime is absent.

Verification is *cofactored* (accept iff ``8·(s·B - h·A - R)`` is the
identity) with RFC 8032 canonical-encoding rejections — the only
criterion under which scalar and batch verdicts provably agree on every
input. See PARITY.md.
"""

from __future__ import annotations

import os
import secrets

from ..errors import ConsensusSchemeError
from .. import native
from . import ConsensusSignatureScheme, PendingVerdicts
from . import _ed25519 as _py

ED25519_SIGNATURE_LENGTH = 64
ED25519_IDENTITY_LENGTH = 32

# Backend selector for batch verification: instances resolve
# device_verify=None against this env at construction. "1"/"on"/"true"
# routes verify_batch/_submit through hashgraph_tpu.crypto_device (the
# JAX pipeline — TPU/GPU/CPU alike); anything else keeps the native
# pool / pure-Python host path. The env seam means a bridge server, the
# sim cluster, and the engine's ingest_wire_columnar crypto prepass all
# reach the device path with zero caller changes.
DEVICE_VERIFY_ENV = "HASHGRAPH_TPU_DEVICE_VERIFY"


def _device_verify_default() -> bool:
    return os.environ.get(DEVICE_VERIFY_ENV, "").lower() in ("1", "on", "true")


class Ed25519ConsensusSigner(ConsensusSignatureScheme):
    """Holds a 32-byte seed; identity is the derived public key.

    ``device_verify`` selects the batch-verification backend:

    - ``None`` (default): consult ``HASHGRAPH_TPU_DEVICE_VERIFY``;
    - ``True``: the instance is constructed as
      :class:`Ed25519DeviceConsensusSigner`, whose class-level batch
      verifiers run the JAX device pipeline (engines resolve scheme
      methods through ``type(signer)``, so the choice rides the
      instance into every ``verify_batch_submit`` call site, and the
      per-scheme metric label / admission-cache namespace pick up the
      distinct subclass identity);
    - ``False``: force the host path even when the env is set.

    A device request that JAX cannot serve raises ``RuntimeError``; it
    never degrades to the host path.

    Signing and scalar ``verify`` are host-side in every case; the
    backends differ only in who executes the batch equation, never in
    verdicts (PARITY.md "Device-resident verification").
    """

    def __new__(cls, seed: bytes = b"", device_verify: "bool | None" = None):
        if cls is Ed25519ConsensusSigner:
            enabled = (
                _device_verify_default()
                if device_verify is None
                else bool(device_verify)
            )
            if enabled:
                _require_device_backend()
                cls = Ed25519DeviceConsensusSigner
        return super().__new__(cls)

    def __init__(self, seed: bytes, device_verify: "bool | None" = None):
        del device_verify  # consumed by __new__ (class identity carries it)
        if len(seed) != 32:
            raise ValueError("ed25519 seed must be 32 bytes")
        self._seed = bytes(seed)
        pub = native.ed25519_public(self._seed)
        self._public = pub if pub is not None else _py.public_key(self._seed)

    @classmethod
    def random(cls) -> "Ed25519ConsensusSigner":
        return cls(secrets.token_bytes(32))

    def identity(self) -> bytes:
        return self._public

    def private_key_bytes(self) -> bytes:
        """Expose the seed for interop/tests (inner() equivalent)."""
        return self._seed

    def sign(self, payload: bytes) -> bytes:
        signature = native.ed25519_sign(self._seed, payload)
        if signature is not None:
            return signature
        return _py.sign(self._seed, payload)

    @classmethod
    def _check_lengths(cls, identity: bytes, signature: bytes) -> None:
        if len(signature) != ED25519_SIGNATURE_LENGTH:
            raise ConsensusSchemeError.verify(
                f"expected {ED25519_SIGNATURE_LENGTH}-byte signature, "
                f"got {len(signature)}"
            )
        if len(identity) != ED25519_IDENTITY_LENGTH:
            raise ConsensusSchemeError.verify(
                f"expected {ED25519_IDENTITY_LENGTH}-byte public key, "
                f"got {len(identity)}"
            )

    @classmethod
    def verify(cls, identity: bytes, payload: bytes, signature: bytes) -> bool:
        # Wrong lengths are scheme errors (the Ethereum convention);
        # length-valid but undecodable points and non-canonical scalars
        # are False — on the wire they are indistinguishable from forged
        # signatures, and the batch path reports them the same way.
        cls._check_lengths(identity, signature)
        verdict = native.ed25519_verify(
            bytes(identity), payload, bytes(signature)
        )
        if verdict is not None:
            return verdict == 1
        return _py.verify(bytes(identity), payload, bytes(signature))

    @classmethod
    def _precheck(
        cls,
        identities: list[bytes],
        payloads: list[bytes],
        signatures: list[bytes],
    ) -> "tuple[list, list[int]]":
        """Length gauntlet shared by the sync and async batch paths:
        returns (out list with scheme errors pre-filled, well-formed
        row indices). zip() truncation keeps the ragged-input contract."""
        out: list = []
        well_formed: list[int] = []
        for i, (identity, _payload, signature) in enumerate(
            zip(identities, payloads, signatures)
        ):
            try:
                cls._check_lengths(identity, signature)
            except ConsensusSchemeError as exc:
                out.append(exc)
                continue
            out.append(False)  # placeholder
            well_formed.append(i)
        return out, well_formed

    @classmethod
    def verify_batch(
        cls,
        identities: list[bytes],
        payloads: list[bytes],
        signatures: list[bytes],
    ) -> list:
        """Native batched verification: chunks verify as ONE randomized
        linear combination (a single multi-scalar multiply) on the
        persistent worker pool; falls back to the scalar loop without
        the native runtime."""
        out, well_formed = cls._precheck(identities, payloads, signatures)
        if not well_formed:
            return out
        results = native.ed25519_verify_batch(
            [bytes(identities[i]) for i in well_formed],
            [payloads[i] for i in well_formed],
            [bytes(signatures[i]) for i in well_formed],
        )
        if results is None:
            for i in well_formed:
                out[i] = _py.verify(
                    bytes(identities[i]), payloads[i], bytes(signatures[i])
                )
            return out
        for i, code in zip(well_formed, results):
            out[i] = bool(code == 1)
        return out

    @classmethod
    def verify_batch_submit(
        cls,
        identities: list[bytes],
        payloads: list[bytes],
        signatures: list[bytes],
    ) -> PendingVerdicts:
        """Start the batch on the native pool NOW; collect() fans the
        codes out exactly as :meth:`verify_batch` would. Without the
        native runtime this degrades to the deferred-sync default."""
        out, well_formed = cls._precheck(identities, payloads, signatures)
        job = (
            native.ed25519_verify_batch_submit(
                [bytes(identities[i]) for i in well_formed],
                [payloads[i] for i in well_formed],
                [bytes(signatures[i]) for i in well_formed],
            )
            if well_formed
            else None
        )
        if well_formed and job is None:
            return super().verify_batch_submit(identities, payloads, signatures)

        def _collect():
            if job is not None:
                for i, code in zip(well_formed, job.collect()):
                    out[i] = bool(code == 1)
            return out

        return PendingVerdicts(_collect)


def _require_device_backend() -> None:
    """Device verification was asked for (argument or env): refuse to
    construct when JAX cannot serve it, rather than quietly verifying
    on the host under the device signer's name."""
    from .. import crypto_device

    if not crypto_device.available():
        raise RuntimeError(
            "device_verify requested but no JAX backend can serve "
            "hashgraph_tpu.crypto_device"
        )


class Ed25519DeviceConsensusSigner(Ed25519ConsensusSigner):
    """Ed25519 with device-resident batch verification.

    Same wire format, same seed handling, same scalar ``verify``, same
    *cofactored* acceptance criterion — a backend, not a divergence:
    ``verify_batch``/``verify_batch_submit`` run the whole batch
    equation (decompression, SHA-512 challenge hashes, the randomized
    Straus MSM) on the JAX backend via :mod:`hashgraph_tpu.crypto_device`,
    with host blame for exact per-item verdicts when the combination
    fails. Constructed via ``Ed25519ConsensusSigner(seed,
    device_verify=True)`` or the ``HASHGRAPH_TPU_DEVICE_VERIFY`` env;
    the distinct class name labels the per-scheme verified-signatures
    counter and namespaces the admission cache."""

    @classmethod
    def device_phase_seconds(cls) -> "dict[str, float]":
        """Per-phase wall seconds of the backend's most recent batch
        (decompress / hash / msm / fallback / total) — the engine's
        wire-path stage attribution reads this instead of
        re-instrumenting the pipeline."""
        from .. import crypto_device

        return crypto_device.last_phase_seconds()

    @classmethod
    def verify_batch(
        cls,
        identities: "list[bytes]",
        payloads: "list[bytes]",
        signatures: "list[bytes]",
    ) -> list:
        return cls.verify_batch_submit(
            identities, payloads, signatures
        ).collect()

    @classmethod
    def verify_batch_submit(
        cls,
        identities: "list[bytes]",
        payloads: "list[bytes]",
        signatures: "list[bytes]",
    ) -> PendingVerdicts:
        """Dispatch decompression + challenge hashing to the device NOW;
        ``collect()`` finishes the MSM and fans out verdicts (falling
        back to the host verifiers for per-item blame on batch
        failure). Scheme errors and ragged truncation are handled by
        the shared precheck, byte-compatible with the host path."""
        from .. import crypto_device

        out, well_formed = cls._precheck(identities, payloads, signatures)
        if not well_formed:
            return PendingVerdicts(lambda: out)
        collect_device = crypto_device.verify_batch_begin(
            [bytes(identities[i]) for i in well_formed],
            [payloads[i] for i in well_formed],
            [bytes(signatures[i]) for i in well_formed],
        )

        def _collect():
            for i, verdict in zip(well_formed, collect_device()):
                out[i] = bool(verdict)
            return out

        return PendingVerdicts(_collect)
