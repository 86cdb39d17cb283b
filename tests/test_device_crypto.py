"""Device-resident Ed25519 batch verification: the correctness battery.

Four layers, each pinned against an independent oracle:

- the u32-limb field core against Python big-int arithmetic (random,
  boundary, AND adversarial near-0xFFFF ripple patterns — the carry
  chain's rigor claim is load-bearing for soundness);
- vectorized SHA-512 against hashlib;
- curve ops + batched decompression against the pure-Python RFC 8032
  twin (``signing/_ed25519.py``), including every 5.1.3 rejection class;
- the seam (``Ed25519DeviceConsensusSigner``) against BOTH host
  verifiers — the pure-Python twin per item and the native pool's batch
  path — on RFC 8032 vectors, a seeded fuzz corpus (non-canonical
  encodings, s >= L, low-order points, corrupted signatures, ragged
  batches), and the exact-per-item-blame contract.

Shape discipline: small-batch tests share ONE set of lane/block buckets
(n <= 6 -> 16-lane MSM) so tier-1 pays each XLA compile once. The
4k-batch blame case and the chaos scenarios are ``slow``-marked: tier-1
skips them, the ``device-crypto`` CI job (and ``pytest -m slow``) runs
them.
"""

import hashlib
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from hashgraph_tpu import native  # noqa: E402
from hashgraph_tpu.crypto_device import curve, msm  # noqa: E402
from hashgraph_tpu.crypto_device import field as fe  # noqa: E402
from hashgraph_tpu.crypto_device import sha512 as sh  # noqa: E402
from hashgraph_tpu.errors import ConsensusSchemeError  # noqa: E402
from hashgraph_tpu.obs import (  # noqa: E402
    DEVICE_VERIFY_BATCHES_TOTAL,
    DEVICE_VERIFY_FALLBACKS_TOTAL,
    DEVICE_VERIFY_SECONDS,
    DEVICE_VERIFY_SIGNATURES_TOTAL,
    registry,
)
from hashgraph_tpu.signing import (  # noqa: E402
    Ed25519ConsensusSigner,
    Ed25519DeviceConsensusSigner,
)
from hashgraph_tpu.signing import _ed25519 as py  # noqa: E402

P = fe.P
L = py.L


def _limbs(vals):
    return jnp.asarray(
        np.array(
            [[(v >> (16 * j)) & 0xFFFF for j in range(16)] for v in vals],
            np.uint32,
        )
    )


def _pt_limbs(pt):
    return np.array(
        [[(v >> (16 * b)) & 0xFFFF for b in range(16)] for v in pt],
        np.uint32,
    )


def _carried(arr) -> bool:
    return bool((np.asarray(arr) < (1 << 16)).all())


class TestFieldCore:
    def test_mul_add_sub_vs_python_ints(self):
        rng = random.Random(0xFE1D)
        vals_a = [rng.getrandbits(256) for _ in range(48)]
        vals_b = [rng.getrandbits(256) for _ in range(48)]
        # Boundaries + adversarial ripple patterns: all-0xFFFF limbs,
        # p itself, 2p, values crafted so carries cascade end to end.
        vals_a += [0, 1, 19, P - 1, P, P + 1, 2 * P, 2**256 - 1,
                   2**256 - 2**240, (2**256 - 2**240) | 0xFFFF]
        vals_b += [2**256 - 1, 2**256 - 1, 2**256 - 1, 1, 0, P, 1,
                   2**256 - 1, 1, 1]
        a, b = _limbs(vals_a), _limbs(vals_b)
        got_mul = np.asarray(fe.mul(a, b))
        got_add = np.asarray(fe.add(a, b))
        got_sub = np.asarray(fe.sub(a, b))
        for i, (x, y) in enumerate(zip(vals_a, vals_b)):
            assert fe.limbs_to_int(got_mul[i]) % P == (x * y) % P
            assert fe.limbs_to_int(got_add[i]) % P == (x + y) % P
            assert fe.limbs_to_int(got_sub[i]) % P == (x - y) % P
        # The carried invariant is soundness-critical: a limb at 2^16
        # would square to 2^32 === 0 in uint32 and verify garbage.
        assert _carried(got_mul) and _carried(got_add) and _carried(got_sub)

    def test_exponentiation_chains(self):
        rng = random.Random(0xCA1)
        vals = [rng.getrandbits(255) for _ in range(8)] + [1, 2, P - 1]
        a = _limbs(vals)
        inv = np.asarray(fe.invert(a))
        p22 = np.asarray(fe.pow22523(a))
        for i, v in enumerate(vals):
            assert fe.limbs_to_int(inv[i]) % P == pow(v % P, P - 2, P)
            assert fe.limbs_to_int(p22[i]) % P == pow(v % P, (P - 5) // 8, P)

    def test_canon_and_bytes(self):
        vals = [0, 1, P - 1, P, P + 1, 2 * P + 5, 2**256 - 1]
        a = _limbs(vals)
        can = np.asarray(fe.canon(a))
        enc = np.asarray(fe.to_bytes(a))
        for i, v in enumerate(vals):
            assert fe.limbs_to_int(can[i]) == v % P
            assert int.from_bytes(enc[i].tobytes(), "little") == v % P
        # Canonical-encoding flags: y < p accepted, y >= p rejected.
        flags = np.asarray(fe.is_canonical_fe(jnp.asarray(np.stack([
            np.frombuffer((P - 1).to_bytes(32, "little"), np.uint8),
            np.frombuffer(P.to_bytes(32, "little"), np.uint8),
            np.frombuffer((2**255 - 1).to_bytes(32, "little"), np.uint8),
        ]))))
        assert flags.tolist() == [True, False, False]


class TestSha512Device:
    def test_against_hashlib_ragged_single_dispatch(self):
        rng = random.Random(5)
        msgs = [b"", b"abc", b"a" * 111, b"b" * 112, b"c" * 127,
                b"d" * 128, b"e" * 129, b"f" * 255,
                bytes(rng.randrange(256) for _ in range(217))]
        out = sh.sha512_batch(msgs, 4)
        for m, d in zip(msgs, out):
            assert d.tobytes() == hashlib.sha512(m).digest(), len(m)

    def test_derived_constants_match_fips(self):
        # Spot-pin the derived K/H against the published first/last
        # values so a broken integer-root can't quietly pass (the
        # hashlib comparison above would catch it too — two oracles).
        assert sh._K64[0] == 0x428A2F98D728AE22
        assert sh._K64[79] == 0x6C44198C4A475817
        assert sh._H64[0] == 0x6A09E667F3BCC908
        assert sh._H64[7] == 0x5BE0CD19137E2179


class TestCurveDevice:
    def test_decompress_parity_with_host_twin(self):
        rng = random.Random(9)
        encs = []
        for _ in range(8):
            encs.append(py._encode(py._mul(py._BASE, rng.getrandbits(252))))
        encs += [
            b"\x01" + b"\x00" * 31,               # identity (y=1)
            bytes(32),                             # y=0 (order-4 point)
            b"\xff" * 32,                          # y >= p: non-canonical
            py.P.to_bytes(32, "little"),           # y = p: non-canonical
            (py.P - 1).to_bytes(32, "little"),     # may lack a root
            b"\x02" + b"\x00" * 31,
            bytes(31) + b"\x80",                   # x=0 with sign bit
            b"\x03" + b"\x00" * 30 + b"\x80",
        ]
        arr = jnp.asarray(
            np.frombuffer(b"".join(encs), np.uint8).reshape(-1, 32)
        )
        pts, ok = curve.decompress(arr)
        pts, ok = np.asarray(pts), np.asarray(ok)
        for i, enc in enumerate(encs):
            want = py._decode(enc)
            assert bool(ok[i]) == (want is not None), enc.hex()
            if want is None:
                continue
            x, y, z, _ = want
            zi = pow(z, P - 2, P)
            for coord, host in ((0, x * zi % P), (1, y * zi % P)):
                got = fe.limbs_to_int(
                    np.asarray(fe.canon(jnp.asarray(pts[i][coord])))
                )
                assert got == host, (i, coord)

    def test_add_dbl_parity_with_host_twin(self):
        rng = random.Random(11)
        host_pts = [
            py._mul(py._BASE, rng.getrandbits(250)) for _ in range(4)
        ] + [py._IDENTITY]
        arr = jnp.asarray(np.stack([_pt_limbs(p) for p in host_pts]))
        got_dbl = np.asarray(curve.dbl(arr))
        got_add = np.asarray(curve.add(arr, arr[::-1].copy()))

        def affine(pt):
            x, y, z, _ = pt
            zi = pow(z, P - 2, P)
            return (x * zi % P, y * zi % P)

        def affine_dev(row):
            x, y, z = (
                fe.limbs_to_int(np.asarray(fe.canon(jnp.asarray(row[j]))))
                for j in range(3)
            )
            zi = pow(z, P - 2, P)
            return (x * zi % P, y * zi % P)

        for i, p in enumerate(host_pts):
            assert affine_dev(got_dbl[i]) == affine(py._dbl(p))
            assert affine_dev(got_add[i]) == affine(
                py._add(p, host_pts[len(host_pts) - 1 - i])
            )

    def test_msm_identity_criterion(self):
        # s*P + (L-s)*P cancels (mod the cofactor the final *8 clears).
        rng = random.Random(13)
        pt = py._decode(py._encode(py._mul(py._BASE, rng.getrandbits(250))))
        pts = np.broadcast_to(curve.IDENTITY, (8, 4, 16)).copy()
        pts[0] = pts[1] = _pt_limbs(pt)
        s = rng.getrandbits(251) % L
        nib = np.zeros((8, 64), np.int32)
        nib[:2] = msm.scalars_to_nibbles([s, L - s])
        assert msm.msm_accepts(jnp.asarray(pts), jnp.asarray(nib))
        nib[0, 63] ^= 1
        assert not msm.msm_accepts(jnp.asarray(pts), jnp.asarray(nib))


# ── The seam: RFC 8032 vectors + decision-identity vs host verifiers ──

RFC8032_VECTORS = [
    # (seed hex, public hex, message hex, signature hex) — RFC 8032 §7.1
    ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
     "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
     "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
     "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
     "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
     "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
     "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
    ("c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
     "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
     "af82",
     "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
     "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"),
]


def _device_batch(idents, payloads, sigs):
    return Ed25519DeviceConsensusSigner.verify_batch(idents, payloads, sigs)


def _host_expected(idents, payloads, sigs):
    """The oracle: per-item pure-Python RFC 8032 verdicts, with the
    seam's length-error convention layered on."""
    out = []
    for ident, payload, sig in zip(idents, payloads, sigs):
        if len(sig) != 64 or len(ident) != 32:
            out.append("scheme-error")
        else:
            out.append(py.verify(bytes(ident), payload, bytes(sig)))
    return out


def _assert_decision_identical(idents, payloads, sigs):
    got = _device_batch(idents, payloads, sigs)
    want = _host_expected(idents, payloads, sigs)
    native_got = Ed25519ConsensusSigner.verify_batch(idents, payloads, sigs)
    assert len(got) == len(want) == len(native_got)
    for g, n, w in zip(got, native_got, want):
        if w == "scheme-error":
            assert isinstance(g, ConsensusSchemeError)
            assert isinstance(n, ConsensusSchemeError)
        else:
            assert g is w, (g, w)
            assert n is w, (n, w)


class TestDeviceSeam:
    def test_rfc8032_vectors_pinned(self):
        idents, payloads, sigs = [], [], []
        for seed_hex, pub_hex, msg_hex, sig_hex in RFC8032_VECTORS:
            signer = Ed25519ConsensusSigner(
                bytes.fromhex(seed_hex), device_verify=True
            )
            assert type(signer) is Ed25519DeviceConsensusSigner
            assert signer.identity().hex() == pub_hex
            msg = bytes.fromhex(msg_hex)
            sig = signer.sign(msg)
            assert sig.hex() == sig_hex
            idents.append(signer.identity())
            payloads.append(msg)
            sigs.append(sig)
        assert _device_batch(idents, payloads, sigs) == [True] * 3
        # Any single-bit corruption must flip exactly that verdict.
        bad = list(sigs)
        bad[1] = bytes([bad[1][0] ^ 1]) + bad[1][1:]
        assert _device_batch(idents, payloads, bad) == [True, False, True]

    def test_selection_seam(self, monkeypatch):
        seed = b"\x42" * 32
        assert type(Ed25519ConsensusSigner(seed)) is Ed25519ConsensusSigner
        dev = Ed25519ConsensusSigner(seed, device_verify=True)
        assert type(dev) is Ed25519DeviceConsensusSigner
        assert dev.identity() == Ed25519ConsensusSigner(seed).identity()
        monkeypatch.setenv("HASHGRAPH_TPU_DEVICE_VERIFY", "1")
        assert type(Ed25519ConsensusSigner(seed)) is (
            Ed25519DeviceConsensusSigner
        )
        # Explicit False beats the env; subclass construction sticks.
        assert type(
            Ed25519ConsensusSigner(seed, device_verify=False)
        ) is Ed25519ConsensusSigner
        assert type(Ed25519DeviceConsensusSigner.random()) is (
            Ed25519DeviceConsensusSigner
        )
        monkeypatch.setenv("HASHGRAPH_TPU_DEVICE_VERIFY", "0")
        assert type(Ed25519ConsensusSigner(seed)) is Ed25519ConsensusSigner

    def test_unservable_device_request_raises(self, monkeypatch):
        """Asked-for device verification that JAX cannot serve refuses to
        construct; it never degrades to the host path."""
        from hashgraph_tpu import crypto_device

        seed = b"\x42" * 32
        monkeypatch.setattr(crypto_device, "available", lambda: False)
        with pytest.raises(RuntimeError, match="device_verify"):
            Ed25519ConsensusSigner(seed, device_verify=True)
        monkeypatch.setenv("HASHGRAPH_TPU_DEVICE_VERIFY", "1")
        with pytest.raises(RuntimeError, match="device_verify"):
            Ed25519ConsensusSigner(seed)
        assert type(
            Ed25519ConsensusSigner(seed, device_verify=False)
        ) is Ed25519ConsensusSigner

    def test_seeded_fuzz_decision_identity(self):
        """Every mutation class the wire can produce, device == host,
        at ONE lane bucket (n=6) so the compile is paid once."""
        rng = random.Random(0xF0D5)
        signers = [Ed25519DeviceConsensusSigner.random() for _ in range(3)]
        low_order = [b"\x01" + b"\x00" * 31, bytes(32),
                     b"\xec" + b"\xff" * 30 + b"\x7f"]  # y = p-3... reject/ok per twin
        for round_no in range(6):
            idents, payloads, sigs = [], [], []
            for i in range(6):
                s = signers[rng.randrange(3)]
                payload = b"fuzz-%d-%d" % (round_no, i)
                ident, sig = s.identity(), s.sign(payload)
                mutation = rng.randrange(8)
                if mutation == 1:
                    sig = bytes([sig[0] ^ (1 << rng.randrange(8))]) + sig[1:]
                elif mutation == 2:  # corrupt s, keep it canonical
                    s_int = int.from_bytes(sig[32:], "little")
                    s_int = (s_int + 1 + rng.getrandbits(100)) % L
                    sig = sig[:32] + s_int.to_bytes(32, "little")
                elif mutation == 3:  # non-canonical scalar s + L
                    s_int = int.from_bytes(sig[32:], "little")
                    if s_int + L < 2**256:
                        sig = sig[:32] + (s_int + L).to_bytes(32, "little")
                elif mutation == 4:  # undecodable / non-canonical A
                    ident = rng.choice([b"\xff" * 32, py.P.to_bytes(32, "little")])
                elif mutation == 5:  # low-order or identity R
                    sig = rng.choice(low_order) + sig[32:]
                elif mutation == 6:  # cross-wired payload
                    payload = b"someone-else's-bytes"
                elif mutation == 7:  # low-order A
                    ident = rng.choice(low_order)
                idents.append(ident)
                payloads.append(payload)
                sigs.append(sig)
            _assert_decision_identical(idents, payloads, sigs)

    def test_ragged_scheme_errors_empty(self):
        s = Ed25519DeviceConsensusSigner.random()
        sig = s.sign(b"p")
        out = _device_batch(
            [s.identity(), b"\x01" * 5, s.identity()],
            [b"p", b"p", b"p"],
            [sig, sig, b"xx"],
        )
        assert out[0] is True
        assert isinstance(out[1], ConsensusSchemeError)
        assert isinstance(out[2], ConsensusSchemeError)
        assert len(
            Ed25519DeviceConsensusSigner.verify_batch(
                [s.identity()] * 4, [b"p"] * 2, [sig] * 4
            )
        ) == 2
        assert Ed25519DeviceConsensusSigner.verify_batch([], [], []) == []

    def test_submit_collect_and_metrics(self):
        batches0 = registry.counter(DEVICE_VERIFY_BATCHES_TOTAL).value
        sigs0 = registry.counter(DEVICE_VERIFY_SIGNATURES_TOTAL).value
        hist0 = registry.histogram(DEVICE_VERIFY_SECONDS).count
        signers = [Ed25519DeviceConsensusSigner.random() for _ in range(3)]
        payloads = [b"m-%d" % i for i in range(6)]
        idents = [signers[i % 3].identity() for i in range(6)]
        sigs = [signers[i % 3].sign(p) for i, p in enumerate(payloads)]
        pend = Ed25519DeviceConsensusSigner.verify_batch_submit(
            idents, payloads, sigs
        )
        got = pend.collect()
        assert got == [True] * 6
        assert pend.collect() is got  # idempotent
        assert registry.counter(DEVICE_VERIFY_BATCHES_TOTAL).value == (
            batches0 + 1
        )
        assert registry.counter(DEVICE_VERIFY_SIGNATURES_TOTAL).value == (
            sigs0 + 6
        )
        assert registry.histogram(DEVICE_VERIFY_SECONDS).count == hist0 + 1
        phases = Ed25519DeviceConsensusSigner.device_phase_seconds()
        assert set(phases) >= {"decompress", "hash", "msm", "total"}

    def test_blame_fallback_exact_and_counted(self):
        """A wrong-but-well-encoded signature survives decompression, so
        the linear combination itself must fail and the host blame pass
        must name exactly the bad row (and count the escalation)."""
        fb0 = registry.counter(DEVICE_VERIFY_FALLBACKS_TOTAL).value
        signers = [Ed25519DeviceConsensusSigner.random() for _ in range(3)]
        payloads = [b"blame-%d" % i for i in range(6)]
        idents = [signers[i % 3].identity() for i in range(6)]
        sigs = [signers[i % 3].sign(p) for i, p in enumerate(payloads)]
        # Tamper with s only (stays canonical, R still decodes): the
        # only rejection path left is the batch equation.
        s_int = int.from_bytes(sigs[4][32:], "little")
        sigs[4] = sigs[4][:32] + ((s_int + 7) % L).to_bytes(32, "little")
        out = _device_batch(idents, payloads, sigs)
        assert out == [True, True, True, True, False, True]
        assert registry.counter(DEVICE_VERIFY_FALLBACKS_TOTAL).value == (
            fb0 + 1
        )
        phases = Ed25519DeviceConsensusSigner.device_phase_seconds()
        assert phases["fallback"] > 0.0

    def test_engine_reaches_device_path_through_seam(self):
        """End to end: an engine built with a device signer runs its
        verify prepass on the backend with ZERO engine changes, and the
        per-scheme counter picks up the distinct backend label."""
        from hashgraph_tpu.engine import TpuConsensusEngine
        from hashgraph_tpu.obs import VERIFIED_SIGNATURES_TOTAL
        from hashgraph_tpu.protocol import compute_vote_hash
        from hashgraph_tpu.types import CreateProposalRequest
        from hashgraph_tpu.wire import Vote

        batches0 = registry.counter(DEVICE_VERIFY_BATCHES_TOTAL).value
        labelled = registry.counter(
            VERIFIED_SIGNATURES_TOTAL
            + '{scheme="Ed25519DeviceConsensusSigner"}'
        )
        labelled0 = labelled.value
        engine = TpuConsensusEngine(
            Ed25519DeviceConsensusSigner.random(),
            capacity=8,
            voter_capacity=4,
        )
        now = 1_700_000_000
        scope = "device-seam"
        voters = [Ed25519DeviceConsensusSigner.random() for _ in range(3)]
        proposal = engine.create_proposals(
            scope,
            [CreateProposalRequest(
                name="p", payload=b"", proposal_owner=b"o",
                expected_voters_count=3, expiration_timestamp=now + 100,
                liveness_criteria_yes=True,
            )],
            now,
        )[0]
        votes = []
        for lane, voter in enumerate(voters):
            vote = Vote(
                vote_id=lane + 1, vote_owner=voter.identity(),
                proposal_id=proposal.proposal_id, timestamp=now,
                vote=True, parent_hash=b"", received_hash=b"",
                vote_hash=b"", signature=b"",
            )
            vote.vote_hash = compute_vote_hash(vote)
            vote.signature = voter.sign(vote.signing_payload())
            votes.append(vote)
        # Corrupt the last vote's signature scalar: the device batch
        # must blame exactly it while admitting the other two.
        s_int = int.from_bytes(votes[2].signature[32:], "little")
        votes[2].signature = votes[2].signature[:32] + (
            (s_int + 3) % L
        ).to_bytes(32, "little")
        statuses = engine.ingest_votes([(scope, v) for v in votes], now)
        assert [int(code) for code in statuses[:2]] == [0, 0]
        assert int(statuses[2]) != 0
        assert registry.counter(DEVICE_VERIFY_BATCHES_TOTAL).value > batches0
        assert labelled.value > labelled0
        engine.delete_scope(scope)


@pytest.mark.skipif(not native.available(), reason="native runtime absent")
class TestNativeParity:
    def test_device_vs_native_pool_mixed_verdicts(self):
        """The two batch backends (device RLC + native pool RLC) must
        agree verdict-for-verdict on a mixed batch — the native-parity
        contract of PARITY.md's 'Device-resident verification' row."""
        rng = random.Random(0xAB)
        signers = [Ed25519DeviceConsensusSigner.random() for _ in range(3)]
        payloads = [b"np-%d" % i for i in range(6)]
        idents = [signers[i % 3].identity() for i in range(6)]
        sigs = [signers[i % 3].sign(p) for i, p in enumerate(payloads)]
        for bad in (1, 4):
            s_int = int.from_bytes(sigs[bad][32:], "little")
            sigs[bad] = sigs[bad][:32] + (
                (s_int + rng.randrange(1, 99)) % L
            ).to_bytes(32, "little")
        device = _device_batch(idents, payloads, sigs)
        pool = native.ed25519_verify_batch(
            [bytes(i) for i in idents], payloads, [bytes(s) for s in sigs]
        )
        assert device == [code == 1 for code in pool]


@pytest.mark.slow
class TestBlame4k:
    def test_one_bad_signature_in_4096_names_exactly_that_index(self):
        rng = random.Random(0x4096)
        signers = [Ed25519DeviceConsensusSigner.random() for _ in range(8)]
        n, bad_index = 4096, 2026
        payloads = [b"batch4k-%04d" % i for i in range(n)]
        idents = [signers[i % 8].identity() for i in range(n)]
        sigs = [signers[i % 8].sign(p) for i, p in enumerate(payloads)]
        s_int = int.from_bytes(sigs[bad_index][32:], "little")
        sigs[bad_index] = sigs[bad_index][:32] + (
            (s_int + 1 + rng.getrandbits(64)) % L
        ).to_bytes(32, "little")
        fb0 = registry.counter(DEVICE_VERIFY_FALLBACKS_TOTAL).value
        out = _device_batch(idents, payloads, sigs)
        assert out[bad_index] is False
        assert all(
            verdict is True for i, verdict in enumerate(out) if i != bad_index
        )
        assert registry.counter(DEVICE_VERIFY_FALLBACKS_TOTAL).value == fb0 + 1


@pytest.mark.slow
class TestChaosWithDeviceBackend:
    """The deterministic chaos scenarios whose injectors attack
    signatures, re-run with the device backend forced on: all three
    machine-checked verdicts (convergence, exact-culprit accountability,
    safety) must hold unchanged — device-rejected rows mint the same
    scorecard attributions as host-rejected ones."""

    def _run(self, name, **kwargs):
        from hashgraph_tpu.sim.scenarios import run_scenario

        outcome = run_scenario(name, 1, **kwargs)
        assert outcome["passed"], outcome["checks"]
        for key, verdict in outcome["verdicts"].items():
            assert verdict["ok"], (name, key, verdict)
        return outcome

    def test_signature_burst_device_backend(self):
        self._run(
            "expired-spam-burst",
            signer_factory=Ed25519DeviceConsensusSigner,
        )

    def test_columnar_wire_storm_device_backend(self):
        self._run(
            "columnar-wire-storm",
            signer_factory=Ed25519DeviceConsensusSigner,
        )

    def test_signature_burst_env_selection(self, monkeypatch):
        """Same scenario, device backend selected by env alone — the
        zero-caller-change path a production deployment flips."""
        monkeypatch.setenv("HASHGRAPH_TPU_DEVICE_VERIFY", "1")
        batches0 = registry.counter(DEVICE_VERIFY_BATCHES_TOTAL).value
        self._run(
            "expired-spam-burst", signer_factory=Ed25519ConsensusSigner
        )
        assert registry.counter(DEVICE_VERIFY_BATCHES_TOTAL).value > batches0


def test_hypothesis_fuzz_decision_identity():
    """Property-based mutation fuzz (skips cleanly without hypothesis,
    like the repo's other property suites)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    signer = Ed25519DeviceConsensusSigner.random()
    good_sig = signer.sign(b"hyp")

    @hyp.settings(max_examples=12, deadline=None)
    @hyp.given(
        flips=st.lists(
            st.tuples(st.integers(0, 63), st.integers(0, 7)),
            min_size=0, max_size=3,
        ),
        ident_mut=st.sampled_from(
            ["keep", "ff", "p", "identity-point"]
        ),
    )
    def check(flips, ident_mut):
        sig = bytearray(good_sig)
        for pos, bit in flips:
            sig[pos] ^= 1 << bit
        ident = {
            "keep": signer.identity(),
            "ff": b"\xff" * 32,
            "p": py.P.to_bytes(32, "little"),
            "identity-point": b"\x01" + b"\x00" * 31,
        }[ident_mut]
        idents = [ident] * 6
        payloads = [b"hyp"] * 6
        sigs = [bytes(sig)] * 6
        _assert_decision_identical(idents, payloads, sigs)

    check()
