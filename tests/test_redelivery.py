"""Gossip redelivery + incremental chain growth: the deliver_proposals
create-or-extend surface and its validated-chain watermark.

Tier-1 smoke for the amortization layer (ISSUE 4): one redelivery wave
with the stub signer exercises cache hits, the watermark suffix path,
fork/truncation rejection, and a grow-then-redeliver workload —
without ``slow`` markers or real ECDSA.
"""

import pytest

from hashgraph_tpu import (
    CreateProposalRequest,
    StubConsensusSigner,
    build_vote,
)
from hashgraph_tpu.engine import TpuConsensusEngine
from hashgraph_tpu.errors import StatusCode
from hashgraph_tpu.wire import Proposal

from common import NOW

OK = int(StatusCode.OK)
EXISTS = int(StatusCode.PROPOSAL_ALREADY_EXIST)


def make_engine(cache="default", voters=16, **kwargs):
    return TpuConsensusEngine(
        StubConsensusSigner(b"\x42" * 20),
        capacity=32,
        voter_capacity=voters,
        verify_cache=cache,
        **kwargs,
    )


def make_chain(n_votes=6, expected=12, scope="s", engine=None):
    """(engine, base proposal, fully grown chain) with ``n_votes`` chained
    votes by distinct stub signers (chain-linked via build_vote)."""
    engine = engine if engine is not None else make_engine()
    proposal = engine.create_proposal(
        scope,
        CreateProposalRequest(
            name="p",
            payload=b"x",
            proposal_owner=b"o",
            expected_voters_count=expected,
            expiration_timestamp=10_000,
            liveness_criteria_yes=True,
        ),
        NOW,
    )
    chain = proposal.clone()
    for i in range(n_votes):
        signer = StubConsensusSigner(bytes([i + 1]) * 20)
        chain.votes.append(build_vote(chain, bool(i % 2), signer, NOW + 1 + i))
    return engine, proposal, chain


def grown(chain, k):
    p = chain.clone()
    p.votes = [v.clone() for v in chain.votes[:k]]
    return p


class TestDeliverProposals:
    def test_unknown_pid_registers(self):
        _, _, chain = make_chain()
        receiver = make_engine()
        [code] = receiver.deliver_proposals([("s", grown(chain, 3))], NOW + 20)
        assert code == OK
        got = receiver.get_proposal("s", chain.proposal_id)
        assert [v.vote_hash for v in got.votes] == [
            v.vote_hash for v in chain.votes[:3]
        ]

    def test_incremental_growth_extends_along_watermark(self):
        _, _, chain = make_chain(n_votes=6)
        receiver = make_engine()
        for k in range(1, len(chain.votes) + 1):
            [code] = receiver.deliver_proposals(
                [("s", grown(chain, k))], NOW + 20
            )
            assert code == OK, (k, code)
        got = receiver.get_proposal("s", chain.proposal_id)
        assert [v.vote_hash for v in got.votes] == [
            v.vote_hash for v in chain.votes
        ]

    def test_exact_redelivery_is_already_exist(self):
        _, _, chain = make_chain()
        receiver = make_engine()
        assert receiver.deliver_proposal("s", grown(chain, 4), NOW + 20) == OK
        assert (
            receiver.deliver_proposal("s", grown(chain, 4), NOW + 21) == EXISTS
        )

    def test_truncated_chain_rejected(self):
        _, _, chain = make_chain()
        receiver = make_engine()
        assert receiver.deliver_proposal("s", grown(chain, 4), NOW + 20) == OK
        assert (
            receiver.deliver_proposal("s", grown(chain, 2), NOW + 21) == EXISTS
        )
        assert len(receiver.get_proposal("s", chain.proposal_id).votes) == 4

    def test_expired_extension_fails_fast_without_crypto(self):
        """Extensions of an expired session are rejected BEFORE the
        signature prepass, matching the expiry fail-fasts on the
        process_incoming_proposal / ingest_proposals entry points: an
        attacker redelivering grown chains of a dead session must not buy
        ECDSA work or churn the shared cache's LRU."""

        class CountingSigner(StubConsensusSigner):
            calls = 0

            @classmethod
            def verify(cls, identity, payload, signature):
                cls.calls += 1
                return super().verify(identity, payload, signature)

        engine = TpuConsensusEngine(
            CountingSigner(b"\x42" * 20),
            capacity=32,
            voter_capacity=16,
            verify_cache="default",
        )
        _, _, chain = make_chain(engine=engine)
        assert engine.deliver_proposal("s", grown(chain, 3), NOW + 20) == OK
        cached = len(engine.verify_cache())
        CountingSigner.calls = 0
        expiry = engine.get_proposal("s", chain.proposal_id).expiration_timestamp
        late = expiry + 1
        [code] = engine.deliver_proposals([("s", grown(chain, 6))], late)
        assert code == int(StatusCode.PROPOSAL_EXPIRED)
        assert CountingSigner.calls == 0
        assert len(engine.verify_cache()) == cached
        # The accepted prefix is untouched.
        assert len(engine.get_proposal("s", chain.proposal_id).votes) == 3

    def test_fork_before_watermark_rejected(self):
        _, proposal, chain = make_chain()
        receiver = make_engine()
        assert receiver.deliver_proposal("s", grown(chain, 4), NOW + 20) == OK
        fork = grown(chain, 5)
        # Replace vote 2 with a differently-signed one: the prefix no
        # longer matches the accepted chain, so nothing applies.
        fork.votes[2] = build_vote(
            proposal, True, StubConsensusSigner(b"\x91" * 20), NOW + 40
        )
        assert receiver.deliver_proposal("s", fork, NOW + 41) == EXISTS
        got = receiver.get_proposal("s", chain.proposal_id)
        assert [v.vote_hash for v in got.votes] == [
            v.vote_hash for v in chain.votes[:4]
        ]

    def test_bad_signature_suffix_rejected_without_applying(self):
        _, _, chain = make_chain()
        receiver = make_engine()
        assert receiver.deliver_proposal("s", grown(chain, 3), NOW + 20) == OK
        bad = grown(chain, 5)
        bad.votes[4].signature = b"\x00" * 32
        code = receiver.deliver_proposal("s", bad, NOW + 21)
        assert code == int(StatusCode.INVALID_VOTE_SIGNATURE)
        # All-or-nothing: vote 3 (valid) must not have landed either.
        assert len(receiver.get_proposal("s", chain.proposal_id).votes) == 3
        # The honest grown chain still applies afterwards (negative cache
        # holds the forged key only).
        assert receiver.deliver_proposal("s", grown(chain, 5), NOW + 22) == OK

    def test_bad_suffix_link_rejected(self):
        _, _, chain = make_chain()
        receiver = make_engine()
        assert receiver.deliver_proposal("s", grown(chain, 3), NOW + 20) == OK
        bad = grown(chain, 5)
        bad.votes[4].received_hash = b"\x13" * 32
        bad.votes[4].vote_hash = b""
        from hashgraph_tpu.protocol import compute_vote_hash

        bad.votes[4].vote_hash = compute_vote_hash(bad.votes[4])
        signer = StubConsensusSigner(bad.votes[4].vote_owner)
        bad.votes[4].signature = signer.sign(bad.votes[4].signing_payload())
        code = receiver.deliver_proposal("s", bad, NOW + 21)
        assert code == int(StatusCode.RECEIVED_HASH_MISMATCH)
        assert len(receiver.get_proposal("s", chain.proposal_id).votes) == 3

    def test_mixed_batch_fresh_extension_redelivery(self):
        engine_a, _, chain_a = make_chain(scope="a")
        _, _, chain_b = make_chain(scope="b")
        receiver = make_engine()
        assert receiver.deliver_proposal("a", grown(chain_a, 2), NOW + 20) == OK
        codes = receiver.deliver_proposals(
            [
                ("a", grown(chain_a, 4)),  # extension
                ("b", grown(chain_b, 3)),  # fresh registration
                ("a", grown(chain_a, 4)),  # redelivery (same batch!)
            ],
            NOW + 21,
        )
        assert codes == [OK, OK, EXISTS]

    def test_batch_equals_sequential(self):
        """A batch delivery is definitionally the same as sequential
        deliveries — load-bearing for WAL record splitting: a chunked
        KIND_DELIVER record replays as consecutive smaller batches."""
        _, _, chain = make_chain(n_votes=4)
        batched = make_engine()
        codes = batched.deliver_proposals(
            [("s", grown(chain, 2)), ("s", grown(chain, 4))], NOW + 20
        )
        assert codes == [OK, OK]  # create, then extend — not ALREADY_EXIST
        sequential = make_engine()
        assert sequential.deliver_proposal("s", grown(chain, 2), NOW + 20) == OK
        assert sequential.deliver_proposal("s", grown(chain, 4), NOW + 20) == OK
        a = batched.export_session("s", chain.proposal_id)
        b = sequential.export_session("s", chain.proposal_id)
        assert [v.vote_hash for v in a.proposal.votes] == [
            v.vote_hash for v in b.proposal.votes
        ]
        assert len(a.proposal.votes) == 4

    def test_configs_must_align(self):
        receiver = make_engine()
        with pytest.raises(ValueError):
            receiver.deliver_proposals([], NOW, configs=[None])

    def test_oracle_parity_final_session(self):
        """The incrementally-extended session equals the one a fresh
        engine builds from the final chain in one delivery."""
        _, _, chain = make_chain(n_votes=6)
        incremental = make_engine()
        for k in range(1, 7):
            assert (
                incremental.deliver_proposal("s", grown(chain, k), NOW + 20)
                == OK
            )
        oneshot = make_engine()
        assert oneshot.deliver_proposal("s", grown(chain, 6), NOW + 20) == OK
        a = incremental.export_session("s", chain.proposal_id)
        b = oneshot.export_session("s", chain.proposal_id)
        assert [v.vote_hash for v in a.proposal.votes] == [
            v.vote_hash for v in b.proposal.votes
        ]
        assert a.state == b.state
        assert set(a.votes) == set(b.votes)

    def test_decision_fires_on_extension(self):
        """A suffix that crosses quorum decides the session — the
        extension path applies through the real vote pipeline, decision
        kernel included."""
        _, _, chain = make_chain(n_votes=6, expected=6)
        receiver = make_engine()
        assert receiver.deliver_proposal("s", grown(chain, 3), NOW + 20) == OK
        assert receiver.get_consensus_result("s", chain.proposal_id) is None
        code = receiver.deliver_proposal("s", grown(chain, 6), NOW + 21)
        assert code in (OK, int(StatusCode.ALREADY_REACHED))
        oracle = make_engine()
        assert oracle.deliver_proposal("s", grown(chain, 6), NOW + 20) == OK
        assert receiver.get_consensus_result(
            "s", chain.proposal_id
        ) == oracle.get_consensus_result("s", chain.proposal_id)


class TestCacheOnOffEquivalence:
    def test_one_redelivery_wave_smoke(self):
        """A redelivery wave in miniature, stub-signed: grow a
        chain delivery by delivery, then redeliver every vote — cache-on
        and cache-off engines must report identical statuses and end in
        identical sessions."""
        _, _, chain = make_chain(n_votes=5)
        results = {}
        for label, cache in (("on", "default"), ("off", None)):
            receiver = make_engine(cache)
            codes = []
            for k in range(1, 6):
                codes.append(
                    receiver.deliver_proposal("s", grown(chain, k), NOW + 20)
                )
            # Redelivery wave through the vote path (embedder fallback).
            wave = [("s", v.clone()) for v in chain.votes]
            codes.append([int(s) for s in receiver.ingest_votes(wave, NOW + 30)])
            session = receiver.export_session("s", chain.proposal_id)
            results[label] = (
                codes,
                [v.vote_hash for v in session.proposal.votes],
                session.state,
            )
        assert results["on"] == results["off"]


class TestDurableDeliver:
    def test_wal_replay_preserves_extensions(self, tmp_path):
        """deliver_proposals logs KIND_DELIVER: a crash after incremental
        extensions replays to the identical chain (a plain-proposals
        record would replay as ingest and drop every suffix)."""
        from hashgraph_tpu.wal import DurableEngine, replay

        _, _, chain = make_chain(n_votes=5)
        wal_dir = str(tmp_path / "wal")
        durable = DurableEngine(make_engine(), wal_dir)
        for k in range(1, 6):
            assert (
                durable.deliver_proposal("s", grown(chain, k), NOW + 20) == OK
            )
        live = durable.export_session("s", chain.proposal_id)
        durable.close()

        recovered = make_engine()
        stats = replay(wal_dir, recovered)
        assert not stats.errors
        session = recovered.export_session("s", chain.proposal_id)
        assert [v.vote_hash for v in session.proposal.votes] == [
            v.vote_hash for v in live.proposal.votes
        ]
        assert session.state == live.state


class TestProcessIncomingProposalCache:
    def test_scalar_path_uses_cache(self):
        """process_incoming_proposal (the bridge opcode path) consults the
        cache for embedded chains — second engine sharing the cache skips
        every verify (observable via identical outcomes; call counting
        lives in test_verify_cache)."""
        from hashgraph_tpu.engine import VerifiedVoteCache

        _, _, chain = make_chain(n_votes=4)
        shared = VerifiedVoteCache()
        r1 = make_engine(shared)
        r2 = make_engine(shared)
        wire = grown(chain, 4).encode()
        r1.process_incoming_proposal("s", Proposal.decode(wire), NOW + 20)
        r2.process_incoming_proposal("s", Proposal.decode(wire), NOW + 20)
        a = r1.export_session("s", chain.proposal_id)
        b = r2.export_session("s", chain.proposal_id)
        assert [v.vote_hash for v in a.proposal.votes] == [
            v.vote_hash for v in b.proposal.votes
        ]
