"""Zero-copy columnar wire ingest: parser twins, ring transport, and
columnar-vs-object server-path parity (the object path is the oracle)."""

import os
import time

import numpy as np
import pytest

from hashgraph_tpu import build_vote, native
from hashgraph_tpu.bridge import columnar as WC
from hashgraph_tpu.bridge import protocol as P
from hashgraph_tpu.bridge.server import BridgeServer
from hashgraph_tpu.protocol import compute_vote_hash
from hashgraph_tpu.signing.stub import StubConsensusSigner
from hashgraph_tpu.sync.snapshot import state_fingerprint
from hashgraph_tpu.wire import Proposal, Vote

NOW = 1_700_000_000


def _pack(rows):
    offsets = np.zeros(len(rows) + 1, np.int64)
    np.cumsum([len(r) for r in rows], out=offsets[1:])
    return np.frombuffer(b"".join(rows) or b"\0", np.uint8), offsets


def _vote(i=1, **kw):
    kw.setdefault("vote_id", i)
    kw.setdefault("vote_owner", bytes([i]) * 20)
    kw.setdefault("proposal_id", 7)
    kw.setdefault("timestamp", NOW)
    kw.setdefault("vote", True)
    kw.setdefault("parent_hash", b"p" * 32)
    kw.setdefault("received_hash", b"r" * 32)
    kw.setdefault("vote_hash", b"h" * 32)
    kw.setdefault("signature", b"s" * 65)
    return Vote(**kw)


class TestColumnParser:
    def test_canonical_vote_parses_flag1_with_exact_columns(self):
        vote = _vote(3, timestamp=123456789, vote=True)
        raw = vote.encode()
        data, offsets = _pack([raw])
        cols, flags = WC.parse_vote_columns_py(data, offsets)
        assert flags.tolist() == [1]
        c = cols[0]
        assert c[WC.COL_VOTE_ID] == 3
        assert c[WC.COL_PID] == 7
        assert c[WC.COL_TS] == 123456789
        assert c[WC.COL_VALUE] == 1
        buf = data.tobytes()
        assert buf[c[WC.COL_OWNER_OFF]:c[WC.COL_OWNER_OFF] + c[WC.COL_OWNER_LEN]] == vote.vote_owner
        assert buf[c[WC.COL_SIG_OFF]:c[WC.COL_SIG_OFF] + c[WC.COL_SIG_LEN]] == vote.signature
        # The signing payload is a PREFIX of canonical wire bytes.
        assert raw[:c[WC.COL_SIGN_LEN]] == vote.signing_payload()

    def test_absent_fields_are_canonical_with_zero_lengths(self):
        vote = Vote(vote_id=5)  # everything else default/empty
        raw = vote.encode()
        data, offsets = _pack([raw])
        cols, flags = WC.parse_vote_columns_py(data, offsets)
        assert flags.tolist() == [1]
        assert cols[0][WC.COL_OWNER_LEN] == 0
        assert cols[0][WC.COL_SIG_LEN] == 0
        assert cols[0][WC.COL_SIGN_LEN] == len(raw)

    @pytest.mark.parametrize(
        "raw",
        [
            b"\xa0\x01\x00",          # field 20 with value 0 (non-canonical)
            b"\xa0\x01\x80\x00",      # non-minimal varint
            b"\xaa\x01\x00",          # empty LEN field (canonical omits)
            b"\xc0\x01\x02",          # bool field with value 2
            b"\xa0",                  # truncated tag
            b"\xaa\x01\x05ab",        # LEN overruns the row
            b"\x08\x01",              # unknown field number
            _vote(1).encode() + b"\x01",  # trailing garbage
            _vote(1).encode()[:-3],   # truncated signature field
        ],
    )
    def test_non_canonical_rows_flag_zero(self, raw):
        data, offsets = _pack([raw])
        cols, flags = WC.parse_vote_columns_py(data, offsets)
        assert flags.tolist() == [0]

    def test_out_of_order_fields_flag_zero_but_decode_still_works(self):
        # Swap two fields: Vote.decode accepts it (last-wins protobuf
        # semantics), the strict parser must NOT (the re-encoded
        # signing payload would differ from the wire prefix).
        reordered = b"\xb0\x01\x07" + b"\xa0\x01\x03"  # pid then vote_id
        assert Vote.decode(reordered).proposal_id == 7
        data, offsets = _pack([reordered])
        _, flags = WC.parse_vote_columns_py(data, offsets)
        assert flags.tolist() == [0]

    @pytest.mark.skipif(not native.available(), reason="native runtime absent")
    def test_native_and_python_parsers_are_output_identical(self):
        rows = [
            _vote(i, timestamp=NOW + i, vote=bool(i % 2)).encode()
            for i in range(1, 9)
        ]
        rows += [
            b"",
            b"\xa0\x01\x00",
            os.urandom(24),
            _vote(1).encode()[:-2],
            Vote(vote_owner=b"x").encode(),
            b"\xa0\x01\xff\xff\xff\xff\xff\xff\xff\xff\xff\x01",  # u64 max
        ]
        data, offsets = _pack(rows)
        cols_n, flags_n = native.parse_vote_columns(data, offsets)
        cols_p, flags_p = WC.parse_vote_columns_py(data, offsets)
        assert flags_n.tolist() == flags_p.tolist()
        ok = flags_n.astype(bool)
        assert np.array_equal(cols_n[ok], cols_p[ok])

    def test_vote_hash_columns_matches_compute_vote_hash(self):
        votes = [
            _vote(i, received_hash=b"", parent_hash=bytes([i]) * 32)
            for i in range(1, 6)
        ]
        rows = [v.encode() for v in votes]
        data, offsets = _pack(rows)
        cols, flags = WC.parse_vote_columns(data, offsets)
        assert flags.all()
        digests = WC.vote_hash_columns(data, cols)
        for i, vote in enumerate(votes):
            assert digests[i].tobytes() == compute_vote_hash(vote)


class TestShmRing:
    def test_roundtrip_wrap_and_full(self):
        from hashgraph_tpu.gossip.shm import ShmRing, shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        ring = ShmRing.create(64)
        try:
            assert ring.try_write([b"hello", b"world"], 10)
            assert ring.read_available() == b"helloworld"
            for k in range(20):  # force wraparound repeatedly
                payload = bytes([k]) * 40
                assert ring.try_write([payload], 40)
                assert ring.read_available() == payload
            # The kernel rounds the segment up to a page: fill the REAL
            # capacity exactly, then one more byte must refuse whole.
            cap = ring.capacity
            assert ring.try_write([b"x" * cap], cap)
            assert not ring.try_write([b"y"], 1)  # full: all-or-nothing
            drained = b""
            while True:
                chunk = ring.read_available()
                if chunk is None:
                    break
                drained += chunk
            assert drained == b"x" * cap
        finally:
            ring.close()

    def test_attach_sees_creator_writes(self):
        from hashgraph_tpu.gossip.shm import ShmRing, shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        a = ShmRing.create(128)
        b = ShmRing.attach(a.name)
        try:
            assert a.try_write([b"abc"], 3)
            assert b.read_available() == b"abc"
        finally:
            b.close()
            a.close()


class _Harness:
    """Two embedded servers fed IDENTICAL frames: wire_columnar on/off.
    Every dispatch asserts byte-identical responses — the object path is
    the parity oracle for the columnar fast path."""

    def __init__(self):
        self.columnar = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=64,
            voter_capacity=24, wire_columnar=True,
        )
        self.objects = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=64,
            voter_capacity=24, wire_columnar=False,
        )
        for server in (self.columnar, self.objects):
            server.start_embedded()
        self.peer_ids = [
            P.Cursor(self._both(P.OP_ADD_PEER, P.u8(32) + b"\x11" * 32)).u32()
        ]

    def _both(self, opcode, payload) -> bytes:
        sc, oc = (
            self.columnar.dispatch_frame(opcode, payload),
            self.objects.dispatch_frame(opcode, payload),
        )
        assert sc == oc, f"parity break on opcode {opcode}: {sc} != {oc}"
        assert sc[0] == P.STATUS_OK, sc
        return sc[1]

    def both_raw(self, opcode, payload):
        """Dispatch to both and require byte-identical (status, body)."""
        sc = self.columnar.dispatch_frame(opcode, payload)
        oc = self.objects.dispatch_frame(opcode, payload)
        assert sc == oc, f"parity break on opcode {opcode}: {sc} != {oc}"
        return sc

    def deliver_proposal(self, scope: str, proposal: Proposal):
        self._both(
            P.OP_PROCESS_PROPOSAL,
            P.u32(self.peer_ids[0]) + P.string(scope) + P.u64(NOW)
            + P.blob(proposal.encode()),
        )

    def fingerprints_equal(self) -> bool:
        pid = self.peer_ids[0]
        return state_fingerprint(
            self.columnar.peer_engine(pid)
        ) == state_fingerprint(self.objects.peer_engine(pid))

    def stop(self):
        self.columnar.stop()
        self.objects.stop()


def _chain(proposal: Proposal, signers, value=True):
    """Signed chained wire votes for ``proposal`` (mutates its votes)."""
    out = []
    for signer in signers:
        vote = build_vote(proposal, value, signer, NOW + 1)
        proposal.votes.append(vote)
        out.append(vote.encode())
    return out


def _proposal(scope_tag: str, voters: int = 20) -> Proposal:
    return Proposal(
        name=f"p-{scope_tag}",
        payload=b"x",
        proposal_id=(abs(hash(scope_tag)) % 1_000_000) + 1,
        proposal_owner=b"\x11" * 20,
        expected_voters_count=voters,
        timestamp=NOW,
        expiration_timestamp=NOW + 3_600,
        liveness_criteria_yes=True,
    )


@pytest.fixture(scope="module")
def harness():
    h = _Harness()
    yield h
    h.stop()


def _batch(h, scope, rows, now=NOW + 1):
    return h.both_raw(
        P.OP_VOTE_BATCH,
        P.encode_vote_batch(now, [(h.peer_ids[0], scope, rows)]),
    )


class TestServerPathParity:
    def test_valid_chain_and_decision(self, harness):
        proposal = _proposal("valid", voters=5)
        harness.deliver_proposal("valid", proposal)
        rows = _chain(proposal, [StubConsensusSigner(bytes([i]) * 20) for i in range(1, 7)])
        status, body = _batch(harness, "valid", rows)
        assert status == P.STATUS_OK
        c = P.Cursor(body)
        assert c.u32() == 6
        assert harness.fingerprints_equal()

    def test_mixed_bad_rows_duplicates_and_junk(self, harness):
        proposal = _proposal("mixed")
        harness.deliver_proposal("mixed", proposal)
        signers = [StubConsensusSigner(bytes([40 + i]) * 20) for i in range(6)]
        rows = _chain(proposal, signers)
        _batch(harness, "mixed", rows[:4])
        flipped = bytearray(rows[4])
        flipped[-1] ^= 0xFF  # signature byte flip: INVALID_VOTE_SIGNATURE
        follow_up = [
            bytes(flipped),
            rows[0],          # duplicate of an accepted vote
            rows[4][:9],      # truncated row (non-canonical -> 241 path)
            os.urandom(40),   # junk row
            rows[5],          # dangles: its predecessor was rejected
        ]
        status, body = _batch(harness, "mixed", follow_up)
        assert status == P.STATUS_OK
        assert harness.fingerprints_equal()

    def test_cross_frame_dangling_guard_stays_armed(self, harness):
        # Drop frame 2 of a chain: frame 3's votes dangle and must be
        # rejected IDENTICALLY on both paths — the wire path's chain
        # continuity state keeps the guard armed past the first frame.
        proposal = _proposal("dangle")
        harness.deliver_proposal("dangle", proposal)
        signers = [StubConsensusSigner(bytes([80 + i]) * 20) for i in range(9)]
        rows = _chain(proposal, signers)
        _batch(harness, "dangle", rows[:3])
        status, body = _batch(harness, "dangle", rows[6:])  # frames 4-6 dropped
        c = P.Cursor(body)
        n = c.u32()
        codes = list(c.raw(n))
        from hashgraph_tpu.errors import StatusCode

        assert codes == [int(StatusCode.RECEIVED_HASH_MISMATCH)] * 3
        assert harness.fingerprints_equal()
        # The repair path (deliver watermark) must still be able to
        # extend the wire-retained session with the missing suffix.
        status, body = harness.both_raw(
            P.OP_DELIVER_PROPOSALS,
            P.encode_deliver_proposals(
                harness.peer_ids[0], [("dangle", proposal.encode())], NOW + 1
            ),
        )
        assert status == P.STATUS_OK
        c = P.Cursor(body)
        assert c.u32() == 1
        assert list(c.raw(1)) == [int(StatusCode.OK)]
        assert harness.fingerprints_equal()

    def test_empty_owner_hash_signature_precedence(self, harness):
        proposal = _proposal("empties")
        harness.deliver_proposal("empties", proposal)
        base = build_vote(
            proposal, True, StubConsensusSigner(b"\x60" * 20), NOW + 1
        )
        no_owner = base.clone()
        no_owner.vote_owner = b""
        no_hash = base.clone()
        no_hash.vote_hash = b""
        no_sig = base.clone()
        no_sig.signature = b""
        bad_hash = base.clone()
        bad_hash.vote_hash = b"\x01" * 32
        expired = build_vote(
            proposal, True, StubConsensusSigner(b"\x61" * 20), NOW + 1
        )
        rows = [v.encode() for v in (no_owner, no_hash, no_sig, bad_hash, expired)]
        status, body = _batch(harness, "empties", rows, now=NOW + 10_000)
        c = P.Cursor(body)
        n = c.u32()
        codes = list(c.raw(n))
        from hashgraph_tpu.errors import StatusCode

        assert codes[:4] == [
            int(StatusCode.EMPTY_VOTE_OWNER),
            int(StatusCode.EMPTY_VOTE_HASH),
            int(StatusCode.EMPTY_SIGNATURE),
            int(StatusCode.INVALID_VOTE_HASH),
        ]
        assert harness.fingerprints_equal()

    def test_unknown_scope_and_unknown_peer(self, harness):
        vote = _vote(1)
        status, body = _batch(harness, "never-created", [vote.encode()])
        c = P.Cursor(body)
        n = c.u32()
        from hashgraph_tpu.errors import StatusCode

        assert list(c.raw(n)) == [int(StatusCode.SESSION_NOT_FOUND)]
        status, body = harness.both_raw(
            P.OP_VOTE_BATCH,
            P.encode_vote_batch(NOW, [(999, "s", [vote.encode()])]),
        )
        c = P.Cursor(body)
        assert list(c.raw(c.u32())) == [P.STATUS_UNKNOWN_PEER]

    def test_malformed_frames_report_identical_errors(self, harness):
        good = P.encode_vote_batch(NOW, [(harness.peer_ids[0], "s", [b"x"])])
        for payload in (
            b"",                      # no header at all
            good[:6],                 # truncated header
            good[:-1],                # truncated vote region
            P.u64(NOW) + P.u32(2) + P.u32(1) + P.string("s") + P.u32(50),
            # count overflow: group claims 2^31 votes
            P.u64(NOW) + P.u32(1) + P.u32(1) + P.string("s")
            + P.u32(0x7FFFFFFF),
        ):
            status_pair = harness.both_raw(P.OP_VOTE_BATCH, payload)
            assert status_pair[0] == P.STATUS_BAD_REQUEST


class TestShmTransportEndToEnd:
    def test_vote_batch_over_shm_ring(self):
        from hashgraph_tpu.gossip import GossipNode
        from hashgraph_tpu.gossip.shm import shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=32, voter_capacity=20
        )
        server.start()
        node = None
        try:
            from hashgraph_tpu.bridge.client import BridgeClient

            client = BridgeClient(*server.address)
            peer_id, _ = client.add_peer(b"\x33" * 32)
            pid, blob = client.create_proposal(
                peer_id, "s", NOW, "p", b"x", 17, 3_600
            )
            proposal = Proposal.decode(blob)
            rows = _chain(
                proposal,
                [StubConsensusSigner(os.urandom(20)) for _ in range(16)],
            )
            node = GossipNode(
                "shm-driver", fanout=None, flush_votes=64,
                shm_ring_bytes=1 << 20,
            )
            node.add_peer("p0", *server.address, peer_id)
            assert node.transport.channel("p0").shm_tx is not None
            node.submit_votes("s", pid, rows, NOW + 1, local=False)
            report = node.drain()
            assert report["acked"] == 16
            assert report["failed_frames"] == 0
            client.close()
        finally:
            if node is not None:
                node.close()
            server.stop()

    def test_attach_refused_keeps_tcp_lane(self):
        from hashgraph_tpu.gossip import GossipNode

        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=8, voter_capacity=4
        )
        server.start()
        node = None
        try:
            # Transport without shm configured: no attach attempted, TCP
            # lane only — and everything still works.
            node = GossipNode("tcp-driver", fanout=None)
            from hashgraph_tpu.bridge.client import BridgeClient

            client = BridgeClient(*server.address)
            peer_id, _ = client.add_peer(b"\x44" * 32)
            node.add_peer("p0", *server.address, peer_id)
            assert node.transport.channel("p0").shm_tx is None
            client.close()
        finally:
            if node is not None:
                node.close()
            server.stop()

    def test_closed_ring_raises_valueerror(self):
        from hashgraph_tpu.gossip.shm import ShmRing, shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        ring = ShmRing.create(64)
        ring.close()
        with pytest.raises(ValueError):
            ring.read_available()
        with pytest.raises(ValueError):
            ring.try_write([b"x"], 1)

    def _shm_transport(self, server, ring_bytes=4096):
        from hashgraph_tpu.gossip.transport import GossipTransport

        transport = GossipTransport(shm_ring_bytes=ring_bytes)
        channel = transport.connect("p0", *server.address)
        if channel.shm_tx is None:
            transport.close()
            pytest.skip("shm attach unavailable")
        return transport, channel

    def test_oversize_frame_rides_tcp_lane(self):
        """A frame the ring can NEVER hold must not shed forever: it
        skips the shm lane and rides the TCP control lane instead."""
        from hashgraph_tpu.gossip.shm import shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=8, voter_capacity=4
        )
        server.start()
        try:
            transport, channel = self._shm_transport(server)
            try:
                payload = b"z" * (channel.shm_tx.capacity + 4096)
                future = transport.try_request("p0", P.OP_PING, payload)
                assert future is not None, "oversize frame was shed"
                assert future.result(10).u32() == P.PROTOCOL_VERSION
                # The shm lane itself stays live for fitting frames.
                small = transport.try_request("p0", P.OP_PING)
                assert small is not None
                assert small.result(10).u32() == P.PROTOCOL_VERSION
            finally:
                transport.close()
        finally:
            server.stop()

    def _assert_channel_dies_typed(self, transport, channel):
        from hashgraph_tpu.bridge.client import (
            BridgeConnectionLost, BridgeError,
        )

        deadline = time.monotonic() + 10
        while channel.alive and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not channel.alive, "corrupt shm stream left the channel up"
        future = transport.try_request("p0", P.OP_PING)
        with pytest.raises((BridgeConnectionLost, BridgeError)):
            future.result(10)

    def test_corrupt_c2s_stream_kills_connection(self):
        """Garbage in the request ring must kill the WHOLE connection
        (server side detects), never silently stop serving the ring
        while the client keeps writing into it."""
        from hashgraph_tpu.gossip.shm import shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=8, voter_capacity=4
        )
        server.start()
        try:
            transport, channel = self._shm_transport(server)
            try:
                # Length prefix 0 is structurally impossible (< tagged
                # minimum of 5): framing is unrecoverable.
                assert channel.shm_tx.try_write([b"\x00" * 4], 4)
                self._assert_channel_dies_typed(transport, channel)
            finally:
                transport.close()
        finally:
            server.stop()

    def test_corrupt_s2c_stream_kills_connection(self):
        """Garbage in the response ring kills the channel typed on the
        client side (rx thread detects)."""
        from hashgraph_tpu.gossip.shm import shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=8, voter_capacity=4
        )
        server.start()
        try:
            transport, channel = self._shm_transport(server)
            try:
                assert channel.shm_rx.try_write([b"\x00" * 4], 4)
                self._assert_channel_dies_typed(transport, channel)
            finally:
                transport.close()
        finally:
            server.stop()

    def test_oversize_response_rides_tcp_lane(self):
        """A response larger than the s2c ring can EVER hold must come
        back on the TCP control lane (corr ids match across lanes) —
        spinning on the full ring would hold the server's tx lock
        forever and wedge every later response on the connection."""
        from hashgraph_tpu.gossip.shm import shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=8, voter_capacity=4
        )
        server.start()
        try:
            transport, channel = self._shm_transport(server, ring_bytes=2048)
            try:
                # GET_METRICS: tiny request (rides the ring), multi-KB
                # process-global registry response (can never fit it).
                future = transport.try_request("p0", P.OP_GET_METRICS)
                assert future is not None
                text = future.result(10).blob()
                assert len(text) > channel.shm_rx.capacity
                assert b"hashgraph" in text
                # The shm lane itself stays live for fitting responses.
                small = transport.try_request("p0", P.OP_PING)
                assert small.result(10).u32() == P.PROTOCOL_VERSION
            finally:
                transport.close()
        finally:
            server.stop()

    def test_mutating_frames_never_split_across_lanes(self):
        """Ordered (mutating) opcodes stay on ONE lane: while any is
        pending on TCP, later mutating frames follow it there; an
        oversize mutating frame is admitted to TCP only once the ring
        is drained (sheds until then). Read-only traffic is unaffected."""
        from hashgraph_tpu.gossip.shm import shm_available

        if not shm_available():
            pytest.skip("shared memory unavailable")
        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=8, voter_capacity=4
        )
        server.start()
        try:
            transport, channel = self._shm_transport(server)
            try:
                # Simulate a mutating frame already pending on TCP.
                with channel.lock:
                    channel.tcp_mutating.add(999_999)
                    corr1 = channel.next_corr
                f1 = transport.try_request("p0", P.OP_PROCESS_VOTE, b"junk")
                assert f1 is not None
                with channel.lock:
                    assert corr1 not in channel.shm_inflight  # rode TCP
                    assert corr1 in channel.tcp_mutating
                    channel.tcp_mutating.discard(999_999)
                with pytest.raises(Exception):
                    f1.result(10)  # junk payload: typed wire error
                # Response received -> the set drains -> mutating frames
                # return to the ring.
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    with channel.lock:
                        if not channel.tcp_mutating:
                            break
                    time.sleep(0.01)
                with channel.lock:
                    assert not channel.tcp_mutating
                    corr2 = channel.next_corr
                f2 = transport.try_request("p0", P.OP_PROCESS_VOTE, b"junk")
                assert f2 is not None
                with channel.lock:
                    rode_ring = corr2 in channel.shm_inflight
                assert rode_ring or f2.done()  # back on the shm lane

                # Oversize mutating frame: gated on a drained ring.
                class _RingProxy:
                    def __init__(self, ring, pending):
                        self._ring = ring
                        self.pending = pending
                        self.capacity = ring.capacity

                    def try_write(self, segments, total):
                        return self._ring.try_write(segments, total)

                    def pending_bytes(self):
                        return self.pending

                    def close(self):
                        self._ring.close()

                real = channel.shm_tx
                proxy = _RingProxy(real, pending=64)
                with channel.lock:
                    channel.shm_tx = proxy
                big = b"z" * (real.capacity + 1024)
                assert transport.try_request(
                    "p0", P.OP_VOTE_BATCH, big
                ) is None  # shed: server has not consumed the ring yet
                proxy.pending = 0
                with channel.lock:
                    corr3 = channel.next_corr
                f3 = transport.try_request("p0", P.OP_VOTE_BATCH, big)
                assert f3 is not None  # drained ring: admitted to TCP
                with channel.lock:
                    assert corr3 in channel.tcp_mutating
                    assert corr3 not in channel.shm_inflight
                    channel.shm_tx = real
                with pytest.raises(Exception):
                    f3.result(10)
            finally:
                transport.close()
        finally:
            server.stop()


class TestShmAttachCleanup:
    def test_failed_second_attach_unmaps_the_first_ring(self, monkeypatch):
        """c2s attaches, s2c raises: the server must close the already
        mapped c2s ring instead of leaking one segment per bad attempt."""
        import threading
        from types import SimpleNamespace

        from hashgraph_tpu.gossip import shm as shm_mod

        closed = []

        class _FakeRing:
            def __init__(self, name):
                self.name = name

            def close(self):
                closed.append(self.name)

            @classmethod
            def attach(cls, name):
                if name == "s2c-bogus":
                    raise OSError("no such segment")
                return cls(name)

        monkeypatch.setattr(shm_mod, "ShmRing", _FakeRing)
        monkeypatch.setattr(shm_mod, "shm_available", lambda: True)
        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=8, voter_capacity=4
        )
        sent = []
        conn = SimpleNamespace(sendall=sent.append)
        state = SimpleNamespace(write_lock=threading.Lock())
        cursor = P.Cursor(
            P.u32(1024) + P.string("c2s-ok") + P.string("s2c-bogus")
        )
        assert server._handle_shm_attach(conn, state, 7, cursor) is True
        assert closed == ["c2s-ok"]
        status, corr, _payload = P.parse_frame(sent[0][4:], tagged=True)
        assert status == P.STATUS_BAD_REQUEST
        assert corr == 7


class TestDurableWireReplay:
    """KIND_WIRE_COLUMNAR: durable wire ingest logs its own record kind
    and replays through the WIRE path, so a recovered peer keeps the
    wire-validated retention (``wire_only``) and the cross-frame
    dangling-vote guard its non-crashed twins have — replaying through
    plain columnar ingest would silently demote both."""

    def _engine(self, identity=b"\x77" * 20):
        from hashgraph_tpu.engine import TpuConsensusEngine

        return TpuConsensusEngine(
            StubConsensusSigner(identity), capacity=32, voter_capacity=24
        )

    @staticmethod
    def _wire_frame(rows):
        data, offsets = _pack(rows)
        cols, flags = WC.parse_vote_columns(data, offsets)
        assert bool(flags.all()), "test rows must be canonical"
        return (
            ["wire-replay"], np.zeros(len(rows), np.int64), cols, data, offsets
        )

    def test_guard_survives_crash_recovery(self, tmp_path):
        from hashgraph_tpu.errors import StatusCode
        from hashgraph_tpu.wal import DurableEngine, replay, scan
        from hashgraph_tpu.wal import format as WF

        proposal = _proposal("wire-replay", voters=20)
        signers = [StubConsensusSigner(bytes([120 + i]) * 20) for i in range(9)]
        durable = DurableEngine(
            self._engine(), str(tmp_path / "wal"), fsync_policy="off"
        )
        twin = self._engine(b"\x78" * 20)
        for engine in (durable, twin):
            engine.ingest_proposals([("wire-replay", proposal.clone())], NOW)
        rows = _chain(proposal, signers)

        frame1 = self._wire_frame(rows[:3])
        got_d = durable.ingest_wire_columnar(*frame1, NOW + 1)
        got_t = twin.ingest_wire_columnar(*frame1, NOW + 1)
        assert list(got_d) == [int(StatusCode.OK)] * 3 == list(got_t)

        kinds = {kind for _, kind, _ in scan(str(tmp_path / "wal")).records}
        assert WF.KIND_WIRE_COLUMNAR in kinds
        assert WF.KIND_COLUMNAR not in kinds

        durable.abandon()
        recovered = self._engine()
        replay(str(tmp_path / "wal"), recovered)

        # Frames covering rows 3..5 never arrive: rows 6..8 dangle and
        # must reject IDENTICALLY on the recovered peer and the
        # never-crashed twin — this is exactly what broke when wire
        # records replayed through the permissive columnar path.
        dangling = self._wire_frame(rows[6:])
        got_r = recovered.ingest_wire_columnar(*dangling, NOW + 1)
        got_t = twin.ingest_wire_columnar(*dangling, NOW + 1)
        assert (
            list(got_r)
            == list(got_t)
            == [int(StatusCode.RECEIVED_HASH_MISMATCH)] * 3
        )

        # The repair path still works on the recovered session: the full
        # chain delivered through the watermark extends it to OK.
        statuses = recovered.deliver_proposals(
            [("wire-replay", proposal.clone())], NOW + 1
        )
        assert list(statuses) == [int(StatusCode.OK)]

    def test_mixed_accept_reject_frame_logs_only_accepted_rows(self, tmp_path):
        from hashgraph_tpu.errors import StatusCode
        from hashgraph_tpu.wal import DurableEngine, replay, scan
        from hashgraph_tpu.wal import format as WF

        proposal = _proposal("wire-replay", voters=20)
        signers = [StubConsensusSigner(bytes([150 + i]) * 20) for i in range(4)]
        durable = DurableEngine(
            self._engine(), str(tmp_path / "wal"), fsync_policy="off"
        )
        durable.ingest_proposals([("wire-replay", proposal.clone())], NOW)
        rows = _chain(proposal, signers)
        bad = bytearray(rows[2])
        bad[-1] ^= 0xFF  # signature flip: INVALID_VOTE_SIGNATURE
        frame = self._wire_frame([rows[0], rows[1], bytes(bad)])
        got = durable.ingest_wire_columnar(*frame, NOW + 1)
        assert list(got) == [
            int(StatusCode.OK),
            int(StatusCode.OK),
            int(StatusCode.INVALID_VOTE_SIGNATURE),
        ]
        wire_records = [
            payload
            for _, kind, payload in scan(str(tmp_path / "wal")).records
            if kind == WF.KIND_WIRE_COLUMNAR
        ]
        assert len(wire_records) == 1
        _, _, _, blob, offsets = WF.decode_columnar(wire_records[0])
        assert len(offsets) - 1 == 2  # only the accepted rows
        assert blob == rows[0] + rows[1]

        durable.abandon()
        recovered = self._engine()
        replay(str(tmp_path / "wal"), recovered)
        # Replay re-accepts exactly the logged prefix: the next chained
        # vote (rows[2] with a good signature) extends it.
        frame2 = self._wire_frame([rows[2]])
        assert list(recovered.ingest_wire_columnar(*frame2, NOW + 1)) == [
            int(StatusCode.OK)
        ]


class TestWireBufSharing:
    """The frame's vote region is materialized as bytes ONCE and shared
    between the crypto prepass, the apply stage, and (durable) the WAL
    blob — the zero-copy receive path doesn't re-copy per stage."""

    def test_apply_reuses_the_prepass_copy(self):
        from hashgraph_tpu.engine import TpuConsensusEngine
        from hashgraph_tpu.errors import StatusCode

        engine = TpuConsensusEngine(
            StubConsensusSigner(b"\x66" * 20), capacity=16, voter_capacity=8
        )
        proposal = _proposal("buf-share", voters=10)
        engine.ingest_proposals([("buf-share", proposal.clone())], NOW)
        rows = _chain(
            proposal, [StubConsensusSigner(bytes([90 + i]) * 20) for i in range(3)]
        )
        data, offsets = _pack(rows)
        cols, flags = WC.parse_vote_columns(data, offsets)
        assert bool(flags.all())
        prepass = engine.wire_verify_begin(data, cols, offsets)
        shared = prepass.buf
        assert shared == data.tobytes()
        got = engine.ingest_wire_columnar(
            ["buf-share"], np.zeros(3, np.int64), cols, data, offsets, NOW + 1,
            _prepass=prepass,
        )
        assert list(got) == [int(StatusCode.OK)] * 3
        assert prepass.buf is shared  # reused, not recomputed

    def test_explicit_buf_wins_and_lands_on_the_prepass(self):
        from hashgraph_tpu.engine import TpuConsensusEngine
        from hashgraph_tpu.errors import StatusCode

        engine = TpuConsensusEngine(
            StubConsensusSigner(b"\x65" * 20), capacity=16, voter_capacity=8
        )
        proposal = _proposal("buf-share2", voters=10)
        engine.ingest_proposals([("buf-share2", proposal.clone())], NOW)
        rows = _chain(
            proposal, [StubConsensusSigner(bytes([95 + i]) * 20) for i in range(2)]
        )
        data, offsets = _pack(rows)
        cols, flags = WC.parse_vote_columns(data, offsets)
        caller_buf = data.tobytes()
        got = engine.ingest_wire_columnar(
            ["buf-share2"], np.zeros(2, np.int64), cols, data, offsets, NOW + 1,
            _buf=caller_buf,
        )
        assert list(got) == [int(StatusCode.OK)] * 2


class TestPreparedFallbackSentinel:
    """A reader-thread prepare that chose the object fallback must not
    be re-run on the serial lane: the sentinel carries the verdict, so a
    sustained stream of non-canonical frames pays ONE columnar parse
    attempt per frame, not two plus the object decode."""

    def test_lane_skips_reprepare_after_reader_fallback(self):
        from hashgraph_tpu.bridge.server import _PREP_FALLBACK
        from hashgraph_tpu.errors import StatusCode

        server = BridgeServer(
            signer_factory=StubConsensusSigner, capacity=16,
            voter_capacity=8, wire_columnar=True,
        )
        server.start_embedded()
        try:
            status, body = server.dispatch_frame(
                P.OP_ADD_PEER, P.u8(32) + b"\x33" * 32
            )
            assert status == P.STATUS_OK
            pid = P.Cursor(body).u32()
            proposal = _proposal("sentinel", voters=10)
            server.dispatch_frame(
                P.OP_PROCESS_PROPOSAL,
                P.u32(pid) + P.string("sentinel") + P.u64(NOW)
                + P.blob(proposal.encode()),
            )
            rows = _chain(
                proposal,
                [StubConsensusSigner(bytes([210 + i]) * 20) for i in range(2)],
            )
            rows.append(rows[-1][:9])  # truncated row -> object fallback
            payload = P.encode_vote_batch(NOW + 1, [(pid, "sentinel", rows)])

            # Reader-thread half: a non-canonical row yields the sentinel.
            prep = server._vote_batch_prepare(P.Cursor(payload)) or _PREP_FALLBACK
            assert prep is _PREP_FALLBACK

            calls = []
            orig = server._vote_batch_prepare
            server._vote_batch_prepare = lambda c: (calls.append(1), orig(c))[1]
            try:
                status, body = server._op_vote_batch(P.Cursor(payload), prep)
            finally:
                server._vote_batch_prepare = orig
            assert calls == []  # the lane went straight to the object path
            assert status == P.STATUS_OK
            c = P.Cursor(body)
            assert c.u32() == 3
            codes = list(c.raw(3))
            assert codes[:2] == [int(StatusCode.OK)] * 2
            assert codes[2] == 241  # undecodable row
        finally:
            server.stop()


class TestWireEquivocationProbe:
    """The wire path's equivocation probe answers "does this session
    already hold an accepted vote by this owner?" from the record's
    chain continuity state where that state is current, and decodes the
    retained chain only for an owner it already holds. Either way the
    statuses and the minted evidence are those of a walk over the
    decoded chain."""

    SCOPE = "probe"

    @staticmethod
    def _counters():
        from hashgraph_tpu.obs import (
            ENGINE_EQUIVOCATION_PROBE_FALLBACKS_TOTAL,
            ENGINE_EQUIVOCATION_PROBE_ROWS_TOTAL,
        )
        from hashgraph_tpu.obs import registry

        return (
            registry.counter(ENGINE_EQUIVOCATION_PROBE_ROWS_TOTAL).value,
            registry.counter(ENGINE_EQUIVOCATION_PROBE_FALLBACKS_TOTAL).value,
        )

    def _ingest(self, engine, rows, now):
        data, offsets = _pack(rows)
        cols, flags = WC.parse_vote_columns(data, offsets)
        assert bool(flags.all()), "test rows must be canonical"
        return list(
            engine.ingest_wire_columnar(
                [self.SCOPE], np.zeros(len(rows), np.int64), cols, data,
                offsets, now,
            )
        )

    @pytest.mark.parametrize(
        "case",
        ["late_first_voter", "identical_resend", "equivocation",
         "equivocation_stale_stamp"],
    )
    def test_probe_answers_from_continuity_state(self, case):
        from hashgraph_tpu.engine import TpuConsensusEngine
        from hashgraph_tpu.errors import StatusCode
        from hashgraph_tpu.obs.health import HealthMonitor
        from hashgraph_tpu.obs.registry import MetricsRegistry

        health = HealthMonitor(registry=MetricsRegistry())
        engine = TpuConsensusEngine(
            StubConsensusSigner(b"\x5a" * 20), capacity=16, voter_capacity=8,
            health_monitor=health,
        )
        proposal = _proposal("wire-probe", voters=4)
        engine.ingest_proposals([(self.SCOPE, proposal.clone())], NOW)
        signers = [StubConsensusSigner(bytes([170 + i]) * 20) for i in range(4)]
        record = engine._records[engine._index[(self.SCOPE, proposal.proposal_id)]]
        ok = int(StatusCode.OK)

        if case == "equivocation_stale_stamp":
            # Two wire accepts, then a scalar accept that decides the
            # session and leaves the wire sync stamp behind the record.
            rows = _chain(proposal, signers[:2])
            assert self._ingest(engine, rows, NOW + 1) == [ok, ok]
            engine.process_incoming_vote(
                self.SCOPE,
                build_vote(proposal, True, signers[2], NOW + 1),
                NOW + 1,
            )
            assert record.wire_sync != (
                len(record.retained_wire), len(record.scalar_seqs)
            )
        else:
            rows = _chain(proposal, signers[:3])
            assert self._ingest(engine, rows, NOW + 1) == [ok, ok, ok]
        assert engine.get_consensus_result(self.SCOPE, proposal.proposal_id)
        assert record.retained_cache is None

        if case == "late_first_voter":
            late = build_vote(proposal, True, signers[3], NOW + 2).encode()
        elif case == "identical_resend":
            late = rows[0]
        else:
            # The first signer validly signs a second, different vote.
            late = build_vote(proposal, False, signers[0], NOW + 2).encode()
        probed0, fallbacks0 = self._counters()
        assert self._ingest(engine, [late], NOW + 2) == [
            int(StatusCode.ALREADY_REACHED)
        ]
        probed1, fallbacks1 = self._counters()
        assert probed1 - probed0 == 1

        if case == "late_first_voter":
            # Answered from wire_seen: no decode, no walk, no evidence.
            assert fallbacks1 - fallbacks0 == 0
            assert record.retained_cache is None
            assert health.evidence() == []
            return
        assert fallbacks1 - fallbacks0 == 1
        assert record.retained_cache is not None
        if case == "identical_resend":
            assert health.evidence() == []
            return
        assert health.evidence() == [
            {
                "kind": "equivocation",
                "offender": signers[0].identity().hex(),
                "scope": self.SCOPE,
                "proposal_id": proposal.proposal_id,
                "detected_at": NOW + 2,
                "vote_a": rows[0].hex(),
                "vote_b": late.hex(),
                "verified": True,
            }
        ]
