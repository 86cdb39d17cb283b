"""The benchmark's own wire codec: vote and proposal protobuf bytes and the
bridge framing, written from the protocol's published layout so that the
yardstick imports nothing of the program under test.

Votes and proposals follow the reference schema
(vacp2p/hashgraph-like-consensus ``src/protos/messages/v1/consensus.proto``)
as prost encodes it: fields in ascending order, defaults omitted. The vote
hash and the signed payload follow the reference's ``src/utils.rs``.

Bridge frames (all little-endian): ``u32 length | u8 lead | payload``;
after a HELLO that grants pipelining, ``u32 length | u8 lead | u32 corr |
payload``.
"""

from __future__ import annotations

import hashlib
import socket
import struct

OP_ADD_PEER = 1
OP_GET_RESULT = 7
OP_HELLO = 18
OP_VOTE_BATCH = 19
OP_DELIVER_PROPOSALS = 20

FEATURE_PIPELINING = 1 << 0
FEATURE_VOTE_BATCH = 1 << 1
FEATURE_DELIVER = 1 << 2

RESULT_NO, RESULT_YES, RESULT_FAILED, RESULT_UNDECIDED = 0, 1, 2, 255

_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")
_HEAD = struct.Struct("<IB")
_TAGGED = struct.Struct("<IBI")


def varint(value: int) -> bytes:
    out = bytearray()
    while value > 0x7F:
        out.append((value & 0x7F) | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


def _len_field(tag: bytes, value: bytes) -> bytes:
    return tag + varint(len(value)) + value if value else b""


def vote_hash(vote_id: int, owner: bytes, pid: int, ts: int, value: bool,
              parent: bytes, received: bytes) -> bytes:
    """SHA-256 over the vote's identifying fields (reference utils.rs:37-47)."""
    return hashlib.sha256(
        vote_id.to_bytes(4, "little") + owner + pid.to_bytes(4, "little")
        + ts.to_bytes(8, "little") + (b"\x01" if value else b"\x00")
        + parent + received
    ).digest()


def vote_signed_fields(vote_id: int, owner: bytes, pid: int, ts: int,
                       value: bool, parent: bytes, received: bytes,
                       vhash: bytes) -> bytes:
    """Fields 20-27: the bytes a vote's signature covers (utils.rs:93-97)."""
    return b"".join((
        b"\xa0\x01" + varint(vote_id) if vote_id else b"",
        _len_field(b"\xaa\x01", owner),
        b"\xb0\x01" + varint(pid) if pid else b"",
        b"\xb8\x01" + varint(ts) if ts else b"",
        b"\xc0\x01\x01" if value else b"",
        _len_field(b"\xca\x01", parent),
        _len_field(b"\xd2\x01", received),
        _len_field(b"\xda\x01", vhash),
    ))


def vote_bytes(signed_fields: bytes, signature: bytes) -> bytes:
    return signed_fields + _len_field(b"\xe2\x01", signature)


def proposal_bytes(name: str, payload: bytes, pid: int, owner: bytes,
                   n: int, timestamp: int, expiration: int,
                   liveness_yes: bool) -> bytes:
    """A vote-free Proposal (consensus.proto fields 10-19)."""
    return b"".join((
        _len_field(b"\x52", name.encode()),
        _len_field(b"\x5a", payload),
        b"\x60" + varint(pid) if pid else b"",
        _len_field(b"\x6a", owner),
        b"\x78" + varint(n) if n else b"",
        b"\x88\x01" + varint(timestamp) if timestamp else b"",
        b"\x90\x01" + varint(expiration) if expiration else b"",
        b"\x98\x01\x01" if liveness_yes else b"",
    ))


def _string(s: str) -> bytes:
    raw = s.encode()
    return _U16.pack(len(raw)) + raw


def vote_batch_payload(now: int, peer: int,
                       groups: "list[tuple[str, list[bytes]]]") -> bytes:
    """OP_VOTE_BATCH: u64 now | u32 groups | (u32 peer, string scope, u32 n)*
    | u32 length per vote | the votes."""
    head = [_U64.pack(now), _U32.pack(len(groups))]
    lens, bodies = [], []
    for scope, votes in groups:
        head.append(_U32.pack(peer) + _string(scope) + _U32.pack(len(votes)))
        lens.extend(_U32.pack(len(v)) for v in votes)
        bodies.extend(votes)
    return b"".join(head + lens + bodies)


def deliver_payload(peer: int, now: int,
                    items: "list[tuple[str, bytes]]") -> bytes:
    out = [_U32.pack(peer), _U64.pack(now), _U32.pack(len(items))]
    for scope, blob in items:
        out.append(_string(scope) + _U32.pack(len(blob)) + blob)
    return b"".join(out)


def get_result_payload(peer: int, scope: str, pid: int) -> bytes:
    return _U32.pack(peer) + _string(scope) + _U32.pack(pid)


def statuses_of(payload: bytes) -> bytes:
    """The per-item status bytes of a VOTE_BATCH or DELIVER reply."""
    (count,) = _U32.unpack_from(payload, 0)
    body = payload[4:4 + count]
    if len(body) != count:
        raise ValueError("truncated status reply")
    return body


class Connection:
    """One pipelined bridge connection. ``send`` and ``recv`` may run on two
    threads (one writer, one reader)."""

    def __init__(self, host: str, port: int, timeout: float = 120.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        want = FEATURE_PIPELINING | FEATURE_VOTE_BATCH | FEATURE_DELIVER
        self.sock.sendall(_HEAD.pack(9, OP_HELLO) + _U32.pack(1)
                          + _U32.pack(want))
        (length,) = _U32.unpack(self._read(4))
        body = self._read(length)
        granted = _U32.unpack_from(body, 5)[0] if body[0] == 0 else 0
        if granted & want != want:
            raise ConnectionError(f"server granted features {granted:#x}")

    def _read(self, n: int) -> bytes:
        buf = bytearray(n)
        view, pos = memoryview(buf), 0
        while pos < n:
            got = self.sock.recv_into(view[pos:])
            if not got:
                raise ConnectionError("bridge closed the connection")
            pos += got
        return bytes(buf)

    def send(self, opcode: int, corr: int, payload: bytes) -> None:
        self.sock.sendall(_TAGGED.pack(5 + len(payload), opcode, corr) + payload)

    def recv(self) -> "tuple[int, int, bytes]":
        """(status, correlation id, payload) of the next reply."""
        (length,) = _U32.unpack(self._read(4))
        body = self._read(length)
        return body[0], _U32.unpack_from(body, 1)[0], body[5:]

    def call(self, opcode: int, payload: bytes) -> "tuple[int, bytes]":
        """One request and its reply (no other request in flight)."""
        self.send(opcode, 0, payload)
        status, _corr, body = self.recv()
        return status, body

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


def add_peer(conn: Connection, key: bytes) -> int:
    status, body = conn.call(OP_ADD_PEER, bytes([len(key)]) + key)
    if status != 0:
        raise RuntimeError(f"ADD_PEER failed with status {status}: {body!r}")
    return _U32.unpack_from(body, 0)[0]
