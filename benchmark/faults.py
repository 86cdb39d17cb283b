"""Faults planted under the served path, for the control runs and for the
test that shows ``correct`` comes out false when the timed path is wrong.
The benchmark's own runs never install one (``--fault`` is a hidden
option). ``control_quorum`` lives in ``run.build_server``: it lowers the
scopes' quorum from 2/3 to 0.6, breaking the configuration's stated
guarantee; the faults here wrap the server's vote-batch handler.

- ``answer_altered``: one status per frame changed where it is produced;
- ``half_dropped``: the second half of every frame left out, answered OK;
- ``state_unchanged``: every vote answered OK and none applied.
"""

from __future__ import annotations

import struct

_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")


def _halve(data: bytes) -> "tuple[bytes, int, int]":
    """Cut an OP_VOTE_BATCH payload to its first half of votes: (payload,
    votes kept, votes in the frame)."""
    pos = 8
    (ngroups,) = _U32.unpack_from(data, pos)
    pos += 4
    groups = []
    for _ in range(ngroups):
        (peer,) = _U32.unpack_from(data, pos)
        (slen,) = _U16.unpack_from(data, pos + 4)
        scope = data[pos + 6:pos + 6 + slen]
        (count,) = _U32.unpack_from(data, pos + 6 + slen)
        pos += 10 + slen
        groups.append((peer, scope, count))
    total = sum(g[2] for g in groups)
    lens = struct.unpack_from(f"<{total}I", data, pos)
    pos += 4 * total
    keep = total // 2
    out = [data[:8], _U32.pack(0)]
    heads, left = [], keep
    for peer, scope, count in groups:
        take = min(count, left)
        if take:
            heads.append(_U32.pack(peer) + _U16.pack(len(scope)) + scope
                         + _U32.pack(take))
        left -= take
    out[1] = _U32.pack(len(heads))
    out.extend(heads)
    out.append(struct.pack(f"<{keep}I", *lens[:keep]))
    out.append(data[pos:pos + sum(lens[:keep])])
    return b"".join(out), keep, total


def install(server, name: str) -> None:
    from hashgraph_tpu.bridge import protocol as P

    original = server._op_vote_batch

    def answer_altered(c, prep=None):
        status, payload = original(c, prep)
        if status == 0 and len(payload) > 4:
            body = bytearray(payload)
            body[4] = 7 if body[4] == 0 else 0
            payload = bytes(body)
        return status, payload

    def half_dropped(c, prep=None):
        data = bytes(c._data[c._pos:])
        half, keep, total = _halve(data)
        if keep == 0:
            return 0, _U32.pack(total) + bytes(total)
        status, payload = original(P.Cursor(half, 0), None)
        if status != 0:
            return status, payload
        return 0, _U32.pack(total) + payload[4:4 + keep] + bytes(total - keep)

    def state_unchanged(c, prep=None):
        data = bytes(c._data[c._pos:])
        _half, _keep, total = _halve(data)
        return 0, _U32.pack(total) + bytes(total)

    faults = {"answer_altered": answer_altered, "half_dropped": half_dropped,
              "state_unchanged": state_unchanged}
    if name not in faults:
        raise SystemExit(f"bench: unknown fault {name!r}")
    server._op_vote_batch = faults[name]
