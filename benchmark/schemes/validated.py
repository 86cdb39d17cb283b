"""Transport-validated votes: the transport (for example libp2p gossipsub
message signing) authenticated them, and the engine runs the program's
stub scheme, a SHA-256 over identity and payload, as the reference's own
tests hand-deliver validated votes. The interface is that of
``schemes/ed25519.py``.

Found by name: ``"signature": "validated"`` in a traffic mix."""

import hashlib

PROGRAM_SIGNER = "StubConsensusSigner"


def keys(raw) -> "tuple[list[bytes], list[bytes]]":
    ids = [r[:20].tobytes() for r in raw]
    return ids, ids


def sign(key: bytes, payload: bytes) -> bytes:
    return hashlib.sha256(key + payload).digest()


def verify(identity: bytes, payload: bytes, signature: bytes) -> bool:
    return hashlib.sha256(identity + payload).digest() == signature
