"""Ed25519 votes, verified on the program's default path.

A scheme gives the load generator each voter's key and identity and a way
to sign, the plain reference a way to verify (with ``cryptography``, not
the program), and the server builder the name of the program's signer
class in ``hashgraph_tpu``. It imports nothing of the program.

Found by name: ``"signature": "ed25519"`` in a traffic mix."""

PROGRAM_SIGNER = "Ed25519ConsensusSigner"


def keys(raw) -> "tuple[list, list[bytes]]":
    """Per-voter signing keys and 32-byte public identities from ``raw``,
    one row of 32 seeded bytes per voter."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )
    from cryptography.hazmat.primitives.serialization import (
        Encoding,
        PublicFormat,
    )

    private = [Ed25519PrivateKey.from_private_bytes(r.tobytes()) for r in raw]
    ids = [k.public_key().public_bytes(Encoding.Raw, PublicFormat.Raw)
           for k in private]
    return private, ids


def sign(key, payload: bytes) -> bytes:
    return key.sign(payload)


def verify(identity: bytes, payload: bytes, signature: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    try:
        Ed25519PublicKey.from_public_bytes(identity).verify(signature, payload)
    except (InvalidSignature, ValueError):
        return False
    return True
