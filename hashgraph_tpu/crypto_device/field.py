"""Vectorized GF(2^255-19) arithmetic in radix-2^16 uint32 limbs.

The device twin of the native runtime's radix-2^51 field core
(``native/consensus_native.cpp``), re-limbed for XLA's integer dtypes:
the repo runs with ``jax_enable_x64`` off, so the widest integer lane is
uint32 and the radix must let a full schoolbook product column
accumulate without overflow. Radix 2^16 does: a 16x16 limb product is an
*exact* uint32 (operands < 2^16), its 16-bit halves land in separate
columns, and a column sums at most 32 half-products (< 2^21) before the
2^256 === 38 (mod p) fold lifts it to < 39*2^21 < 2^27 — comfortably
inside uint32. Carries are lazy in the native sense: additions stack
un-carried and a shared three-pass carry chain restores the invariant.

Representation: a field element is a ``uint32[..., 16]`` array, little-
endian limbs, value = sum(limb[i] * 2^(16 i)). The *carried* form
(every public op's output) has all limbs < 2^16; the value may still be
anywhere in [0, 2^256) — only :func:`canon` reduces below p, and only
the comparison/export paths need it.

Everything here is shape-polymorphic over leading batch axes: one call
squares/multiplies/inverts every signature lane in the batch at once,
which is where the device throughput comes from (and why the inverse-
sqrt exponentiation in :mod:`.curve` runs as ONE 254-squaring chain
across all lanes rather than per-point ladders).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

LIMBS = 16
RADIX = 16
MASK = (1 << RADIX) - 1

P = 2**255 - 19
# 2^256 mod p: the fold factor for product columns >= 16 and for the
# carry out of limb 15.
FOLD = 38

_U32 = jnp.uint32


def _int_to_limbs(value: int) -> np.ndarray:
    return np.array(
        [(value >> (RADIX * i)) & MASK for i in range(LIMBS)], np.uint32
    )


def limbs_to_int(limbs) -> int:
    """Host-side decode (tests / debugging only)."""
    arr = np.asarray(limbs, np.uint64)
    return sum(int(arr[..., i]) << (RADIX * i) for i in range(LIMBS))


P_LIMBS = _int_to_limbs(P)

# Subtraction pad: 4p spread so every limb is >= 2^16 (>= any carried
# limb of the subtrahend), keeping a - b + PAD4P non-negative per limb.
# Derivation: 4p = 2^257 - 76 = (2^18-76) + sum_{i=1..14} (2^18-4) 2^16i
# + (2^17-4) 2^240 — asserted below rather than trusted.
PAD4P = np.array(
    [2**18 - 76] + [2**18 - 4] * 14 + [2**17 - 4], np.uint32
)
assert sum(int(c) << (RADIX * i) for i, c in enumerate(PAD4P)) == 4 * P
assert all(int(c) >= 1 << RADIX for c in PAD4P)

ZERO = _int_to_limbs(0)
ONE = _int_to_limbs(1)
# Curve constants in device limbs (values from the pure-Python twin's
# derivation; signing/_ed25519.py is the host reference).
D = _int_to_limbs((-121665 * pow(121666, P - 2, P)) % P)
D2 = _int_to_limbs((2 * ((-121665 * pow(121666, P - 2, P)) % P)) % P)
SQRT_M1 = _int_to_limbs(pow(2, (P - 1) // 4, P))


def _carry_vec(t):
    """Carry-save pass: every limb sheds its high bits to its neighbor
    simultaneously (the 2^256-weight carry folds to limb 0 as +38c).
    O(1) depth — the cheap way down from 2^27-bound columns to
    almost-carried limbs; cannot resolve a 0xFFFF ripple chain alone."""
    c = t >> RADIX
    t = (t & MASK).at[..., 1:].add(c[..., :-1])
    return t.at[..., 0].add(c[..., -1] * FOLD)


def _carry_seq(t):
    """Exact sequential pass, rolled as lax.scan over the limb axis so
    the compiled graph stays one small loop body. Output limbs < 2^16
    except limb 0, which absorbs 38*carry_out un-masked."""
    xs = jnp.moveaxis(t, -1, 0)
    zero = jnp.zeros(t.shape[:-1], _U32)

    def step(c, x):
        cur = x + c
        return cur >> RADIX, cur & MASK
    carry_out, ys = lax.scan(step, zero, xs)
    out = jnp.moveaxis(ys, 0, -1)
    return out.at[..., 0].add(carry_out * FOLD)


def carry(t):
    """Restore the carried invariant (all limbs strictly < 2^16) from
    column sums < 2^27. Two carry-save passes bound every limb by
    2^16+38; the first sequential pass then rippless exactly, and its
    end fold (+38*c, c <= 1) can only fire after limb 15 wrapped to 0 —
    so the second sequential pass provably carries nothing out of limb
    15 and its own fold is +0. The bound chain is adversarial-input
    rigorous (a 3-pass variant is not: a crafted 0xFFFF ripple survives
    it) and is property-tested against Python ints in
    tests/test_device_crypto.py."""
    return _carry_seq(_carry_seq(_carry_vec(_carry_vec(t))))


def add(a, b):
    """a + b (carried inputs -> carried output)."""
    return carry(a + b)


def sub(a, b):
    """a - b mod p via the 4p pad (no negative intermediates: every pad
    limb exceeds any carried limb of b)."""
    pad = jnp.asarray(PAD4P)
    return carry(a + (pad - b))


# Column-assignment matrix for the schoolbook product: half-product
# (i, j)'s lo lands in column i+j, its hi in column i+j+1. Encoding the
# anti-diagonal scatter as ONE 0/1 integer matmul compiles and runs far
# better than a 256-way scatter-add (uint32 matmul wraps mod 2^32,
# which is exact here — columns stay < 2^27).
_COL_MATRIX = np.zeros((2 * LIMBS * LIMBS, 2 * LIMBS), np.uint32)
for _i in range(LIMBS):
    for _j in range(LIMBS):
        _COL_MATRIX[_i * LIMBS + _j, _i + _j] = 1              # lo
        _COL_MATRIX[LIMBS * LIMBS + _i * LIMBS + _j, _i + _j + 1] = 1  # hi
del _i, _j


def mul(a, b):
    """Schoolbook 16x16 product with hi/lo column split and 2^256===38
    fold. Carried inputs required (products must be exact in uint32)."""
    # (..., 16, 16) exact products; hi/lo split, then the column matmul
    # and the 2^256 === 38 fold.
    prod = a[..., :, None] * b[..., None, :]
    halves = jnp.concatenate(
        [
            (prod & MASK).reshape(*prod.shape[:-2], LIMBS * LIMBS),
            (prod >> RADIX).reshape(*prod.shape[:-2], LIMBS * LIMBS),
        ],
        axis=-1,
    )
    cols = halves @ jnp.asarray(_COL_MATRIX)
    t = cols[..., :LIMBS] + cols[..., LIMBS:] * FOLD
    return carry(t)


def sqr(a):
    return mul(a, a)


def pow2k(a, k: int):
    """a^(2^k): k fused squarings as ONE rolled loop (keeps the XLA
    graph small — the inverse-sqrt chain squares 252 times)."""
    return lax.fori_loop(0, k, lambda _, x: sqr(x), a)


def pow22523(z):
    """z^((p-5)/8) = z^(2^252 - 3): the shared exponent of inverse-sqrt
    decompression (RFC 8032 5.1.3), one chain across every lane. Same
    addition chain as the native fe_pow22523."""
    z2 = sqr(z)
    z9 = mul(pow2k(z2, 2), z)            # z^9
    z11 = mul(z9, z2)                    # z^11
    z2_5_0 = mul(sqr(z11), z9)           # z^(2^5 - 1)
    z2_10_0 = mul(pow2k(z2_5_0, 5), z2_5_0)
    z2_20_0 = mul(pow2k(z2_10_0, 10), z2_10_0)
    z2_40_0 = mul(pow2k(z2_20_0, 20), z2_20_0)
    z2_50_0 = mul(pow2k(z2_40_0, 10), z2_10_0)
    z2_100_0 = mul(pow2k(z2_50_0, 50), z2_50_0)
    z2_200_0 = mul(pow2k(z2_100_0, 100), z2_100_0)
    z2_250_0 = mul(pow2k(z2_200_0, 50), z2_50_0)
    return mul(pow2k(z2_250_0, 2), z)    # z^(2^252 - 3)


def invert(z):
    """z^(p-2) = z^(2^255 - 21) (Fermat). Zero maps to zero."""
    z2 = sqr(z)
    z9 = mul(pow2k(z2, 2), z)
    z11 = mul(z9, z2)
    z2_5_0 = mul(sqr(z11), z9)
    z2_10_0 = mul(pow2k(z2_5_0, 5), z2_5_0)
    z2_20_0 = mul(pow2k(z2_10_0, 10), z2_10_0)
    z2_40_0 = mul(pow2k(z2_20_0, 20), z2_20_0)
    z2_50_0 = mul(pow2k(z2_40_0, 10), z2_10_0)
    z2_100_0 = mul(pow2k(z2_50_0, 50), z2_50_0)
    z2_200_0 = mul(pow2k(z2_100_0, 100), z2_100_0)
    z2_250_0 = mul(pow2k(z2_200_0, 50), z2_50_0)
    return mul(pow2k(z2_250_0, 5), z11)  # z^(2^255 - 21)


def _cond_sub_p(x):
    """One conditional subtract of p (borrow chain; carried input)."""
    p_l = jnp.asarray(P_LIMBS)
    out = []
    borrow = jnp.zeros(x.shape[:-1], _U32)
    for i in range(LIMBS):
        d = x[..., i] + (1 << RADIX) - p_l[i] - borrow
        out.append(d & MASK)
        borrow = 1 - (d >> RADIX)
    diff = jnp.stack(out, axis=-1)
    keep = (borrow == 1)[..., None]  # x < p: keep x
    return jnp.where(keep, x, diff)


def canon(x):
    """Canonical representative in [0, p). A carried value is < 2^256 =
    2p + 38, so two conditional subtractions always suffice."""
    return _cond_sub_p(_cond_sub_p(x))


def is_zero(x):
    """Carried input -> bool array over batch axes (exact mod-p test)."""
    return jnp.all(canon(x) == 0, axis=-1)


def eq(a, b):
    return is_zero(sub(a, b))


def parity(x):
    """Bit 0 of the canonical representative (the RFC 8032 sign bit)."""
    return canon(x)[..., 0] & 1


def from_bytes(b):
    """uint8[..., 32] little-endian -> carried limbs (top bit included;
    callers mask the sign bit themselves where the encoding demands)."""
    b32 = b.astype(_U32)
    return b32[..., 0::2] | (b32[..., 1::2] << 8)


def to_bytes(x):
    """Canonical little-endian uint8[..., 32] encoding."""
    c = canon(x)
    lo = (c & 0xFF).astype(jnp.uint8)
    hi = ((c >> 8) & 0xFF).astype(jnp.uint8)
    return jnp.stack([lo, hi], axis=-1).reshape(*c.shape[:-1], 32)


def is_canonical_fe(b):
    """RFC 8032 5.1.3 field-encoding check: the 255-bit y (sign bit
    already masked) must be < p."""
    y = from_bytes(b)
    p_l = jnp.asarray(P_LIMBS)
    borrow = jnp.zeros(y.shape[:-1], _U32)
    for i in range(LIMBS):
        d = y[..., i] + (1 << RADIX) - p_l[i] - borrow
        borrow = 1 - (d >> RADIX)
    return borrow == 1  # y < p


def const(limbs: np.ndarray, batch_shape=()):
    """Broadcast a host constant to a batch of lanes."""
    return jnp.broadcast_to(jnp.asarray(limbs), (*batch_shape, LIMBS))
