"""Property tests for the φ-accrual core (hypothesis-gated).

Three laws the detector must hold for EVERY inter-arrival history, not
just the hand-picked ones in test_liveness.py:

1. phi is monotone non-decreasing in silence (a longer wait can only
   raise suspicion);
2. a heartbeat revises suspicion to zero instantly (Chandra–Toueg:
   suspicion may be wrong and must be cheap to revise);
3. at equal mean and equal silence, a history with wider spread never
   yields MORE suspicion than a tighter one (jitter earns tolerance).

The module skips cleanly where hypothesis is not installed (the repo
adds no dependencies); tests/test_liveness.py carries fixed-example
mirrors of each law so the properties are never entirely unexercised.
"""

import pytest

hypothesis = pytest.importorskip("hypothesis")

from hypothesis import example, given, settings, strategies as st  # noqa: E402

from hashgraph_tpu.obs.accrual import (  # noqa: E402
    DEFAULT_MAX_PHI,
    PhiAccrual,
    phi_from_deviation,
)

# Inter-arrival histories: enough samples to clear the min_samples gate,
# intervals wide enough apart that float noise cannot flip an ordering.
intervals = st.lists(
    st.floats(min_value=0.5, max_value=1_000.0,
              allow_nan=False, allow_infinity=False),
    min_size=8,
    max_size=64,
)
silences = st.floats(min_value=0.0, max_value=100_000.0,
                     allow_nan=False, allow_infinity=False)


def _fed(history: "list[float]") -> "tuple[PhiAccrual, float]":
    acc = PhiAccrual()
    now = 0.0
    acc.heartbeat(now)
    for gap in history:
        now += gap
        acc.heartbeat(now)
    return acc, now


@settings(max_examples=200, deadline=None)
@given(st.floats(min_value=-50.0, max_value=200.0,
                 allow_nan=False, allow_infinity=False),
       st.floats(min_value=0.0, max_value=50.0,
                 allow_nan=False, allow_infinity=False))
def test_phi_from_deviation_monotone_bounded(x, dx):
    lo, hi = phi_from_deviation(x), phi_from_deviation(x + dx)
    assert 0.0 <= lo <= hi <= DEFAULT_MAX_PHI


@settings(max_examples=100, deadline=None)
@given(intervals, silences, silences)
def test_phi_non_decreasing_under_silence(history, s1, s2):
    acc, now = _fed(history)
    a, b = sorted((s1, s2))
    assert acc.phi(now + a) <= acc.phi(now + b)


@settings(max_examples=100, deadline=None)
@given(intervals, st.floats(min_value=0.5, max_value=10_000.0,
                            allow_nan=False, allow_infinity=False))
def test_phi_resets_on_heartbeat(history, silence):
    acc, now = _fed(history)
    probe = now + silence
    acc.heartbeat(probe)
    assert acc.phi(probe) == 0.0
    # And the history stays sane: suspicion resumes from zero, bounded.
    assert 0.0 <= acc.phi(probe + silence) <= DEFAULT_MAX_PHI


@settings(max_examples=100, deadline=None)
@given(st.floats(min_value=2.0, max_value=100.0,
                 allow_nan=False, allow_infinity=False),
       st.floats(min_value=0.0, max_value=0.9,
                 allow_nan=False, allow_infinity=False),
       st.integers(min_value=8, max_value=32),
       silences)
# Silence equal to the mean, where clock rounding alone once put the wide
# history a hair past its mean and stepped its phi to -log10(1/2).
@example(mean=51.99997810579886, spread=0.75, n=20,
         silence=51.99997810579886)
def test_phi_monotone_in_spread_at_equal_mean(mean, spread, n, silence):
    """Alternating mean±d histories: same mean, wider d -> phi no higher
    at the same silence (the effective stddev floor keeps this true even
    as d -> 0)."""
    d = spread * mean
    tight, _ = _fed([mean] * (2 * n))
    wide, now = _fed([mean - d, mean + d] * n)
    assert wide.phi(now + silence) <= tight.phi(now + silence) + 1e-9
