"""Property-based F2/BMMC algebra tests (hypothesis).

Random compose/inverse round-trips, ``f2.ulp`` factorization validity and
BP/BPC/tiled classification invariants across sizes n = 2..16 — the
offline algebra every kernel plan and every autodiff inverse relies on.
"""
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import f2
from repro.core.bmmc import Bmmc


def _rand_bmmc(n, rng, bpc=False):
    return Bmmc.random_bpc(n, rng) if bpc else Bmmc.random(n, rng)


# ---------------------------------------------------------------------------
# compose / inverse round-trips
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@given(st.integers(2, 16), st.integers(0, 10**6), st.booleans())
@settings(max_examples=40, deadline=None)
def test_inverse_roundtrip(n, seed, bpc):
    """b.inverse() is a two-sided inverse, elementwise and as a matrix."""
    rng = random.Random(seed)
    b = _rand_bmmc(n, rng, bpc)
    binv = b.inverse()
    assert binv.compose(b).is_identity_perm()
    assert b.compose(binv).is_identity_perm()
    for _ in range(8):
        x = rng.randrange(1 << n)
        assert binv.apply(b.apply(x)) == x
        assert b.apply(binv.apply(x)) == x
    assert binv.inverse() == b


@pytest.mark.tier1
@given(st.integers(2, 16), st.integers(0, 10**6))
@settings(max_examples=40, deadline=None)
def test_compose_is_function_composition(n, seed):
    """(a @ b).apply == a.apply ∘ b.apply on random indices."""
    rng = random.Random(seed)
    a, b = _rand_bmmc(n, rng), _rand_bmmc(n, rng, bpc=True)
    ab = a @ b
    for _ in range(8):
        x = rng.randrange(1 << n)
        assert ab.apply(x) == a.apply(b.apply(x))


@pytest.mark.tier1
@given(st.integers(2, 12), st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_compose_associative_and_identity(n, seed):
    rng = random.Random(seed)
    a, b, c = (_rand_bmmc(n, rng) for _ in range(3))
    assert (a @ b) @ c == a @ (b @ c)
    i = Bmmc.identity(n)
    assert a @ i == a and i @ a == a


@pytest.mark.tier1
@given(st.integers(2, 16), st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_inverse_antihomomorphism(n, seed):
    """(a @ b)^-1 == b^-1 @ a^-1."""
    rng = random.Random(seed)
    a, b = _rand_bmmc(n, rng), _rand_bmmc(n, rng)
    assert (a @ b).inverse() == b.inverse() @ a.inverse()


# ---------------------------------------------------------------------------
# f2.ulp factorization validity
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@given(st.integers(2, 16), st.integers(0, 10**6))
@settings(max_examples=40, deadline=None)
def test_ulp_factorization_valid(n, seed):
    """M = U L P with U upper, L lower, both unit-diagonal, P a perm."""
    rng = random.Random(seed)
    m = f2.random_invertible(n, rng)
    u, l, p = f2.ulp(m)
    assert f2.matmul(u, f2.matmul(l, p)) == m
    assert f2.is_upper(u) and f2.is_unit_diag(u)
    assert f2.is_lower(l) and f2.is_unit_diag(l)
    assert f2.to_perm(p) is not None


@pytest.mark.tier1
@given(st.integers(2, 16), st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_lup_factorization_valid(n, seed):
    """The underlying column-pivoted LUP: M = L U P."""
    rng = random.Random(seed)
    m = f2.random_invertible(n, rng)
    l, u, p = f2.lup(m)
    assert f2.matmul(l, f2.matmul(u, p)) == m
    assert f2.is_lower(l) and f2.is_unit_diag(l)
    assert f2.is_upper(u)
    assert f2.to_perm(p) is not None


@pytest.mark.tier1
@given(st.integers(3, 14), st.integers(0, 10**6), st.integers(1, 6))
@settings(max_examples=30, deadline=None)
def test_factor_tiled_composes_back(n, seed, t):
    """factor_tiled yields 1-2 factors, each tiled, composing to self."""
    t = min(t, max(1, n // 2))
    rng = random.Random(seed)
    b = _rand_bmmc(n, rng)
    factors = b.factor_tiled(t)
    assert 1 <= len(factors) <= 2
    acc = factors[0]
    for f in factors[1:]:
        acc = f @ acc
    assert acc == b
    if t < n:
        for f in factors:
            assert f.is_tiled(t)


# ---------------------------------------------------------------------------
# classification invariants
# ---------------------------------------------------------------------------

@pytest.mark.tier1
@given(st.integers(2, 16), st.integers(0, 10**6))
@settings(max_examples=40, deadline=None)
def test_classification_invariants(n, seed):
    """BP => BPC; BPC closed under compose/inverse; perm() faithful."""
    rng = random.Random(seed)
    bp = Bmmc(f2.random_perm_matrix(n, rng))
    bpc = _rand_bmmc(n, rng, bpc=True)
    assert bp.is_bp() and bp.is_bpc()
    assert bpc.is_bpc()
    assert bpc.is_bp() == (bpc.c == 0)
    assert (bpc @ bp).is_bpc()
    assert bpc.inverse().is_bpc()
    p = bp.perm()
    assert sorted(p) == list(range(n))
    for _ in range(4):
        x = rng.randrange(1 << n)
        y = bp.apply(x)
        for j in range(n):
            assert ((y >> p[j]) & 1) == ((x >> j) & 1)


@pytest.mark.tier1
@given(st.integers(2, 16), st.integers(0, 10**6), st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_bpc_always_tiled(n, seed, t):
    """Every BPC is tiled for every t <= n (paper §5.1); witness valid."""
    t = min(t, n)
    rng = random.Random(seed)
    b = _rand_bmmc(n, rng, bpc=True)
    cols = b.tiled_columns(t)
    assert cols is not None
    low_mask = (1 << t) - 1
    sub = [f2.column(b.rows, j) for j in cols]
    assert all((c >> t) == 0 for c in sub)      # zero block below
    assert f2.rank(tuple(c & low_mask for c in sub)) == t  # invertible top


@pytest.mark.tier1
@given(st.integers(4, 14), st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_tiled_closed_under_inverse_of_factors(n, seed):
    """Inverses of tiled factors stay invertible and compose to b^-1."""
    t = max(2, n // 3)
    rng = random.Random(seed)
    b = _rand_bmmc(n, rng)
    factors = b.factor_tiled(t)
    inv = Bmmc.identity(n)
    for f in factors:  # (f2 f1)^-1 = f1^-1 f2^-1
        inv = inv @ f.inverse()
    assert inv == b.inverse()
