"""Property tests for GF(2) linear algebra (hypothesis)."""
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import f2


def rand_invertible(n, seed):
    return f2.random_invertible(n, random.Random(seed))


@given(st.integers(2, 14), st.integers(0, 10**6))
@settings(max_examples=60, deadline=None)
def test_inverse_roundtrip(n, seed):
    a = rand_invertible(n, seed)
    ai = f2.inverse(a)
    assert f2.matmul(a, ai) == f2.identity(n)
    assert f2.matmul(ai, a) == f2.identity(n)


@given(st.integers(2, 12), st.integers(0, 10**6))
@settings(max_examples=60, deadline=None)
def test_lup(n, seed):
    a = rand_invertible(n, seed)
    l, u, p = f2.lup(a)
    assert f2.matmul(l, f2.matmul(u, p)) == a
    assert f2.is_lower(l) and f2.is_unit_diag(l)
    assert f2.is_upper(u)
    assert f2.to_perm(p) is not None


@given(st.integers(2, 12), st.integers(0, 10**6))
@settings(max_examples=60, deadline=None)
def test_ulp_paper_order(n, seed):
    """Paper §5.2: A = U L P with U upper, L lower, P a permutation."""
    a = rand_invertible(n, seed)
    u, l, p = f2.ulp(a)
    assert f2.matmul(u, f2.matmul(l, p)) == a
    assert f2.is_upper(u)
    assert f2.is_lower(l)
    assert f2.to_perm(p) is not None


@given(st.integers(1, 14), st.integers(0, 10**6), st.integers(0, 10**6))
@settings(max_examples=40, deadline=None)
def test_matvec_linear(n, seed, xseed):
    a = rand_invertible(n, seed)
    r = random.Random(xseed)
    x, y = r.randrange(1 << n), r.randrange(1 << n)
    assert f2.matvec(a, x ^ y) == f2.matvec(a, x) ^ f2.matvec(a, y)


@given(st.integers(2, 10), st.integers(0, 10**6))
@settings(max_examples=40, deadline=None)
def test_matmul_assoc_transpose(n, seed):
    r = random.Random(seed)
    a, b = f2.random_invertible(n, r), f2.random_invertible(n, r)
    assert f2.transpose(f2.matmul(a, b)) == f2.matmul(f2.transpose(b), f2.transpose(a))
    x = r.randrange(1 << n)
    assert f2.matvec(f2.matmul(a, b), x) == f2.matvec(a, f2.matvec(b, x))


def test_perm_matrix_semantics():
    # paper §3: P_{i,j} = 1 iff i = p(j); y_{p(j)} = x_j
    p = [2, 0, 3, 1]
    m = f2.from_perm(p)
    for j in range(4):
        x = 1 << j
        y = f2.matvec(m, x)
        assert y == 1 << p[j]
    assert f2.to_perm(m) == p


def test_reversal_involution():
    for n in (1, 3, 8):
        r = f2.reversal(n)
        assert f2.matmul(r, r) == f2.identity(n)


@given(st.integers(2, 12), st.integers(0, 10**6), st.integers(1, 6))
@settings(max_examples=60, deadline=None)
def test_tiled_columns_witness(n, seed, t):
    """tiled_columns returns a valid witness whenever it returns one."""
    if t > n:
        return
    a = rand_invertible(n, seed)
    cols = f2.tiled_columns(a, t)
    if cols is None:
        return
    assert len(cols) == t
    low = (1 << t) - 1
    sub_rows = []
    for i in range(t):
        bits = 0
        for k, j in enumerate(cols):
            if (a[i] >> j) & 1:
                bits |= 1 << k
        sub_rows.append(bits)
    assert f2.rank(tuple(sub_rows)) == t          # top t x t invertible
    for i in range(t, n):
        for j in cols:
            assert not (a[i] >> j) & 1            # bottom rows zero
