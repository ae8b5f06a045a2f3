"""BMMC semantics, classification, and the §5.2 two-pass factorization."""
import random

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import f2
from repro.core.bmmc import Bmmc


def ref_perm(b: Bmmc, xs):
    out = [None] * len(xs)
    for x, v in enumerate(xs):
        out[b.apply(x)] = v
    return out


@given(st.integers(2, 12), st.integers(0, 10**6))
@settings(max_examples=50, deadline=None)
def test_is_permutation(n, seed):
    b = Bmmc.random(n, random.Random(seed))
    xs = list(range(1 << n))
    ys = ref_perm(b, xs)
    assert sorted(ys) == xs


@given(st.integers(2, 12), st.integers(0, 10**6))
@settings(max_examples=50, deadline=None)
def test_inverse_compose(n, seed):
    rng = random.Random(seed)
    b = Bmmc.random(n, rng)
    xs = list(range(1 << n))
    assert ref_perm(b.inverse(), ref_perm(b, xs)) == xs
    b2 = Bmmc.random(n, rng)
    assert ref_perm(b2 @ b, xs) == ref_perm(b2, ref_perm(b, xs))


@given(st.integers(4, 12), st.integers(0, 10**6), st.integers(2, 5))
@settings(max_examples=60, deadline=None)
def test_factor_tiled_two_passes(n, seed, t):
    """Any BMMC = at most two tiled BMMCs (paper §5.2), each tiled."""
    if 2 * t > n:
        return
    b = Bmmc.random(n, random.Random(seed))
    fs = b.factor_tiled(t)
    assert 1 <= len(fs) <= 2
    for fac in fs:
        assert fac.is_tiled(t)
    xs = list(range(1 << n))
    cur = xs
    for fac in fs:
        cur = ref_perm(fac, cur)
    assert cur == ref_perm(b, xs)


@given(st.integers(4, 12), st.integers(0, 10**6), st.integers(2, 5))
@settings(max_examples=30, deadline=None)
def test_bpc_always_tiled(n, seed, t):
    """BPCs are tiled for every tile size (paper §5.1)."""
    if t > n:
        return
    b = Bmmc.random_bpc(n, random.Random(seed))
    assert b.is_tiled(t)
    cols = b.tiled_columns(t)
    p = b.perm()
    assert sorted(cols) == sorted(j for j in range(n) if p[j] < t)


def test_paper_examples():
    # 4x4 matrix transpose (paper §3): y_i = x_{(i+2) % 4}
    tr = Bmmc.matrix_transpose(2, 2)
    assert tr.perm() == [(i + 2) % 4 for i in range(4)]
    # bit reversal: y_i = x_{n-1-i}
    br = Bmmc.bit_reverse(4)
    assert br.apply(0b0111) == 0b1110
    # array reversal: identity matrix, c = 1...1
    rv = Bmmc.reverse_array(4)
    assert rv.apply(0) == 15 and rv.apply(5) == 10
    assert rv.is_bpc() and not rv.is_bp()


def test_classification():
    assert Bmmc.bit_reverse(5).is_bp()
    assert not Bmmc.reverse_array(5).is_bp()
    assert Bmmc.reverse_array(5).is_bpc()
    rng = random.Random(3)
    # random dense BMMC is almost surely not a BPC
    b = Bmmc.random(10, rng)
    assert b.perm() is None or b.is_bpc()


def test_singular_rejected():
    with pytest.raises(f2.SingularError):
        Bmmc((1, 1), 0)  # duplicate rows: singular
