"""parm combinator (§7.2) and the sorting network (§7.1)."""
import random

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import f2
from repro.core.bmmc import Bmmc
from repro.core.parm import lsb, parm, parm_matrix, parm_ref
from repro.core.sort import (compile_sort, fuse, num_perm_stages, run_stages,
                             sort_compiled, sort_rec)
from repro.kernels.ops import bmmc_permute


def test_parm_matrix_paper_fig13():
    """mask = 0b110 on 3 bits: the matrix of paper Fig. 13b."""
    a = parm_matrix(3, 0b110)
    assert a.rows == (0b001, 0b100, 0b110)
    # sub-array assignments from Fig. 13a
    want_sub = [0, 0, 1, 1, 1, 1, 0, 0]
    for x in range(8):
        assert (a.apply(x) >> 2) == want_sub[x]


def test_parm_matrix_paper_section3():
    """parm 0b0011 example from §3."""
    a = parm_matrix(4, 0b0011)
    assert a.rows == (0b0010, 0b0100, 0b1000, 0b0011)


@given(st.integers(2, 8), st.integers(0, 10**6))
@settings(max_examples=50, deadline=None)
def test_parm_matrix_invertible_and_semantics(n, seed):
    rng = random.Random(seed)
    mask = rng.randrange(1, 1 << n)
    a = parm_matrix(n, mask)  # constructor asserts invertibility
    half = 1 << (n - 1)
    for x in (0, 1, (1 << n) - 1, rng.randrange(1 << n)):
        y = a.apply(x)
        sub = bin(x & mask).count("1") & 1
        assert (y >= half) == bool(sub)          # sub-array bit on top
        # sub-index: drop the lsb(mask) bit of x
        l = lsb(mask)
        sub_idx = (x & ((1 << l) - 1)) | ((x >> (l + 1)) << l)
        assert (y & (half - 1)) == sub_idx


@given(st.integers(2, 7), st.integers(0, 10**6))
@settings(max_examples=40, deadline=None)
def test_parm_bmmc_equals_direct(n, seed):
    rng = random.Random(seed)
    mask = rng.randrange(1, 1 << n)
    xs = np.random.default_rng(seed).integers(0, 100, size=1 << n).astype(np.int32)
    want = parm_ref(mask, lambda h: h[::-1], xs)
    got = np.asarray(parm(mask, lambda h: h[::-1], jnp.asarray(xs)))
    assert np.array_equal(want, got)


def test_parm_with_pallas_engine():
    """parm compiled through the tiled Pallas kernels end-to-end."""
    n, mask = 8, 0b0110
    xs = jnp.arange(1 << n, dtype=jnp.float32)
    engine = lambda x, b: bmmc_permute(x, b, t=3)
    got = np.asarray(parm(mask, lambda h: h[::-1], xs, engine=engine))
    want = parm_ref(mask, lambda h: h[::-1], np.asarray(xs))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6])
def test_sort_recursion(n):
    xs = np.random.default_rng(n).integers(0, 1000, size=1 << n).astype(np.int32)
    assert np.array_equal(sort_rec(n, xs.copy()), np.sort(xs))


@pytest.mark.parametrize("n", [1, 3, 5, 7])
def test_sort_compiled(n):
    xs = np.random.default_rng(n + 50).integers(0, 1000, size=1 << n).astype(np.int32)
    got = np.asarray(sort_compiled(jnp.asarray(xs)))
    assert np.array_equal(got, np.sort(xs))


def test_sort_compiled_with_pallas_engine():
    n = 7
    xs = np.random.default_rng(7).integers(0, 1000, size=1 << n).astype(np.int32)
    engine = lambda x, b: bmmc_permute(x, b, t=3)
    got = np.asarray(sort_compiled(jnp.asarray(xs), engine=engine))
    assert np.array_equal(got, np.sort(xs))


def test_fusion_reduces_perm_stages():
    """The §7.2 rewrite algebra: fused program is drastically shorter."""
    raw = compile_sort(6)
    fz = fuse(raw)
    assert num_perm_stages(fz) < num_perm_stages(raw) / 5
    # fused program still sorts
    xs = np.random.default_rng(0).integers(0, 99, size=64).astype(np.int32)
    got = np.asarray(run_stages(fz, jnp.asarray(xs)))
    assert np.array_equal(got, np.sort(xs))


@given(st.lists(st.integers(-1000, 1000), min_size=16, max_size=16))
@settings(max_examples=30, deadline=None)
def test_sort_property(values):
    xs = np.asarray(values, dtype=np.int32)
    assert np.array_equal(sort_rec(4, xs.copy()), np.sort(xs))
    assert np.array_equal(np.asarray(sort_compiled(jnp.asarray(xs))), np.sort(xs))
