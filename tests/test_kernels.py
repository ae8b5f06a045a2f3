"""Per-kernel correctness: Pallas tiled kernels vs the pure-jnp oracle.

Sweeps shapes / dtypes / permutation kinds and uses hypothesis for random
invertible matrices; every case asserts exact equality with ref.py
(permutations move data, they never compute, so equality is exact even for
floats).
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.bmmc import Bmmc
from repro.core.tiling import plan_bmmc, plan_tiled
from repro.kernels.bmmc_permute import copy_through_vmem, tiled_permute
from repro.kernels.ops import bmmc_permute, choose_tile, num_passes
from repro.kernels.ref import bmmc_ref, bmmc_ref_jnp


def _want(b, x):
    out = np.empty_like(np.asarray(x))
    xs = np.asarray(x)
    for i in range(xs.shape[0]):
        out[b.apply(i)] = xs[i]
    return out


KINDS = ("bitrev", "transpose", "reverse", "bpc", "bmmc")


def _make(kind, n, rng):
    return {"bitrev": lambda: Bmmc.bit_reverse(n),
            "transpose": lambda: Bmmc.matrix_transpose(n // 2, n - n // 2),
            "reverse": lambda: Bmmc.reverse_array(n),
            "bpc": lambda: Bmmc.random_bpc(n, rng),
            "bmmc": lambda: Bmmc.random(n, rng)}[kind]()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n,t", [(6, 2), (8, 3), (10, 3), (12, 4), (13, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_pallas_vs_ref(kind, n, t, dtype):
    rng = random.Random(n * 131 + t)
    b = _make(kind, n, rng)
    x = jnp.arange(1 << n).astype(dtype)
    got = np.asarray(bmmc_permute(x, b, t=t))
    assert np.array_equal(got, _want(b, x)), (kind, n, t)
    assert np.array_equal(got, np.asarray(bmmc_ref(x, b)))


@pytest.mark.parametrize("d", [2, 5, 8])
def test_pallas_rows_variant(d):
    """(2^n, d) leading-axis permutation — the tokens x features layout."""
    rng = random.Random(d)
    n = 9
    b = Bmmc.random(n, rng)
    x = jnp.arange((1 << n) * d, dtype=jnp.float32).reshape(1 << n, d)
    got = np.asarray(bmmc_permute(x, b, t=3))
    want = np.asarray(bmmc_ref(x, b))
    assert np.array_equal(got, want)


@given(st.integers(6, 12), st.integers(0, 10**6), st.integers(2, 4))
@settings(max_examples=25, deadline=None)
def test_pallas_random_bmmc_property(n, seed, t):
    if 2 * t > n:
        return
    b = Bmmc.random(n, random.Random(seed))
    x = jnp.arange(1 << n, dtype=jnp.float32)
    got = np.asarray(bmmc_permute(x, b, t=t))
    assert np.array_equal(got, np.asarray(bmmc_ref(x, b)))


def test_ref_jnp_cross_check():
    rng = random.Random(0)
    for n in (5, 9, 12):
        b = Bmmc.random(n, rng)
        x = jnp.arange(1 << n, dtype=jnp.int32)
        assert np.array_equal(np.asarray(bmmc_ref(x, b)),
                              np.asarray(bmmc_ref_jnp(x, b)))


def test_pass_counts():
    """BPC -> 1 pass; general BMMC -> <= 2 passes (paper §5.2/§6)."""
    rng = random.Random(1)
    assert num_passes(Bmmc.bit_reverse(12), 4) == 1
    assert num_passes(Bmmc.random_bpc(12, rng), 4) == 1
    for _ in range(5):
        assert num_passes(Bmmc.random(12, rng), 4) in (1, 2)


def test_small_array_fallback():
    """Tiny arrays use the ref gather (choose_tile None)."""
    assert choose_tile(1, 4) is None
    b = Bmmc.reverse_array(1)
    x = jnp.asarray([3.0, 7.0])
    assert np.array_equal(np.asarray(bmmc_permute(x, b)), [7.0, 3.0])


def test_identity_shortcut():
    b = Bmmc.identity(8)
    x = jnp.arange(256, dtype=jnp.float32)
    assert bmmc_permute(x, b) is x


def test_copy_kernel_identity():
    x = jnp.arange(1 << 12, dtype=jnp.float32)
    got = copy_through_vmem(x, rows_per_block=4, row_len=64)
    assert np.array_equal(np.asarray(got), np.asarray(x))


def test_dma_run_merging():
    """Contiguous tile rows are merged into multi-row DMA descriptors."""
    # transpose with row bits adjacent to the low bits: runs > 1
    b = Bmmc.matrix_transpose(6, 6)
    p = plan_tiled(b, 3)
    assert p is not None
    # in/out runs are powers of two and divide rows_per_tile
    assert p.rows_per_tile % p.in_run == 0
    assert p.rows_per_tile % p.out_run == 0
    # identity-like BPC: fully contiguous rows -> maximal runs
    ident_rows = plan_tiled(Bmmc.identity(10), 3)
    assert ident_rows.in_run == ident_rows.rows_per_tile
    assert ident_rows.out_run == ident_rows.rows_per_tile
