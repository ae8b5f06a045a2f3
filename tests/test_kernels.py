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
from repro.kernels import bmmc_permute as bp
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


def _row_runs(geometry, plan):
    """The pass with both sides copied per run of consecutive rows, as
    the kernel copies a side that is no box."""
    return geometry[:3] + (plan.in_run, plan.out_run) + geometry[5:]


def _oriented(geometry, plan, intra):
    """The pass run through one given intra-tile factorization (either
    orientation): its geometry and tables."""
    return (geometry[:7] + (intra.steps,),
            (plan.in_rows, plan.out_rows, intra.ktab, intra.words))


@pytest.mark.parametrize("layout", ["flat", "batched", "planar"])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.bfloat16])
def test_box_and_row_dma_paths_match_ref(dtype, layout):
    """Box sides (a BPC with a complement: multi-run boxes on both sides)
    and sides that are no box (a random BMMC) match ``kernels/ref.py``
    bit for bit, forward and through the gradient kernel, in every
    orientation of the intra-tile factorization a layout may take (1-wide
    items also transpose the tile) with its sparse shears; the BPC also
    with its sides forced onto the per-run path."""
    n, t = 10, 4
    rng = random.Random(11)
    bpc = Bmmc(Bmmc.random_bpc(n, rng).rows, rng.getrandbits(n))
    bmmc = Bmmc.random(n, rng)
    batched = layout == "batched"
    d = 2 if layout == "planar" else 1
    shape = {"flat": (1 << n,), "batched": (3, 1 << n),
             "planar": (1 << n, 2)}[layout]
    x = jnp.asarray(np.random.default_rng(0).integers(
        -2**15, 2**15, size=shape)).astype(dtype)
    ct = x[::-1] if not batched else x[:, ::-1]
    orients = set()
    for b in (bpc, bmmc):
        (plan,) = plan_bmmc(b, t)
        assert (plan.in_box is not None) == (b is bpc)
        geoms = [(bp.plan_geometry(plan, d=d),
                  bp.plan_geometry(plan, inverse=True, d=d))]
        if b is bpc:
            assert plan.out_box is not None
            geoms.append((_row_runs(geoms[0][0], plan),
                          _row_runs(geoms[0][1], plan)))
        intras = [(f, i) for f, i in zip(plan.intra_options,
                                         plan.intra_inv_options)
                  if d == 1 or not f.steps.transpose]
        want = np.asarray(bmmc_ref(x, b, batched=batched))
        want_ct = np.asarray(bmmc_ref(ct, b.inverse(), batched=batched))
        for fwd, bwd in geoms:
            for f_intra, i_intra in intras:
                orients.add(f_intra.steps.transpose)
                geom, tables = _oriented(fwd, plan, f_intra)
                got = bp.tiled_permute_tables(x, *tables, geometry=geom,
                                              batched=batched)
                assert np.asarray(got).tobytes() == want.tobytes()
                geom, tables = _oriented(bwd, plan, i_intra)
                got = bp.tiled_permute_bwd_tables(
                    x, ct, *tables, geometry=geom, batched=batched)
                assert np.asarray(got).tobytes() == want_ct.tobytes()
    assert orients == ({False, True} if d == 1 else {False})
