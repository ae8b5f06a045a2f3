"""TilePlan invariants (paper §4.1): coverage, coalescing, conflict-freedom."""
import random

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.bmmc import Bmmc
from repro.core.tiling import (naive_write_runs, plan_bmmc, plan_general,
                               plan_tiled)


@given(st.integers(6, 12), st.integers(0, 10**6), st.integers(2, 4))
@settings(max_examples=40, deadline=None)
def test_tile_row_coverage(n, seed, t):
    """Every input row is read exactly once; every output row written once."""
    if 2 * t > n:
        return
    b = Bmmc.random_bpc(n, random.Random(seed))
    p = plan_tiled(b, t)
    assert p is not None
    nrows = 1 << (n - t)
    assert sorted(p.in_rows.reshape(-1).tolist()) == list(range(nrows))
    assert sorted(p.out_rows.reshape(-1).tolist()) == list(range(nrows))


@given(st.integers(6, 12), st.integers(0, 10**6), st.integers(2, 4))
@settings(max_examples=40, deadline=None)
def test_src0_is_tile_permutation(n, seed, t):
    if 2 * t > n:
        return
    b = Bmmc.random_bpc(n, random.Random(seed))
    p = plan_tiled(b, t)
    flat = p.src0.reshape(-1)
    assert sorted(flat.tolist()) == list(range(flat.size))


@given(st.integers(6, 12), st.integers(0, 10**6))
@settings(max_examples=30, deadline=None)
def test_bpc_has_zero_xor(n, seed):
    """For BPCs the per-tile lane XOR vanishes (block bits map high)."""
    b = Bmmc.random_bpc(n, random.Random(seed))
    p = plan_tiled(b, min(3, n // 2))
    assert p is not None
    assert (p.xor_low == 0).all()


def test_simulated_kernel_matches_reference():
    """Full numpy simulation of the tiled pipeline == direct permutation."""
    rng = random.Random(9)
    for n, t in [(10, 3), (12, 4)]:
        for b in (Bmmc.bit_reverse(n), Bmmc.random(n, rng)):
            plans = plan_bmmc(b, t)
            x = np.arange(1 << n)
            cur = x
            for p in plans:
                rl = p.row_len
                xv = cur.reshape(-1, rl)
                out = np.empty_like(xv)
                for g in range(p.n_tiles):
                    tile = xv[p.in_rows[g]].reshape(-1)
                    j = np.arange(tile.size)
                    src = p.src0.reshape(-1)[(j & ~(rl - 1)) | ((j ^ p.xor_low[g]) & (rl - 1))]
                    out[p.out_rows[g]] = tile[src].reshape(-1, rl)
                cur = out.reshape(-1)
            want = np.empty_like(x)
            for i in range(1 << n):
                want[b.apply(i)] = x[i]
            assert np.array_equal(cur, want)


def _parity(v):
    v = v.copy()
    for sh in (16, 8, 4, 2, 1):
        v ^= v >> sh
    return v & 1


def _apply_intra(im, tile, g):
    """The kernel's intra-tile steps (``IntraMap``) in numpy: shears as
    XOR moves, the row and lane maps as one-hot matmuls."""
    rows, cols = tile.shape
    p, t = rows.bit_length() - 1, cols.bit_length() - 1
    w = [int(v) for v in im.words]
    f1, sinv, dcol = w[:p], w[p:2 * p], w[2 * p:2 * p + t]
    e, f0 = w[2 * p + t:2 * p + 2 * t], w[2 * p + 2 * t:]
    r, c = np.arange(rows)[:, None], np.arange(cols)[None, :]
    k = int(im.ktab[g])

    def row_shear(x, f):
        for j in range(p):
            x = np.where(_parity(c & f[j]) == 1, x[r ^ (1 << j), c], x)
        return x

    def span(idx, basis):
        out = np.zeros_like(idx)
        for i, v in enumerate(basis):
            out ^= ((idx >> i) & 1) * v
        return out

    x = tile
    if im.steps[0]:
        x = row_shear(x, f1)
    if im.steps[1]:
        src = span(np.arange(rows), sinv) ^ (k >> t)
        x = (np.arange(rows)[:, None] == src[None, :]).astype(np.int64) @ x
    if im.steps[2]:
        src = span(np.arange(cols), dcol) ^ (k & (cols - 1))
        x = x @ (np.arange(cols)[:, None] == src[None, :]).astype(np.int64)
    if im.steps[3]:
        for i in range(t):
            x = np.where(_parity(r & e[i]) == 1, x[r, c ^ (1 << i)], x)
    if im.steps[4]:
        x = row_shear(x, f0)
    return x


@pytest.mark.parametrize("n,t", [(8, 4), (10, 3), (12, 4), (9, 4)])
def test_intra_factorization_matches_src0_gather(n, t):
    """The factored intra-tile steps reproduce every tile's ``src0`` /
    ``xor_low`` gather, and the inverse factorization undoes it; general
    plans include BMMCs with a nonzero complement."""
    rng = random.Random(n * 100 + t)
    cases = [Bmmc.bit_reverse(n), Bmmc.random_bpc(n, rng), Bmmc.random(n, rng)]
    cases.append(Bmmc(cases[-1].rows, rng.getrandbits(n) | 1))
    for b in cases:
        plans = list(plan_bmmc(b, t))
        general = plan_general(b, t)
        if general is not None:
            plans.append(general)
        for p in plans:
            rows, cols = p.rows_per_tile, p.row_len
            tile = np.arange(rows * cols).reshape(rows, cols)
            j = np.arange(rows * cols)
            for g in range(p.n_tiles):
                src = p.src0.reshape(-1)[(j & ~(cols - 1))
                                         | ((j ^ p.xor_low[g]) & (cols - 1))]
                want = tile.reshape(-1)[src].reshape(rows, cols)
                assert np.array_equal(_apply_intra(p.intra, tile, g), want)
                assert np.array_equal(_apply_intra(p.intra_inv, want, g),
                                      tile)


def test_transaction_model_tiled_vs_naive():
    """The tiled pipeline is fully coalesced; the naive kernel is not.

    This is the offline counterpart of the paper's Fig. 9: bit-reversal's
    naive kernel touches ~seg_elems segments per warp (worst case), the
    tiled kernel exactly 1 contiguous run per row.
    """
    n, t = 16, 4
    b = Bmmc.bit_reverse(n)
    runs = naive_write_runs(b, seg_elems=1 << t)
    assert runs == float(1 << t)          # worst case: fully uncoalesced
    p = plan_tiled(b, t)
    in_bytes, out_bytes = p.bytes_per_descriptor(4)
    assert in_bytes >= (1 << t) * 4 and out_bytes >= (1 << t) * 4
    # identity: naive already coalesced
    assert naive_write_runs(Bmmc.identity(n), seg_elems=1 << t) == 1.0
