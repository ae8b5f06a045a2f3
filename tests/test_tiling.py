"""TilePlan invariants (paper §4.1): coverage, coalescing, conflict-freedom."""
import random

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.bmmc import Bmmc
from repro.core.tiling import (naive_write_runs, plan_bmmc, plan_general,
                               plan_stats, plan_stats_general, plan_tiled,
                               stats_bmmc)
from repro.kernels import bmmc_permute as bp
from repro.kernels.ops import choose_tile


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


def _assert_ascending_box(rows):
    """The side's rows form a box enumerated in ascending slot order:
    every tile's first row is clear on the box's bits, and slot r adds
    the bits of r spread over them lowest first (offsets strictly
    increase, 2^p of them within p bits)."""
    off = rows ^ rows[:, :1]
    mask = int(np.bitwise_or.reduce(off, axis=None))
    assert bin(mask).count("1") == rows.shape[1].bit_length() - 1
    assert not (rows[:, 0] & mask).any()
    assert (np.diff(off, axis=1) > 0).all()


def _box_sides(p):
    return [rows for rows, box in ((p.in_rows, p.in_box),
                                   (p.out_rows, p.out_box)) if box]


@pytest.mark.parametrize("n", range(10, 25))
@pytest.mark.parametrize("kind", ["bitrev", "bpc"])
def test_bpc_sides_are_ascending_boxes(kind, n):
    """Both sides of every bit-reverse and BPC pass (complement
    included) form a box, enumerated in ascending slot order."""
    rng = random.Random(n)
    b = (Bmmc.bit_reverse(n) if kind == "bitrev" else
         Bmmc(Bmmc.random_bpc(n, rng).rows, rng.getrandbits(n)))
    (p,) = plan_bmmc(b, choose_tile(n, 4))
    assert p.in_box and p.out_box
    for rows in _box_sides(p):
        _assert_ascending_box(rows)


def test_sort_box_sides_are_ascending():
    """Every box side of the 2^20 sort's tiled passes enumerates its
    row bits in ascending slot order; every input side of a classic
    pass is a box."""
    from repro.combinators import execute as ex
    from repro.combinators.ir import Perm
    from repro.combinators.optimize import FusedStage
    from repro.combinators.sort import compiled_sort
    from repro.kernels.ops import class_plan
    n = 20
    t = choose_tile(n, 4)
    plans = []
    for s in compiled_sort(n, engine="pallas").clustered_program(n, t):
        if isinstance(s, FusedStage):
            plans += ex._fused_plan_cached(s, t)[0]
        elif isinstance(s, Perm):
            kernel, payload = class_plan(s.bmmc, t)
            if kernel in ("tiled", "general", "general2"):
                plans += payload
    out_boxes = sum(p.out_box is not None for p in plans)
    assert out_boxes >= 13
    for p in plans:
        if p.row_cols:
            assert p.in_box
        for rows in _box_sides(p):
            _assert_ascending_box(rows)


@pytest.mark.parametrize("n,t", [(8, 3), (10, 4), (12, 6), (12, 5), (9, 5),
                                 (14, 6)])
def test_descriptor_counts_agree(n, t):
    """``TilePlan.dma_descriptors`` equals the analytic ``PlanStats``
    count and what the kernel issues: one descriptor per tile for a
    box side, one per run of consecutive rows for any other."""
    rng = random.Random(n * 7 + t)
    cases = [Bmmc.bit_reverse(n)]
    cases += [Bmmc(Bmmc.random_bpc(n, rng).rows, rng.getrandbits(n))
              for _ in range(3)]
    cases += [Bmmc.random(n, rng) for _ in range(4)]
    n_rows = 1 << (n - t)
    for b in cases:
        for build, stats in ((plan_tiled, plan_stats),
                             (plan_general, plan_stats_general)):
            p, s = build(b, t), stats(b, t)
            if p is None:
                assert s is None
                continue
            assert (s.in_box, s.out_box, s.in_run, s.out_run) == \
                (p.in_box, p.out_box, p.in_run, p.out_run)
            geom = bp.plan_geometry(p)
            issued = sum(bp._Side(layout, p.rows_per_tile, n_rows, None).count
                         for layout in geom[3:5])
            assert p.dma_descriptors() == s.dma_descriptors() \
                == p.n_tiles * issued == bp.geometry_descriptors(geom)
        assert sum(s.dma_descriptors() for s in stats_bmmc(b, t)) == sum(
            p.dma_descriptors() for p in plan_bmmc(b, t))
