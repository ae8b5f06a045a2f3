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
    """The kernel's intra-tile steps (``IntraMap``) in numpy: the tile
    transposed first where the schedule says so, then only the XOR moves
    the schedule names, the row and lane maps as one-hot matmuls."""
    rows, cols = tile.shape
    p, t = rows.bit_length() - 1, cols.bit_length() - 1
    w = [int(v) for v in im.words]
    f1, sinv, dcol = w[:p], w[p:2 * p], w[2 * p:2 * p + t]
    e, f0 = w[2 * p + t:2 * p + 2 * t], w[2 * p + 2 * t:]
    r, c = np.arange(rows)[:, None], np.arange(cols)[None, :]
    k = int(im.ktab[g])
    st = im.steps

    def row_shear(x, f, bits):
        for j in bits:
            x = np.where(_parity(c & f[j]) == 1, x[r ^ (1 << j), c], x)
        return x

    def span(idx, basis):
        out = np.zeros_like(idx)
        for i, v in enumerate(basis):
            out ^= ((idx >> i) & 1) * v
        return out

    x = tile.T if st.transpose else tile
    x = row_shear(x, f1, st.f1)
    if st.row_map:
        src = span(np.arange(rows), sinv) ^ (k >> t)
        x = (np.arange(rows)[:, None] == src[None, :]).astype(np.int64) @ x
    if st.lane_map:
        src = span(np.arange(cols), dcol) ^ (k & (cols - 1))
        x = x @ (np.arange(cols)[:, None] == src[None, :]).astype(np.int64)
    for i in st.e:
        x = np.where(_parity(r & e[i]) == 1, x[r, c ^ (1 << i)], x)
    return row_shear(x, f0, st.f0)


def _intra_cases(n, t):
    rng = random.Random(n * 100 + t)
    cases = [Bmmc.bit_reverse(n), Bmmc.random_bpc(n, rng), Bmmc.random(n, rng)]
    cases.append(Bmmc(cases[-1].rows, rng.getrandbits(n) | 1))
    for b in cases:
        plans = list(plan_bmmc(b, t))
        general = plan_general(b, t)
        if general is not None:
            plans.append(general)
        yield from plans


@pytest.mark.parametrize("n,t,transpose", [
    pytest.param(n, t, tr, id=f"{n}-{t}" + ("-transposed" if tr else ""))
    for n, t in [(8, 4), (10, 3), (12, 4), (9, 4), (8, 5)]
    for tr in (False, True)])
def test_intra_factorization_matches_src0_gather(n, t, transpose):
    """The factored intra-tile steps of either orientation reproduce
    every tile's ``src0`` / ``xor_low`` gather, and the inverse
    factorization undoes it; general plans include BMMCs with a nonzero
    complement. Only square tiles have the transposed orientation, and
    a schedule names every nonzero shear word and no other."""
    for p in _intra_cases(n, t):
        rows, cols = p.rows_per_tile, p.row_len
        fwd = [im for im in p.intra_options if im.steps.transpose == transpose]
        inv = [im for im in p.intra_inv_options
               if im.steps.transpose == transpose]
        assert len(fwd) == len(inv) == int(rows == cols or not transpose)
        tile = np.arange(rows * cols).reshape(rows, cols)
        j = np.arange(rows * cols)
        for im, im_inv in zip(fwd, inv):
            for m in (im, im_inv):
                pb, tb = rows.bit_length() - 1, cols.bit_length() - 1
                w = [int(v) for v in m.words]
                for bits, words in ((m.steps.f1, w[:pb]),
                                    (m.steps.e, w[2 * pb + tb:2 * pb + 2 * tb]),
                                    (m.steps.f0, w[2 * pb + 2 * tb:])):
                    assert bits == tuple(k for k, v in enumerate(words) if v)
            for g in range(p.n_tiles):
                src = p.src0.reshape(-1)[(j & ~(cols - 1))
                                         | ((j ^ p.xor_low[g]) & (cols - 1))]
                want = tile.reshape(-1)[src].reshape(rows, cols)
                assert np.array_equal(_apply_intra(im, tile, g), want)
                assert np.array_equal(_apply_intra(im_inv, want, g), tile)


@given(st.integers(1, 7), st.integers(1, 7), st.integers(0, 10**6))
@settings(max_examples=60, deadline=None)
def test_greedy_f0_is_sparse(p, t, seed):
    """The greedy F0 makes ``D0 ^ C F0`` invertible with one unit word
    for each lane bit ``D0`` lacks, for any invertible intra-tile map."""
    from repro.core import f2
    from repro.core.tiling import _blocks, _greedy_f0
    rng = random.Random(seed)
    m = f2.random_invertible(p + t, rng)
    _, _, c, d0 = _blocks(m, p, t)
    for order in (range(p), range(p - 1, -1, -1)):
        f0 = _greedy_f0(c, d0, order, t)
        d = tuple(r ^ v for r, v in zip(d0, f2.matmul(c, f0)))
        assert f2.is_invertible(d)
        assert all(v & (v - 1) == 0 for v in f0)
        assert sum(v != 0 for v in f0) == t - f2.rank(d0)


def _parent_moves(m, ks, p, t):
    """XOR moves of the factorization the kernel ran before it named its
    moves: F0 = 0 where D0 is invertible, else the first random draw
    (seed 0) that makes D invertible, and all p or t moves of each shear
    whose words are not all zero, in the direct orientation."""
    from repro.core import f2
    from repro.core.tiling import _blocks, _factor
    _, _, c, d0 = _blocks(m, p, t)
    f0, rng = (0,) * p, random.Random(0)
    while not f2.is_invertible(tuple(
            x ^ y for x, y in zip(d0, f2.matmul(c, f0)))):
        f0 = tuple(rng.getrandbits(t) for _ in range(p))
    s = _factor(m, ks, p, t, f0, False).steps
    return p * bool(s.f1) + t * bool(s.e) + p * bool(s.f0)


@given(st.integers(6, 14), st.integers(0, 10**6), st.sampled_from(
    ["bitrev", "bpc", "bmmc"]))
@settings(max_examples=40, deadline=None)
def test_no_plan_emits_more_moves_than_before(n, seed, kind):
    """No pass, forward or inverse, for 1- or 2-wide items, emits more
    XOR moves than the factorization it replaced."""
    from repro.core import f2
    from repro.core.tiling import _intra_affine
    rng = random.Random(seed)
    t = rng.randrange(2, n // 2 + 1)
    b = {"bitrev": lambda: Bmmc.bit_reverse(n),
         "bpc": lambda: Bmmc(Bmmc.random_bpc(n, rng).rows, rng.getrandbits(n)),
         "bmmc": lambda: Bmmc(Bmmc.random(n, rng).rows,
                              rng.getrandbits(n))}[kind]()
    plans = list(plan_bmmc(b, t)) + [q for q in [plan_general(b, t)] if q]
    for p in plans:
        m, ks = _intra_affine(p.src0, p.xor_low)
        minv = f2.inverse(m)
        rows = p.rows_per_tile.bit_length() - 1
        for inverse, (mm, kk) in ((False, (m, ks)), (True, (
                minv, [f2.matvec(minv, k) for k in ks]))):
            before = _parent_moves(mm, kk, rows, p.t)
            for d in (1, 2):
                assert p.intra_map(d, inverse=inverse).steps.xor_moves <= before


def test_suite_intra_moves_at_24():
    """At n = 24, t = 9 the paper suite's bit-reverse emits no XOR move
    and its random BPC (``paper_fig9.cases``) at most 3, both on the
    transposed tile; the inverse gathers too."""
    t = choose_tile(24, 4)
    for b, most in ((Bmmc.bit_reverse(24), 0),
                    (Bmmc.random_bpc(24, random.Random(42)), 3)):
        (p,) = plan_bmmc(b, t)
        for im in (p.intra, p.intra_inv):
            assert im.steps.transpose and im.steps.xor_moves <= most


@pytest.mark.parametrize("n,t", [(10, 4), (12, 6), (12, 5)])
def test_planar_tiles_never_transpose(n, t):
    """A tile of 2-wide items (the FFT's planar (re, im) layout) keeps
    the direct orientation, forward and inverse, though 1-wide items of
    the same passes transpose; the kernel refuses a transposed schedule
    for it."""
    plans = list(_intra_cases(n, t))
    assert any(p.intra.steps.transpose for p in plans)
    for p in plans:
        for inverse in (False, True):
            assert not p.intra_map(2, inverse=inverse).steps.transpose
            assert not bp.plan_geometry(p, inverse=inverse, d=2)[7].transpose
    p = next(p for p in plans if p.intra.steps.transpose)
    x = np.zeros((1 << n, 2), np.float32)
    with pytest.raises(ValueError, match="cannot transpose"):
        bp.tiled_permute_tables(x, *bp.plan_tables(p),
                                geometry=bp.plan_geometry(p))


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


def _sort_plans(n=20):
    """The tiled passes of one call of the compiled sort of 2^n keys."""
    from repro.combinators import execute as ex
    from repro.combinators.ir import Perm
    from repro.combinators.optimize import FusedStage
    from repro.combinators.sort import compiled_sort
    from repro.kernels.ops import class_plan
    t = choose_tile(n, 4)
    plans = []
    for s in compiled_sort(n, engine="pallas").clustered_program(n, t):
        if isinstance(s, FusedStage):
            plans += ex._fused_plan_cached(s, t)[0]
        elif isinstance(s, Perm):
            kernel, payload = class_plan(s.bmmc, t)
            if kernel in ("tiled", "general", "general2"):
                plans += payload
    return plans


def test_sort_box_sides_are_ascending():
    """Every box side of the 2^20 sort's tiled passes enumerates its
    row bits in ascending slot order; every input side of a classic
    pass is a box."""
    plans = _sort_plans()
    out_boxes = sum(p.out_box is not None for p in plans)
    assert out_boxes >= 13
    for p in plans:
        if p.row_cols:
            assert p.in_box
        for rows in _box_sides(p):
            _assert_ascending_box(rows)


def test_sort_intra_moves():
    """The 2^20 sort's 31 tiled passes emit at most 300 XOR moves a
    call (828 before the schedule named its moves) and transpose some
    of its tiles."""
    steps = [p.intra.steps for p in _sort_plans()]
    assert len(steps) == 31
    assert sum(s.xor_moves for s in steps) <= 300
    assert any(s.transpose for s in steps)


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
