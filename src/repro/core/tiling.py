"""Tile-bit partitioning and offline table generation (paper §4.1-4.3, §5.1).

For a *tiled* BMMC ``(A, c)`` on ``n``-bit indices and tile parameter ``t``
(= ``n_tile``; one "row" = 2^t consecutive elements), input index bits are
partitioned into:

* tile column bits  — the low ``t`` bits (set L),
* tile row bits     — the witness columns ``i_1..i_t`` (set R; for a BPC these
  are exactly ``{j : p(j) < t}``),
* overlap bits      — R ∩ L (``n_over`` of them),
* thread-block bits — the rest (``n_TB = n - 2t + n_over``), all >= t.

One tile = all index combinations of (L ∪ R) bits with the block bits fixed:
``2^(t - n_over)`` full input rows, mapping onto as many full output rows.
The lowest ``n_over`` block bits are folded into the tile as well, so a
tile is ``2^t`` rows whenever ``2t <= n`` (``_square_up``). This module
precomputes, per permutation (offline, matching the paper's codegen
setting):

* ``in_rows[g, r]``   — input row id read by tile ``g`` (row view: (2^(n-t), 2^t)),
* ``out_rows[g, r']`` — output row id written by tile ``g``,
* ``xor_low[g]``      — per-tile XOR on the intra-tile lane gather (the
  block-bit contribution to the low output bits; 0 for every BPC),
* ``src0``            — flat intra-tile gather table for tile 0:
  ``out_tile.flat[j] = in_tile.flat[src0[j ^ xor_low[g]]]``; the kernel
  applies it in the factored form :class:`IntraMap`.

The per-tile XOR trick is the TPU replacement for re-deriving indices per
thread: tables are computed once; the kernel's scalar core only reads them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import numpy as np

from .bmmc import Bmmc
from . import f2


def _scatter_bits(value: int, positions: list) -> int:
    """Place bit k of ``value`` at ``positions[k]``."""
    out = 0
    for k, pos in enumerate(positions):
        if (value >> k) & 1:
            out |= 1 << pos
    return out


def _gather_all(xs: np.ndarray, positions: list) -> np.ndarray:
    """Bit ``k`` of each result is bit ``positions[k]`` of ``xs``."""
    out = np.zeros_like(xs)
    for k, pos in enumerate(positions):
        out |= ((xs >> pos) & 1) << k
    return out


def _tile0_sources(bmmc: Bmmc, out_rows0: np.ndarray, t: int) -> np.ndarray:
    """Input index of every (output row slot, lane) of tile 0."""
    ys = ((out_rows0.astype(np.int64)[:, None] << t)
          | np.arange(1 << t, dtype=np.int64)[None, :])
    return bmmc.inverse().apply_all(ys)


def _run_length(rows: np.ndarray) -> int:
    """Largest power-of-two run of consecutive row ids shared by all tiles.

    This is the DMA-merge factor: ``run`` consecutive rows can be copied by a
    single descriptor (the TPU analogue of the paper's §4.3 amortization).
    """
    n_tiles, rpt = rows.shape
    run = 1
    while run * 2 <= rpt:
        nxt = run * 2
        blocks = rows.reshape(n_tiles, rpt // nxt, nxt)
        diff = blocks - blocks[..., :1]
        if np.array_equal(diff, np.broadcast_to(np.arange(nxt), diff.shape)):
            run = nxt
        else:
            break
    return run


def _bit_runs(mask: int) -> tuple:
    """Maximal runs ``((lo, width), ...)`` of the set bits of ``mask``,
    lowest first."""
    runs, lo = [], 0
    while mask >> lo:
        if (mask >> lo) & 1:
            w = 0
            while (mask >> (lo + w)) & 1:
                w += 1
            runs.append((lo, w))
            lo += w
        else:
            lo += 1
    return tuple(runs)


def row_box(rows: np.ndarray) -> Optional[tuple]:
    """The box a side's row table forms, or None.

    A side is a box when every tile's rows are its first row with every
    combination of a fixed set of row-id bits, clear in that first row,
    and slot ``r`` sets those bits to the bits of ``r`` in ascending
    order. Returns the set as runs ``((lo, width), ...)`` of consecutive
    row-id bits, lowest first: the kernel copies such a side with one
    strided descriptor over a view with one axis per run."""
    rpt = rows.shape[1]
    imgs = [int(rows[0, 1 << k] ^ rows[0, 0])
            for k in range(rpt.bit_length() - 1)]
    if not imgs or any(v <= 0 or v & (v - 1) for v in imgs) or any(
            a >= b for a, b in zip(imgs, imgs[1:])):
        return None
    slots = np.arange(rpt, dtype=np.int64)
    want = np.zeros(rpt, dtype=np.int64)
    for k, v in enumerate(imgs):
        want |= ((slots >> k) & 1) * v
    if not np.array_equal(rows, rows[:, :1] | want[None, :]):
        return None
    return _bit_runs(sum(imgs))


def _box_basis(vecs: list, imgs: list) -> Optional[tuple]:
    """``(dirs, mask)``: combinations of ``vecs`` whose images are the
    single bits of ``mask``, the images' span, lowest bit first, when
    that span is a box (spanned by unit row-id bits); None otherwise.
    ``imgs[k]`` is the row-id image of ``vecs[k]`` under a linear map,
    so a combination's image is the XOR of its parts' images."""
    mask = 0
    for h in imgs:
        mask |= h
    if not imgs or bin(mask).count("1") != len(imgs):
        return None
    todo, done = list(zip(imgs, vecs)), []
    for b in range(mask.bit_length()):   # Gauss-Jordan on the images
        if (mask >> b) & 1:
            h, v = todo.pop(next(i for i, (h, _) in enumerate(todo)
                                 if (h >> b) & 1))
            todo, done = ([(h2 ^ h, v2 ^ v) if (h2 >> b) & 1 else (h2, v2)
                           for h2, v2 in part] for part in (todo, done))
            done.append((h, v))
    return [v for _, v in done], mask


class _Descriptors:
    """DMA descriptor counts of a tile plan: a box side takes one
    descriptor per tile, any other side one per run of ``in_run`` /
    ``out_run`` consecutive rows."""

    def side_descriptors(self) -> tuple:
        """Descriptors per tile, (input reads, output writes)."""
        rpt = self.rows_per_tile
        return (1 if self.in_box else rpt // self.in_run,
                1 if self.out_box else rpt // self.out_run)

    def dma_descriptors(self) -> int:
        """Total HBM DMA descriptors issued (reads + writes)."""
        return self.n_tiles * sum(self.side_descriptors())

    def bytes_per_descriptor(self, itemsize: int) -> tuple:
        tile = self.rows_per_tile * self.row_len * itemsize
        return tuple(tile // k for k in self.side_descriptors())


@dataclasses.dataclass(frozen=True)
class TilePlan(_Descriptors):
    """Offline execution plan for one tiled-BMMC pass.

    ``row_dirs`` are the witness *directions* spanning the tile's row
    structure — full n-bit vectors whose high parts are independent;
    tile slot ``r`` holds rows offset by ``XOR(row_dirs[k] for bits k of
    r)``. For a classically tiled plan (paper §5.1) these are the unit
    vectors of the witness columns above ``t``; the generalized planner
    (:func:`plan_general`) uses any basis of ``ker(A[t:, :])``, which
    always exists — so every invertible BMMC gets a ONE-pass plan.
    """

    bmmc: Bmmc
    t: int                      # n_tile: log2 elements per row
    row_cols: tuple             # R, sorted (classic witness; () if general)
    n_over: int
    tb_positions: tuple         # thread-block bit positions, sorted (all >= t)
    in_rows: np.ndarray         # (n_tiles, rows_per_tile) int32
    out_rows: np.ndarray        # (n_tiles, rows_per_tile) int32
    xor_low: np.ndarray         # (n_tiles,) int32
    src0: np.ndarray            # (rows_per_tile, 2^t) int32 flat gather table
    in_run: int                 # input DMA merge run (rows)
    out_run: int                # output DMA merge run (rows)
    row_dirs: tuple = ()        # witness directions, len == log2(rows_per_tile)

    @property
    def n(self) -> int:
        return self.bmmc.n

    @property
    def n_tiles(self) -> int:
        return self.in_rows.shape[0]

    @property
    def rows_per_tile(self) -> int:
        return self.in_rows.shape[1]

    @property
    def row_len(self) -> int:
        return 1 << self.t

    @functools.cached_property
    def in_box(self) -> Optional[tuple]:
        """:func:`row_box` of the rows each tile reads."""
        return row_box(self.in_rows)

    @functools.cached_property
    def out_box(self) -> Optional[tuple]:
        """:func:`row_box` of the rows each tile writes."""
        return row_box(self.out_rows)

    def audit(self) -> "TilePlan":
        """Descriptor-bounds + semantic audit (guard ring 1): every
        table entry within the geometry, ``src0`` a bijection, and the
        kernel contract routing exactly what the BMMC demands. Raises
        :class:`repro.guard.DescriptorOOB`."""
        from ..guard.validate import audit_tile_plan  # lazy: no cycle
        audit_tile_plan(self)
        return self

    @functools.cached_property
    def intra_options(self) -> tuple:
        """The kernel's factorizations of the ``src0``/``xor_low``
        gather, one per orientation (:func:`intra_options`)."""
        m, ks = _intra_affine(self.src0, self.xor_low)
        return intra_options(m, ks, self.rows_per_tile, self.row_len)

    @functools.cached_property
    def intra_inv_options(self) -> tuple:
        """Factorizations of the inverse gather (the gradient kernel's
        un-permute of the cotangent tile)."""
        m, ks = _intra_affine(self.src0, self.xor_low)
        minv = f2.inverse(m)
        return intra_options(minv, [f2.matvec(minv, k) for k in ks],
                             self.rows_per_tile, self.row_len)

    def intra_map(self, d: int = 1, *, inverse: bool = False) -> "IntraMap":
        """The cheapest factorization a tile of ``d``-wide items can run
        (:func:`best_intra`), of the gather or of its inverse."""
        return best_intra(self.intra_inv_options if inverse
                          else self.intra_options, d)

    @property
    def intra(self) -> "IntraMap":
        """:meth:`intra_map` for 1-wide items."""
        return self.intra_map()

    @property
    def intra_inv(self) -> "IntraMap":
        """:meth:`intra_map` of the inverse gather for 1-wide items."""
        return self.intra_map(inverse=True)


# ---------------------------------------------------------------------------
# Intra-tile factorization (the TPU form of the src0 gather).
#
# Tile slot (r, c) — row r of p bits, lane c of t bits, flat index
# r * 2^t + c — of tile g gathers from slot ``M (r, c) ^ K_g``: the
# src0 table is affine (M, K_0) and xor_low[g] only moves the constant.
# A vector unit has no general in-memory gather, so the kernel applies M
# as four steps, each of which lowers to rolls, selects and one-hot
# matmuls (gather form: step i reads z_i[u] = z_{i-1}[M_i u]):
#
#   1. row shear     z[r, c] = w[r ^ F1 c, c]
#   2. row/lane maps z[r, c] = w[S r ^ kr, D c ^ kc]   (one-hot matmuls)
#   3. lane shear    z[r, c] = w[r, c ^ E r]
#   4. row shear     z[r, c] = w[r ^ F0 c, c]
#
# With M = [[A, B], [C, D0]] (row/lane blocks): pick F0 so that
# D = D0 ^ C F0 is invertible, then F1 = B' D^-1, S = A ^ F1 C,
# E = D^-1 C with B' = B ^ A F0. The per-tile constant rides step 2:
# (kr, kc) = M1 K_g. A shear is one XOR move (two rolls, two selects)
# per row or lane bit whose word is nonzero, so the kernel emits only
# those; a map is a one-hot matmul per byte plane.
#
# The F0 candidates: 0 when D0 is invertible; A^-1 B when A is (then
# F1 = 0, and D is the Schur complement, invertible as M is); and a
# greedy pick of unit words, one for each of the t - rank(D0) lane
# bits D0 lacks. Setting word j of F0 to v adds (column j of C) v^T
# to D, which raises its rank exactly when that column lies outside
# D's column space and v outside its row space; as [C D0] has full
# row rank, some column of C qualifies while D is singular, and some
# unit v always does, so the pick never fails.
#
# A square tile of 1-wide items may also be transposed first: the tile
# then gathers by T M, constants T K_g, where T swaps the row and lane
# fields. A bit permutation that moves bits between rows and lanes
# (bit-reverse, most BPCs) has a singular D0 in one orientation and a
# nearly block-diagonal M in the other. The plan keeps each
# orientation's cheapest candidate; the kernel runs the cheaper one
# its items allow (``best_intra``).
# ---------------------------------------------------------------------------

class IntraSteps(NamedTuple):
    """The static schedule of one intra-tile gather (part of the
    executable key): whether the tile is transposed first, the row bits
    whose first row-shear word is nonzero, whether the row and lane maps
    do work, the lane bits whose lane-shear word is nonzero, and the row
    bits of the second row shear."""

    transpose: bool = False
    f1: tuple = ()
    row_map: bool = False
    lane_map: bool = False
    e: tuple = ()
    f0: tuple = ()

    @property
    def xor_moves(self) -> int:
        return len(self.f1) + len(self.e) + len(self.f0)

    @property
    def maps(self) -> int:
        return int(self.row_map) + int(self.lane_map)

    def cost(self) -> tuple:
        """Fewest XOR moves, then fewest maps, then no transpose."""
        return self.xor_moves, self.maps, self.transpose

    @staticmethod
    def union(schedules) -> "IntraSteps":
        """One schedule that serves every one of ``schedules`` (stacked
        per-shard tables of one orientation): a move or map runs where
        any of them needs it; with zero words it moves nothing."""
        schedules = list(schedules)
        (transpose,) = {s.transpose for s in schedules}

        def bits(name):
            return tuple(sorted(set().union(
                *(getattr(s, name) for s in schedules))))
        return IntraSteps(transpose, bits("f1"),
                          any(s.row_map for s in schedules),
                          any(s.lane_map for s in schedules), bits("e"),
                          bits("f0"))


@dataclasses.dataclass(frozen=True)
class IntraMap:
    """Kernel tables of one intra-tile affine gather.

    ``steps`` (:class:`IntraSteps`) is the static schedule. ``words``
    holds the steps' parameters as ``f1 (p) | sinv (p) | dcol (t) | e
    (t) | f0 (p)`` bit masks; ``ktab[g]`` packs tile g's
    ``S^-1 kr << t | kc``.
    """

    steps: IntraSteps
    words: np.ndarray     # (3p + 2t,) int32
    ktab: np.ndarray      # (n_tiles,) int32


def _intra_affine(src0: np.ndarray, xor_low: np.ndarray) -> tuple:
    """``(M, [K_g])``: src0 as an affine map on the flat slot index
    (rows representation over p + t bits) and every tile's constant."""
    flat = src0.reshape(-1).astype(np.int64)
    nbits = flat.size.bit_length() - 1
    k0 = int(flat[0])
    cols = [int(flat[1 << j]) ^ k0 for j in range(nbits)]
    idx = np.arange(flat.size, dtype=np.int64)
    pred = np.full_like(idx, k0)
    for j, col in enumerate(cols):
        pred ^= ((idx >> j) & 1) * col
    if not np.array_equal(pred, flat):
        raise ValueError("intra-tile gather table is not affine")
    m = tuple(sum(((col >> i) & 1) << j for j, col in enumerate(cols))
              for i in range(nbits))
    return m, [k0 ^ f2.matvec(m, int(x)) for x in xor_low]


def _madd(a, b) -> tuple:
    return tuple(x ^ y for x, y in zip(a, b))


def _bits_set(words) -> tuple:
    return tuple(j for j, w in enumerate(words) if w)


def _greedy_f0(c: tuple, d0: tuple, order, t: int) -> tuple:
    """Unit words of F0, one per lane bit ``D0`` lacks, that make
    ``D0 ^ C F0`` invertible; row bits tried in ``order``."""
    f0 = [0] * len(order)
    d, rank = d0, f2.rank(d0)
    for j in order:
        if rank == t:
            break
        for q in range(t):
            nd = tuple(r ^ (((ci >> j) & 1) << q) for r, ci in zip(d, c))
            if f2.rank(nd) > rank:
                f0[j], d, rank = 1 << q, nd, rank + 1
                break
    assert rank == t, "[C D0] has full row rank"
    return tuple(f0)


def _f0_candidates(a, b, c, d0, p: int, t: int) -> list:
    """The F0 candidates of the block comment above."""
    out = []
    if f2.is_invertible(d0):
        out.append((0,) * p)
    if f2.is_invertible(a):
        out.append(f2.matmul(f2.inverse(a), b))
    out += [_greedy_f0(c, d0, order, t)
            for order in (range(p), range(p - 1, -1, -1))]
    return out


def _blocks(m: tuple, p: int, t: int) -> tuple:
    """``(A, B, C, D0)``: the row/lane blocks of ``M``."""
    tmask, pmask = (1 << t) - 1, (1 << p) - 1
    return (tuple((m[t + i] >> t) & pmask for i in range(p)),
            tuple(m[t + i] & tmask for i in range(p)),
            tuple(m[i] >> t for i in range(t)),
            tuple(m[i] & tmask for i in range(t)))


def _factor(m: tuple, ks: list, p: int, t: int, f0: tuple,
            transpose: bool) -> IntraMap:
    """The steps of one orientation's gather ``(M, [K_g])`` for one F0."""
    tmask = (1 << t) - 1
    a, b, c, d0 = _blocks(m, p, t)
    d = _madd(d0, f2.matmul(c, f0))
    dinv = f2.inverse(d)
    f1 = f2.matmul(_madd(b, f2.matmul(a, f0)), dinv)
    s = _madd(a, f2.matmul(f1, c))
    e = f2.matmul(dinv, c)
    sinv = f2.inverse(s)
    ktab = np.empty((len(ks),), dtype=np.int32)
    for g, k in enumerate(ks):
        kc = k & tmask
        kr = f2.matvec(sinv, (k >> t) ^ f2.matvec(f1, kc))
        ktab[g] = (kr << t) | kc
    words = np.asarray(
        list(f1) + [f2.column(sinv, i) for i in range(p)]
        + [f2.column(d, i) for i in range(t)] + list(e) + list(f0),
        dtype=np.int32)
    steps = IntraSteps(
        transpose, _bits_set(f1),
        s != f2.identity(p) or bool((ktab >> t).any()),
        d != f2.identity(t) or bool((ktab & tmask).any()),
        _bits_set(e), _bits_set(f0))
    return IntraMap(steps=steps, words=words, ktab=ktab)


def intra_options(m: tuple, ks: list, rows: int, row_len: int) -> tuple:
    """The cheapest factorization of the gather ``z[u] = w[M u ^ K_g]``
    on a (rows, row_len) tile in each orientation: the direct one, and
    for a square tile the transposed one (see the block comment above)."""
    t = row_len.bit_length() - 1
    p = rows.bit_length() - 1
    if not f2.is_invertible(m):
        raise ValueError("intra-tile gather is not a bijection")
    orients = [(False, m, ks)]
    if p == t:
        full = (1 << 2 * t) - 1

        def swap(v: int) -> int:
            return ((v << t) | (v >> t)) & full
        orients.append((True, tuple(m[(i + t) % (2 * t)]
                                    for i in range(2 * t)),
                        [swap(k) for k in ks]))
    out = []
    for transpose, mo, ko in orients:
        out.append(min((_factor(mo, ko, p, t, f0, transpose)
                        for f0 in _f0_candidates(*_blocks(mo, p, t), p, t)),
                       key=lambda im: im.steps.cost()))
    return tuple(out)


def best_intra(options, d: int = 1) -> IntraMap:
    """The cheapest of ``options`` (:meth:`IntraSteps.cost`) that a tile
    of ``d``-wide items can run: only 1-wide items transpose."""
    return min((im for im in options if d == 1 or not im.steps.transpose),
               key=lambda im: im.steps.cost())


def _square_up(tb: list, deficit: int) -> tuple:
    """Split the thread-block positions into (absorbed, remaining).

    A tile spans ``2^(t - deficit)`` rows of its own; the lowest
    ``deficit`` thread-block positions are folded into it so every tile
    is ``2^t x 2^t`` whenever ``2t <= n`` (fewer only for t > n/2). A
    square tile keeps the kernel's row axis a whole number of sublane
    tiles and gives one tile geometry per ``t``, independent of how many
    witness bits overlap the lanes."""
    k = min(deficit, len(tb))
    return tb[:k], tb[k:]


def _out_side(bmmc: Bmmc, t: int, out_pos: list, tb: list) -> tuple:
    """``(dirs, off, box)`` of a tile's output side: output slot ``r'``
    holds the images of ``base ^ off ^ XOR(dirs[k] for bits k of r')``.
    When the rows written form a box that no tile base moves, the unit
    directions of ``out_pos`` are re-based so the slot bits enumerate
    the box's row-id bits in ascending order, and ``off`` clears those
    bits in every tile's first row; otherwise the units stay as they
    are and ``box`` is None."""
    units = [1 << p for p in out_pos]

    def hi(v: int) -> int:
        return f2.matvec(bmmc.rows, v) >> t
    got = _box_basis(units, [hi(v) for v in units])
    if got is None or any(hi(1 << q) & got[1] for q in tb):
        return units, 0, None
    dirs, mask = got
    off = 0   # each tile's first row carries c's bits of the box
    for v in dirs:
        if hi(v) & (bmmc.c >> t):
            off ^= v
    return dirs, off, _bit_runs(mask)


def _in_box(row_dirs: list, t: int) -> tuple:
    """``(dirs, box)`` of a tile's input side: ``row_dirs`` re-based so
    the slot bits enumerate the box's row-id bits in ascending order
    when the rows read form a box (the tile bases, unit positions
    outside the directions' span, never touch it); else unchanged."""
    got = _box_basis(row_dirs, [v >> t for v in row_dirs])
    if got is None:
        return row_dirs, None
    return got[0], _bit_runs(got[1])


def _classic_layout(bmmc: Bmmc, t: int) -> Optional[tuple]:
    """``(cols, n_over, row_pos, (out_dirs, out_off, out_box), tb)`` of
    the classic tiled plan: tile slot ``r`` reads input row bits
    ``row_pos`` (ascending: the input side is always a box), output
    slot ``r'`` is enumerated by :func:`_out_side`, and ``tb`` indexes
    the tiles. None if ``bmmc`` is not tiled for this ``t``."""
    n = bmmc.n
    if t > n:
        return None
    cols = bmmc.tiled_columns(t)
    if cols is None:
        return None
    low = set(range(t))
    r_set = set(cols)
    n_over = len(r_set & low)
    if n - 2 * t + n_over < 0:
        return None  # tile would exceed the array; caller falls back
    r_not_l = sorted(r_set - low)           # t - n_over positions, all >= t
    l_not_r = sorted(low - r_set)           # t - n_over positions, all < t
    tb = sorted(set(range(n)) - low - r_set)
    assert len(tb) == n - 2 * t + n_over
    extra, tb = _square_up(tb, n_over)
    return (cols, n_over, sorted(r_not_l + extra),
            _out_side(bmmc, t, l_not_r + extra, tb), tb)


def plan_tiled(bmmc: Bmmc, t: int) -> Optional[TilePlan]:
    """Build a TilePlan, or None if ``bmmc`` is not tiled for this ``t``."""
    lay = _classic_layout(bmmc, t)
    if lay is None:
        return None
    cols, n_over, row_pos, (out_dirs, out_off, _), tb = lay
    rpt = 1 << len(row_pos)                  # rows per tile
    n_tiles = 1 << len(tb)
    row_len = 1 << t
    low_mask = row_len - 1

    in_rows = np.empty((n_tiles, rpt), dtype=np.int32)
    out_rows = np.empty((n_tiles, rpt), dtype=np.int32)
    xor_low = np.empty((n_tiles,), dtype=np.int32)

    # Row tables. y_high = A[t:, :] x ^ c_high depends only on non-R bits of x
    # (the zero block kills R), i.e. on (L\R, absorbed TB, TB).
    for g in range(n_tiles):
        base = _scatter_bits(g, tb)
        delta = f2.matvec(bmmc.rows, base)
        xor_low[g] = delta & low_mask
        for r in range(rpt):
            in_rows[g, r] = (base | _scatter_bits(r, row_pos)) >> t
        for rp in range(rpt):
            y = bmmc.apply(base ^ out_off ^ _xor_dirs(rp, out_dirs))
            out_rows[g, rp] = y >> t

    # Intra-tile gather table for tile 0 (other tiles differ by xor_low only).
    xs = _tile0_sources(bmmc, out_rows[0], t)
    assert not _gather_all(xs, tb).any(), "tile-0 source must be in tile 0"
    src0 = (_gather_all(xs, row_pos) * row_len
            + (xs & low_mask)).astype(np.int32)
    return TilePlan(
        bmmc=bmmc, t=t, row_cols=tuple(sorted(cols)), n_over=n_over,
        tb_positions=tuple(tb), in_rows=in_rows, out_rows=out_rows,
        xor_low=xor_low, src0=src0,
        in_run=_run_length(in_rows), out_run=_run_length(out_rows),
        row_dirs=tuple(1 << p for p in row_pos),
    )


# ---------------------------------------------------------------------------
# Generalized one-pass planning (§5.1 with witness *directions*).
#
# The classic tiled condition demands t witness COLUMNS: unit directions
# e_j with A e_j supported on the low t rows. But the kernel's actual
# requirements are weaker: (1) each tile reads whole input rows, (2)
# writes whole output rows, (3) tiles share one gather table up to a
# per-tile lane XOR. All three survive replacing unit directions by ANY
# basis of D = ker(A[t:, :]) — which has dimension exactly t for every
# invertible A. Splitting D into pure-low directions (a of them; the
# n_over analogue) and directions with independent high parts (the
# rows-per-tile span), and choosing the thread-block complement among
# the HIGH unit positions (so the per-tile base never touches the
# lanes), yields tables honouring the exact same kernel contract:
#
#     out.flat[j] = tile.flat[src0[j ^ xor_low[g]]]
#
# Consequence: any BMMC with n - 2t + a >= 0 (always true for 2t <= n)
# runs in ONE tiled pass — the §5.2 two-pass factorization becomes a
# fallback for t > n/2 instead of the general path.
# ---------------------------------------------------------------------------


def _split_directions(bmmc: Bmmc, t: int) -> tuple:
    """Basis of ``ker(A[t:, :])`` split into (a, row_dirs): ``a`` counts
    the pure-low directions; ``row_dirs`` have independent high parts."""
    d = f2.nullspace(bmmc.rows[t:], bmmc.n)
    assert len(d) == t, "kernel of the high rows must have dimension t"
    row_dirs: list = []
    a = 0
    for v in d:
        h = v >> t
        for w in row_dirs:  # eliminate previously-chosen high pivots
            if h & ((w >> t) & -(w >> t)):
                v ^= w
                h = v >> t
        if h == 0:
            a += 1
        else:
            row_dirs.append(v)
    return a, row_dirs


def _tb_complement(row_dirs: list, t: int, n: int) -> list:
    """High unit positions completing ``{high(row_dirs)}`` to F2^(n-t)."""
    gens = [v >> t for v in row_dirs]
    tb = []
    for pos in range(t, n):
        u = 1 << (pos - t)
        if not f2.in_span(u, gens):
            gens.append(u)
            tb.append(pos)
    return tb


def _xor_dirs(r: int, row_dirs) -> int:
    v = 0
    k = 0
    while r:
        if r & 1:
            v ^= row_dirs[k]
        r >>= 1
        k += 1
    return v


def _out_low_positions(bmmc: Bmmc, t: int, count: int) -> list:
    """Low unit positions whose images under A[t:, :] are independent —
    these enumerate a tile's distinct output rows."""
    chosen: list = []
    imgs: list = []
    for j in range(t):
        img = f2.matvec(bmmc.rows, 1 << j) >> t
        if img and not f2.in_span(img, imgs):
            imgs.append(img)
            chosen.append(j)
            if len(chosen) == count:
                break
    assert len(chosen) == count, "output row images must span"
    return chosen


def _general_layout(bmmc: Bmmc, t: int) -> Optional[tuple]:
    """``(a, (row_dirs, in_box), (out_dirs, out_off, out_box), tb)`` of
    the generalized plan: the witness directions plus the absorbed
    thread-block unit directions (:func:`_in_box`), the output side
    enumerated from the low positions whose images span a tile's
    output rows (:func:`_out_side`), and the remaining thread-block
    positions. None when the tile would exceed the array (``n - 2t + a
    < 0``, only possible for t > n/2)."""
    n = bmmc.n
    if not 0 < t <= n:
        return None
    a, row_dirs = _split_directions(bmmc, t)
    if n - 2 * t + a < 0:
        return None
    extra, tb = _square_up(_tb_complement(row_dirs, t, n), a)
    out_pos = _out_low_positions(bmmc, t, t - a) + extra
    return (a, _in_box(row_dirs + [1 << p for p in extra], t),
            _out_side(bmmc, t, out_pos, tb), tb)


def plan_general(bmmc: Bmmc, t: int) -> Optional[TilePlan]:
    """One-pass plan for an arbitrary invertible BMMC (see block comment
    above). Returns None when the tile would exceed the array
    (``n - 2t + a < 0``, only possible for t > n/2)."""
    lay = _general_layout(bmmc, t)
    if lay is None:
        return None
    a, (row_dirs, _), (out_dirs, out_off, _), tb = lay
    low_mask = (1 << t) - 1
    rpt = 1 << len(row_dirs)
    n_tiles = 1 << len(tb)
    row_len = 1 << t

    in_rows = np.empty((n_tiles, rpt), dtype=np.int32)
    out_rows = np.empty((n_tiles, rpt), dtype=np.int32)
    xor_low = np.empty((n_tiles,), dtype=np.int32)
    for g in range(n_tiles):
        base = _scatter_bits(g, tb)
        xor_low[g] = f2.matvec(bmmc.rows, base) & low_mask
        for r in range(rpt):
            in_rows[g, r] = (base ^ _xor_dirs(r, row_dirs)) >> t
        for rp in range(rpt):
            y = bmmc.apply(base ^ out_off ^ _xor_dirs(rp, out_dirs))
            out_rows[g, rp] = y >> t

    order = np.argsort(in_rows[0])
    rows0 = in_rows[0][order]
    assert np.unique(rows0).size == rpt, "tile rows must be distinct"
    xs = _tile0_sources(bmmc, out_rows[0], t)
    pos = np.minimum(np.searchsorted(rows0, xs >> t), rpt - 1)
    assert np.array_equal(rows0[pos], xs >> t), "tile-0 source must be in tile 0"
    src0 = (order[pos] * row_len + (xs & low_mask)).astype(np.int32)
    return TilePlan(
        bmmc=bmmc, t=t, row_cols=(), n_over=a, tb_positions=tuple(tb),
        in_rows=in_rows, out_rows=out_rows, xor_low=xor_low, src0=src0,
        in_run=_run_length(in_rows), out_run=_run_length(out_rows),
        row_dirs=tuple(row_dirs),
    )


# ---------------------------------------------------------------------------
# Fused-compute tables: everything a megakernel epilogue needs to run a
# CmpHalves / Bfly stage on the tile while it sits in VMEM (DESIGN.md §10).
#
# The compute pairs intermediate index m with m ^ 2^(n-1), where m = M x
# (+) c_M and M is the composition of the run's perms *before* the
# compute. Pulled back to input space the partner of x is x ^ v with
# v = A_M^-1 e_{n-1}; when v lies in the span of the plan's tile row (R)
# and column (L) bits, the partner is resident in the same tile at
# position (r ^ vr, lane ^ vc). Which element of a pair is the "hi" half
# (bit n-1 of m set) and which twiddle a butterfly pair uses are affine
# in x, so they split into tiny per-row / per-lane tables XORed with one
# per-tile scalar — the same trick as `xor_low`.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ComputeTables:
    """Offline tables for one in-VMEM compute applied inside a tiled pass."""

    kind: str                        # "cmp" | "bfly"
    vr: int                          # partner XOR on the tile-row slot
    vc: int                          # partner XOR on the lane
    hi_row: np.ndarray               # (rows_per_tile,) int32 parity bits
    hi_lane: np.ndarray              # (row_len,) int32 parity bits
    hi_base: np.ndarray              # (n_tiles,) int32 per-tile parity bit
    tw_row: Optional[np.ndarray] = None    # (rows_per_tile,) int32 (bfly)
    tw_lane: Optional[np.ndarray] = None   # (row_len,) int32 (bfly)
    tw_base: Optional[np.ndarray] = None   # (n_tiles,) int32 (bfly)


def pairing_vector(prefix: Bmmc) -> int:
    """The input-space partner XOR ``v = A_M^{-1} e_{n-1}`` of a compute
    whose pair bit is n-1 in the output space of ``prefix``."""
    return f2.matvec(f2.inverse(prefix.rows), 1 << (prefix.n - 1))


def _dir_coords(v: int, row_dirs: tuple, t: int) -> Optional[int]:
    """Coordinates ``vr`` with ``high(v) == high(XOR(row_dirs[k] for bits
    k of vr))``, or None when ``high(v)`` escapes the span."""
    red: list = []                          # (high part, coordinate mask)
    for k, d in enumerate(row_dirs):
        hp, co = d >> t, 1 << k
        for rh, rc in red:
            if hp & (rh & -rh):
                hp ^= rh
                co ^= rc
        if hp:
            red.append((hp, co))
    h, coord = v >> t, 0
    for rh, rc in red:
        if h & (rh & -rh):
            h ^= rh
            coord ^= rc
    return coord if h == 0 else None


def compute_tables(plan: TilePlan, prefix: Bmmc,
                   kind: str) -> Optional[ComputeTables]:
    """Build the epilogue tables for one compute, or None if the compute
    is not tile-local under ``plan`` (pairing vector escapes the tile
    span — row directions plus the low lane bits)."""
    n, t = plan.n, plan.t
    dirs = plan.row_dirs
    tb = list(plan.tb_positions)
    low_mask = (1 << t) - 1

    v = pairing_vector(prefix)
    vr = _dir_coords(v, dirs, t)
    if vr is None:
        return None
    vc = v & low_mask   # slot lane == low bits of x, so the lane XOR is raw

    rowvec = prefix.rows[n - 1]            # row n-1 of A_M: hi(x) predicate
    cbit = (prefix.c >> (n - 1)) & 1
    rpt, row_len, n_tiles = plan.rows_per_tile, plan.row_len, plan.n_tiles
    hi_mask = ~low_mask  # slots address rows by direction HIGH parts only

    # hi(x) = <rowvec, x> is F2-linear, so it splits over the tile's
    # decomposition x = base_g ^ high(rowvec(r)) ^ lane: per-row (XOR of
    # direction high parts), per-lane, per-tile terms.
    hi_row = np.fromiter(
        (f2.parity(rowvec & (_xor_dirs(r, dirs) & hi_mask))
         for r in range(rpt)),
        dtype=np.int32, count=rpt)
    hi_lane = np.fromiter(
        (f2.parity(rowvec & c) for c in range(row_len)),
        dtype=np.int32, count=row_len)
    hi_base = np.fromiter(
        (f2.parity(rowvec & _scatter_bits(g, tb)) ^ cbit
         for g in range(n_tiles)),
        dtype=np.int32, count=n_tiles)

    tw_row = tw_lane = tw_base = None
    if kind == "bfly":
        twmask = (1 << (n - 1)) - 1        # pair index: m with bit n-1 dropped
        tw_row = np.fromiter(
            (f2.matvec(prefix.rows, _xor_dirs(r, dirs) & hi_mask) & twmask
             for r in range(rpt)), dtype=np.int32, count=rpt)
        tw_lane = np.fromiter(
            (f2.matvec(prefix.rows, c) & twmask for c in range(row_len)),
            dtype=np.int32, count=row_len)
        tw_base = np.fromiter(
            ((f2.matvec(prefix.rows, _scatter_bits(g, tb)) ^ prefix.c)
             & twmask for g in range(n_tiles)),
            dtype=np.int32, count=n_tiles)
    return ComputeTables(kind=kind, vr=vr, vc=vc, hi_row=hi_row,
                         hi_lane=hi_lane, hi_base=hi_base, tw_row=tw_row,
                         tw_lane=tw_lane, tw_base=tw_base)


@dataclasses.dataclass(frozen=True)
class PlanStats(_Descriptors):
    """Analytic plan statistics — O(n^2) bit math, no table enumeration.

    Matches TilePlan's n_over / rows_per_tile / n_tiles / in_run /
    out_run / in_box / out_box (property-tested against the enumerated
    tables), usable at paper scale (n = 30 => 2^20 tiles) where building
    per-tile tables is infeasible.
    """
    n: int
    t: int
    n_over: int
    n_tiles: int
    rows_per_tile: int
    row_len: int
    in_run: int
    out_run: int
    in_box: Optional[tuple]
    out_box: Optional[tuple]


def _out_run_bits(bmmc: Bmmc, t: int, out_side: tuple, tb: list) -> int:
    """log2 of the output DMA run: ``out_rows[g, r']`` is affine in the
    bits of ``r'``; runs of 2^k are consecutive iff bit i of r' moves
    y_high by exactly 2^i for i < k and no other contribution (higher
    slot bits, base bits, the first slot's offset and c) touches the
    low k bits of y_high."""
    out_dirs, out_off, _ = out_side
    deltas = [f2.matvec(bmmc.rows, v) >> t for v in out_dirs]
    others = [f2.matvec(bmmc.rows, 1 << pos) >> t for pos in tb]
    others.append((f2.matvec(bmmc.rows, out_off) ^ bmmc.c) >> t)
    k_out = 0
    while k_out < len(deltas):
        k = k_out + 1
        mask = (1 << k) - 1
        ok = all(deltas[i] == (1 << i) for i in range(k))
        ok = ok and all((d & mask) == 0 for d in deltas[k:])
        ok = ok and all((o & mask) == 0 for o in others)
        if not ok:
            break
        k_out = k
    return k_out


def plan_stats(bmmc: Bmmc, t: int) -> Optional[PlanStats]:
    """Analytic counterpart of ``plan_tiled`` (no per-tile enumeration)."""
    lay = _classic_layout(bmmc, t)
    if lay is None:
        return None
    _, n_over, row_pos, out_side, tb = lay
    # input-run: rows consecutive iff the low row positions are t, t+1, ...
    k_in = 0
    while k_in < len(row_pos) and row_pos[k_in] == t + k_in:
        k_in += 1
    k_out = _out_run_bits(bmmc, t, out_side, tb)
    in_box = _bit_runs(sum(1 << (p - t) for p in row_pos)) or None
    return PlanStats(n=bmmc.n, t=t, n_over=n_over, n_tiles=1 << len(tb),
                     rows_per_tile=1 << len(row_pos), row_len=1 << t,
                     in_run=1 << k_in, out_run=1 << k_out,
                     in_box=in_box, out_box=out_side[2])


def plan_stats_general(bmmc: Bmmc, t: int) -> Optional[PlanStats]:
    """Analytic counterpart of :func:`plan_general` (O(n^2) bit math)."""
    lay = _general_layout(bmmc, t)
    if lay is None:
        return None
    a, (row_dirs, in_box), out_side, tb = lay
    # input-run: in_rows[g, r] counts binarily in r iff high(row_dirs[i])
    # == 2^i for i < k and nothing else (higher dirs, tb base bits)
    # touches the low k row-id bits.
    hi = [v >> t for v in row_dirs]
    k_in = 0
    while k_in < len(hi):
        k = k_in + 1
        mask = (1 << k) - 1
        ok = all(hi[i] == (1 << i) for i in range(k))
        ok = ok and all((h & mask) == 0 for h in hi[k:])
        ok = ok and all((pos - t) >= k for pos in tb)
        if not ok:
            break
        k_in = k
    k_out = _out_run_bits(bmmc, t, out_side, tb)
    return PlanStats(n=bmmc.n, t=t, n_over=a, n_tiles=1 << len(tb),
                     rows_per_tile=1 << len(row_dirs), row_len=1 << t,
                     in_run=1 << k_in, out_run=1 << k_out,
                     in_box=in_box, out_box=out_side[2])


def stats_bmmc(bmmc: Bmmc, t: int) -> list:
    """Analytic stats for the tiled passes of an arbitrary BMMC: one
    (classic or generalized) pass whenever possible, the §5.2 two-pass
    factorization as the fallback."""
    s = plan_stats(bmmc, t)
    if s is not None:
        return [s]
    s = plan_stats_general(bmmc, t)
    if s is not None:
        return [s]
    out = []
    for factor in bmmc.factor_tiled(t):
        s = plan_stats(factor, t) or plan_stats_general(factor, t)
        if s is None:
            raise ValueError(f"factor expected tiled for t={t}")
        out.append(s)
    return out


def plan_bmmc(bmmc: Bmmc, t: int) -> list:
    """Plan an arbitrary BMMC as tiled passes: 1 via the classic witness
    columns (paper §5.1) or the generalized witness directions
    (:func:`plan_general`), else 2 via the §5.2 factorization (now only
    reachable for t > n/2, where the direction split may fall short)."""
    p = plan_tiled(bmmc, t)
    if p is not None:
        return [p]
    p = plan_general(bmmc, t)
    if p is not None:
        return [p]
    plans = []
    for factor in bmmc.factor_tiled(t):
        p = plan_tiled(factor, t) or plan_general(factor, t)
        if p is None:
            raise ValueError(f"factor expected to be tiled for t={t}: {factor}")
        plans.append(p)
    return plans


def pass_spans(bmmc: Bmmc, t: int) -> Optional[list]:
    """Per-pass tile spans of :func:`plan_bmmc`, without table enumeration.

    Each span is a tuple of generating direction vectors: a vector ``v``
    is tile-local for that pass iff ``v`` lies in the span — the
    membership check :mod:`repro.combinators.optimize` uses to decide
    whether a compute can ride the pass's tiles. The first pass's span
    is the MAXIMAL achievable one, ``ker(A[t:, :]) + low`` — the classic
    witness-column span is always contained in it, and the plan builder
    falls back to :func:`plan_general` (whose span IS the maximum) when
    a compute needs the extra room. Returns None when a pass's tile
    would exceed the array (t > n/2 with a deficient direction split).
    """
    n = bmmc.n
    if not 0 < t <= n:
        return None
    low = tuple(1 << j for j in range(t))

    def span_of(b: Bmmc) -> Optional[tuple]:
        a, row_dirs = _split_directions(b, t)
        if n - 2 * t + a < 0:
            return None
        return tuple(row_dirs) + low

    s = span_of(bmmc)
    if s is not None:
        return [s]
    spans = []
    for factor in bmmc.factor_tiled(t):
        s = span_of(factor)
        if s is None:
            return None
        spans.append(s)
    return spans


# ---------------------------------------------------------------------------
# Class fast-path plans (DESIGN.md §11). The simplest BMMC classes skip
# the tiled gather pipeline entirely:
#
# * block (tile-index-only): whole aligned 2^b blocks move wholesale —
#   a grid-remapped DMA copy, descriptor count identical to the
#   copy-through-VMEM roofline baseline.
# * lane (lane-local): rows stay in place and every row is permuted
#   identically — a single in-VMEM row gather, no transpose pass.
# ---------------------------------------------------------------------------

_COPY_BLOCK_BITS = 11   # log2(8 rows x 256 lanes): copy_through_vmem's block


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Grid-remapped DMA plan: output block ``g`` is input block
    ``src_rows[g]``, each block 2^b consecutive elements."""

    bmmc: Bmmc
    b: int                      # log2 elements per moved block
    src_rows: np.ndarray        # (2^(n-b),) int32

    @property
    def n(self) -> int:
        return self.bmmc.n

    @property
    def n_rows(self) -> int:
        return self.src_rows.shape[0]

    def dma_descriptors(self) -> int:
        """One read + one write per block — the copy kernel's count when
        ``b == _COPY_BLOCK_BITS``."""
        return 2 * self.n_rows

    def audit(self) -> "BlockPlan":
        """Guard ring-1 audit: ``src_rows`` a bounded permutation whose
        block map matches the BMMC. Raises
        :class:`repro.guard.DescriptorOOB`."""
        from ..guard.validate import audit_block_plan  # lazy: no cycle
        audit_block_plan(self)
        return self


@dataclasses.dataclass(frozen=True)
class LanePlan:
    """Single-pass in-VMEM row gather: ``out[row, lane] = x[row,
    src_lane[lane]]`` — rows never move, so there is no transpose pass."""

    bmmc: Bmmc
    t: int                      # log2 lanes per row
    src_lane: np.ndarray        # (2^t,) int32
    rows_per_block: int         # rows staged through VMEM per grid step

    @property
    def n(self) -> int:
        return self.bmmc.n

    @property
    def n_rows(self) -> int:
        return 1 << (self.n - self.t)

    def dma_descriptors(self) -> int:
        return 2 * (self.n_rows // self.rows_per_block)

    def audit(self) -> "LanePlan":
        """Guard ring-1 audit: ``src_lane`` a bounded permutation whose
        in-row gather matches the BMMC. Raises
        :class:`repro.guard.DescriptorOOB`."""
        from ..guard.validate import audit_lane_plan  # lazy: no cycle
        audit_lane_plan(self)
        return self


def _block_granularity(bmmc: Bmmc) -> int:
    """log2 elements per moved block: the class granularity capped at
    the copy baseline's block, so descriptor counts match
    ``copy_through_vmem`` exactly whenever the class allows it."""
    return min(bmmc.block_bits(), _COPY_BLOCK_BITS, bmmc.n - 1)


def _lane_rows_per_block(n: int, t: int) -> int:
    """Rows staged per grid step: a square 2^t x 2^t block (the tiled
    pass's VMEM budget), so the one-hot lane matrix is built once per
    2^t rows; all rows when the array has fewer."""
    return min(1 << (n - t), 1 << t)


def plan_block(bmmc: Bmmc, t: int) -> Optional[BlockPlan]:
    """Block-permute plan, or None if not tile-index-only at ``t``."""
    n = bmmc.n
    k = bmmc.block_bits()
    if not (0 < t <= k < n):
        return None
    b = _block_granularity(bmmc)
    # sub-BMMC on the high n-b bits (rows >= b read only columns >= b)
    sub_rows = tuple(bmmc.rows[i] >> b for i in range(b, n))
    sub = Bmmc(sub_rows, bmmc.c >> b)
    sub_inv = sub.inverse()
    src = np.fromiter((sub_inv.apply(g) for g in range(1 << (n - b))),
                      dtype=np.int32, count=1 << (n - b))
    return BlockPlan(bmmc=bmmc, b=b, src_rows=src)


def plan_lane(bmmc: Bmmc, t: int) -> Optional[LanePlan]:
    """Lane-permute plan, or None if not lane-local at ``t``."""
    n = bmmc.n
    if not bmmc.is_lane_local(t):
        return None
    low_mask = (1 << t) - 1
    sub = Bmmc(tuple(bmmc.rows[i] & low_mask for i in range(t)),
               bmmc.c & low_mask)
    sub_inv = sub.inverse()
    src = np.fromiter((sub_inv.apply(l) for l in range(1 << t)),
                      dtype=np.int32, count=1 << t)
    return LanePlan(bmmc=bmmc, t=t, src_lane=src,
                    rows_per_block=_lane_rows_per_block(n, t))


def copy_descriptors(n: int) -> int:
    """Modeled descriptor count of the copy-through-VMEM roofline
    baseline for a 2^n array: one read + one write per copy block."""
    return 2 * (1 << max(0, n - _COPY_BLOCK_BITS))


def dispatch_kernel(bmmc: Bmmc, t: int) -> str:
    """The kernel the class dispatch selects (DESIGN.md §11):

    ``none`` (identity), ``block`` (grid-remapped DMA, no gather),
    ``lane`` (single in-VMEM row gather), ``tiled`` (classic §5.1 one-
    pass), ``general`` (generalized witness-direction one-pass), or
    ``general2`` (§5.2 two-pass fallback, t > n/2 only).
    """
    cls = bmmc.bmmc_class(t)
    if cls == "identity":
        return "none"
    if cls == "complement":
        # a high-only complement moves whole blocks; a low-only one
        # permutes lanes; a mixed complement is a BPC -> one tiled pass
        low_part, high_part = bmmc.c & ((1 << t) - 1), bmmc.c >> t
        if low_part and high_part:
            return "tiled"
        return "block" if not low_part else "lane"
    if cls in ("block", "lane", "tiled"):
        return cls
    return "general" if plan_stats_general(bmmc, t) else "general2"


def class_stats(bmmc: Bmmc, t: int) -> dict:
    """Analytic per-class execution stats: the BMMC class, dispatched
    kernel, pass count, modeled DMA descriptors, and the copy-roofline
    ratio (copy descriptors / class descriptors; 1.0 == executes at the
    speed of an array copy, the paper's §2.3 reference point)."""
    n = bmmc.n
    cls = bmmc.bmmc_class(t)
    kernel = dispatch_kernel(bmmc, t)
    copy_desc = copy_descriptors(n)
    # block / lane counts are closed-form (no table enumeration — the
    # PlanStats principle: usable at paper scale, n = 30)
    if kernel == "none":
        desc, passes = 0, 0
    elif kernel == "block":
        desc, passes = 2 * (1 << (n - _block_granularity(bmmc))), 1
    elif kernel == "lane":
        desc = 2 * ((1 << (n - t)) // _lane_rows_per_block(n, t))
        passes = 1
    else:
        stats = stats_bmmc(bmmc, t)
        desc = sum(s.dma_descriptors() for s in stats)
        passes = len(stats)
    return {"class": cls, "kernel": kernel, "passes": passes,
            "descriptors": desc, "copy_descriptors": copy_desc,
            "roofline_ratio": copy_desc / max(desc, 1) if passes else 1.0}


# ---------------------------------------------------------------------------
# Naive-kernel transaction model (paper §6 "naive" column): each warp/DMA
# touches whatever segments its element mapping hits. On TPU a naive gather
# issues one descriptor per non-contiguous run; we count exact runs.
# ---------------------------------------------------------------------------

def naive_write_runs(bmmc: Bmmc, seg_elems: int, sample_tiles: int = 64) -> float:
    """Average # of distinct segments written per contiguous input segment.

    ``seg_elems`` plays the role of warp-width/segment (32 for the paper's
    GPU model; a lane-row for TPU). 1.0 == fully coalesced.
    """
    n = bmmc.n
    size = 1 << n
    segs = min(sample_tiles, size // seg_elems)
    total = 0
    rng = np.random.default_rng(0)
    starts = rng.choice(size // seg_elems, size=segs, replace=False)
    for s in starts:
        ys = {bmmc.apply(int(s) * seg_elems + i) // seg_elems for i in range(seg_elems)}
        total += len(ys)
    return total / segs
