"""Pallas TPU kernels for tiled BMMC permutations (paper §4-5, TPU-adapted).

Design (see DESIGN.md §2 for the GPU->TPU mapping, §10 for the fused
pipeline):

* The array lives in HBM as a (2^(n-t), 2^t[, d]) row view. The offline
  ``TilePlan`` guarantees both the rows read and the rows written by one
  *tile* (= ``rows_per_tile`` full rows) are whole, contiguous
  ``2^t``-element runs (the TPU analogue of full coalescing).
* Row id tables (``in_rows``/``out_rows``), the per-tile lane XOR and the
  intra-tile gather table ``src0`` are *offline* artifacts (scalar-prefetch /
  VMEM constants), mirroring the paper's offline codegen setting.
* A tile side (the rows read or the rows written) whose row ids form a
  *box* — the tile's first row with every combination of a fixed set of
  row-id bits (``TilePlan.in_box`` / ``out_box``) — is ONE strided DMA
  descriptor over an HBM view with one axis per run of those bits. Any
  other side merges consecutive row ids into one descriptor per
  ``in_run`` / ``out_run`` rows — the DMA analogue of the paper's §4.3
  iteration amortization.
* The intra-tile permutation is a flat VMEM gather
  ``out.flat[j] = tile.flat[src0[j ^ xor_low[g]]]`` — the per-tile XOR trick
  replaces per-thread index recomputation. The paper's shared-memory shift
  (§4.2, bank conflicts) has no TPU analogue and is intentionally not ported.
* One kernel invocation walks ALL tiles through a **double-buffered DMA
  pipeline**: tile ``g+1``'s input DMAs are launched while tile ``g``
  computes and drains, with ``num_buffers`` VMEM slots per direction
  (``num_buffers`` is part of :func:`plan_geometry`, so pipelined and
  unpipelined executables never share a cache entry).
* A **compute-epilogue hook**: a tuple of fused compute stages
  (min/max compare-exchange, twiddle butterfly, elementwise ``Map``)
  applied to the tile while it sits in VMEM, *before* the intra-tile
  gather — the kernel-side half of the fused-stage megakernel
  (:mod:`repro.combinators.optimize` ``cluster()``; DESIGN.md §10).
  Pair partners, lo/hi selection, and twiddle indices come from the
  offline :class:`repro.core.tiling.ComputeTables`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.tiling import TilePlan

_HBM = pltpu.MemorySpace.HBM
_VMEM = pltpu.MemorySpace.VMEM
# Scoped VMEM for one pass: two tile slots per direction plus the
# one-hot matrices and byte planes of the intra-tile steps (v5e holds
# 128 MiB; the compiler's default scope is 16 MiB).
_VMEM_LIMIT = 96 * 1024 * 1024
# Narrowest row, in lanes, the compiled kernels take (one vreg row).
MIN_LANES = 128


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly when the
    default backend is not a TPU. Every ``pallas_call`` in the package
    asks this one function, so the chip never runs the emulator."""
    return jax.default_backend() != "tpu"


def check_lanes(lanes: int) -> None:
    """Refuse a kernel row narrower than one vreg on the chip."""
    if lanes < MIN_LANES and not interpret_mode():
        from ..guard.errors import BadInput
        raise BadInput(
            f"a kernel row of {lanes} lanes is narrower than the "
            f"{MIN_LANES} a TPU vreg holds; permute arrays of at least "
            f"2^14 elements (t >= 7) on this backend")


def _compiler_params(n_grid: int):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * n_grid,
        vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------------------
# In-VMEM building blocks. Payloads move as int32 bit patterns (16- and
# 8-bit types widened), so every step is exact for any dtype, NaNs and
# signed zeros included.
# ---------------------------------------------------------------------------

def _to_bits(v: jax.Array) -> jax.Array:
    size = v.dtype.itemsize
    if size == 4:
        return (v if v.dtype == jnp.int32
                else jax.lax.bitcast_convert_type(v, jnp.int32))
    narrow = {2: jnp.int16, 1: jnp.int8}[size]
    if v.dtype != narrow:
        v = jax.lax.bitcast_convert_type(v, narrow)
    return v.astype(jnp.int32) & ((1 << (8 * size)) - 1)


def _from_bits(b: jax.Array, dtype) -> jax.Array:
    dtype = jnp.dtype(dtype)
    size = dtype.itemsize
    if size == 4:
        return b if dtype == jnp.int32 else jax.lax.bitcast_convert_type(
            b, dtype)
    narrow = {2: jnp.int16, 1: jnp.int8}[size]
    v = b.astype(narrow)
    return v if dtype == narrow else jax.lax.bitcast_convert_type(v, dtype)


def _parity(v: jax.Array, nbits: int) -> jax.Array:
    """Elementwise popcount mod 2 of the low ``nbits`` bits."""
    sh = 1
    while sh < nbits:
        sh <<= 1
    while sh > 1:
        sh >>= 1
        v = v ^ (v >> sh)
    return v & 1


def _xor_move(x: jax.Array, axis: int, shift: int) -> jax.Array:
    """``z[.., i, ..] = x[.., i ^ shift, ..]`` along ``axis`` for a
    power-of-two ``shift``: two rotations and a select. Which rotation
    serves index i is read off a rotated iota, so the result does not
    depend on the rotation's direction convention."""
    size = x.shape[axis]
    ishape = [1, 1]
    ishape[axis] = size
    idx = jax.lax.broadcasted_iota(jnp.int32, tuple(ishape), axis)
    fwd = pltpu.roll(idx, shift, axis) == (idx ^ shift)
    return jnp.where(fwd, pltpu.roll(x, shift, axis),
                     pltpu.roll(x, size - shift, axis))


def _onehot_apply(bits: jax.Array, mat: jax.Array, left: bool,
                  nbytes: int) -> jax.Array:
    """``mat @ bits`` (``left``) or ``bits @ mat`` for a 0/1 ``mat`` with
    one 1 per output, exactly: each byte plane is an integer below 256,
    which bf16 holds exactly, and the f32 accumulation adds one product."""
    acc = None
    for k in range(nbytes):
        plane = ((bits >> (8 * k)) & 255).astype(jnp.float32).astype(
            jnp.bfloat16)
        y = (jnp.dot(mat, plane, preferred_element_type=jnp.float32) if left
             else jnp.dot(plane, mat, preferred_element_type=jnp.float32))
        y = y.astype(jnp.int32) << (8 * k)
        acc = y if acc is None else acc | y
    return acc


class _TileIndex:
    """Row/lane index vectors of one (rows, row_len * d) tile; lane
    ``c * d + k`` holds component k of tile column c."""

    def __init__(self, rows: int, row_len: int, d: int):
        self.rows, self.row_len, self.d = rows, row_len, d
        self.p = rows.bit_length() - 1
        self.t = row_len.bit_length() - 1
        self.r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, row_len * d), 1)
        self.c = lane // d
        self.k = lane % d

    def row_xor(self, x: jax.Array, j: int) -> jax.Array:
        return _xor_move(x, 0, 1 << j)

    def lane_xor(self, x: jax.Array, i: int) -> jax.Array:
        # column XOR 2^i moves whole d-lane groups by d * 2^i lanes, down
        # where bit i of the column is set and up where it is clear; as
        # in _xor_move, a rotated iota says which rotation serves a lane
        shift = self.d << i
        lanes = x.shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
        src = lane + jnp.where(((self.c >> i) & 1) == 1, -shift, shift)
        return jnp.where(pltpu.roll(lane, shift, 1) == src,
                         pltpu.roll(x, shift, 1),
                         pltpu.roll(x, lanes - shift, 1))

    def partner(self, x: jax.Array, vr: int, vc: int) -> jax.Array:
        """``pv[r, c] = x[r ^ vr, c ^ vc]`` (static XORs)."""
        b = _to_bits(x)
        for j in range(self.p):
            if (vr >> j) & 1:
                b = self.row_xor(b, j)
        for i in range(self.t):
            if (vc >> i) & 1:
                b = self.lane_xor(b, i)
        return _from_bits(b, x.dtype)

    def permute(self, x: jax.Array, steps: tuple, words, kt) -> jax.Array:
        """The intra-tile gather of :class:`repro.core.tiling.IntraMap`:
        ``words`` is its SMEM parameter table, ``kt`` the tile's packed
        constant."""
        if not any(steps):
            return x
        p, t, d = self.p, self.t, self.d
        b = _to_bits(x)
        nbytes = x.dtype.itemsize

        def row_shear(b, off):
            for j in range(p):
                m = _parity(self.c & words[off + j], t) == 1
                b = jnp.where(m, self.row_xor(b, j), b)
            return b

        if steps[0]:
            b = row_shear(b, 0)
        if steps[1]:
            s = jax.lax.broadcasted_iota(jnp.int32, (1, self.rows), 1)
            src = jnp.zeros_like(s) ^ (kt >> t)
            for i in range(p):
                src = src ^ jnp.where(((s >> i) & 1) == 1, words[p + i], 0)
            q = jax.lax.broadcasted_iota(jnp.int32, (self.rows, self.rows),
                                         0) == src
            b = _onehot_apply(b, q.astype(jnp.bfloat16), True, nbytes)
        if steps[2]:
            src = jnp.zeros_like(self.c) ^ (kt & (self.row_len - 1))
            for i in range(t):
                src = src ^ jnp.where(((self.c >> i) & 1) == 1,
                                      words[2 * p + i], 0)
            src = src * d + self.k
            lanes = self.row_len * d
            q = jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 0) == src
            b = _onehot_apply(b, q.astype(jnp.bfloat16), False, nbytes)
        if steps[3]:
            for i in range(t):
                m = _parity(self.r & words[2 * p + t + i], p) == 1
                b = jnp.where(m, self.lane_xor(b, i), b)
        if steps[4]:
            b = row_shear(b, 2 * p + 2 * t)
        return _from_bits(b, x.dtype)

    def hi_mask(self, hi_row, hi_lane, base) -> jax.Array:
        return (hi_row[...] ^ hi_lane[...] ^ base) == 1

    def swap_planar(self, v: jax.Array) -> jax.Array:
        """Exchange the (re, im) lanes of every element (d == 2)."""
        return _from_bits(_xor_move(_to_bits(v), 1, 1), v.dtype)


def _epi_layout(epi: tuple) -> tuple:
    """(scalar-prefetch, VMEM, HBM) kernel args one epilogue entry takes:
    ``("cmp", vr, vc)`` -> hi_base | hi_row, hi_lane; ``("bfly", vr, vc,
    wlen)`` -> the same plus the per-tile twiddle stream in HBM;
    ``("map", name)`` -> nothing (the function itself is static)."""
    kind = epi[0]
    if kind == "cmp":
        return 1, 2, 0
    if kind == "bfly":
        return 1, 2, 1
    if kind == "map":
        return 0, 0, 0
    raise ValueError(f"unknown epilogue kind {kind!r}")


def _bfly(ti: _TileIndex, vals, pv, hi, w):
    """One planar butterfly on interleaved (re, im) lanes: the pair's
    ``lo +- w * hi`` with the same products and sums, in the same order,
    as the stage-at-a-time oracle."""
    even = ti.k == 0
    ws = ti.swap_planar(w)
    wr = jnp.where(even, w, ws)
    wi = jnp.where(even, ws, w)
    lo = jnp.where(hi, pv, vals)
    hv = jnp.where(hi, vals, pv)
    hs = ti.swap_planar(hv)
    tw = jnp.where(even, wr * hv - wi * hs, wr * hv + wi * hs)
    return jnp.where(hi, lo - tw, lo + tw)


def _bfly_t(ti: _TileIndex, ct, q, hi, w):
    """Transpose of :func:`_bfly`: ``ct0 + ct1`` on the lo slot,
    ``conj(w) (ct0 - ct1)`` on the hi slot."""
    even = ti.k == 0
    ws = ti.swap_planar(w)
    wr = jnp.where(even, w, ws)
    wi = jnp.where(even, ws, w)
    s = q - ct
    ss = ti.swap_planar(s)
    wt = jnp.where(even, wr * s + wi * ss, wr * s - wi * ss)
    return jnp.where(hi, wt, ct + q)


def _each(count: int, fn) -> None:
    """Run ``fn(i)`` for i < count: unrolled when short, a loop when not."""
    if count <= 8:
        for i in range(count):
            fn(i)
        return

    def body(i, carry):
        fn(i)
        return carry
    jax.lax.fori_loop(0, count, body, 0)


class _Epilogues:
    """Parsed per-epilogue refs of one pass (forward and gradient)."""

    def __init__(self, epis, scalars, vmem, hbm, wbuf, wsem, ti, g):
        self.epis, self.scalars, self.vmem, self.hbm = epis, scalars, vmem, hbm
        self.wbuf, self.wsem, self.ti, self.g = wbuf, wsem, ti, g

    def hi(self, k):
        return self.ti.hi_mask(self.vmem[k][0], self.vmem[k][1],
                               self.scalars[k][0][self.g])

    def twiddles(self, k):
        """Tile g's twiddle block of bfly ``k``, DMAed into VMEM."""
        cp = pltpu.make_async_copy(self.hbm[k][0].at[self.g], self.wbuf,
                                   self.wsem)
        cp.start()
        cp.wait()
        return self.wbuf[...]


def _box_axes(box: tuple, nbits: int) -> tuple:
    """``(lo, width, varies)`` per HBM axis of a box side's row view,
    highest row-id bits first: one axis per run of the box and one per
    stretch of fixed bits around them."""
    axes, top = [], nbits
    for lo, w in reversed(box):
        if top > lo + w:
            axes.append((lo + w, top - lo - w, False))
        axes.append((lo, w, True))
        top = lo
    if top:
        axes.append((0, top, False))
    return tuple(axes)


def _side_shapes(layout, n_rows: int, rpt: int) -> tuple:
    """(HBM row dims, VMEM slot row dims) of one tile side: a box
    (``layout`` a tuple of runs) splits the row id into
    :func:`_box_axes`, the slot into its runs; a plain side (``layout``
    the run of consecutive rows per descriptor) keeps one row axis."""
    if not isinstance(layout, tuple):
        return (n_rows,), (rpt,)
    axes = _box_axes(layout, n_rows.bit_length() - 1)
    return (tuple(1 << w for _, w, _ in axes),
            tuple(1 << w for _, w, v in axes if v))


class _Side:
    """The DMA descriptors that move one tile side between its HBM rows
    and a VMEM slot: one strided descriptor for a box, whose fixed axes
    index the tile's first row id (``rows[g, 0]``), else one per run of
    consecutive row ids."""

    def __init__(self, layout, rpt: int, n_rows: int, b):
        self.b = b   # batch index (batched kernels) or None
        if isinstance(layout, tuple):
            self.axes = _box_axes(layout, n_rows.bit_length() - 1)
            self.count = 1
        else:
            self.axes, self.run = None, layout
            self.count = rpt // layout

    def copy(self, hbm, rows, buf, sem, g, slot, i, *, write: bool):
        lead = () if self.b is None else (self.b,)
        if self.axes is None:
            far = hbm.at[lead + (pl.ds(rows[g, i * self.run], self.run),)]
            near = buf.at[slot, pl.ds(i * self.run, self.run)]
        else:
            r0 = rows[g, 0]
            far = hbm.at[lead + tuple(
                slice(None) if varies else (r0 >> lo) & ((1 << w) - 1)
                for lo, w, varies in self.axes)]
            near = buf.at[slot]
        src, dst = (near, far) if write else (far, near)
        return pltpu.make_async_copy(src, dst, sem.at[slot])


def _take_refs(refs, epis, nb_scalar_head: int, n_x: int):
    it = iter(refs)
    head = tuple(next(it) for _ in range(nb_scalar_head))
    scalars = [tuple(next(it) for _ in range(_epi_layout(e)[0]))
               for e in epis]
    xs = tuple(next(it) for _ in range(n_x))
    vmem, hbm = [], []
    for e in epis:
        _, nv, nh = _epi_layout(e)
        vmem.append(tuple(next(it) for _ in range(nv)))
        hbm.append(tuple(next(it) for _ in range(nh)))
    return head, scalars, xs, vmem, hbm, it


def _tile_kernel(*refs, rpt: int, row_len: int, d: int, n_rows: int,
                 in_layout, out_layout, batched: bool, n_tiles: int,
                 num_buffers: int, steps: tuple, epis: tuple,
                 map_fns: tuple):
    """The fused-stage megakernel: one invocation = all tiles of one pass.

    Ref layout (in pallas order): scalar prefetch ``in_rows, out_rows,
    ktab, words`` + per-epilogue per-tile scalars; inputs ``x_hbm`` +
    per-epilogue VMEM tables and HBM twiddle streams; output ``o_hbm``;
    scratch ``tiles, obuf`` (``num_buffers`` slots each), their DMA
    semaphores, and a twiddle block + semaphore when a butterfly rides.

    Pipeline schedule (``NB = num_buffers``)::

        start_in(0)
        for g in range(n_tiles):          # fori_loop, slot = g % NB
            start_in(g+1)                 # prefetch next tile  (NB > 1)
            wait_in(g)
            tile -> epilogues -> permute  # compute while g+1 is in flight
            wait_out(g - NB)              # slot's previous write drained?
            obuf[slot] = ...; start_out(g)
        wait_out(last NB tiles)           # drain

    ``batched=True`` adds a leading batch axis to the HBM row views and
    runs the whole pipeline once per batch element (grid = (B,)); the
    index tables (and therefore the tile geometry) are shared by every
    batch element.
    """
    nb = num_buffers
    (in_rows, out_rows, ktab, words), epi_scalar, (x_hbm,), epi_vmem, \
        epi_hbm, it = _take_refs(refs, epis, 4, 1)
    o_hbm = next(it)
    tiles, obuf, in_sems, out_sems = next(it), next(it), next(it), next(it)
    wbuf, wsem = (next(it), next(it)) if any(
        e[0] == "bfly" for e in epis) else (None, None)

    b = pl.program_id(0) if batched else None
    src = _Side(in_layout, rpt, n_rows, b)
    dst = _Side(out_layout, rpt, n_rows, b)

    # DMA descriptors are reconstructed at wait time (waiting only touches
    # the semaphore), so start/wait can live in different loop iterations.
    def in_copy(g, slot, i):
        return src.copy(x_hbm, in_rows, tiles, in_sems, g, slot, i,
                        write=False)

    def out_copy(g, slot, i):
        return dst.copy(o_hbm, out_rows, obuf, out_sems, g, slot, i,
                        write=True)

    def start_in(g):
        slot = jax.lax.rem(g, nb)
        _each(src.count, lambda i: in_copy(g, slot, i).start())

    def wait_in(g):
        slot = jax.lax.rem(g, nb)
        _each(src.count, lambda i: in_copy(g, slot, i).wait())

    def start_out(g):
        slot = jax.lax.rem(g, nb)
        _each(dst.count, lambda i: out_copy(g, slot, i).start())

    def wait_out(g):
        slot = jax.lax.rem(g, nb)
        _each(dst.count, lambda i: out_copy(g, slot, i).wait())

    ti = _TileIndex(rpt, row_len, d)

    def apply_computes(vals, g):
        """Fused compute stages on the in-VMEM tile (DESIGN.md §10).

        Each compare/butterfly pairs tile position (r, c) with
        (r ^ vr, c ^ vc); which element is the "hi" half is affine in the
        index, split into per-row/per-lane parity tables XORed with one
        per-tile scalar; butterfly twiddles stream in per tile.
        """
        ep = _Epilogues(epis, epi_scalar, epi_vmem, epi_hbm, wbuf, wsem,
                        ti, g)
        mi = 0
        for k, e in enumerate(epis):
            if e[0] == "map":
                vals = map_fns[mi](vals)
                mi += 1
                continue
            pv = ti.partner(vals, e[1], e[2])
            hi = ep.hi(k)
            if e[0] == "cmp":
                vals = jnp.where(hi, jnp.maximum(vals, pv),
                                 jnp.minimum(vals, pv))
            else:
                vals = _bfly(ti, vals, pv, hi, ep.twiddles(k))
        return vals

    def process(g):
        slot = jax.lax.rem(g, nb)
        wait_in(g)
        vals = tiles[slot].reshape(rpt, -1)
        if epis:
            vals = apply_computes(vals, g)
        permuted = ti.permute(vals, steps, words, ktab[g])

        @pl.when(g >= nb)  # slot's previous write must have drained
        def _():
            wait_out(g - nb)

        obuf[slot] = permuted.reshape(obuf.shape[1:])
        start_out(g)

    start_in(0)

    def body(g, carry):
        if nb > 1:
            @pl.when(g + 1 < n_tiles)
            def _():
                start_in(g + 1)  # prefetch overlaps tile g's compute+write
        else:
            @pl.when(g > 0)
            def _():
                start_in(g)      # unpipelined: sequential read-compute-write
        process(g)
        return carry

    jax.lax.fori_loop(0, n_tiles, body, 0)

    for k in range(min(nb, n_tiles)):  # drain the tail writes
        wait_out(n_tiles - 1 - k)


def _tile_bwd_kernel(*refs, rpt: int, row_len: int, d: int, n_rows: int,
                     in_layout, out_layout, batched: bool, n_tiles: int,
                     num_buffers: int, steps: tuple, epis: tuple,
                     map_fns: tuple):
    """The gradient megakernel: the exact transpose of one fused pass.

    Tile ``g`` reads the saved cluster input ``x`` at the forward's
    ``in_rows`` AND the cotangent at the forward's ``out_rows`` (where
    the forward wrote), then in VMEM (a) un-permutes the cotangent tile
    through the inverse intra-tile gather (``IntraMap`` of the inverse
    map: ``out[u] = pre[M u ^ K] ⇒ ct_pre[s] = ct_out[M⁻¹ s ^ M⁻¹ K]``),
    (b) replays the forward epilogue chain on the x tile to recover every
    intermediate, (c) applies the TRANSPOSED epilogues in reverse order —
    masks from the recomputed intermediates, the partner flip being its
    own transpose (involution) — and writes the result to ``in_rows``.
    One kernel invocation is therefore the whole cluster backward:
    ``ctᵢₙ = (B ∘ C̃m ∘ … ∘ C̃1)ᵀ ctₒᵤₜ``, the same DMA round trip count
    as the forward pass it mirrors.
    """
    nb = num_buffers
    (in_rows, out_rows, ktab, words), epi_scalar, (x_hbm, ct_hbm), \
        epi_vmem, epi_hbm, it = _take_refs(refs, epis, 4, 2)
    o_hbm = next(it)
    (xtiles, ctiles, obuf, in_sems, out_sems) = (
        next(it), next(it), next(it), next(it), next(it))
    wbuf, wsem = (next(it), next(it)) if any(
        e[0] == "bfly" for e in epis) else (None, None)

    b = pl.program_id(0) if batched else None
    fwd_in = _Side(in_layout, rpt, n_rows, b)
    fwd_out = _Side(out_layout, rpt, n_rows, b)

    def x_copy(g, slot, i):
        return fwd_in.copy(x_hbm, in_rows, xtiles, in_sems, g, slot, i,
                           write=False)

    def ct_copy(g, slot, i):
        return fwd_out.copy(ct_hbm, out_rows, ctiles, in_sems, g, slot, i,
                            write=False)

    def out_copy(g, slot, i):
        # the transpose WRITES where the forward READ: the input side
        return fwd_in.copy(o_hbm, in_rows, obuf, out_sems, g, slot, i,
                           write=True)

    def start_in(g):
        slot = jax.lax.rem(g, nb)
        _each(fwd_in.count, lambda i: x_copy(g, slot, i).start())
        _each(fwd_out.count, lambda i: ct_copy(g, slot, i).start())

    def wait_in(g):
        slot = jax.lax.rem(g, nb)
        _each(fwd_in.count, lambda i: x_copy(g, slot, i).wait())
        _each(fwd_out.count, lambda i: ct_copy(g, slot, i).wait())

    def start_out(g):
        slot = jax.lax.rem(g, nb)
        _each(fwd_in.count, lambda i: out_copy(g, slot, i).start())

    def wait_out(g):
        slot = jax.lax.rem(g, nb)
        _each(fwd_in.count, lambda i: out_copy(g, slot, i).wait())

    ti = _TileIndex(rpt, row_len, d)

    def forward_chain(vals, ep):
        """Replay the epilogues, keeping EVERY intermediate (the masks of
        the transposed compares come from the values each stage saw)."""
        us = [vals]
        mi = 0
        for k, e in enumerate(epis):
            if e[0] == "map":
                vals = map_fns[mi](vals)
                mi += 1
            elif e[0] == "cmp":
                pv = ti.partner(vals, e[1], e[2])
                hi = ep.hi(k)
                vals = jnp.where(hi, jnp.maximum(vals, pv),
                                 jnp.minimum(vals, pv))
            else:
                pv = ti.partner(vals, e[1], e[2])
                vals = _bfly(ti, vals, pv, ep.hi(k), ep.twiddles(k))
            us.append(vals)
        return us

    def transposed_epilogues(ct, us, ep):
        mi = len(map_fns)
        for k in range(len(epis) - 1, -1, -1):
            e = epis[k]
            u = us[k]
            if e[0] == "map":
                mi -= 1
                _, vjpf = jax.vjp(map_fns[mi], u)
                ct = vjpf(ct)[0]
                continue
            vr, vc = e[1], e[2]
            if e[0] == "cmp":
                # o = the forward's own output tile (us[k+1]); jax's
                # balanced-eq tie splitting: d = ct · 1{u==o}/(1+1{w==o}),
                # identical on both min/max branches GIVEN o, so the hi
                # mask drops out of the backward entirely
                o = us[k + 1]
                w = ti.partner(u, vr, vc)
                one = jnp.ones((), u.dtype)
                zero = jnp.zeros((), u.dtype)
                two = jnp.full((), 2, u.dtype)
                m1 = (jnp.where(u == o, one, zero)
                      / jnp.where(w == o, two, one))
                m2 = (jnp.where(w == o, one, zero)
                      / jnp.where(u == o, two, one))
                ct = ct * m1 + ti.partner(ct * m2, vr, vc)
            else:
                ct = _bfly_t(ti, ct, ti.partner(ct, vr, vc), ep.hi(k),
                             ep.twiddles(k))
        return ct

    def process(g):
        slot = jax.lax.rem(g, nb)
        wait_in(g)
        cv = ti.permute(ctiles[slot].reshape(rpt, -1), steps, words,
                        ktab[g])
        if epis:
            ep = _Epilogues(epis, epi_scalar, epi_vmem, epi_hbm, wbuf,
                            wsem, ti, g)
            xv = xtiles[slot].reshape(rpt, -1)
            cv = transposed_epilogues(cv, forward_chain(xv, ep), ep)

        @pl.when(g >= nb)
        def _():
            wait_out(g - nb)

        obuf[slot] = cv.reshape(obuf.shape[1:])
        start_out(g)

    start_in(0)

    def body(g, carry):
        if nb > 1:
            @pl.when(g + 1 < n_tiles)
            def _():
                start_in(g + 1)
        else:
            @pl.when(g > 0)
            def _():
                start_in(g)
        process(g)
        return carry

    jax.lax.fori_loop(0, n_tiles, body, 0)

    for k in range(min(nb, n_tiles)):
        wait_out(n_tiles - 1 - k)


def _pass_args(x, geometry, batched, epilogue, epi_scalar, epi_vmem):
    """Shared wrapper work of the forward and gradient passes: the HBM
    row views and VMEM slot shapes of the input and output sides
    (trailing ``d`` folded into the lanes), the per-epilogue kernel args
    and their specs, and the scratch the epilogues need.

    Rows are viewed as ``(rows, 1, lanes)`` in HBM and in the VMEM slots,
    the row axis split per :func:`_side_shapes`: a unit second-minor dim
    tiles as (1, 128), so a DMA may start at any single row, where the
    (8, 128) tiling of a 2-D view would demand 8-row alignment."""
    n, t, rpt, in_layout, out_layout, n_tiles, num_buffers, steps = geometry
    row_len = 1 << t
    lead = 1 if batched else 0
    d = x.shape[1 + lead] if x.ndim == 2 + lead else 1
    check_lanes(row_len * d)
    lanes = (1, row_len * d)
    batch = x.shape[:1] if batched else ()
    views, slots = [], []
    for layout in (in_layout, out_layout):
        hbm, slot = _side_shapes(layout, 1 << (n - t), rpt)
        views.append(batch + hbm + lanes)
        slots.append(pltpu.VMEM((num_buffers,) + slot + lanes, x.dtype))
    scal, vmem, specs = [], [], []
    for e, sc, vm in zip(epilogue, epi_scalar, epi_vmem):
        if e[0] == "map":
            continue
        scal.append(jnp.asarray(sc[0]))
        vmem.append(jnp.asarray(vm[0]).reshape(rpt, 1))
        vmem.append(jnp.repeat(jnp.asarray(vm[1]), d).reshape(1, -1))
        specs += [pl.BlockSpec(memory_space=_VMEM)] * 2
        if e[0] == "bfly":
            # the butterfly's twiddle per tile slot, gathered once per
            # call from the resident table and streamed tile by tile
            tw_row, tw_lane, w = vm[2], vm[3], jnp.asarray(vm[4], x.dtype)
            idx = (jnp.asarray(sc[1])[:, None, None]
                   ^ jnp.asarray(tw_row)[None, :, None]
                   ^ jnp.asarray(tw_lane)[None, None, :])
            vmem.append(jnp.take(w, idx, axis=0).reshape(
                n_tiles, rpt, row_len * 2))
            specs.append(pl.BlockSpec(memory_space=_HBM))
    scratch = []
    if any(e[0] == "bfly" for e in epilogue):
        scratch = [pltpu.VMEM((rpt, row_len * d), x.dtype),
                   pltpu.SemaphoreType.DMA(())]
    return views, slots, d, scal, vmem, specs, scratch


def tiled_permute_bwd_tables(x: jax.Array, ct: jax.Array, in_rows, out_rows,
                             ktab, words, *, geometry: tuple,
                             epilogue: tuple = (), epi_scalar: tuple = (),
                             epi_vmem: tuple = (), map_fns: tuple = (),
                             batched: bool = False) -> jax.Array:
    """The VJP of one fused tiled pass as ONE kernel invocation.

    ``x`` is the saved cluster input (masks of the transposed compares are
    recomputed from it in VMEM), ``ct`` the output-space cotangent;
    ``ktab``/``words`` the pass's INVERSE intra-tile tables
    (``plan.intra_inv``) and ``geometry`` its :func:`plan_geometry` with
    ``inverse=True``. Returns the input-space cotangent, same shape as
    ``x``. Mirrors :func:`tiled_permute_tables` exactly: same epilogue
    signature, same DMA pipeline depth.
    """
    n, t, rpt, in_layout, out_layout, n_tiles, num_buffers, steps = geometry
    (in_view, out_view), (in_slots, out_slots), d, scal, vmem, specs, \
        extra = _pass_args(x, geometry, batched, epilogue, epi_scalar,
                           epi_vmem)
    kern = functools.partial(
        _tile_bwd_kernel, rpt=rpt, row_len=1 << t, d=d, n_rows=1 << (n - t),
        in_layout=in_layout, out_layout=out_layout, batched=batched,
        n_tiles=n_tiles, num_buffers=num_buffers, steps=steps,
        epis=tuple(epilogue), map_fns=tuple(map_fns),
    )
    grid = (x.shape[0],) if batched else (1,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + len(scal),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=_HBM),   # x rows
                  pl.BlockSpec(memory_space=_HBM)]   # ct rows
        + specs,
        out_specs=pl.BlockSpec(memory_space=_HBM),
        scratch_shapes=[
            in_slots,    # x slots
            out_slots,   # ct slots
            in_slots,    # out slots: written where the forward read
            pltpu.SemaphoreType.DMA((num_buffers,)),
            pltpu.SemaphoreType.DMA((num_buffers,)),
        ] + extra,
    )
    args = [jnp.asarray(in_rows), jnp.asarray(out_rows), jnp.asarray(ktab),
            jnp.asarray(words)] + scal
    args += [x.reshape(in_view), ct.reshape(out_view)] + vmem
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(in_view, x.dtype),
        interpret=interpret_mode(),
        compiler_params=_compiler_params(len(grid)),
        name="bmmc_tile_bwd",
    )(*args)
    return out.reshape(x.shape)


def default_num_buffers(n_tiles: int) -> int:
    """2 (double buffering) whenever there is more than one tile."""
    return 1 if n_tiles == 1 else 2


def plan_geometry(plan: TilePlan, num_buffers: int = None, *,
                  inverse: bool = False) -> tuple:
    """The hashable tile geometry of a plan — everything that shapes the
    kernel *except* the per-stage index tables. Two plans with equal
    geometry can share one compiled kernel executable (tables are runtime
    arguments), which is what :mod:`repro.combinators.execute` exploits to
    amortize trace/compile cost across the stages of a fused program.
    ``num_buffers`` (the DMA pipeline depth) is part of the geometry so
    executables with different buffering never share a cache entry; so
    are the intra-tile steps in use (``plan.intra``, or ``plan.intra_inv``
    with ``inverse=True`` for the gradient kernel). Each side's layout
    is its box (``plan.in_box`` / ``out_box``, a tuple of runs) when it
    has one, else its run of consecutive rows per descriptor."""
    if num_buffers is None:
        num_buffers = default_num_buffers(plan.n_tiles)
    intra = plan.intra_inv if inverse else plan.intra
    return (plan.n, plan.t, plan.rows_per_tile, plan.in_box or plan.in_run,
            plan.out_box or plan.out_run, plan.n_tiles, num_buffers,
            intra.steps)


def geometry_descriptors(geometry: tuple) -> int:
    """HBM DMA descriptors that one pass of :func:`plan_geometry`
    ``geometry`` issues: per tile, each side's :class:`_Side` count."""
    n, t, rpt, in_layout, out_layout, n_tiles = geometry[:6]
    return n_tiles * sum(_Side(layout, rpt, 1 << (n - t), None).count
                         for layout in (in_layout, out_layout))


def plan_tables(plan: TilePlan, *, inverse: bool = False) -> tuple:
    """The runtime tables of one pass, in kernel order: ``(in_rows,
    out_rows, ktab, words)``."""
    intra = plan.intra_inv if inverse else plan.intra
    return plan.in_rows, plan.out_rows, intra.ktab, intra.words


def tiled_permute_tables(x: jax.Array, in_rows, out_rows, ktab, words, *,
                         geometry: tuple, epilogue: tuple = (),
                         epi_scalar: tuple = (), epi_vmem: tuple = (),
                         map_fns: tuple = (),
                         batched: bool = False) -> jax.Array:
    """One tiled-BMMC pass with the index tables as (traced) arguments.

    ``geometry`` is :func:`plan_geometry` output and the tables are
    :func:`plan_tables` output; tables may be jax arrays, so this
    function traces once per geometry under ``jax.jit``.

    ``epilogue`` is the static fused-compute signature (tuple of
    ``("cmp", vr, vc)`` / ``("bfly", vr, vc, wlen)`` / ``("map", name)``
    entries); ``epi_scalar`` / ``epi_vmem`` carry the matching runtime
    tables, one tuple per entry — ``(hi_base,) | (hi_row, hi_lane)`` for
    a compare, ``(hi_base, tw_base) | (hi_row, hi_lane, tw_row, tw_lane,
    w)`` for a butterfly — and ``map_fns`` the ``Map`` callables in
    order. The epilogue signature must be part of any executable cache
    key alongside ``geometry``.

    ``batched=True`` accepts a leading batch axis — ``(B, 2^n)`` or
    ``(B, 2^n, d)`` — folded into the HBM row view as ``(B, 2^(n-t), 1,
    2^t * d)`` and into the grid as ``(B,)``. Geometry (and hence the
    compiled kernel cache key) is independent of B; only the jit retrace,
    not the plan, depends on the batch size.
    """
    n, t, rpt, in_layout, out_layout, n_tiles, num_buffers, steps = geometry
    (in_view, out_view), (in_slots, out_slots), d, scal, vmem, specs, \
        extra = _pass_args(x, geometry, batched, epilogue, epi_scalar,
                           epi_vmem)
    kern = functools.partial(
        _tile_kernel, rpt=rpt, row_len=1 << t, d=d, n_rows=1 << (n - t),
        in_layout=in_layout, out_layout=out_layout, batched=batched,
        n_tiles=n_tiles, num_buffers=num_buffers, steps=steps,
        epis=tuple(epilogue), map_fns=tuple(map_fns),
    )
    grid = (x.shape[0],) if batched else (1,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + len(scal),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=_HBM)] + specs,
        out_specs=pl.BlockSpec(memory_space=_HBM),
        scratch_shapes=[
            in_slots,
            out_slots,
            pltpu.SemaphoreType.DMA((num_buffers,)),
            pltpu.SemaphoreType.DMA((num_buffers,)),
        ] + extra,
    )
    args = [jnp.asarray(in_rows), jnp.asarray(out_rows), jnp.asarray(ktab),
            jnp.asarray(words)] + scal + [x.reshape(in_view)] + vmem
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_view, x.dtype),
        interpret=interpret_mode(),
        compiler_params=_compiler_params(len(grid)),
        name="bmmc_tile",
    )(*args)
    return out.reshape(x.shape)


def _trap_tables(pairs, x) -> None:
    """Host-side descriptor trap at the kernel-launch boundary: when
    guards are on and the plan tables are still concrete (numpy, not
    traced runtime arguments), refuse to launch a kernel whose gather /
    DMA tables address outside their geometry. This is the last line
    before a poisoned table becomes a baked trace constant; the traced
    twin of the same check lives in :mod:`repro.guard.runtime`
    (DESIGN.md §14, ring 2)."""
    from .. import guard as _g
    if not _g.enabled():
        return
    from ..guard import runtime as _grt
    if not _grt._trace_state_clean(x):
        # under a trace (incl. ring 2's own guarded executable) the
        # in-program OOB flag owns this check — raising here would
        # preempt the trap → fallback machinery
        return
    from ..guard.errors import DescriptorOOB
    for name, tab, hi in pairs:
        if not isinstance(tab, np.ndarray):
            continue  # traced table: the in-program OOB trap covers it
        if tab.size and (int(tab.min()) < 0 or int(tab.max()) >= hi):
            raise DescriptorOOB(
                f"kernel launch refused: table {name!r} addresses "
                f"[{int(tab.min())}, {int(tab.max())}] outside [0, {hi})")


def tiled_permute(x: jax.Array, plan: TilePlan, *,
                  batched: bool = False) -> jax.Array:
    """Apply one tiled-BMMC pass. ``x``: (2^n,) or (2^n, d); with
    ``batched=True``, (B, 2^n) or (B, 2^n, d)."""
    n_rows = 1 << (plan.n - plan.t)
    _trap_tables([("in_rows", plan.in_rows, n_rows),
                  ("out_rows", plan.out_rows, n_rows),
                  ("xor_low", plan.xor_low, plan.row_len),
                  ("src0", plan.src0, plan.rows_per_tile * plan.row_len)], x)
    return tiled_permute_tables(x, *plan_tables(plan),
                                geometry=plan_geometry(plan), batched=batched)


# ---------------------------------------------------------------------------
# Class fast-path kernels (DESIGN.md §11). The simplest BMMC classes do
# not need the two-buffer gather pipeline at all:
#
# * block-permute: whole 2^b-element blocks move wholesale. The kernel
#   is a copy whose *input grid mapping* is remapped through the offline
#   source-row table (scalar prefetch feeding the BlockSpec index_map) —
#   pallas's own pipeline double-buffers the DMAs, there is no intra-
#   tile gather, and the descriptor count equals `copy_through_vmem`'s.
# * lane-permute: rows never move; each row is permuted in place by the
#   same t-bit map. One pass, an in-VMEM one-hot matmul along the lanes,
#   no transpose pass.
# ---------------------------------------------------------------------------


def _block_kernel(src_ref, x_ref, o_ref):
    del src_ref  # consumed by the index_map; the body is a pure copy
    o_ref[...] = x_ref[...]


def block_permute_tables(x: jax.Array, src_rows, *, geometry: tuple,
                         batched: bool = False) -> jax.Array:
    """Grid-remapped DMA copy: output block ``g`` reads input block
    ``src_rows[g]``. ``geometry`` is :func:`block_geometry` output. A
    block of ``2^b * d`` elements is viewed as (sublanes, 128 lanes)
    when it fills whole lane rows, so the block shape is a whole tile."""
    n, b, n_rows = geometry
    lead = 1 if batched else 0
    d = x.shape[1 + lead] if x.ndim == 2 + lead else 1
    blk = (1 << b) * d
    check_lanes(blk)
    inner = (blk // 128, 128) if blk % 128 == 0 else (1, blk)
    row_view = (n_rows,) + inner
    if batched:
        row_view = (x.shape[0],) + row_view
    blk_shape = ((1,) if batched else ()) + (1,) + inner

    if batched:
        def in_map(bi, i, src_ref):
            return (bi, src_ref[i], 0, 0)

        def out_map(bi, i, src_ref):
            return (bi, i, 0, 0)
        grid = (x.shape[0], n_rows)
    else:
        def in_map(i, src_ref):
            return (src_ref[i], 0, 0)

        def out_map(i, src_ref):
            return (i, 0, 0)
        grid = (n_rows,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec(blk_shape, in_map)],
        out_specs=pl.BlockSpec(blk_shape, out_map),
    )
    out = pl.pallas_call(
        _block_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(row_view, x.dtype),
        interpret=interpret_mode(),
        compiler_params=_compiler_params(len(grid)),
        name="bmmc_block",
    )(jnp.asarray(src_rows), x.reshape(row_view))
    return out.reshape(x.shape)


def block_geometry(plan) -> tuple:
    """Hashable kernel geometry of a :class:`repro.core.tiling.BlockPlan`."""
    return (plan.n, plan.b, plan.n_rows)


def block_permute(x: jax.Array, plan, *, batched: bool = False) -> jax.Array:
    _trap_tables([("src_rows", plan.src_rows, plan.n_rows)], x)
    return block_permute_tables(x, plan.src_rows,
                                geometry=block_geometry(plan),
                                batched=batched)


def lane_permute_tables(x: jax.Array, src_lane, *, geometry: tuple,
                        batched: bool = False) -> jax.Array:
    """Single-pass in-VMEM row permutation: ``out[.., row, lane] =
    x[.., row, src_lane[lane]]``, applied as an exact one-hot matmul on
    the byte planes. ``geometry`` is :func:`lane_geometry` output."""
    n, t, rpb = geometry
    row_len = 1 << t
    n_rows = 1 << (n - t)
    lead = 1 if batched else 0
    d = x.shape[1 + lead] if x.ndim == 2 + lead else 1
    lanes = row_len * d
    check_lanes(lanes)
    row_view = (n_rows, lanes)
    if batched:
        row_view = (x.shape[0],) + row_view
    blk_shape = ((1,) if batched else ()) + (rpb, lanes)
    # source lane of every (column, component) lane
    src = (jnp.asarray(src_lane)[:, None] * d
           + jnp.arange(d, dtype=jnp.int32)[None, :]).reshape(1, lanes)

    def kern(src_ref, x_ref, o_ref):
        onehot = jax.lax.broadcasted_iota(
            jnp.int32, (lanes, lanes), 0) == src_ref[...]
        v = x_ref[...].reshape(rpb, lanes)
        out = _onehot_apply(_to_bits(v), onehot.astype(jnp.bfloat16), False,
                            v.dtype.itemsize)
        o_ref[...] = _from_bits(out, v.dtype).reshape(o_ref.shape)

    if batched:
        def blk_map(bi, i):
            return (bi, i, 0)
        grid = (x.shape[0], n_rows // rpb)
    else:
        def blk_map(i):
            return (i, 0)
        grid = (n_rows // rpb,)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=_VMEM),
                  pl.BlockSpec(blk_shape, blk_map)],
        out_specs=pl.BlockSpec(blk_shape, blk_map),
        out_shape=jax.ShapeDtypeStruct(row_view, x.dtype),
        interpret=interpret_mode(),
        compiler_params=_compiler_params(len(grid)),
        name="bmmc_lane",
    )(src, x.reshape(row_view))
    return out.reshape(x.shape)


def lane_geometry(plan) -> tuple:
    """Hashable kernel geometry of a :class:`repro.core.tiling.LanePlan`."""
    return (plan.n, plan.t, plan.rows_per_block)


def lane_permute(x: jax.Array, plan, *, batched: bool = False) -> jax.Array:
    _trap_tables([("src_lane", plan.src_lane, 1 << plan.t)], x)
    return lane_permute_tables(x, plan.src_lane,
                               geometry=lane_geometry(plan),
                               batched=batched)


# ---------------------------------------------------------------------------
# Baseline copy kernel — the "100% effective bandwidth" reference in the
# paper's tables (§2.3, §6). Same DMA structure, identity permutation.
# ---------------------------------------------------------------------------

def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def copy_pad_elems(size: int, rows_per_block: int = 8,
                   row_len: int = 256) -> int:
    """Elements of zero padding :func:`copy_through_vmem` appends so the
    array divides into whole blocks (0 = exact fit). Benchmarks label
    padded baselines with this, so a padded copy is never mistaken for a
    pure roofline number."""
    blk = rows_per_block * row_len
    return (-size) % blk


def copy_through_vmem(x: jax.Array, *, rows_per_block: int = 8,
                      row_len: int = 256) -> jax.Array:
    """Block copy staged through VMEM; the bandwidth roofline baseline.

    Sizes that don't divide into whole (rows_per_block, row_len) blocks
    are zero-padded up, copied through the same Pallas kernel, and
    sliced back — the degenerate path always enters pallas, so the
    roofline baseline stays honest (use :func:`copy_pad_elems` to label
    padded measurements).
    """
    total = x.size
    blk = rows_per_block * row_len
    pad = copy_pad_elems(total, rows_per_block, row_len)
    flat = x.reshape(-1)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    nblk = (total + pad) // blk
    xv = flat.reshape(nblk, rows_per_block, row_len)
    out = pl.pallas_call(
        _copy_kernel,
        grid=(nblk,),
        in_specs=[pl.BlockSpec((1, rows_per_block, row_len), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, rows_per_block, row_len), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(xv.shape, x.dtype),
        interpret=interpret_mode(),
        name="bmmc_copy",
    )(xv)
    return out.reshape(-1)[:total].reshape(x.shape)
