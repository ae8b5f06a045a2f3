"""Public BMMC permutation ops: planning, class dispatch, jit wrappers.

``bmmc_permute`` is the user-facing entry point. Dispatch walks the
class hierarchy most-specialized-first (DESIGN.md §11):

* degenerate / tiny arrays                -> pure-jnp gather (ref oracle);
* identity                                -> no-op;
* tile-index-only (incl. high complement) -> block-permute fast path
                                             (grid-remapped DMA copy);
* lane-local (incl. low complement)       -> lane-permute fast path
                                             (single in-VMEM row gather);
* tiled BMMC (incl. every BPC)            -> one tiled Pallas pass;
* general BMMC                            -> ONE generalized tiled pass
                                             (witness directions), with
                                             the §5.2 two-pass
                                             factorization as fallback.

The BMMC is a *trace-time constant* (offline setting, paper §3/§6): plans
and tables are built once per (matrix, shape) and cached.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bmmc import Bmmc
from ..core.tiling import (class_stats, copy_descriptors, dispatch_kernel,
                           plan_block, plan_bmmc, plan_lane)
from ..obs import metrics as _ometrics
from ..obs import trace as _otrace
from . import ref as _ref
from .bmmc_permute import (block_geometry, block_permute,
                           block_permute_tables, check_lanes,
                           geometry_descriptors, lane_geometry, lane_permute,
                           lane_permute_tables, plan_geometry, plan_tables,
                           tiled_permute, tiled_permute_tables)

# VMEM working-set budget for one tile buffer. The double-buffered pipeline
# holds 2 * num_buffers tile-sized slots (in + out, default num_buffers=2);
# v5e has 16 MiB VMEM, leave headroom for the gather table + epilogue tables.
_VMEM_TILE_BYTES = 2 * 1024 * 1024
_MAX_T = 12


def choose_tile(n: int, itemsize: int, d: int = 1, t: Optional[int] = None) -> Optional[int]:
    """Pick n_tile: the LARGEST t whose worst-case (2^t x 2^t) tile fits the
    per-buffer VMEM budget (perf iteration: kernel-hillclimb #1 —
    descriptor-issue, not bandwidth, bounds scattered-bit permutations, and
    descriptors fall 4x per +1 of t; the paper's warp-sized t=5 is far off
    the TPU optimum).

    Returns None if the array is too small to be worth tiling (fallback to
    the reference gather — the whole array fits in VMEM anyway).
    """
    if t is not None:
        return t if 2 * t <= n else None
    t = _MAX_T
    # fit (2^t x 2^t) worst-case tile (n_over = 0) in the VMEM budget
    while t > 1 and (1 << (2 * t)) * itemsize * d > _VMEM_TILE_BYTES:
        t -= 1
    t = min(t, n // 2)
    if t < 1:
        return None
    return t


@functools.lru_cache(maxsize=512)
def _plans_cached(rows: tuple, c: int, t: int) -> tuple:
    return tuple(plan_bmmc(Bmmc(rows, c), t))


def _build_class_plan(rows: tuple, c: int, t: int) -> tuple:
    """Plan from scratch (the store's ``build`` rung): derive the class
    dispatch and construct its payload tables."""
    bmmc = Bmmc(rows, c)
    kernel = dispatch_kernel(bmmc, t)
    if kernel == "none":
        return (kernel, ())
    if kernel == "block":
        return (kernel, plan_block(bmmc, t))
    if kernel == "lane":
        return (kernel, plan_lane(bmmc, t))
    return (kernel, _plans_cached(rows, c, t))


@functools.lru_cache(maxsize=512)
def _class_plan_cached(rows: tuple, c: int, t: int) -> tuple:
    """(kernel name, plan payload) for the class dispatch — the offline
    decision shared by `bmmc_permute` and the combinator executor. The
    payload is the fast-path plan for "block"/"lane", the tiled pass
    tuple otherwise. Backed by the durable plan store when one is
    configured (``REPRO_STORE``): a disk hit is decoded and re-audited
    through guard ring 1 before it is trusted; integrity failures
    quarantine the entry and fall through to fresh planning."""
    from .. import store as _store

    def build():
        with _otrace.span("plan.class", n=len(rows), t=t):
            return _build_class_plan(rows, c, t)

    return _store.class_plan_through(rows, c, t, build)


def bmmc_plans(bmmc: Bmmc, t: int):
    return _plans_cached(bmmc.rows, bmmc.c, t)


def class_plan(bmmc: Bmmc, t: int) -> tuple:
    """Class-dispatch decision: ``(kernel, payload)``; see
    :func:`repro.core.tiling.dispatch_kernel` for the kernel names."""
    return _class_plan_cached(bmmc.rows, bmmc.c, t)


def class_dispatch(x: jax.Array, bmmc: Bmmc, t: Optional[int],
                   batched: bool) -> Optional[tuple]:
    """The full class-dispatch decision for this array: ``(kernel,
    payload)``, or None when the array is too small to tile (callers
    fall back to the reference gather).

    This is the executor stack's single dispatch-decision choke point,
    so telemetry hangs here: one ``kernel.dispatch`` span plus the
    per-kernel / per-class counters and the modeled descriptor /
    round-trip totals — recorded at dispatch/trace time, from offline
    plans, with no device interaction."""
    lead = 1 if batched else 0
    d = x.shape[1 + lead] if x.ndim == 2 + lead else 1
    teff = choose_tile(bmmc.n, x.dtype.itemsize, d, t)
    if teff is None:
        # the interpreter takes the gather oracle; the chip refuses
        check_lanes(d)
        return None
    if not _otrace._state.enabled:
        return class_plan(bmmc, teff)
    with _otrace.span("kernel.dispatch", n=bmmc.n, t=teff) as sargs:
        got = class_plan(bmmc, teff)
        sargs["kernel"] = got[0]
        tx = modeled_transactions(bmmc, teff, x.dtype.itemsize)
        tiled = got[0] not in ("none", "block", "lane")
        record_dispatch(got[0], bmmc.bmmc_class(teff), tx["descriptors"],
                        tx["passes"], got[1] if tiled else ())
    return got


def record_dispatch(kernel: str, cls: str, descriptors: int,
                    round_trips: int, plans=(), geometries=()) -> None:
    """The counters of one dispatch decision: ``dispatch.kernel``,
    ``dispatch.class``, the modeled ``dma.descriptors`` and
    ``model.round_trips``, and the box sides of its tiled passes, given
    as plans or as their :func:`plan_geometry`."""
    _ometrics.inc("dispatch.kernel", kernel=kernel)
    _ometrics.inc("dispatch.class", cls=cls)
    _ometrics.inc("dma.descriptors", descriptors)
    _ometrics.inc("model.round_trips", round_trips)
    count_box_sides(plans, geometries)


def count_box_sides(plans=(), geometries=()) -> None:
    """``dma.box_sides{side}``: one count per side of each tiled pass
    whose rows form a box (one strided descriptor per tile). A plan's
    box is ``in_box`` / ``out_box``; a geometry's, a side whose layout
    is a tuple of runs."""
    layouts = [(p.in_box, p.out_box) for p in plans]
    layouts += [g[3:5] for g in geometries]
    for pair in layouts:
        for side, layout in zip(("in", "out"), pair):
            if isinstance(layout, tuple) and layout:
                _ometrics.inc("dma.box_sides", side=side)


def bmmc_permute(x: jax.Array, bmmc: Bmmc, *, t: Optional[int] = None,
                 engine: str = "pallas", batched: bool = False) -> jax.Array:
    """Permute ``x`` (shape (2^n,) or (2^n, d)) by ``out[A i ^ c] = x[i]``.

    ``engine``: "pallas" (class-dispatched kernels) or "ref" (pure-jnp
    oracle). ``batched=True`` shifts the permuted axis to axis 1 — ``x``
    is ``(B, 2^n)`` or ``(B, 2^n, d)`` and all batch rows share one plan.
    """
    lead = 1 if batched else 0
    assert x.shape[lead] == bmmc.size, (x.shape, bmmc.n)
    from .. import guard as _guard
    if _guard.enabled() and engine in ("pallas", "ref"):
        from ..guard import runtime as _grt
        if _grt._trace_state_clean(x):
            # ring 2: guarded twin — kernel + probes in one executable,
            # flag readback + pallas → ref fallback at this edge. Under
            # an outer trace the readback is impossible; fall through.
            return _grt.guarded_bmmc_permute(
                x, bmmc, t=t, engine=engine, batched=batched)
    if engine == "ref":
        return _ref.bmmc_ref(x, bmmc, batched=batched)
    if bmmc.is_identity_perm():
        _ometrics.inc("dispatch.kernel", kernel="none")
        return x
    got = class_dispatch(x, bmmc, t, batched)
    if got is None:
        return _ref.bmmc_ref(x, bmmc, batched=batched)
    kernel, payload = got
    if kernel == "block":
        return block_permute(x, payload, batched=batched)
    if kernel == "lane":
        return lane_permute(x, payload, batched=batched)
    for plan in payload:
        x = tiled_permute(x, plan, batched=batched)
    return x


def _shared_geometry(passes) -> tuple:
    """One :func:`plan_geometry` that serves every plan of ``passes``
    (one pass of one matrix under different complements, which move
    row ids but not the tile structure). A side keeps its box where
    every plan has that box, else takes the shortest run any plan has:
    runs are powers of two, so a shorter run is also a run of every
    longer one. An intra-tile step runs where any plan needs it; with
    its identity parameters it moves nothing."""
    geoms = [plan_geometry(p) for p in passes]
    g0 = geoms[0]
    assert all(g[:3] + g[5:7] == g0[:3] + g0[5:7] for g in geoms), geoms
    sides = tuple(
        g0[k] if len({g[k] for g in geoms}) == 1 else min(runs)
        for k, runs in ((3, [p.in_run for p in passes]),
                        (4, [p.out_run for p in passes])))
    steps = tuple(any(on) for on in zip(*(g[7] for g in geoms)))
    return g0[:3] + sides + g0[5:7] + (steps,)


def _pick(tables, index) -> jax.Array:
    """``tables[index]`` of stacked equal-shape tables, ``index`` traced."""
    stack = jnp.asarray(np.stack([np.asarray(v) for v in tables]))
    return jax.lax.dynamic_index_in_dim(stack, index, 0, keepdims=False)


def bmmc_permute_complements(x: jax.Array, rows: tuple, cs: tuple,
                             index: jax.Array) -> jax.Array:
    """``out[A i ^ cs[index]] = x[i]`` with ``index`` traced: one
    kernel whose tables, one set per complement, are stacked and
    chosen by ``index`` on the device. A sharded program passes its
    shard's index, so every shard runs the same compiled kernel.

    The complements share the class dispatch of ``rows`` except where
    ``rows`` is the identity (none, block, lane or tiled by the bits
    of ``c``); those take the tiled pass. The ``dispatch.*`` and
    ``dma.*`` counters record the one kernel."""
    if len(set(cs)) == 1:
        return bmmc_permute(x, Bmmc(rows, cs[0]))
    n = len(rows)
    d = x.shape[1] if x.ndim == 2 else 1
    t = choose_tile(n, x.dtype.itemsize, d)
    if t is None:
        check_lanes(d)
        return jax.lax.switch(index, [functools.partial(
            _ref.bmmc_ref, bmmc=Bmmc(rows, c)) for c in cs], x)
    got = [class_plan(Bmmc(rows, c), t) for c in cs]
    kernel = got[0][0]
    if any(k != kernel for k, _ in got):
        kernel, got = "tiled", [("tiled", _plans_cached(rows, c, t))
                                for c in cs]
    payloads = [p for _, p in got]
    geoms = [] if kernel in ("block", "lane") else [
        _shared_geometry(passes) for passes in zip(*payloads)]
    if _otrace._state.enabled:
        descriptors = (sum(geometry_descriptors(g) for g in geoms) if geoms
                       else payloads[0].dma_descriptors())
        record_dispatch(kernel, Bmmc(rows, cs[0]).bmmc_class(t),
                        descriptors, max(len(geoms), 1), geometries=geoms)
    if kernel == "block":
        return block_permute_tables(
            x, _pick([p.src_rows for p in payloads], index),
            geometry=block_geometry(payloads[0]))
    if kernel == "lane":
        return lane_permute_tables(
            x, _pick([p.src_lane for p in payloads], index),
            geometry=lane_geometry(payloads[0]))
    for k, geometry in enumerate(geoms):
        tables = zip(*(plan_tables(passes[k]) for passes in payloads))
        x = tiled_permute_tables(x, *(_pick(v, index) for v in tables),
                                 geometry=geometry)
    return x


def num_passes(bmmc: Bmmc, t: int) -> int:
    """1 for every BMMC the one-pass planners take (tiled, generalized);
    2 only for the §5.2 fallback (t > n/2)."""
    return len(bmmc_plans(bmmc, t))


def make_bmmc_permute(bmmc: Bmmc, *, t: Optional[int] = None,
                      engine: str = "pallas"):
    """Returns a jit-compiled unary function specialized to ``bmmc``."""
    @jax.jit
    def fn(x):
        return bmmc_permute(x, bmmc, t=t, engine=engine)
    return fn


# ---------------------------------------------------------------------------
# Transaction model — the offline counterpart of the paper's effective-
# bandwidth measurements: descriptor and byte counts derived from the
# plans, valid on any backend. Times come only from runs on the chip
# (DESIGN.md §7.4).
# ---------------------------------------------------------------------------

def modeled_transactions(bmmc: Bmmc, t: int, itemsize: int = 4) -> dict:
    """DMA descriptor counts + bytes for the class-dispatched kernel vs a
    copy. ``class``/``kernel``/``roofline_ratio`` report the dispatch
    decision and the modeled fraction of copy-kernel descriptor
    throughput (1.0 == the permutation costs exactly an array copy)."""
    n = bmmc.n
    nbytes = (1 << n) * itemsize
    cs = class_stats(bmmc, t)
    passes = max(cs["passes"], 0)
    kernel, payload = class_plan(bmmc, t)
    if kernel in ("none", "block", "lane"):
        total_desc = cs["descriptors"]
        min_run_bytes = nbytes if kernel == "none" else (
            (1 << payload.b) * itemsize if kernel == "block"
            else payload.rows_per_block * (1 << payload.t) * itemsize)
    else:
        plans = payload
        total_desc = sum(p.dma_descriptors() for p in plans)
        min_run = min(min(p.in_run, p.out_run) for p in plans)
        min_run_bytes = min_run * (1 << t) * itemsize
    return {
        "class": cs["class"],
        "kernel": kernel,
        "passes": passes,
        "descriptors": total_desc,
        # copy baseline at the tiled row view (legacy key) and at the
        # copy kernel's own block size (what roofline_ratio uses)
        "copy_descriptors": 2 * (1 << (n - t)),
        "roofline_ratio": (copy_descriptors(n) / max(total_desc, 1)
                           if passes else 1.0),
        "bytes_moved": nbytes * 2 * passes,
        "copy_bytes": nbytes * 2,
        "min_run_bytes": min_run_bytes,
        # modeled fraction of copy throughput, assuming descriptor-issue
        # bound when runs are short and bandwidth bound otherwise:
        "bandwidth_fraction": 1.0 if passes == 0 else 1.0 / passes,
    }
