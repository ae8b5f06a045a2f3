"""Multi-engine executor with a compiled-plan cache.

Engines map a ``Perm`` stage to an actual array permutation:

* ``"ref"``    — the pure-jnp gather oracle (:mod:`repro.kernels.ref`).
* ``"pallas"`` — the tiled Pallas pipeline (:mod:`repro.kernels`), with a
  twist: the per-stage kernel executable is cached by *tile geometry*
  (:func:`repro.kernels.bmmc_permute.plan_geometry`), and the per-stage
  index tables are passed as runtime arguments. A fused program with many
  distinct BMMCs but few distinct geometries therefore pays the pallas
  trace/lower cost only once per geometry, not once per stage.

Any callable ``(x, bmmc) -> x`` is also accepted wherever an engine name
is, so tests can inject instrumented engines.

``compile_expr(expr)`` is the user entry point: lowering + fusion happen
once per ``(expr, n)``; kernel plans once per ``(bmmc, t)``; kernel
executables once per geometry. The returned function is jax-traceable
(it can be wrapped in ``jax.jit``), and cheap to call as-is.

Autodiff (DESIGN.md §9, §13): every ``Perm`` stage executes through
:func:`perm_apply`, a ``jax.custom_vjp`` primitive whose backward pass
applies the *offline-inverted* BMMC (``Bmmc.inverse``) through the same
engine. A BMMC permutation is orthogonal — its Jacobian transpose is the
inverse permutation — so no residuals are saved and cotangents ride the
same geometry-cached tiled kernels as the forward pass. Pallas DMA
kernels have no JVP/transpose rules of their own; this rule is what
makes ``jax.grad`` flow through the "pallas" engine at all.

The backward pass is itself a compiled program (DESIGN.md §13). A
permutation-only program — every stage a ``Perm`` or a compute-free
cluster — executes through :func:`program_apply`, a whole-program
``custom_vjp`` primitive whose backward dispatches the offline-inverted
program (:func:`repro.combinators.optimize.inverse_program`, which
inverts *clustered* programs cluster-for-cluster) through its own
``(program, engine, batched)`` executable-cache entry, warmed alongside
the forward. No residuals are saved anywhere on this path. Compute-
bearing clusters save only the cluster input and run a *pulled-back*
backward: the cluster forward factors as ``B ∘ C̃m ∘ … ∘ C̃1`` (each
``C̃j = Mj⁻¹ ∘ Cj ∘ Mj`` an input-space XOR-partner pairwise compute
with offline side/twiddle tables), so the cotangent takes ONE inverse
megakernel dispatch for ``B⁻¹`` plus cheap jnp pairwise VJPs — the
per-stage inverse replay survives only as the fallback for layouts the
tables don't model (complex butterflies).

Batching: ``run_program`` / ``CompiledExpr.__call__`` take
``batched=True`` to accept a leading batch axis — ``(B, 2^n)`` or
``(B, 2^n, d)`` — folded into the kernel grid with the tile plan shared
across the batch. Injected engines that don't understand ``batched``
are transparently wrapped with ``jax.vmap`` (the vmap fallback).

Fused stages (DESIGN.md §10): on the "pallas" engine the compiled
program is additionally run through :func:`repro.combinators.optimize.
cluster`, which groups ``Perm → compute → Perm`` runs into
:class:`~repro.combinators.optimize.FusedStage`\\ s. A FusedStage
dispatches to the double-buffered megakernel — one HBM round trip for
the whole run, with the interior ``CmpHalves``/``Bfly``/``Map`` stages
applied to each tile in VMEM. Every other engine (the "ref" oracle,
injected engines) executes the cluster's original stages one at a time,
while the megakernel's backward pass dispatches the *inverse cluster*
(permutation-only clusters, zero residuals) or the pulled-back compute
chain (§13). Clusters whose layout the kernel cannot take (complex
dtype, non-planar butterflies, arrays too small to tile) transparently
fall back to stage-at-a-time execution.
"""
from __future__ import annotations

import collections
import functools
import inspect
import threading
import time
import weakref
from typing import Callable, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bmmc import Bmmc
from ..obs import metrics as _ometrics
from ..obs import trace as _otrace
from ..core.tiling import (compute_tables, pairing_vector, plan_bmmc,
                           plan_general)
from ..kernels import ref as _ref
from ..kernels.bmmc_permute import (block_geometry, block_permute_tables,
                                    item_width, lane_geometry,
                                    lane_permute_tables, plan_geometry,
                                    plan_tables,
                                    tiled_permute_bwd_tables,
                                    tiled_permute_tables)
from .ir import Bfly, CmpHalves, Expr, Map, Perm
from .optimize import (COMPUTES, Program, FusedStage, _run_fused, cluster,
                       fold_free, lower, fuse, inverse_program,
                       inverse_stage, is_perm_program)

EngineFn = Callable[[jax.Array, Bmmc], jax.Array]

_ENGINES: Dict[str, EngineFn] = {}


def register_engine(name: str, fn: EngineFn) -> None:
    _ENGINES[name] = fn


def get_engine(engine: Union[str, EngineFn, None]) -> EngineFn:
    if engine is None:
        return _ENGINES["ref"]
    if callable(engine):
        return engine
    try:
        return _ENGINES[engine]
    except KeyError:
        from ..guard.errors import UnknownEngine
        raise UnknownEngine(
            f"unknown engine {engine!r}; registered engines: "
            f"{sorted(_ENGINES)}") from None


def engines() -> tuple:
    return tuple(sorted(_ENGINES))


# ---------------------------------------------------------------------------
# The "pallas" engine: geometry-cached kernel executables.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=512)
def _geom_executable(geometry: tuple, batched: bool = False,
                     epilogue: tuple = (), map_fns: tuple = ()):
    """One jitted tiled-pass executable per (tile geometry, epilogue
    signature). Index/epilogue tables are arguments, so every stage
    sharing this key reuses the trace. The cache key is independent of
    the batch size: growing B re-specializes the jit trace but never
    adds a geometry entry."""
    return jax.jit(functools.partial(
        tiled_permute_tables, geometry=geometry, batched=batched,
        epilogue=epilogue, map_fns=map_fns))


@functools.lru_cache(maxsize=512)
def _geom_bwd_executable(geometry: tuple, batched: bool = False,
                         epilogue: tuple = (), map_fns: tuple = ()):
    """One jitted gradient-megakernel executable per (tile geometry,
    epilogue signature) — the backward twin of :func:`_geom_executable`,
    same cache-key discipline (tables are runtime arguments)."""
    return jax.jit(functools.partial(
        tiled_permute_bwd_tables, geometry=geometry, batched=batched,
        epilogue=epilogue, map_fns=map_fns))


@functools.lru_cache(maxsize=256)
def _block_executable(geometry: tuple, batched: bool = False):
    """One jitted block-permute (grid-remapped DMA copy) executable per
    geometry; the source-row table is a runtime argument."""
    return jax.jit(functools.partial(
        block_permute_tables, geometry=geometry, batched=batched))


@functools.lru_cache(maxsize=256)
def _lane_executable(geometry: tuple, batched: bool = False):
    """One jitted lane-permute (in-VMEM row gather) executable per
    geometry; the lane table is a runtime argument."""
    return jax.jit(functools.partial(
        lane_permute_tables, geometry=geometry, batched=batched))


def _pallas_engine(x: jax.Array, bmmc: Bmmc, *, t: Optional[int] = None,
                   batched: bool = False) -> jax.Array:
    from ..kernels import ops

    if bmmc.is_identity_perm():
        _ometrics.inc("dispatch.kernel", kernel="none")
        return x
    if jnp.iscomplexobj(x):
        # pallas TPU has no complex dtype; a permutation is dtype-agnostic,
        # so complex arrays ride the gather oracle (planar (re, im) float
        # layouts take the tiled kernels)
        return _ref.bmmc_ref(x, bmmc, batched=batched)
    got = ops.class_dispatch(x, bmmc, t, batched)
    if got is None:  # too small to tile; whole array fits anywhere
        return _ref.bmmc_ref(x, bmmc, batched=batched)
    kernel, payload = got
    if kernel == "none":
        return x
    if kernel == "block":
        run = _block_executable(block_geometry(payload), batched)
        return run(x, payload.src_rows)
    if kernel == "lane":
        run = _lane_executable(lane_geometry(payload), batched)
        return run(x, payload.src_lane)
    d = item_width(x, batched)
    for plan in payload:
        run = _geom_executable(plan_geometry(plan, d=d), batched)
        x = run(x, *plan_tables(plan, d=d))
    return x


register_engine("ref", _ref.bmmc_ref)
register_engine("pallas", _pallas_engine)


# ---------------------------------------------------------------------------
# Fused-stage execution: the megakernel dispatch path (DESIGN.md §10)
# ---------------------------------------------------------------------------

def _fused_entries(plans, computes):
    entries = []
    for comp, prefix in computes:
        if isinstance(comp, Map):
            entries.append(("map", comp))
            continue
        kind = "cmp" if isinstance(comp, CmpHalves) else "bfly"
        ct = compute_tables(plans[0], prefix, kind)
        if ct is None:
            return None
        entries.append((kind, comp, ct))
    return tuple(entries)


def _build_fused_plan(fs: FusedStage, t: int):
    """Plan a cluster from scratch (the store's ``build`` rung).

    A classic plan's tile span can be narrower than the maximal
    ``ker(A[t:, :])`` span the clustering validated against; when a
    compute's pairing vector needs the extra room, the first pass is
    re-planned with :func:`repro.core.tiling.plan_general`, whose span
    IS the maximum."""
    try:
        plans = list(plan_bmmc(fs.bmmc, t))
    except ValueError:
        return None
    entries = _fused_entries(plans, fs.computes)
    if entries is None and plans[0].row_cols:
        general = plan_general(plans[0].bmmc, t)
        if general is not None:
            plans[0] = general
            entries = _fused_entries(plans, fs.computes)
    if entries is None:
        return None
    return tuple(plans), tuple(entries)


@functools.lru_cache(maxsize=256)
def _fused_plan_cached(fs: FusedStage, t: int):
    """(pass plans, per-compute ComputeTables-or-Map entries) for a
    cluster, or None when the megakernel cannot run it at this tile
    parameter (no pass plannable, or a compute not tile-local in the
    first pass — possible when the runtime ``t`` differs from the
    clustering ``t``). The composed BMMC runs as ONE tiled pass (classic
    witness columns or generalized witness directions), falling back to
    the §5.2 two-pass factorization only for t > n/2; computes always
    ride the FIRST pass's tiles (they are pulled back to input space,
    where pass 1 reads).

    Backed by the durable plan store when one is configured
    (``REPRO_STORE``): only the offline tables travel to disk — compute
    entries are re-seated against this cluster's live ``computes`` on
    decode, so Map callables never serialize — and every loaded plan is
    re-audited through guard ring 1 before it is trusted."""
    from .. import store as _store

    def build():
        with _otrace.span("plan.fused", stages=len(fs.stages), t=t):
            return _build_fused_plan(fs, t)

    return _store.fused_plan_through(fs, t, build)


@functools.lru_cache(maxsize=64)
def _w_planar_cached(twiddles: tuple, dtype: str) -> np.ndarray:
    """The (2^(n-1), 2) resident (re, im) twiddle-value table."""
    return np.stack([np.asarray([w.real for w in twiddles], dtype=dtype),
                     np.asarray([w.imag for w in twiddles], dtype=dtype)],
                    axis=-1)


def _fused_tile(x: jax.Array, fs: FusedStage, batched: bool) -> Optional[int]:
    """The tile parameter the megakernel would use on ``x``, or None when
    the fused fast path cannot take this input (falls back per-stage)."""
    from ..kernels import ops

    lead = 1 if batched else 0
    if x.ndim not in (1 + lead, 2 + lead) or jnp.iscomplexobj(x):
        return None
    d = x.shape[1 + lead] if x.ndim == 2 + lead else 1
    if any(isinstance(c, Bfly) for c, _ in fs.computes):
        if x.ndim != 2 + lead or d != 2:
            return None  # butterflies need the planar (re, im) layout
    t = ops.choose_tile(fs.bmmc.n, x.dtype.itemsize, d)
    if t is None or _fused_plan_cached(fs, t) is None:
        return None
    return t


def _fused_kernel_args(entries: tuple, dtype) -> tuple:
    """(signature, scalar tables, VMEM tables, map fns) shared verbatim
    by the forward megakernel and its gradient twin — one table set, two
    kernels."""
    sig, scal, vmem, map_fns = [], [], [], []
    for e in entries:
        if e[0] == "map":
            sig.append(("map", e[1].name))
            map_fns.append(e[1].fn)
            scal.append(())
            vmem.append(())
            continue
        kind, comp, ct = e
        if kind == "cmp":
            sig.append(("cmp", ct.vr, ct.vc))
            scal.append((ct.hi_base,))
            vmem.append((ct.hi_row, ct.hi_lane))
        else:
            w = _w_planar_cached(comp.twiddles, np.dtype(dtype).name)
            sig.append(("bfly", ct.vr, ct.vc, len(comp.twiddles)))
            scal.append((ct.hi_base, ct.tw_base))
            vmem.append((ct.hi_row, ct.hi_lane, ct.tw_row, ct.tw_lane, w))
    return tuple(sig), tuple(scal), tuple(vmem), tuple(map_fns)


def _fused_pallas(x: jax.Array, fs: FusedStage, t: int, *,
                  batched: bool = False) -> jax.Array:
    """Run one cluster as a double-buffered megakernel dispatch: the
    first tiled pass carries every fused compute as an in-VMEM epilogue;
    a second plain pass (general BMMCs only, §5.2) finishes the
    permutation."""
    plans, entries = _fused_plan_cached(fs, t)
    plan = plans[0]
    d = item_width(x, batched)
    sig, scal, vmem, map_fns = _fused_kernel_args(entries, x.dtype)
    run = _geom_executable(plan_geometry(plan, d=d), batched, sig, map_fns)
    x = run(x, *plan_tables(plan, d=d), epi_scalar=scal, epi_vmem=vmem)
    for plan in plans[1:]:
        run = _geom_executable(plan_geometry(plan, d=d), batched)
        x = run(x, *plan_tables(plan, d=d))
    return x


def _fused_forward(x, fs, engine, batched):
    if engine == "pallas":
        t = _fused_tile(x, fs, batched)
        if t is not None:
            if _otrace._state.enabled:
                from ..kernels import ops
                plans, _ = _fused_plan_cached(fs, t)
                _ometrics.inc("dispatch.kernel", kernel="fused")
                _ometrics.inc("model.round_trips", len(plans))
                _ometrics.inc("dma.descriptors",
                              sum(p.dma_descriptors() for p in plans))
                d = item_width(x, batched)
                ops.count_tiled_passes(
                    [plan_geometry(p, d=d) for p in plans])
                with _otrace.span("kernel.fused", stages=len(fs.stages),
                                  passes=len(plans), t=t):
                    return _fused_pallas(x, fs, t, batched=batched)
            return _fused_pallas(x, fs, t, batched=batched)
    if engine == "pallas":
        # cluster validated at plan time but re-rejected for this input
        # (dtype/shape/tile mismatch): the honest count the model lacks
        _ometrics.inc("dispatch.fused_fallback")
    return run_program(fs.stages, x, engine, batched=batched)


# ---------------------------------------------------------------------------
# Compiled backward pass (DESIGN.md §13)
#
# Every custom-VJP backward rule below runs under _vjp_observed, which
# opens a "<kind>.vjp" span and credits the modeled round trips the rule
# dispatches to ``model.vjp_round_trips`` — the backward twin of the
# forward ``model.round_trips`` accounting, so one cold backward call's
# counter delta can be held against ``program_cost(inverse_program(p))``.
# ---------------------------------------------------------------------------

_VJP_STATE = threading.local()


def _vjp_observed(kind: str, fn: Callable):
    """Run one backward-rule body under a ``<kind>.vjp`` span.

    Counters fire at trace time (host-side Python), so the delta of
    ``model.round_trips`` across the rule IS the modeled cost of the
    backward program it dispatched. Nested rules — e.g. per-stage
    ``Perm`` VJPs inside a fused fallback replay — fold into the
    outermost rule's span via the reentrancy depth guard, never
    double-counting ``model.vjp_round_trips``.
    """
    if not _otrace._state.enabled or getattr(_VJP_STATE, "depth", 0):
        return fn()
    _VJP_STATE.depth = 1
    try:
        rt0 = _ometrics.counter_total("model.round_trips")
        with _otrace.span(kind + ".vjp") as sargs:
            out = fn()
            delta = _ometrics.counter_total("model.round_trips") - rt0
            sargs["model_round_trips"] = delta
        _ometrics.inc("dispatch.vjp", kind=kind)
        if delta:
            _ometrics.inc("model.vjp_round_trips", delta)
    finally:
        _VJP_STATE.depth = 0
    return out


@functools.lru_cache(maxsize=512)
def _fused_inverse_cached(fs: FusedStage) -> FusedStage:
    """The offline inverse of a permutation-only cluster — itself a
    cluster (per-class closure, DESIGN.md §13)."""
    return inverse_stage(fs)


def _np_parity(vals: np.ndarray) -> np.ndarray:
    """Elementwise F2 parity (popcount mod 2) of an int64 index array."""
    v = vals.astype(np.int64)
    for s in (32, 16, 8, 4, 2, 1):
        v ^= v >> s
    return v & 1


@functools.lru_cache(maxsize=256)
def _pulled_back_tables(prefix: Bmmc, kind: str) -> tuple:
    """Offline numpy tables for one pulled-back compute ``C̃ = M⁻¹CM``.

    ``partner[i] = i ^ v`` with ``v = A_M⁻¹ e_{n-1}`` the pairing
    vector; ``side0[i]`` marks the "lo" role (bit n-1 of ``M(i)`` clear)
    — the same predicate :func:`repro.core.tiling.compute_tables` splits
    into per-row/lane/tile terms for the in-VMEM epilogue; ``w_idx[i]``
    (bfly only) the twiddle slot = ``M(i)`` with the pair bit dropped,
    shared by both partners since ``M(v) = e_{n-1}``.
    """
    n = prefix.n
    idx = np.arange(1 << n, dtype=np.int64)
    partner = (idx ^ pairing_vector(prefix)).astype(np.int32)
    side0 = (_np_parity(idx & prefix.rows[n - 1])
             ^ ((prefix.c >> (n - 1)) & 1)) == 0
    w_idx = None
    if kind == "bfly":
        w_idx = np.zeros(1 << n, dtype=np.int64)
        for j in range(n - 1):
            w_idx |= _np_parity(idx & prefix.rows[j]) << j
        w_idx = (w_idx ^ (prefix.c & ((1 << (n - 1)) - 1))).astype(np.int32)
    return partner, side0, w_idx


@functools.lru_cache(maxsize=256)
def _pulled_back_fn(comp: Expr, prefix: Bmmc, batched: bool) -> tuple:
    """The compute conjugated into the cluster's input space, as an
    explicit ``(fwd, bwd)`` pair of plain-jnp functions.

    ``fwd(u)`` recomputes the conjugated stage — an XOR-partner gather
    plus the pairwise compute, bitwise-matching the per-stage oracle:
    the (lo, hi) argument ORDER of the min/max (and the ``lo ± w·hi``
    butterfly terms) is canonicalized by the side predicate, so
    tie-breaking and NaN routing agree with :func:`run_program`'s replay
    exactly. ``bwd(u, ct)`` is the hand-written VJP: the backward rule
    only needs cotangent VALUES, and keeping these plain functions —
    no nested ``custom_vjp`` wrapper — avoids the exponential jaxpr
    growth jax exhibits when chained custom-vjp calls are linearized
    inside another rule's transpose.
    """
    if isinstance(comp, Map):
        def map_bwd(u, ct):
            _, vjp = jax.vjp(comp.fn, u)
            return vjp(ct)[0]
        # elementwise: conjugation by a permutation is a no-op
        return comp.fn, map_bwd
    axis = 1 if batched else 0
    kind = "cmp" if isinstance(comp, CmpHalves) else "bfly"
    # the closures hold NUMPY tables, lifted to constants by the jnp ops
    # at each trace — caching a jnp.asarray here would pin a tracer when
    # the first build happens under an active trace (e.g. linearization
    # of the whole-program executable) and leak it into later traces
    partner, side0, w_idx = _pulled_back_tables(prefix, kind)

    def expand(tbl, ndim):
        return tbl.reshape((1,) * axis + (-1,) + (1,) * (ndim - axis - 1))

    if kind == "cmp":
        def g(u, up):  # elementwise pairwise compare, canonical arg order
            s0 = expand(side0, u.ndim)
            lo = jnp.where(s0, u, up)
            hi = jnp.where(s0, up, u)
            return jnp.where(s0, jnp.minimum(lo, hi), jnp.maximum(lo, hi))
    else:
        w = np.asarray(comp.twiddles, dtype=np.complex128)[w_idx]
        w_re = np.ascontiguousarray(w.real)
        w_im = np.ascontiguousarray(w.imag)
        side0_b = side0[:, None]  # broadcasts over the (re, im) dim

        def g(u, up):  # planar layout: (..., 2^n, 2)
            wr = w_re.astype(u.dtype)
            wi = w_im.astype(u.dtype)
            lo = jnp.where(side0_b, u, up)
            hi = jnp.where(side0_b, up, u)
            tre = wr * hi[..., 0] - wi * hi[..., 1]
            tim = wr * hi[..., 1] + wi * hi[..., 0]
            t = jnp.stack([tre, tim], axis=-1)
            return jnp.where(side0_b, lo + t, lo - t)

    # fwd = g(u, P u) with P the (involutive) partner gather. The VJP is
    # written by hand so the gather's transpose stays a GATHER — XLA
    # would otherwise emit a scatter-add for the take's transpose, which
    # dominated the backward wall clock. ``Pᵀ = P`` for an involution,
    # so ct_u = ∂g/∂u · ct + P(∂g/∂up · ct); the elementwise partials
    # come from jax.vjp of the pure-elementwise g, keeping the min/max
    # tie-breaking and NaN routing bit-identical to the per-stage oracle.
    def fwd(u):
        return g(u, jnp.take(u, partner, axis=axis))

    def bwd(u, ct):
        up = jnp.take(u, partner, axis=axis)
        _, vjp = jax.vjp(g, u, up)
        d1, d2 = vjp(ct)
        return d1 + jnp.take(d2, partner, axis=axis)

    return fwd, bwd


_BwdPlan = collections.namedtuple(
    "_BwdPlan", ["n", "recs", "links", "segs", "final", "has_bfly"])


@functools.lru_cache(maxsize=256)
def _program_bwd_plan(prog: Program, batched: bool):
    """The collapsed whole-program backward plan (DESIGN.md §13), or
    None when a stage falls outside the pairwise algebra (``Map``).

    Every transposed compute in the backward chain is a PAIRWISE op
    (XOR-partner gather plus elementwise math), so it can be conjugated
    through the BMMC passes that follow it in backward time: with
    ``Π`` the accumulated permutation, ``Lᵀ`` becomes ``Π⁻¹ Lᵀ Π`` —
    still pairwise, with pairing vector and per-element tables permuted
    OFFLINE (closure of the affine group under conjugation, the same
    §7.2 algebra the forward clusterer uses). Bubbling every perm to
    the end collapses the entire backward to: all transposed computes
    in forward-OUTPUT coordinates, then ONE composed inverse BMMC pass
    — the backward mirror of the paper's "everything is one BMMC"
    thesis, and the reason fwd+bwd costs ~2 passes, not ~2 per stage.

    The sweep executes maximal same-kind link runs as single
    :func:`jax.lax.scan`\\ s over stacked per-link tables. This is not
    just compile-size hygiene: XLA CPU's loop-fusion emitter re-emits a
    producer once per in-fusion gather consumer, so a chained
    gather-of-the-cotangent backward fused into one kLoop recomputes
    the upstream chain at a fresh permuted index every link — measured
    EXPONENTIAL wall clock in chain depth (k=5: 351µs → k=7: 4.9ms on a
    2^8×8 batch) with a linear-size HLO, and ``optimization_barrier``
    does not split the fusion. A scan body is a separate XLA
    computation, so fusion physically cannot span links.

    Returns ``(n, recs, links, segs, final, has_bfly)``:

    - ``recs[k] = (res_index, fwd_fns | None)`` — one per compute-bearing
      stage in BACKWARD order; ``fwd_fns`` recomputes the pulled-back
      intermediate chain from the saved stage input (None when no link
      needs intermediates, e.g. all-butterfly: linear, residual-free).
    - ``links`` — transposed computes in backward-time order, conjugated
      into output coordinates: ``("cmp", rec, j, gu, gup, pY)`` with
      ``gu``/``gup`` the static u/partner gather tables and ``pY`` the
      conjugated pairing; ``("bfly", pY, side0, w_re, w_im)``.
    - ``segs`` — maximal same-kind runs ``(kind, link indices)``.
    - ``final`` — the composed inverse BMMC as a compute-free
      :class:`FusedStage` (one megakernel/class-dispatch pass), or None
      if it collapses to the identity.
    """
    with _otrace.span("plan.bwd", stages=len(prog)):
        return _build_bwd_plan(prog, batched)


def _build_bwd_plan(prog: Program, batched: bool):
    n = None
    for st in prog:
        if isinstance(st, FusedStage):
            if any(isinstance(c, Map) for c, _ in st.computes):
                return None
            n = st.bmmc.n
        elif isinstance(st, Perm):
            n = st.bmmc.n
        elif not isinstance(st, (CmpHalves, Bfly)):
            return None
    if n is None:
        return None
    ident = Bmmc.identity(n)
    # residual slots: res[0] is the program input (kept for the replay
    # fallback), then one entry per compute-bearing stage in forward
    # order — permutation stages and perm-only clusters save NOTHING
    res_of, ri = {}, 1
    for si, st in enumerate(prog):
        if isinstance(st, (CmpHalves, Bfly)) or (
                isinstance(st, FusedStage) and st.computes):
            res_of[si] = ri
            ri += 1
    sigma = ident  # X-coords -> Y-coords map of the perms bubbled so far
    links, recs = [], []
    has_bfly = False
    for si in range(len(prog) - 1, -1, -1):
        st = prog[si]
        if isinstance(st, Perm):
            sigma = sigma @ st.bmmc
            continue
        if isinstance(st, FusedStage):
            # FSᵀ = c̃1ᵀ ∘ … ∘ c̃mᵀ ∘ B⁻¹: the B⁻¹ factor bubbles first,
            # so the cluster's own links are conjugated through it too
            sigma = sigma @ st.bmmc
            comps = st.computes
        else:
            comps = ((st, ident),)
        if not comps:
            continue
        rec_id = len(recs)
        fwds = tuple(_pulled_back_fn(c, p, batched)[0] for c, p in comps)
        recs.append([res_of[si], fwds, False])
        # Y index -> link-space index
        tau_tab = sigma.inverse().apply_all(np.arange(1 << n, dtype=np.int64))
        a_off = sigma.apply(0)
        for j in range(len(comps) - 1, -1, -1):
            comp, prefix = comps[j]
            kind = "cmp" if isinstance(comp, CmpHalves) else "bfly"
            partner, side0, w_idx = _pulled_back_tables(prefix, kind)
            pv = int(pairing_vector(prefix))
            # conjugated pairing: partner'(y) = σ(σ⁻¹(y) ^ v) = y ^ A_σ v
            p_y = (np.arange(1 << n, dtype=np.int64)
                   ^ (sigma.apply(pv) ^ a_off)).astype(np.int32)
            if kind == "cmp":
                recs[rec_id][2] = True  # masks need the recomputed chain
                links.append(("cmp", rec_id, j, tau_tab.astype(np.int32),
                              (tau_tab ^ pv).astype(np.int32), p_y))
            else:
                has_bfly = True
                w = np.asarray(comp.twiddles, np.complex128)[w_idx]
                links.append(("bfly", p_y, side0[tau_tab],
                              np.ascontiguousarray(w.real)[tau_tab],
                              np.ascontiguousarray(w.imag)[tau_tab]))
    recs = tuple((r[0], r[1] if r[2] else None) for r in recs)
    segs, start = [], 0
    for i in range(1, len(links) + 1):
        if i == len(links) or links[i][0] != links[start][0]:
            segs.append((links[start][0], tuple(range(start, i))))
            start = i
    final = None
    if not sigma.is_identity_perm():
        # Perm(g) gathers from g⁻¹, so realizing the bubbled op (source
        # map σ) takes the stage whose BMMC is σ⁻¹
        final = _run_fused((Perm(sigma.inverse()),), n)
    return _BwdPlan(n, recs, tuple(links), tuple(segs), final, has_bfly)


def _collapsed_cmp_sweep(ct, entries, us, axis):
    """Backward sweep over a run of conjugated transposed compares, two
    links per scan step (backward-time order).

    The compare's VJP factors as ``ct ↦ m1 ⊙ ct + P(m2 ⊙ ct)`` with
    jax's balanced-eq tie masks ``m1 = 1{u==o} / (1 + 1{up==o})`` (and
    ``m2`` with the roles swapped) — identical on both min/max branches
    GIVEN the forward output ``o``, so the side predicate drops out.
    The masks depend only on the recomputed intermediates, never on the
    cotangent, so they are computed VECTORIZED over the link axis
    outside the loop; the scan body — the only sequential part — is
    four ops per link. Mask values are exactly ``{0, 1/2, 1}`` built by
    nested selects (no divide), bitwise-equal to the balanced-eq
    quotient, so VALUES match ``jax.vjp`` of the per-stage replay
    exactly; only their positions ride in permuted coordinates until
    the final composed pass.

    Layout notes, all measured on the 2^8×8 sort backward: the link
    axis is stacked at ``axis`` (right before the index axis) and then
    FLATTENED into it, so the three conjugation gathers are plain 1-D
    static ``take``\\ s — the batched ``take_along_axis`` form lowers to
    an XLA gather with batch dims that costs ~2.5× more here. Pairing
    two links per scan step halves the loop overhead; wider groups
    regress (G=6 is 4× slower than G=2) because XLA CPU's fusion
    emitter re-emits the cotangent chain once per in-body gather
    consumer — the same recompute pathology that makes the scan
    necessary in the first place (see :func:`_program_bwd_plan`)."""
    dt = ct.dtype
    L = len(entries)
    n_idx = entries[0][3].size
    # stack links at `axis`, flatten (L, 2^n) -> (L*2^n,) for flat takes
    u_stack = jnp.stack([us[e[1]][e[2]] for e in entries], axis=axis)
    o_stack = jnp.stack([us[e[1]][e[2] + 1] for e in entries], axis=axis)
    flat_shape = u_stack.shape[:axis] + (L * n_idx,) + u_stack.shape[axis + 2:]
    u_stack = u_stack.reshape(flat_shape)
    o_stack = o_stack.reshape(flat_shape)
    offs = np.arange(L, dtype=np.int64)[:, None] * n_idx

    def flat_idx(tabs):
        idx = offs + np.stack(tabs).astype(np.int64)
        return idx.reshape(-1).astype(np.int32 if L * n_idx < 2**31
                                      else np.int64)

    f_tab = flat_idx([e[3] for e in entries])
    f_tabp = flat_idx([e[4] for e in entries])
    ueq = jnp.take(u_stack, f_tab, axis=axis) == jnp.take(
        o_stack, f_tab, axis=axis)
    peq = jnp.take(u_stack, f_tabp, axis=axis) == jnp.take(
        o_stack, f_tab, axis=axis)
    half = jnp.asarray(0.5, dt)
    one = jnp.ones((), dt)
    zero = jnp.zeros((), dt)
    m1 = jnp.where(ueq, jnp.where(peq, half, one), zero)
    m2 = jnp.where(peq, jnp.where(ueq, half, one), zero)
    link_shape = m1.shape[:axis] + (L, n_idx) + m1.shape[axis + 1:]
    m1 = jnp.moveaxis(m1.reshape(link_shape), axis, 0)
    m2 = jnp.moveaxis(m2.reshape(link_shape), axis, 0)
    p_stack = np.stack([e[5] for e in entries])

    def one_link(c, m1_, m2_, p_):
        return m1_ * c + jnp.take(m2_ * c, p_, axis=axis)

    head = L % 2
    if head:
        ct = one_link(ct, m1[0], m2[0], p_stack[0])
    if L > head:
        pairs = (L - head) // 2
        m1g = m1[head:].reshape((pairs, 2) + m1.shape[1:])
        m2g = m2[head:].reshape((pairs, 2) + m2.shape[1:])
        pg = p_stack[head:].reshape(pairs, 2, -1)

        def body(c, xs):
            m1_, m2_, p_ = xs
            c = one_link(c, m1_[0], m2_[0], p_[0])
            return one_link(c, m1_[1], m2_[1], p_[1]), None

        ct, _ = jax.lax.scan(body, ct, (m1g, m2g, pg))
    return ct


def _collapsed_bfly_sweep(ct, entries, axis):
    """Backward sweep over a run of conjugated transposed butterflies
    (planar layout), one scan step per link in backward-time order. The
    stage is LINEAR — pair ``(a₀, a₁) ↦ (a₀ + W a₁, a₀ − W a₁)`` with
    ``W`` the twiddle rotation — so its transpose ``ct₀ ↦ ct₀ + ct₁,
    ct₁ ↦ Wᵀ(ct₀ − ct₁)`` needs no forward intermediates at all."""
    dt = ct.dtype
    p_stack = np.stack([e[1] for e in entries])
    # side0 stays 1-D: the body selects on component slices ``c[..., k]``
    # whose planar axis is already gone, so it broadcasts over the index
    # axis only (leading batch dims broadcast from the left)
    s_stack = np.stack([e[2] for e in entries])
    wr_stack = np.stack([e[3] for e in entries]).astype(dt)
    wi_stack = np.stack([e[4] for e in entries]).astype(dt)

    def body(c, xs):
        p, s0, wr, wi = xs
        q = jnp.take(c, p, axis=axis)
        s_re = q[..., 0] - c[..., 0]
        s_im = q[..., 1] - c[..., 1]
        wt_re = wr * s_re + wi * s_im
        wt_im = wr * s_im - wi * s_re
        out = jnp.stack([jnp.where(s0, c[..., 0] + q[..., 0], wt_re),
                         jnp.where(s0, c[..., 1] + q[..., 1], wt_im)],
                        axis=-1)
        return out, None

    ct, _ = jax.lax.scan(body, ct, (p_stack, s_stack, wr_stack, wi_stack))
    return ct


def _collapsed_bwd(plan, res, ct, engine, batched):
    """Execute a collapsed backward plan: recompute the pulled-back
    intermediate chains from the saved stage inputs, sweep every
    transposed compute in forward-output coordinates, then dispatch the
    ONE composed inverse BMMC pass through the fused engine."""
    axis = 1 if batched else 0
    us = []
    for res_i, fwds in plan.recs:
        if fwds is None:
            us.append(None)
            continue
        chain = [res[res_i]]
        for f in fwds:
            chain.append(f(chain[-1]))
        us.append(chain)
    for kind, idxs in plan.segs:
        entries = [plan.links[i] for i in idxs]
        if kind == "cmp":
            ct = _collapsed_cmp_sweep(ct, entries, us, axis)
        else:
            ct = _collapsed_bfly_sweep(ct, entries, axis)
    if plan.final is not None:
        ct = fused_apply(ct, plan.final, engine, batched)
    return ct


@functools.lru_cache(maxsize=256)
def _fused_bwd_kernel_plan(fs: FusedStage, t: int):
    """Offline artifacts of the gradient megakernel for one cluster, or
    None when it can't run at this tile parameter: the forward plan +
    epilogue entries (shared tables; the kernel un-permutes through the
    plan's ``intra_inv`` factorization), and the inverse plans of any
    trailing plain passes (§5.2 two-pass factorizations — undone
    pass-by-pass before the gradient kernel, keeping the backward
    round-trip count equal to the forward's)."""
    got = _fused_plan_cached(fs, t)
    if got is None:
        return None
    plans, entries = got
    extra = []
    for pass_plan in plans[1:]:
        try:
            extra.append(tuple(plan_bmmc(pass_plan.bmmc.inverse(), t)))
        except ValueError:
            return None
        if len(extra[-1]) != 1:
            return None  # inverse pass count must mirror the forward's
    return plans, entries, tuple(extra)


def _fused_bwd_pallas(fs, t, batched, x, ct):
    """One-kernel cluster backward: undo the trailing plain passes, then
    dispatch the gradient megakernel over the forward's own plan."""
    plans, entries, extra = _fused_bwd_kernel_plan(fs, t)
    d = item_width(ct, batched)
    for inv_plans in reversed(extra):
        for p in inv_plans:
            run = _geom_executable(plan_geometry(p, d=d), batched)
            ct = run(ct, *plan_tables(p, d=d))
    plan = plans[0]
    sig, scal, vmem, map_fns = _fused_kernel_args(entries, x.dtype)
    run = _geom_bwd_executable(plan_geometry(plan, inverse=True, d=d),
                               batched, sig, map_fns)
    return run(x, ct, *plan_tables(plan, inverse=True, d=d),
               epi_scalar=scal, epi_vmem=vmem)


# The one-kernel gradient megakernel (`_tile_bwd_kernel`) is the
# hardware-shaped backward: ONE pallas round trip per compute cluster,
# streaming the saved input alongside the cotangent and replaying /
# transposing every epilogue in VMEM. Under interpret mode the emulated
# kernel's cost scales with the traced in-VMEM body (measured 1.7-3x the
# forward per cluster at 2^8), so the mask-precomputed scan sweep below
# — which keeps all link-parallel work in plain XLA fusions and carries
# only the cotangent through the sequential part — is faster on this
# backend. Flip this for compiled-backend runs; the kernel path keeps
# bitwise-parity coverage in tests either way.
BWD_MEGAKERNEL = False


def _fused_bwd_impl(fs, engine, batched, x, ct):
    if not fs.computes:
        # permutation-only: dispatch the precompiled inverse cluster —
        # same megakernel path, same class, zero residuals (x is None)
        return fused_apply(ct, _fused_inverse_cached(fs), engine, batched)
    lead = 1 if batched else 0
    planar = ct.ndim == 2 + lead and ct.shape[-1] == 2
    if jnp.iscomplexobj(ct) or (not planar and any(
            isinstance(c, Bfly) for c, _ in fs.computes)):
        # layouts the pulled-back tables don't model (complex / non-planar
        # butterflies): replay the stage program under jax.vjp, matching
        # the forward's own oracle fallback for these inputs
        _, vjp = jax.vjp(
            lambda v: run_program(fs.stages, v, engine, batched=batched), x)
        return vjp(ct)[0]
    if engine == "pallas" and BWD_MEGAKERNEL:
        t = _fused_tile(x, fs, batched)
        if t is not None and _fused_bwd_kernel_plan(fs, t) is not None:
            if _otrace._state.enabled:
                from ..kernels import ops
                plans, _, extra = _fused_bwd_kernel_plan(fs, t)
                rt = 1 + sum(len(ip) for ip in extra)
                _ometrics.inc("dispatch.kernel", kernel="fused")
                _ometrics.inc("model.round_trips", rt)
                # the gradient kernel streams x in ADDITION to ct: its
                # descriptor count is the forward's plus one extra read
                # stream per tile — counted honestly, not mirrored
                p0 = plans[0]
                _ometrics.inc(
                    "dma.descriptors",
                    p0.dma_descriptors()
                    + p0.n_tiles * p0.side_descriptors()[0]
                    + sum(p.dma_descriptors()
                          for ip in extra for p in ip))
                d = item_width(ct, batched)
                ops.count_tiled_passes(
                    [plan_geometry(p0, inverse=True, d=d)]
                    + [plan_geometry(p, d=d) for ip in extra for p in ip])
                with _otrace.span("kernel.fused_bwd", stages=len(fs.stages),
                                  passes=rt, t=t):
                    return _fused_bwd_pallas(fs, t, batched, x, ct)
            return _fused_bwd_pallas(fs, t, batched, x, ct)
    plan = _program_bwd_plan((fs,), batched)
    if plan is None:
        # Map-bearing cluster: replay the stage program under jax.vjp
        # (per-stage custom-vjp boundaries — linear, no fusion blowup)
        _, vjp = jax.vjp(
            lambda v: run_program(fs.stages, v, engine, batched=batched), x)
        return vjp(ct)[0]
    return _collapsed_bwd(plan, (x, x), ct, engine, batched)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def fused_apply(x: jax.Array, fs: FusedStage,
                engine: Union[str, EngineFn, None] = None,
                batched: bool = False) -> jax.Array:
    """Differentiable fused-cluster execution.

    Forward: ONE megakernel pass on the "pallas" engine (per-stage
    otherwise). Backward (DESIGN.md §13): a permutation-only cluster
    saves NO residual and dispatches its precompiled inverse cluster;
    a compute-bearing cluster saves only its input and runs the
    pulled-back backward — one inverse megakernel for the composed
    ``B⁻¹`` plus the jnp VJPs of the input-space pairwise computes.
    The old per-stage ``jax.vjp`` replay survives only as the fallback
    for layouts the pulled-back tables don't model.
    """
    return _fused_forward(x, fs, engine, batched)


def _fused_fwd(x, fs, engine, batched):
    # permutation-only clusters need no residual: their cotangent rule
    # is the precompiled inverse cluster applied to ``ct`` alone
    return (_fused_forward(x, fs, engine, batched),
            x if fs.computes else None)


def _fused_bwd(fs, engine, batched, x, ct):
    return (_vjp_observed(
        "fused", lambda: _fused_bwd_impl(fs, engine, batched, x, ct)),)


fused_apply.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# perm_apply — the differentiable permutation primitive
# ---------------------------------------------------------------------------

_BATCHED_SIG = weakref.WeakKeyDictionary()  # doesn't pin injected engines


def _accepts_batched(fn: Callable) -> bool:
    # only an explicit ``batched`` parameter proves support — a bare
    # ``**kwargs`` would swallow the flag and permute the wrong axis
    try:
        return _BATCHED_SIG[fn]
    except (KeyError, TypeError):
        pass
    try:
        got = "batched" in inspect.signature(fn).parameters
    except (TypeError, ValueError):  # builtins, odd callables
        got = False
    try:
        _BATCHED_SIG[fn] = got
    except TypeError:  # not weakref-able; just re-probe next time
        pass
    return got


def _call_engine(fn: EngineFn, x: jax.Array, bmmc: Bmmc,
                 batched: bool) -> jax.Array:
    """Invoke an engine, vmapping over the batch axis if it only speaks the
    unbatched ``(x, bmmc) -> x`` protocol."""
    if not batched:
        return fn(x, bmmc)
    if _accepts_batched(fn):
        return fn(x, bmmc, batched=True)
    return jax.vmap(lambda xb: fn(xb, bmmc))(x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def perm_apply(x: jax.Array, bmmc: Bmmc,
               engine: Union[str, EngineFn, None] = None,
               batched: bool = False) -> jax.Array:
    """Differentiable BMMC permutation through any engine.

    The VJP applies ``bmmc.inverse()`` — precomputed offline over F2 —
    through the *same* engine: the cotangent of a pallas-permuted array is
    itself a pallas permutation (no gather transpose is materialized, and
    backward passes share the forward geometry cache).
    """
    return _call_engine(get_engine(engine), x, bmmc, batched)


def _perm_apply_fwd(x, bmmc, engine, batched):
    return perm_apply(x, bmmc, engine, batched), None


def _perm_apply_bwd(bmmc, engine, batched, _res, ct):
    return (_vjp_observed("stage", lambda: perm_apply(
        ct, bmmc.inverse(), engine, batched)),)


perm_apply.defvjp(_perm_apply_fwd, _perm_apply_bwd)


# ---------------------------------------------------------------------------
# Program execution
# ---------------------------------------------------------------------------

def _apply_bfly(x: jax.Array, twiddles: tuple, axis: int = 0) -> jax.Array:
    """(lo, hi) -> (lo + w·hi, lo - w·hi) along ``axis``. Complex arrays, or
    float arrays with a trailing dim of 2 holding (re, im) channels."""
    h = x.shape[axis] // 2
    lo = jax.lax.slice_in_dim(x, 0, h, axis=axis)
    hi = jax.lax.slice_in_dim(x, h, 2 * h, axis=axis)
    if jnp.iscomplexobj(x):
        w = jnp.asarray(np.asarray(twiddles, dtype=np.complex64))
        w = w.reshape((1,) * axis + (h,) + (1,) * (x.ndim - axis - 1))
        t = w * hi
        return jnp.concatenate([lo + t, lo - t], axis=axis)
    if x.ndim != axis + 2 or x.shape[-1] != 2:
        from ..guard.errors import BadInput
        raise BadInput("real-typed Bfly input must have a trailing "
                       f"(re, im) dim of 2; got shape {x.shape}")
    wshape = (1,) * axis + (h,)
    wr = jnp.asarray(np.asarray([w.real for w in twiddles],
                                dtype=x.dtype)).reshape(wshape)
    wi = jnp.asarray(np.asarray([w.imag for w in twiddles],
                                dtype=x.dtype)).reshape(wshape)
    tre = wr * hi[..., 0] - wi * hi[..., 1]
    tim = wr * hi[..., 1] + wi * hi[..., 0]
    t = jnp.stack([tre, tim], axis=-1)
    return jnp.concatenate([lo + t, lo - t], axis=axis)


def _exec_stage(s: Expr, x: jax.Array, engine, batched: bool,
                axis: int) -> jax.Array:
    """Dispatch ONE primitive/fused stage (the run_program loop body)."""
    if isinstance(s, Perm):
        return perm_apply(x, s.bmmc, engine, batched)
    if isinstance(s, FusedStage):
        return fused_apply(x, s, engine, batched)
    if isinstance(s, CmpHalves):
        h = x.shape[axis] // 2
        lo = jax.lax.slice_in_dim(x, 0, h, axis=axis)
        hi = jax.lax.slice_in_dim(x, h, 2 * h, axis=axis)
        return jnp.concatenate([jnp.minimum(lo, hi), jnp.maximum(lo, hi)],
                               axis=axis)
    if isinstance(s, Bfly):
        return _apply_bfly(x, s.twiddles, axis)
    if isinstance(s, Map):
        return s.fn(x)
    from ..guard.errors import BadStage
    raise BadStage(f"non-primitive stage {type(s).__name__}; "
                   "lower() the expression first")


def run_program(program: Sequence[Expr], x: jax.Array,
                engine: Union[str, EngineFn, None] = None,
                *, batched: bool = False) -> jax.Array:
    """Execute a lowered (primitive-only) stage program.

    Differentiable: ``Perm`` stages go through :func:`perm_apply` (offline
    -inverted backward pass), the rest are plain jnp. ``batched=True``
    moves the permuted axis to axis 1, with a leading batch dim.

    When telemetry is enabled each stage records a ``stage.*`` span and
    standalone computes count as ``sweep`` kernel dispatches (matching
    :func:`repro.combinators.optimize.program_cost`); the check is one
    module attribute, so the disabled path is the plain loop below.
    """
    get_engine(engine)  # validate the name up front, even for Perm-free
    axis = 1 if batched else 0
    if not _otrace._state.enabled:
        for s in program:
            x = _exec_stage(s, x, engine, batched, axis)
        return x
    for s in program:
        kind = type(s).__name__.lower()
        with _otrace.span("stage." + kind):
            x = _exec_stage(s, x, engine, batched, axis)
        if isinstance(s, COMPUTES):
            # a standalone compute pays one full elementwise HBM sweep —
            # the same unit program_cost charges it
            _ometrics.inc("dispatch.kernel", kernel="sweep")
            _ometrics.inc("model.round_trips", 1)
    return x


# ---------------------------------------------------------------------------
# compile_expr — the compiled-plan cache
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1024)
def _lowered_cached(expr: Expr, n: int, optimized: bool) -> Program:
    with _otrace.span("plan.lower", n=n):
        prog = lower(expr, n)
        return fuse(prog) if optimized else prog


@functools.lru_cache(maxsize=1024)
def _clustered_cached(expr: Expr, n: int, optimized: bool,
                      t: int) -> tuple:
    prog = _lowered_cached(expr, n, optimized)
    with _otrace.span("plan.cluster", n=n, t=t):
        return fold_free(cluster(prog, n, t), n, t)


# ---------------------------------------------------------------------------
# Whole-program executables: ONE jitted callable per (program, engine,
# batched) key. All per-stage Python work — plan-cache lookups, table ->
# device conversion, DMA descriptor enumeration, kernel re-dispatch —
# happens once at trace time; the offline tables are baked into the
# jaxpr as constants. Repeated calls pay a single XLA dispatch instead
# of one Python round per stage (the host-side overhead that dominates
# multi-stage programs: the 2^12 sort re-dispatched 79 fused stages per
# call before this cache). The key is independent of batch size, dtype
# and trailing dims — jax.jit re-specializes on those internally without
# growing this cache.
# ---------------------------------------------------------------------------


def _has_map(prog: Program) -> bool:
    """Does the program carry a user ``Map`` callable (top-level or
    inside a cluster's replay stages)?"""
    return any(isinstance(s, Map)
               or (isinstance(s, FusedStage)
                   and any(isinstance(ss, Map) for ss in s.stages))
               for s in prog)


@functools.lru_cache(maxsize=512)
def _program_executable(prog: Program, engine: str, batched: bool):
    def run(x):
        return run_program(prog, x, engine, batched=batched)
    return jax.jit(run)


@functools.lru_cache(maxsize=512)
def _inverse_program_cached(prog: Program) -> Program:
    """The offline-inverted program (clusters invert to clusters) —
    what :func:`program_apply`'s backward dispatches."""
    return inverse_program(prog)


def _observed_program_call(prog: Program, x: jax.Array, engine,
                           batched: bool, use_exec: bool) -> jax.Array:
    """The telemetry-enabled whole-program call path: one
    ``program.call`` span + latency histogram per invocation, warm/cold
    labeled by whether a fresh jit trace ran. Inside it, the executable's
    cache lookup (``program.lookup``) is timed apart from its call up to
    its return (``program.enqueue``). Blocks on the result only when
    ``obs.enable(sync=True)`` asked for end-to-end timings."""
    eng = engine if isinstance(engine, str) else "injected"
    with _otrace.span("program.call", engine=eng, stages=len(prog),
                      path="executable" if use_exec else "per-stage",
                      batched=batched) as sargs:
        t0 = time.perf_counter_ns()
        if use_exec:
            misses0 = _program_executable.cache_info().misses
            with _otrace.span("program.lookup"):
                run = _program_executable(prog, engine, batched)
            cold = _program_executable.cache_info().misses > misses0
            with _otrace.span("program.enqueue"):
                out = run(x)
        else:
            out = run_program(prog, x, engine, batched=batched)
            cold = False
        if _otrace._state.sync:
            jax.block_until_ready(out)
        dur_us = (time.perf_counter_ns() - t0) / 1e3
        sargs["dur_us"] = round(dur_us, 1)
        sargs["cache"] = "cold" if cold else "warm"
    _ometrics.observe("program.call_us", dur_us, engine=eng,
                      cache="cold" if cold else "warm")
    return out


def _dispatch_program(prog: Program, x: jax.Array, engine,
                      batched: bool) -> jax.Array:
    """Run a resolved program: whole-program executable when the engine
    is named and the program carries no user ``Map`` (one XLA dispatch
    per call), eager per-stage otherwise; observed when telemetry is on."""
    use_exec = isinstance(engine, str) and not _has_map(prog)
    if not _otrace._state.enabled:
        if use_exec:
            return _program_executable(prog, engine, batched)(x)
        return run_program(prog, x, engine, batched=batched)
    return _observed_program_call(prog, x, engine, batched, use_exec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def program_apply(x: jax.Array, prog: Program, t: Optional[int],
                  engine: Union[str, EngineFn, None] = None,
                  batched: bool = False) -> jax.Array:
    """Differentiable whole-program execution.

    Forward and backward are SYMMETRIC compiled programs, and the whole
    call is ONE custom-vjp boundary (the per-stage ``perm_apply`` /
    ``fused_apply`` rules never fire under it):

    - permutation-only programs dispatch the offline-inverted program —
      the *clustered* inverse of a clustered forward, so every stage
      keeps its kernel class — through its own ``(program, engine,
      batched)`` whole-program executable entry; NO residuals are saved.
    - compute-bearing programs run the COLLAPSED backward
      (:func:`_program_bwd_plan`): every transposed pairwise compute
      conjugated into forward-output coordinates, then ONE composed
      inverse BMMC pass. Residuals are the inputs of compute-bearing
      stages only (a permutation needs none).
    - anything else (``Map`` stages, complex dtypes, non-planar
      butterflies) falls back to the per-stage ``jax.vjp`` replay.
    """
    return _dispatch_program(prog, x, engine, batched)


def _program_apply_fwd(x, prog, t, engine, batched):
    if is_perm_program(prog):
        return program_apply(x, prog, t, engine, batched), None
    res = [x]
    v = x
    for st in prog:
        if isinstance(st, (CmpHalves, Bfly, Map)) or (
                isinstance(st, FusedStage) and st.computes):
            res.append(v)
        v = run_program((st,), v, engine, batched=batched)
    return v, tuple(res)


def _program_apply_bwd(prog, t, engine, batched, res, ct):
    if is_perm_program(prog):
        return (_vjp_observed("program", lambda: program_apply(
            ct, _inverse_program_cached(prog), t, engine, batched)),)
    plan = _program_bwd_plan(prog, batched)
    lead = 1 if batched else 0
    planar = ct.ndim == 2 + lead and ct.shape[-1] == 2
    if plan is None or jnp.iscomplexobj(ct) or (
            plan.has_bfly and not planar):
        x0 = res[0]

        def replay():
            _, vjp = jax.vjp(lambda v: run_program(
                prog, v, engine, batched=batched), x0)
            return vjp(ct)[0]

        return (_vjp_observed("program", replay),)
    return (_vjp_observed("program", lambda: _collapsed_bwd(
        plan, res, ct, engine, batched)),)


program_apply.defvjp(_program_apply_fwd, _program_apply_bwd)


CacheStats = collections.namedtuple(
    "CacheStats", ["hits", "misses", "maxsize", "currsize"])


def cache_stats() -> Dict[str, CacheStats]:
    """Aggregate stats for EVERY executor/ops cache, by name.

    Covers the kernel-executable caches (``geom`` / ``block`` / ``lane``
    / ``program``), the plan/table caches (``fused_plan`` / ``w_planar``
    / ``lowered`` / ``clustered`` and the ops
    ``plans`` / ``class_plan``), and the ``compiled_exprs`` memo.
    Replaces the old single-cache ``geom_cache_info`` /
    ``program_cache_info`` pair, which made every other cache invisible.
    """
    from ..kernels import ops

    out = {
        "geom": _geom_executable,
        "block": _block_executable,
        "lane": _lane_executable,
        "program": _program_executable,
        "fused_plan": _fused_plan_cached,
        "w_planar": _w_planar_cached,
        "lowered": _lowered_cached,
        "clustered": _clustered_cached,
        "inverse_program": _inverse_program_cached,
        "fused_inverse": _fused_inverse_cached,
        "program_bwd_plan": _program_bwd_plan,
        "fused_bwd_kernel_plan": _fused_bwd_kernel_plan,
        "geom_bwd": _geom_bwd_executable,
        "pulled_back": _pulled_back_fn,
        "plans": ops._plans_cached,
        "class_plan": ops._class_plan_cached,
    }
    stats = {name: CacheStats(*fn.cache_info()) for name, fn in out.items()}
    stats["compiled_exprs"] = CacheStats(
        hits=_compiled_stats["hits"], misses=_compiled_stats["misses"],
        maxsize=None, currsize=len(_COMPILED))
    from ..guard.validate import guard_cache_stats
    for name, info in guard_cache_stats().items():
        stats[name] = CacheStats(*info)
    from .. import store as _store
    ss = _store.stats()
    st = _store.active()
    stats["store"] = CacheStats(
        hits=ss["hit"], misses=ss["miss"], maxsize=None,
        currsize=st.entry_count() if st is not None else 0)
    return stats


class CompiledExpr:
    """A callable compiled combinator expression — a first-class JAX value.

    Calling it executes the (fused) stage program through the chosen
    engine; the result is jit-able, ``jax.grad``-able (``Perm`` stages
    carry the offline-inverted custom VJP) and batchable via
    ``batched=True`` (leading batch dim sharing one tile plan).
    ``program(n)`` exposes the stage program for inspection; ``cost(n,
    t)`` the modeled transaction report; ``vjp_program(n)`` the exact
    program the backward pass of a permutation-only expression executes.
    """

    def __init__(self, expr: Expr, engine: Union[str, EngineFn],
                 optimized: bool):
        self.expr = expr
        self.engine = engine
        self.optimized = optimized

    def program(self, n: int) -> Program:
        return _lowered_cached(self.expr, n, self.optimized)

    def clustered_program(self, n: int, t: int) -> tuple:
        """The program with ``Perm → compute → Perm`` runs grouped into
        megakernel :class:`FusedStage`\\ s for tile parameter ``t`` —
        what the "pallas" engine actually executes."""
        return _clustered_cached(self.expr, n, self.optimized, t)

    def cost(self, n: int, t: int, itemsize: int = 4, *,
             clustered: bool = False) -> dict:
        from .optimize import program_cost
        prog = (self.clustered_program(n, t) if clustered
                else self.program(n))
        return program_cost(prog, t, itemsize)

    def is_permutation(self, n: int) -> bool:
        """True if the program is pure ``Perm`` stages (hence invertible)."""
        return all(isinstance(s, Perm) for s in self.program(n))

    def vjp_program(self, n: int, t: Optional[int] = None) -> Program:
        """The offline-inverted program (reversed stages, each BMMC
        inverted) — what the cotangent flows through. With ``t`` the
        CLUSTERED inverse — clusters invert to clusters (§13), which is
        exactly what the "pallas" backward executes. Permutation-only."""
        prog = self.program(n) if t is None else self.clustered_program(n, t)
        return inverse_program(prog)

    def vjp_round_trips(self, n: int, t: Optional[int],
                        batched: bool = False) -> Optional[int]:
        """Modeled HBM round trips of ONE backward (cotangent) pass —
        what a cold backward call's ``model.round_trips`` counter delta
        should equal (the backward honesty gate, DESIGN.md §13).
        Permutation-only programs dispatch the clustered inverse
        program; compute-bearing programs with a collapsed plan pay
        exactly the final composed pass. None when the backward is the
        per-stage replay (no compiled model to hold it against)."""
        from .optimize import program_cost
        prog = (self.clustered_program(n, t)
                if self.engine == "pallas" and self.optimized
                and t is not None else self.program(n))
        if is_perm_program(prog):
            if t is None:
                return None
            return program_cost(inverse_program(prog), t)["round_trips"]
        plan = _program_bwd_plan(prog, batched)
        if plan is None or t is None:
            return None
        if plan.final is None:
            return 0
        return program_cost((plan.final,), t)["round_trips"]

    def inverse(self, n: int) -> "CompiledExpr":
        """The compiled inverse of a permutation-only expression."""
        from .ir import seq
        inv = seq(*self.vjp_program(n))
        return compile_expr(inv, engine=self.engine, optimize=self.optimized)

    def _resolve(self, x: jax.Array, batched: bool) -> tuple:
        """(program, tile parameter) the executor will run on ``x``."""
        from ..guard.errors import BadInput
        axis = 1 if batched else 0
        if x.ndim <= axis:
            what = ("a leading batch dim plus the permuted axis" if batched
                    else "a permutable leading axis")
            raise BadInput(f"input needs {what}, got shape {x.shape}")
        n = int(x.shape[axis]).bit_length() - 1
        if (1 << n) != x.shape[axis]:
            raise BadInput(
                f"array length {x.shape[axis]} is not a power of 2")
        from ..kernels.ops import choose_tile
        d = x.shape[axis + 1] if x.ndim == axis + 2 else 1
        t = choose_tile(n, x.dtype.itemsize, d)
        prog = self.program(n)
        if self.engine == "pallas" and self.optimized and t is not None:
            # megakernel clustering + free-stage folding; the ref oracle
            # and injected engines stay stage-at-a-time
            prog = self.clustered_program(n, t)
        from .. import guard as _g
        if _g.enabled():
            # ring 1: prove the resolved program's invariants (BMMC
            # invertibility, class-predicate consistency, descriptor
            # bounds) before any executable bakes its tables in. Cached
            # per (program, t); warm calls pay an identity-memo hit
            # (the deep program-tuple hash is too slow per call).
            from ..guard.validate import validate_program_fast
            validate_program_fast(tuple(prog), t)
        return prog, t

    def _resolve_program(self, x: jax.Array, batched: bool) -> Program:
        return self._resolve(x, batched)[0]

    def __call__(self, x: jax.Array, *, batched: bool = False) -> jax.Array:
        if not _otrace._state.enabled:
            prog, t = self._resolve(x, batched)
            run, args = self._route(prog, t, x, batched)
            return run(*args)
        with _otrace.span("entry.resolve"):
            prog, t = self._resolve(x, batched)
        with _otrace.span("entry.route"):
            run, args = self._route(prog, t, x, batched)
        with _otrace.span("entry.apply"):
            return run(*args)

    def _route(self, prog: Program, t: Optional[int], x: jax.Array,
               batched: bool) -> tuple:
        """The dispatch that runs the resolved program on ``x``: a
        function and the arguments to call it with."""
        from .. import guard as _g
        if _g.enabled():
            from ..guard import runtime as _grt
            if _grt._trace_state_clean(x):
                # ring 2: guarded dispatch — program + in-program
                # probes in one executable (wrapping the inner jitted
                # _program_executable, so the cache/telemetry contracts
                # hold), flags resolved at this edge, with the pallas →
                # ref fallback machine on a trap. Skipped under an
                # outer trace (the flag readback needs a concrete
                # value); ring 1 in _resolve still ran.
                return _grt.guarded_call, (prog, t, x, self.engine, batched)
        # Programs carrying user Map callables stay on the eager
        # per-stage path (inside _dispatch_program): Map's contract says
        # "a jax function", but eager execution historically tolerated
        # trace-unsafe fns (concrete-value branching, numpy round trips)
        # and wrapping them in jit would turn that tolerance into a crash.
        if is_perm_program(prog):
            # permutation-only: the whole call is ONE custom-vjp
            # primitive whose backward dispatches the precompiled
            # inverse program. Warm the inverse's executable-cache
            # entry alongside the forward so a training step's first
            # backward pays no extra Python-side cache miss.
            if isinstance(self.engine, str):
                _program_executable(_inverse_program_cached(prog),
                                    self.engine, batched)
            return program_apply, (x, prog, t, self.engine, batched)
        if (not _has_map(prog)
                and _program_bwd_plan(prog, batched) is not None):
            # compute-bearing program with a collapsed backward plan:
            # one custom-vjp boundary; the backward sweeps every
            # transposed pairwise compute in forward-output coordinates
            # and finishes with ONE composed inverse BMMC pass (§13)
            return program_apply, (x, prog, t, self.engine, batched)
        return _dispatch_program, (prog, x, self.engine, batched)

    def call_per_stage(self, x: jax.Array, *,
                       batched: bool = False) -> jax.Array:
        """Execute stage-at-a-time through the Python dispatcher, with
        no whole-program executable. Tests check ``__call__`` against it
        bitwise and drive the per-stage backward through it; it is also
        a debugging aid."""
        prog = self._resolve_program(x, batched)
        return run_program(prog, x, self.engine, batched=batched)


_COMPILED: Dict[tuple, CompiledExpr] = {}
_compiled_stats = {"hits": 0, "misses": 0}


def clear_caches() -> None:
    """Drop every compiled artifact the executor pins, and reset the
    telemetry counters/spans with them (cache hygiene: hit/miss counts
    and dispatch counters describe the caches being dropped).

    The geometry / block / lane / whole-program executable caches hold
    jitted pallas executables (each pinning a traced kernel),
    ``_COMPILED`` grows one entry per ``(expr, engine, optimize)``
    triple, and the plan/table caches hold offline numpy tables — none
    of which is bounded across a long geometry sweep. Test fixtures that
    iterate many sizes/dtypes call this between sweeps to keep memory
    flat.
    """
    from ..kernels import ops
    from .. import obs

    _geom_executable.cache_clear()
    _block_executable.cache_clear()
    _lane_executable.cache_clear()
    _program_executable.cache_clear()
    _fused_plan_cached.cache_clear()
    _w_planar_cached.cache_clear()
    _lowered_cached.cache_clear()
    _clustered_cached.cache_clear()
    _inverse_program_cached.cache_clear()
    _fused_inverse_cached.cache_clear()
    _program_bwd_plan.cache_clear()
    _fused_bwd_kernel_plan.cache_clear()
    _geom_bwd_executable.cache_clear()
    _pulled_back_fn.cache_clear()
    _pulled_back_tables.cache_clear()
    _COMPILED.clear()
    _compiled_stats["hits"] = _compiled_stats["misses"] = 0
    ops._plans_cached.cache_clear()
    ops._class_plan_cached.cache_clear()
    from ..guard.validate import clear_guard_caches
    clear_guard_caches()
    from .. import guard, resilience, store
    guard.reset_stats()
    store.reset_stats()
    resilience.reset()
    obs.reset()


def compile_expr(expr: Expr, *, engine: Union[str, EngineFn] = "pallas",
                 optimize: bool = True) -> CompiledExpr:
    """Compile ``expr`` to a jit-able function running minimal tiled passes.

    Lowered/fused programs, kernel plans, and kernel executables are all
    cached, so repeated calls (and repeated ``compile_expr`` of the same
    expression) share everything expensive.
    """
    key = (expr, engine if isinstance(engine, str) else id(engine), optimize)
    got = _COMPILED.get(key)
    if got is None:
        _compiled_stats["misses"] += 1
        got = _COMPILED[key] = CompiledExpr(expr, engine, optimize)
    else:
        _compiled_stats["hits"] += 1
    return got
