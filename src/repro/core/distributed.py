"""Distributed BMMC permutations over sharded arrays (beyond-paper).

For an array of 2^n elements sharded along the leading axis over 2^s
devices, the global index splits as x = (shard || local). This module
factors any global BMMC into a short sequence of *rounds*:

* ``LocalRound``   — per-shard BMMC on local indices, with a shard-dependent
                     complement (``c_eff = c ^ A_ls . shard``): zero
                     communication;
* ``PermuteRound`` — an affine relabeling of shards
                     (``shard' = S . shard ^ c_s``): one collective_permute;
* ``ExchangeRound``— swap the top-k local index bits with the low-k shard
                     bits: one (sub-axis) all_to_all.

Construction (generalizing paper §5.2 to the sharded setting): a matrix
with ``A_sl = 0`` is shard-separable (one local round, one permute). A
bit permutation, with or without a complement, lowers to [permute,
local, exchange(k), local, permute], k the bits that change sides, its
complement split between the last local and permute rounds. Any other
matrix takes the F2 decomposition A = U L P and L = R U' R (R = bit
reversal),

    A  =  U  ∘  R  ∘  U'  ∘  (R P)

where U, U' are shard-*separable* (upper-triangular => shard-out depends
only on shard-in) and R, RP are bit permutations. Adjacent rounds of one
type are fused, and identities dropped, until nothing merges: at most
**2 exchange rounds** — the sharded analogue of the paper's two-pass
theorem.

Every plan is verified by composing the rounds back into a global BMMC
(`plan_to_bmmc(plan) == A`). The executor (`make_distributed_bmmc`) is
one jitted shard_map over a binary sub-axis mesh: every local round runs
the class-dispatched Pallas kernels of `kernels/ops.py` on the chip's
shard, and a complement that depends on the shard runs one kernel whose
tables, one set per shard, are chosen by the shard's mesh index
(`ops.bmmc_permute_complements`). It is validated on fake multi-device
CPU meshes in tests/test_distributed.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Union

from ..obs import metrics as _ometrics
from ..obs import trace as _otrace
from . import f2
from .bmmc import Bmmc


# ---------------------------------------------------------------------------
# Round IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LocalRound:
    n_local: int
    rows: tuple          # (n_local) x (n_local) local matrix
    c: int               # static complement
    ls_rows: tuple       # n_local rows over s shard bits: c_eff ^= ls . shard


@dataclasses.dataclass(frozen=True)
class PermuteRound:
    s: int
    rows: tuple          # s x s shard matrix
    c: int


@dataclasses.dataclass(frozen=True)
class ExchangeRound:
    k: int               # swap local bits [n_local-k, n_local) with shard bits [0, k)


Round = Union[LocalRound, PermuteRound, ExchangeRound]


# ---------------------------------------------------------------------------
# Rounds -> global BMMC (offline verification)
# ---------------------------------------------------------------------------

def round_to_bmmc(r: Round, n: int, s: int) -> Bmmc:
    nl = n - s
    if isinstance(r, LocalRound):
        rows = [r.rows[i] | (r.ls_rows[i] << nl) for i in range(nl)]
        rows += [1 << i for i in range(nl, n)]
        return Bmmc(tuple(rows), r.c)
    if isinstance(r, PermuteRound):
        rows = [1 << i for i in range(nl)]
        rows += [r.rows[i - nl] << nl for i in range(nl, n)]
        return Bmmc(tuple(rows), r.c << nl)
    # ExchangeRound: transpositions local nl-k+m <-> shard nl+m
    p = list(range(n))
    for m in range(r.k):
        p[nl - r.k + m], p[nl + m] = p[nl + m], p[nl - r.k + m]
    return Bmmc.from_perm(p)


def plan_to_bmmc(plan: List[Round], n: int, s: int) -> Bmmc:
    out = Bmmc.identity(n)
    for r in plan:
        out = round_to_bmmc(r, n, s) @ out
    return out


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------

def _split_blocks(b: Bmmc, s: int):
    """A = [[A_ll, A_ls], [A_sl, A_ss]] in the (local, shard) basis."""
    n = b.n
    nl = n - s
    lmask = (1 << nl) - 1
    a_ll = tuple(b.rows[i] & lmask for i in range(nl))
    a_ls = tuple(b.rows[i] >> nl for i in range(nl))
    a_sl = tuple(b.rows[i] & lmask for i in range(nl, n))
    a_ss = tuple(b.rows[i] >> nl for i in range(nl, n))
    return a_ll, a_ls, a_sl, a_ss


def _separable_rounds(b: Bmmc, s: int) -> List[Round]:
    """b with A_sl == 0: local round then shard permute."""
    n = b.n
    nl = n - s
    a_ll, a_ls, a_sl, a_ss = _split_blocks(b, s)
    assert all(v == 0 for v in a_sl), "factor is not shard-separable"
    return [
        LocalRound(nl, a_ll, b.c & ((1 << nl) - 1), a_ls),
        PermuteRound(s, a_ss, b.c >> nl),
    ]


def _local_perm(positions_to_top: List[int], nl: int) -> list:
    """Local bit perm sending sorted(positions) to the top |positions| bits."""
    k = len(positions_to_top)
    rest = [j for j in range(nl) if j not in set(positions_to_top)]
    p = [0] * nl
    for i, j in enumerate(rest):
        p[j] = i
    for m, j in enumerate(sorted(positions_to_top)):
        p[j] = nl - k + m
    return p


def _bp_rounds(b: Bmmc, s: int) -> List[Round]:
    """Bit-permutation factor -> [permute, local, exchange, local, permute]."""
    n = b.n
    nl = n - s
    p = b.perm()
    assert p is not None and b.c == 0, "expected a BP factor"
    a2 = [j for j in range(nl) if p[j] >= nl]          # local -> shard
    b2 = [j for j in range(nl, n) if p[j] < nl]        # shard -> local
    k = len(a2)
    assert len(b2) == k
    rounds: List[Round] = []

    # sigma1: relabel shard bits so the departing ones (b2) occupy the
    # exchange window [0, k); the rest stack above in order.
    b2_bits = set(j - nl for j in b2)
    sig1 = [0] * s
    m = 0
    for j in sorted(b2_bits):
        sig1[j] = m
        m += 1
    fill = k
    for j in range(s):
        if j not in b2_bits:
            sig1[j] = fill
            fill += 1
    rounds.append(PermuteRound(s, f2.from_perm(sig1), 0))

    # L1: move the departing local bits (a2) to the top-k local positions
    l1 = _local_perm(a2, nl)
    rounds.append(LocalRound(nl, f2.from_perm(l1), 0, tuple([0] * nl)))

    if k:
        rounds.append(ExchangeRound(k))

    # solve the remainder: rho = b ∘ (sigma1;l1;X)^-1 must be block diagonal
    partial = plan_to_bmmc(rounds, n, s)
    rho = b @ partial.inverse()
    a_ll, a_ls, a_sl, a_ss = _split_blocks(rho, s)
    assert all(v == 0 for v in a_sl), "bp residue: shard<-local leak"
    assert all(v == 0 for v in a_ls), "bp residue: local<-shard leak"
    rounds.append(LocalRound(nl, a_ll, 0, tuple([0] * nl)))
    rounds.append(PermuteRound(s, a_ss, 0))
    return rounds


def _merge(prev: Round, r: Round, nl: int, s: int) -> Optional[Round]:
    """``r ∘ prev`` as one round when both are local or both permute
    rounds; None otherwise."""
    if isinstance(r, LocalRound) and isinstance(prev, LocalRound):
        rows = f2.matmul(r.rows, prev.rows)
        # combine: y = R2 (R1 x ^ L1 sigma ^ c1) ^ L2 sigma ^ c2
        ls_cols = []
        for bit in range(s):
            col_prev = sum(((prev.ls_rows[i] >> bit) & 1) << i
                           for i in range(nl))
            col_new = f2.matvec(r.rows, col_prev)
            col_new ^= sum(((r.ls_rows[i] >> bit) & 1) << i
                           for i in range(nl))
            ls_cols.append(col_new)
        ls = tuple(sum(((ls_cols[bit] >> i) & 1) << bit
                       for bit in range(s)) for i in range(nl))
        c = f2.matvec(r.rows, prev.c) ^ r.c
        return LocalRound(nl, rows, c, ls)
    if isinstance(r, PermuteRound) and isinstance(prev, PermuteRound):
        return PermuteRound(s, f2.matmul(r.rows, prev.rows),
                            f2.matvec(r.rows, prev.c) ^ r.c)
    return None


def _is_identity(r: Round, nl: int, s: int) -> bool:
    if isinstance(r, LocalRound):
        return (r.rows == f2.identity(nl) and r.c == 0
                and not any(r.ls_rows))
    if isinstance(r, PermuteRound):
        return r.rows == f2.identity(s) and r.c == 0
    return r.k == 0


def _fuse(plan: List[Round], n: int, s: int) -> List[Round]:
    """Merge adjacent same-type rounds and drop identities, until
    nothing merges: dropping an identity can make two rounds of one
    type adjacent."""
    nl = n - s
    while True:
        out: List[Round] = []
        for r in plan:
            if _is_identity(r, nl, s):
                continue
            merged = _merge(out[-1], r, nl, s) if out else None
            if merged is None:
                out.append(r)
            else:
                out[-1] = merged
        if out == plan:
            return out
        plan = out


def make_plan(bmmc: Bmmc, s: int) -> List[Round]:
    """Factor a global BMMC into rounds for 2^s leading-axis shards."""
    n = bmmc.n
    assert 0 < s < n
    nl = n - s
    a_ll, a_ls, a_sl, a_ss = _split_blocks(bmmc, s)
    if all(v == 0 for v in a_sl):
        plan = _separable_rounds(bmmc, s)
    elif bmmc.perm() is not None:
        # BP(C): one exchange of the bits that change sides. The
        # complement splits into a local and a shard part, folded into
        # the last local and permute rounds (the local part commutes
        # with the last round, which moves shard bits only).
        *plan, last = _bp_rounds(Bmmc(bmmc.rows, 0), s)
        plan += [LocalRound(nl, f2.identity(nl), bmmc.c & ((1 << nl) - 1),
                            (0,) * nl),
                 last, PermuteRound(s, f2.identity(s), bmmc.c >> nl)]
    else:
        u, l, p = f2.ulp(bmmc.rows)
        r = f2.reversal(n)
        u2 = f2.matmul(r, f2.matmul(l, r))            # upper (= R L R)
        rp = Bmmc(f2.matmul(r, p), 0)                 # BP
        plan = []
        plan += _bp_rounds(rp, s)
        plan += _separable_rounds(Bmmc(u2, 0), s)
        plan += _bp_rounds(Bmmc.bit_reverse(n), s)
        plan += _separable_rounds(Bmmc(u, bmmc.c), s)
    plan = _fuse(plan, n, s)
    got = plan_to_bmmc(plan, n, s)
    if got.rows != bmmc.rows or got.c != bmmc.c:
        raise AssertionError("plan verification failed")
    return plan


def plan_cost(plan: List[Round]) -> dict:
    return {
        "local": sum(isinstance(r, LocalRound) for r in plan),
        "permute": sum(isinstance(r, PermuteRound) for r in plan),
        "exchange": sum(isinstance(r, ExchangeRound) for r in plan),
        "exchange_bits": sum(r.k for r in plan if isinstance(r, ExchangeRound)),
    }


def plan_bytes(plan: List[Round], s: int, nbytes: int) -> dict:
    """Bytes one call of ``plan`` sends between chips, summed over the
    chips, for an array of ``nbytes``: an exchange of k bits keeps
    2^-k of each shard in place and sends the rest (``all_to_all``); a
    shard permute sends each shard it moves (``ppermute``)."""
    out = {"all_to_all": 0, "ppermute": 0}
    for r in plan:
        if isinstance(r, ExchangeRound):
            out["all_to_all"] += nbytes - (nbytes >> r.k)
        elif isinstance(r, PermuteRound):
            moved = sum(f2.matvec(r.rows, g) ^ r.c != g for g in range(1 << s))
            out["ppermute"] += moved * (nbytes >> s)
    return out


# ---------------------------------------------------------------------------
# Executor (shard_map over a binary sub-axis mesh)
# ---------------------------------------------------------------------------

def binary_mesh(s: int, devices=None):
    """Mesh of 2^s devices (the first 2^s of ``jax.devices()`` unless
    given) as s binary axes sb{s-1}..sb0 (msb first)."""
    import jax
    names = tuple(f"sb{m}" for m in reversed(range(s)))
    return jax.make_mesh((2,) * s, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * s,
                         devices=devices)


def shard_spec(s: int):
    """The leading axis split over all s binary mesh axes, msb first."""
    from jax.sharding import PartitionSpec as P
    return P(tuple(f"sb{m}" for m in reversed(range(s))))


@functools.lru_cache(maxsize=64)
def _plan_executable(plan: tuple, s: int, mesh):
    """One jitted shard_map program per (plan, mesh). Local rounds run
    the class-dispatched Pallas kernels of ``ops``; a complement that
    depends on the shard takes the tables of the shard's own index."""
    import jax
    import jax.numpy as jnp
    from ..kernels import ops

    names_msb = tuple(f"sb{m}" for m in reversed(range(s)))

    def shard_fn(xs):
        nbytes = xs.size * xs.dtype.itemsize << s
        for r in plan:
            kind = ("local" if isinstance(r, LocalRound) else
                    "permute" if isinstance(r, PermuteRound) else "exchange")
            _ometrics.inc("dist.rounds", kind=kind)
        for kind, v in plan_bytes(list(plan), s, nbytes).items():
            _ometrics.inc("dist.bytes", v, kind=kind)
        shard = jnp.zeros((), jnp.int32)
        for m in range(s):
            shard = shard | (jax.lax.axis_index(f"sb{m}").astype(jnp.int32)
                             << m)
        nl = xs.shape[0].bit_length() - 1
        for r in plan:
            if isinstance(r, LocalRound):
                # c_eff = c ^ (ls . shard), one complement per shard
                cs = tuple(r.c ^ f2.matvec(r.ls_rows, g)
                           for g in range(1 << s))
                xs = ops.bmmc_permute_complements(xs, r.rows, cs, shard)
            elif isinstance(r, PermuteRound):
                pairs = [(g, f2.matvec(r.rows, g) ^ r.c)
                         for g in range(1 << s)]
                xs = jax.lax.ppermute(xs, names_msb, pairs)
            else:  # ExchangeRound
                k = r.k
                tail = xs.shape[1:]
                xs2 = xs.reshape((1 << k, 1 << (nl - k)) + tail)
                ex_names = tuple(f"sb{m}" for m in reversed(range(k)))
                xs2 = jax.lax.all_to_all(xs2, ex_names, split_axis=0,
                                         concat_axis=0, tiled=True)
                xs = xs2.reshape((1 << nl,) + tail)
        return xs

    spec = shard_spec(s)
    # check_vma=False: the kernels' pallas_call outputs carry no
    # varying-axes annotation
    return jax.jit(jax.shard_map(shard_fn, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


@functools.lru_cache(maxsize=256)
def _cached_plan(rows: tuple, c: int, s: int) -> tuple:
    with _otrace.span("plan.dist", n=len(rows), s=s):
        return tuple(make_plan(Bmmc(rows, c), s))


def make_distributed_bmmc(bmmc: Bmmc, s: int, mesh=None):
    """A function applying ``bmmc`` to an array of shape (2^n,) or
    (2^n, d) whose leading axis is split over the 2^s devices of
    ``mesh`` (default :func:`binary_mesh`), as :func:`shard_spec` says.
    The first call builds the plan, once per (matrix, s), as
    ``ops.make_bmmc_permute`` plans at its first trace; the function is
    one jitted ``shard_map`` program, traced once per shape and dtype."""
    mesh = mesh or binary_mesh(s)
    fn = None

    def call(x):
        nonlocal fn
        with _otrace.span("dist.call", n=bmmc.n, s=s):
            if fn is None:
                fn = _plan_executable(_cached_plan(bmmc.rows, bmmc.c, s),
                                      s, mesh)
            return fn(x)
    return call


def distributed_bmmc(x, bmmc: Bmmc, s: int, mesh=None):
    """End-to-end: plan + execute a BMMC over a 2^s-sharded array."""
    return make_distributed_bmmc(bmmc, s, mesh)(x)
