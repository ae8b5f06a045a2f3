"""Paper §7: merge sort with a balanced periodic merger, as a combinator
expression.

The declarative network (``parm`` recursion in repro.combinators.sort)
lowers to a [BMMC permute | compare-exchange] stage program; BMMC fusion
collapses ~30x of the permutation stages, and each remaining BMMC runs
as <=2 fully-coalesced tiled kernel passes.

Run: PYTHONPATH=src python examples/sorting_network.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.combinators import fuse, lower, num_perm_stages
from repro.combinators.sort import compiled_sort, sort_expr
from repro.core.sort import sort_rec


def main():
    n = 10
    xs = np.random.default_rng(0).integers(0, 10**6, size=1 << n).astype(np.int32)

    # reference recursion (paper pseudocode, numpy)
    ref = sort_rec(n, xs.copy())
    assert np.array_equal(ref, np.sort(xs))

    # the lazy expression, lowered and fused offline
    raw = lower(sort_expr(n), n)
    prog = fuse(raw)
    print(f"2^{n} elements: {num_perm_stages(raw)} raw perm stages "
          f"-> {num_perm_stages(prog)} fused BMMC stages "
          f"({len(prog) - num_perm_stages(prog)} compare-exchange sweeps)")

    # run through both engines via the compiled-plan cache
    got_ref = np.asarray(compiled_sort(n, engine="ref")(jnp.asarray(xs)))
    pallas_sort = compiled_sort(n, engine="pallas")
    t0 = time.perf_counter()
    got_pallas = np.asarray(pallas_sort(jnp.asarray(xs)))
    dt = time.perf_counter() - t0
    assert np.array_equal(got_ref, np.sort(xs))
    assert np.array_equal(got_pallas, np.sort(xs))
    print(f"sorted correctly via tiled Pallas kernels "
          f"({dt:.2f}s cold on {jax.default_backend()})")
    t0 = time.perf_counter()
    np.asarray(pallas_sort(jnp.asarray(xs)))
    print(f"warm re-run {time.perf_counter() - t0:.3f}s "
          f"(geometry-cached kernel executables)")


if __name__ == "__main__":
    main()
