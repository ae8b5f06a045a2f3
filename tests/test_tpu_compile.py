"""Compile-only rehearsals: every kernel class compiled for a described
TPU v5e at real sizes (2^24 int32 for the permutation suite, the 2^20
sort's fused compare cluster, a fused planar FFT butterfly cluster, the
gradient kernel, and the four-chip reshard of 2^26 int32). Nothing runs — the chip's compiler only has to
accept each kernel, which the Pallas interpreter cannot show.

The topology is described inside a module fixture (never at import:
only one process may load the TPU library), and the test steers the
package's single backend decision to the compiled path itself.
"""
import functools
import json
import random
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.combinators import execute as ex
from repro.combinators.fft import compiled_fft
from repro.combinators.ir import Bfly, CmpHalves
from repro.combinators.optimize import FusedStage
from repro.combinators.sort import compiled_sort
from repro.core import distributed
from repro.core.bmmc import Bmmc
from repro.core.tiling import plan_block, plan_bmmc, plan_lane
from repro.kernels import bmmc_permute as bp
from repro.kernels.ops import choose_tile

N = 24


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip compile written to the persistent cache cannot be read back
    # without the chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(monkeypatch, one_chip):
    """Compile ``fn`` at the given shapes for one described v5e chip and
    return the HLO text; the kernels take their compiled (not
    interpreted) path for the duration of the test."""
    monkeypatch.setattr(bp, "interpret_mode", lambda: False)

    def compile_text(fn, *shapes):
        specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in shapes]
        return jax.jit(fn).lower(*specs).compile().as_text()
    return compile_text


def _has_kernel(text: str) -> bool:
    return "tpu_custom_call" in text


# runs per box side (input, output); None where a side is no box
_BOX_RUNS = {"bit_reverse": (1, 1), "bpc": (5, 3), "bmmc": (None, None)}


@pytest.mark.parametrize("name,transpose", [
    pytest.param(name, tr, id=name + ("-transposed" if tr else ""))
    for name in sorted(_BOX_RUNS) for tr in (False, True)])
def test_tiled_pass_compiles(chip, name, transpose):
    """The paper suite's passes: bit-reverse and the random BPC of
    ``paper_fig9.cases`` take strided box descriptors on both sides, the
    random BMMC per-row descriptors; each with its intra-tile gather
    factored in either orientation (the transposed one transposes the
    512 x 512 tile first)."""
    b = {"bit_reverse": lambda: Bmmc.bit_reverse(N),
         "bpc": lambda: Bmmc.random_bpc(N, random.Random(42)),
         "bmmc": lambda: Bmmc.random(N, random.Random(7))}[name]()
    t = choose_tile(N, 4)
    (plan,) = plan_bmmc(b, t)
    assert tuple(box and len(box) for box in (plan.in_box, plan.out_box)) \
        == _BOX_RUNS[name]
    (intra,) = [im for im in plan.intra_options
                if im.steps.transpose == transpose]
    fn = functools.partial(
        bp.tiled_permute_tables,
        geometry=bp.plan_geometry(plan)[:7] + (intra.steps,))
    tables = (plan.in_rows, plan.out_rows, intra.ktab, intra.words)
    assert _has_kernel(chip(lambda x: fn(x, *tables), ((1 << N,), jnp.int32)))


def test_block_and_lane_compile(chip):
    t = choose_tile(N, 4)
    perm = list(range(N))     # swap two high bits: whole blocks move
    perm[N - 1], perm[t + 2] = perm[t + 2], perm[N - 1]
    block = Bmmc.from_perm(perm)
    bplan = plan_block(block, t)
    assert bplan is not None
    lane = Bmmc.from_perm(list(reversed(range(t))) + list(range(t, N)))
    lplan = plan_lane(lane, t)
    assert lplan is not None
    x = ((1 << N,), jnp.int32)
    assert _has_kernel(chip(lambda v: bp.block_permute_tables(
        v, bplan.src_rows, geometry=bp.block_geometry(bplan)), x))
    assert _has_kernel(chip(lambda v: bp.lane_permute_tables(
        v, lplan.src_lane, geometry=bp.lane_geometry(lplan)), x))
    assert _has_kernel(chip(bp.copy_through_vmem, x))


def _cluster(prog, kind, want: int):
    """The first cluster carrying ``want`` computes of ``kind``."""
    for s in prog:
        if isinstance(s, FusedStage) and sum(
                isinstance(c, kind) for c, _ in s.computes) >= want:
            return s
    raise AssertionError("no such cluster")


def _fused_pass(fs, t, dtype, inverse=False, d=1):
    plans, entries = ex._fused_plan_cached(fs, t)
    sig, scal, vmem, map_fns = ex._fused_kernel_args(entries, dtype)
    geom = bp.plan_geometry(plans[0], inverse=inverse, d=d)
    tables = bp.plan_tables(plans[0], inverse=inverse, d=d)
    kern = bp.tiled_permute_bwd_tables if inverse else bp.tiled_permute_tables
    fn = functools.partial(kern, geometry=geom, epilogue=sig,
                           map_fns=map_fns)
    return fn, tables, scal, vmem


def test_fused_sort_cmp_cluster_compiles(chip):
    n = 20
    t = choose_tile(n, 4)
    fs = _cluster(compiled_sort(n, engine="pallas").clustered_program(n, t),
                  CmpHalves, 3)
    plans, _ = ex._fused_plan_cached(fs, t)
    assert plans[0].in_box and plans[0].out_box
    fn, tables, scal, vmem = _fused_pass(fs, t, jnp.float32)
    text = chip(lambda x: fn(x, *tables, epi_scalar=scal, epi_vmem=vmem),
                ((1 << n,), jnp.float32))
    assert _has_kernel(text)


def test_fused_fft_bfly_cluster_compiles(chip):
    n = 16
    t = choose_tile(n, 4, 2)
    fs = _cluster(compiled_fft(n, engine="pallas").clustered_program(n, t),
                  Bfly, 2)
    fn, tables, scal, vmem = _fused_pass(fs, t, jnp.float32, d=2)
    text = chip(lambda x: fn(x, *tables, epi_scalar=scal, epi_vmem=vmem),
                ((1 << n, 2), jnp.float32))
    assert _has_kernel(text)


@pytest.mark.parametrize("transpose", [False, True])
def test_gradient_kernel_with_cmp_compiles(chip, transpose):
    """The gradient megakernel of a sort cluster: the first with three
    compares, and the first whose inverse gather transposes the tile."""
    n = 20
    t = choose_tile(n, 4)
    prog = compiled_sort(n, engine="pallas").clustered_program(n, t)
    fs = (next(s for s in prog if isinstance(s, FusedStage) and s.computes
               and ex._fused_plan_cached(s, t)[0][0].intra_inv.steps.transpose)
          if transpose else _cluster(prog, CmpHalves, 3))
    fn, tables, scal, vmem = _fused_pass(fs, t, jnp.float32, inverse=True)
    x = ((1 << n,), jnp.float32)
    text = chip(lambda v, c: fn(v, c, *tables, epi_scalar=scal,
                                epi_vmem=vmem), x, x)
    assert _has_kernel(text)


RESHARD = json.loads((Path(__file__).resolve().parent.parent / "bench" / "traffic"
                      / "reshard_round_robin.json").read_text())


@pytest.mark.parametrize("name", [op["name"] for op in RESHARD["ops"]])
def test_reshard_compiles_for_four_chips(monkeypatch, topo, name):
    """The reshard cell's sharded programs at 2^26 int32 over the
    described 2x2 host: one compiled Pallas kernel per local round, one
    all-to-all per exchange and one collective permute per shard
    permute, and no gather."""
    monkeypatch.setattr(bp, "interpret_mode", lambda: False)
    op = next(o for o in RESHARD["ops"] if o["name"] == name)
    plan = distributed.make_plan(Bmmc(tuple(op["rows"]), op["c"]), 2)
    mesh = distributed.binary_mesh(2, topo.devices)
    x = jax.ShapeDtypeStruct((1 << 26,), jnp.int32, sharding=NamedSharding(
        mesh, distributed.shard_spec(2)))
    text = distributed._plan_executable(
        tuple(plan), 2, mesh).lower(x).compile().as_text()
    cost = distributed.plan_cost(plan)
    assert text.count("tpu_custom_call") == cost["local"]
    assert len(re.findall(r"all-to-all\(", text)) == cost["exchange"]
    assert len(re.findall(r"collective-permute(-start)?\(", text)) \
        == cost["permute"]
    assert not re.search(r" gather\(", text)
