"""HLO analyzer: collective bytes + trip-weighted flops vs hand counts.

Runs in a subprocess with 8 fake devices (jax device count is locked at
first import in the main test process).
"""
import json
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import analyze_hlo

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
M, N, K, T = 256, 128, 64, 5

def f(x, w):
    def body(c, _):
        c = c @ w
        c = c @ w.T
        return c, ()
    y, _ = jax.lax.scan(body, x, None, length=T)
    return y.sum()

jf = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),
                              NamedSharding(mesh, P(None, "model"))))
comp = jf.lower(jax.ShapeDtypeStruct((M, K), jnp.float32),
                jax.ShapeDtypeStruct((K, N), jnp.float32)).compile()
r = analyze_hlo(comp.as_text())
print(json.dumps(r))
"""


@pytest.mark.slow
def test_analyzer_hand_count():
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                          "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    # per device, 5 loop iterations:
    #  - all-reduce of the (M/4, N/2->K) intermediate: 5 * 64*64*4 B
    #    (+ one scalar f32 all-reduce for the final sum: 4 B)
    assert r["all-reduce"] == 5 * 64 * 64 * 4 + 4
    #  - dots: c@w (out 64x64, contract 64) + c@w.T (out 64x64, contract 64)
    assert r["dot_flops"] == 5 * 2 * (2 * 64 * 64 * 64)
    assert r["collective_total"] == r["all-reduce"]


def test_analyzer_plain_text():
    """Parser handles a minimal synthetic module (no jax involved)."""
    from repro.launch.hlo_analysis import analyze_hlo
    hlo = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %g = f32[8,8] get-tuple-element(%p), index=1
  %ar = f32[8,8] all-reduce(%g), replica_groups={}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %z = s32[] constant(0)
  %tup = (s32[], f32[8,8]) tuple(%z, %a)
  %w = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body
  ROOT %o = f32[8,8] get-tuple-element(%w), index=1
}
"""
    r = analyze_hlo(hlo)
    assert r["all-reduce"] == 3 * 8 * 8 * 4  # trip count 3 from the cond


def test_analyzer_tuple_result_collective():
    """Tuple-typed collectives (XLA-combined ops): operand parens follow the
    opcode, not the result type — regression for the all-to-all undercount."""
    from repro.launch.hlo_analysis import analyze_hlo
    hlo = """
HloModule t

ENTRY %main (a: f32[4,8], b: f32[4,8]) -> f32[4,8] {
  %a = f32[4,8] parameter(0)
  %b = f32[4,8] parameter(1)
  %aa = (f32[4,8], f32[4,8]) all-to-all(%a, %b), replica_groups={}
  %g0 = f32[4,8] get-tuple-element(%aa), index=0
  %ar = (f32[4,8], f32[4,8]) all-reduce(%g0, %b), replica_groups={}, to_apply=%main
  ROOT %o = f32[4,8] get-tuple-element(%ar), index=0
}
"""
    r = analyze_hlo(hlo)
    assert r["all-to-all"] == 2 * 4 * 8 * 4
    assert r["all-reduce"] == 2 * 4 * 8 * 4
