"""MoE: group-local GSPMD dispatch + explicit all-to-all (shard_map) path.

Both implementations are checked against a dense no-drop reference (large
capacity factor => no token drops => exact agreement is required).
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe import moe_ffn, router_topk


def dense_reference(x2d, rw, wg, wu, wd, k):
    t, e = x2d.shape
    p = jax.nn.softmax(x2d.astype(jnp.float32) @ rw.astype(jnp.float32), -1)
    vals, ids = jax.lax.top_k(p, k)
    w = vals / vals.sum(-1, keepdims=True)
    g = jnp.einsum("te,xef->txf", x2d, wg)
    u = jnp.einsum("te,xef->txf", x2d, wu)
    y_all = jnp.einsum("txf,xfe->txe",
                       jax.nn.silu(g.astype(jnp.float32)).astype(x2d.dtype) * u,
                       wd)
    sel = jnp.take_along_axis(y_all, ids[:, :, None], axis=1)
    return (sel * w[:, :, None].astype(x2d.dtype)).sum(1)


@pytest.mark.parametrize("groups", [1, 4])
def test_moe_ffn_matches_dense_reference(groups):
    t, e, f, x_n, k = 64, 8, 12, 8, 2
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (t, e), jnp.float32)
    rw = jax.random.normal(jax.random.PRNGKey(1), (e, x_n), jnp.float32)
    wg = jax.random.normal(jax.random.PRNGKey(2), (x_n, e, f)) * 0.2
    wu = jax.random.normal(jax.random.PRNGKey(3), (x_n, e, f)) * 0.2
    wd = jax.random.normal(jax.random.PRNGKey(4), (x_n, f, e)) * 0.2
    want = dense_reference(x, rw, wg, wu, wd, k)
    got, aux = moe_ffn(x.reshape(groups, t // groups, e), rw, wg, wu, wd,
                       top_k=k, capacity_factor=8.0)  # no drops
    np.testing.assert_allclose(np.asarray(got.reshape(t, e)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    assert float(aux) > 0


def test_router_topk_weights_normalized():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    w, ids, aux = router_topk(logits, 4)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert ids.shape == (32, 4)


@pytest.mark.tier1
def test_a2a_slot_shuffle_roundtrips_with_metadata():
    """The dispatch_shuffle building block: payload and int metadata take
    the same slot permutation and the inverse restores packing order."""
    from repro.core.bmmc import Bmmc
    from repro.models.moe_a2a import _slot_shuffle

    peers, cap, e = 4, 32, 8
    bmmc = Bmmc.bit_reverse(cap.bit_length() - 1)
    payload = jax.random.normal(jax.random.PRNGKey(0), (peers, cap, e))
    eid = jax.random.randint(jax.random.PRNGKey(1), (peers, cap), 0, 7)
    ps, es = _slot_shuffle(payload, bmmc), _slot_shuffle(eid, bmmc)
    assert not np.array_equal(np.asarray(ps), np.asarray(payload))
    # metadata rides along: the multiset of (eid, payload-row) pairs is
    # preserved within each peer block
    for p in range(peers):
        src = sorted((int(e_),) + tuple(row) for e_, row in
                     zip(np.asarray(eid[p]), np.asarray(payload[p])))
        got = sorted((int(e_),) + tuple(row) for e_, row in
                     zip(np.asarray(es[p]), np.asarray(ps[p])))
        assert src == got
    assert np.array_equal(
        np.asarray(_slot_shuffle(ps, bmmc, inverse=True)),
        np.asarray(payload))
    assert np.array_equal(
        np.asarray(_slot_shuffle(es, bmmc, inverse=True)), np.asarray(eid))
    # differentiable: grad of a shuffled sum-loss is the inverse shuffle
    w = jax.random.normal(jax.random.PRNGKey(2), (peers, cap, e))
    g = jax.grad(lambda x: jnp.sum(w * _slot_shuffle(x, bmmc)))(payload)
    assert np.allclose(np.asarray(g),
                       np.asarray(_slot_shuffle(w, bmmc, inverse=True)))


def test_capacity_drops_tokens():
    """With a tiny capacity factor, some token outputs must be zero."""
    t, e, f, x_n, k = 256, 8, 8, 2, 1
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (1, t, e), jnp.float32)
    rw = jnp.zeros((e, x_n)).at[:, 0].set(1.0)  # all tokens pick expert 0
    wg = jnp.ones((x_n, e, f)) * 0.1
    wu = jnp.ones((x_n, e, f)) * 0.1
    wd = jnp.ones((x_n, f, e)) * 0.1
    out, _ = moe_ffn(x, rw, wg, wu, wd, top_k=k, capacity_factor=0.25)
    zero_rows = np.sum(np.abs(np.asarray(out[0])).sum(-1) == 0)
    assert zero_rows > 0  # overflow beyond capacity was dropped


A2A_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
from repro.models.moe_a2a import moe_ffn_a2a

B, S, E, F, X, K = 2, 16, 8, 12, 8, 2
x = jax.random.normal(jax.random.PRNGKey(0), (B, S, E), jnp.float32)
rw = jax.random.normal(jax.random.PRNGKey(1), (E, X), jnp.float32)
wg = jax.random.normal(jax.random.PRNGKey(2), (X, E, F)) * 0.2
wu = jax.random.normal(jax.random.PRNGKey(3), (X, E, F)) * 0.2
wd = jax.random.normal(jax.random.PRNGKey(4), (X, F, E)) * 0.2

def ref(x, wg_):
    t = x.reshape(-1, E)
    p = jax.nn.softmax(t @ rw, -1)
    vals, ids = jax.lax.top_k(p, K)
    w = vals / vals.sum(-1, keepdims=True)
    g = jnp.einsum("te,xef->txf", t, wg_)
    u = jnp.einsum("te,xef->txf", t, wu)
    y = jnp.einsum("txf,xfe->txe", jax.nn.silu(g) * u, wd)
    sel = jnp.take_along_axis(y, ids[:, :, None], axis=1)
    return (sel * w[:, :, None]).sum(1).reshape(B, S, E)

out, aux = jax.jit(lambda x: moe_ffn_a2a(x, rw, wg, wu, wd, top_k=K,
                                         capacity_factor=8.0, mesh=mesh))(x)
assert np.abs(np.asarray(out) - np.asarray(ref(x, wg))).max() < 1e-4
g1 = jax.jit(jax.grad(lambda w_: jnp.sum(
    moe_ffn_a2a(x, rw, w_, wu, wd, top_k=K, capacity_factor=8.0,
                mesh=mesh)[0] ** 2)))(wg)
g2 = jax.grad(lambda w_: jnp.sum(ref(x, w_) ** 2))(wg)
rel = np.abs(np.asarray(g1) - np.asarray(g2)).max() / np.abs(np.asarray(g2)).max()
assert rel < 1e-3, rel
# dispatch_shuffle neutrality at no-drop capacity: bit-identical output
out_s, _ = jax.jit(lambda x: moe_ffn_a2a(x, rw, wg, wu, wd, top_k=K,
                                         capacity_factor=8.0, mesh=mesh,
                                         dispatch_shuffle=True))(x)
assert np.array_equal(np.asarray(out), np.asarray(out_s))
print("OK")
"""


@pytest.mark.slow
def test_moe_a2a_forward_and_grad():
    out = subprocess.run(
        [sys.executable, "-c", A2A_SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
