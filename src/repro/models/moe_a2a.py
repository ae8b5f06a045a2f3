"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The GSPMD capacity formulation (models/moe.py) ends every MoE layer with an
all-reduce of the full token activation across the model axis (each EP rank
holds partial expert outputs). Here tokens instead *travel*: each device
routes its own token slice, packs per-peer send buffers, `all_to_all`s them
to the experts' owners, computes locally, and `all_to_all`s results back —
wire bytes ~ top_k * capacity_factor * token-slice bytes instead of a full
activation ring reduction (EXPERIMENTS.md §Perf hillclimb 5).

This is the data-dependent instance of the distributed-BP pattern in
core/distributed.py: the (token-slot <-> expert-slot) relayout is the
exchange round; routing metadata rides along with the payload.

``dispatch_shuffle=True`` adds a *static* BMMC permutation of the send
slots inside each peer's capacity block (routing metadata rides along, so
expert compute is unaffected; the return trip is inverse-permuted) — the
differentiable batched BMMC executor as a dispatch layer (DESIGN.md §9).
It de-correlates slot addresses from routing order, and because it is
offline and affine it fuses with any surrounding BMMC relayout instead of
costing a data-dependent gather. The permutation itself is exactly
neutral; enabling the flag also rounds the per-peer capacity up to a
power of two (the shuffle's block size), which can *reduce* token drops
versus the unshuffled run when a peer block overflows — at equal
effective capacity the outputs are bit-identical (tested).

Token layout inside shard_map: batch over the dp axes, **sequence over
``model``** — the sequence-parallel residual layout.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..combinators.execute import perm_apply
from ..core.bmmc import Bmmc
from ..kernels.ref import bmmc_ref


def _slot_shuffle(buf, bmmc, *, inverse: bool = False):
    """Permute the slot axis (axis 1) of a (peers, cap[, e]) buffer by a
    static BMMC; every peer block shares the one offline plan. Integer
    metadata takes the plain gather (no VJP machinery on int dtypes)."""
    b = bmmc.inverse() if inverse else bmmc
    if jnp.issubdtype(buf.dtype, jnp.integer):
        return bmmc_ref(buf, b, batched=True)
    return perm_apply(buf, b, "ref", True)


def _device_moe(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                n_experts: int, capacity_factor: float,
                model_axis: str, dp_axes: Tuple[str, ...],
                dispatch_shuffle: bool = False):
    """Per-device body. x: (T_local, E). Expert weights arrive model-sharded
    on dim 0 and FSDP-sharded over dp on the embed dim; gathered here."""
    t, e = x.shape
    n_peers = jax.lax.axis_size(model_axis)
    xpp = n_experts // n_peers                     # experts per peer

    def gather_dp(w, axis):
        for ax in dp_axes:
            w = jax.lax.all_gather(w, ax, axis=axis, tiled=True)
        return w
    wg = gather_dp(w_gate, 1)                      # (xpp, E, F)
    wu = gather_dp(w_up, 1)
    wd = gather_dp(w_down, 2)                      # (xpp, F, E)

    # -- route ----------------------------------------------------------------
    logits = jnp.einsum("te,ex->tx", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    vals, ids = jax.lax.top_k(probs, top_k)        # (T, k)
    weights = (vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9))
    frac = jnp.zeros((n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    frac = frac / jnp.maximum(frac.sum(), 1.0)
    aux = n_experts * jnp.sum(frac * probs.mean(0))
    aux = jax.lax.pmean(aux, dp_axes + (model_axis,))

    # -- pack per-peer send buffers --------------------------------------------
    cap = int(np.ceil(top_k * t * capacity_factor / n_peers))
    cap = max(8, int(np.ceil(cap / 8)) * 8)
    if dispatch_shuffle:  # slot shuffle needs a power-of-two block
        cap = 1 << (cap - 1).bit_length()
        slot_bmmc = Bmmc.bit_reverse(cap.bit_length() - 1)
    flat_ids = ids.reshape(-1)
    peer = flat_ids // xpp
    order = jnp.argsort(peer)
    peer_s = jnp.take(peer, order)
    eid_s = (jnp.take(flat_ids, order) % xpp).astype(jnp.int32)
    tok_s = order // top_k
    w_s = jnp.take(weights.reshape(-1), order)

    starts = jnp.searchsorted(peer_s, jnp.arange(n_peers), side="left")
    pos = jnp.arange(t * top_k) - jnp.take(starts, peer_s)
    keep = pos < cap
    slot = jnp.where(keep, peer_s * cap + pos, n_peers * cap)  # OOB -> drop

    send = jnp.zeros((n_peers * cap, e), x.dtype)
    send = send.at[slot].set(jnp.take(x, tok_s, axis=0), mode="drop")
    send_eid = jnp.full((n_peers * cap,), xpp, jnp.int32)      # pad sentinel
    send_eid = send_eid.at[slot].set(eid_s, mode="drop")

    # -- exchange: tokens travel to their experts' owners ----------------------
    send3 = send.reshape(n_peers, cap, e)
    send_eid2 = send_eid.reshape(n_peers, cap)
    if dispatch_shuffle:  # static slot relayout; eids ride along
        send3 = _slot_shuffle(send3, slot_bmmc)
        send_eid2 = _slot_shuffle(send_eid2, slot_bmmc)
    recv = jax.lax.all_to_all(send3, model_axis,
                              split_axis=0, concat_axis=0, tiled=True)
    recv_eid = jax.lax.all_to_all(send_eid2, model_axis,
                                  split_axis=0, concat_axis=0, tiled=True)
    rt = recv.reshape(n_peers * cap, e)
    re_ = recv_eid.reshape(n_peers * cap)

    # -- local expert compute: pack by local expert id --------------------------
    order2 = jnp.argsort(re_)
    eid2 = jnp.take(re_, order2)
    # rt.shape[0] = n_peers*cap already carries the capacity_factor slack;
    # dividing by xpp keeps the same per-expert overprovisioning.
    cap2 = max(8, int(np.ceil(rt.shape[0] / xpp / 8)) * 8)
    cap2 = min(cap2, rt.shape[0])
    starts2 = jnp.searchsorted(eid2, jnp.arange(xpp), side="left")
    pos2 = jnp.arange(rt.shape[0]) - jnp.take(starts2, eid2)
    keep2 = (pos2 < cap2) & (eid2 < xpp)           # drop pad sentinels
    slot2 = jnp.where(keep2, eid2 * cap2 + pos2, xpp * cap2)
    buf = jnp.zeros((xpp * cap2, e), x.dtype)
    buf = buf.at[slot2].set(jnp.take(rt, order2, axis=0), mode="drop")
    buf = buf.reshape(xpp, cap2, e)

    g = jnp.einsum("xce,xef->xcf", buf, wg)
    u = jnp.einsum("xce,xef->xcf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    yb = jnp.einsum("xcf,xfe->xce", h, wd).reshape(xpp * cap2, e)

    # un-permute local results back to recv-slot order
    y_sorted = jnp.take(yb, jnp.minimum(slot2, xpp * cap2 - 1), axis=0)
    y_sorted = jnp.where(keep2[:, None], y_sorted, 0)
    y_recv = jnp.zeros((rt.shape[0], e), x.dtype).at[order2].add(y_sorted)

    # -- return trip + weighted combine ----------------------------------------
    back = jax.lax.all_to_all(y_recv.reshape(n_peers, cap, e), model_axis,
                              split_axis=0, concat_axis=0, tiled=True)
    if dispatch_shuffle:  # undo the slot relayout: back to packing order
        back = _slot_shuffle(back, slot_bmmc, inverse=True)
    back = back.reshape(n_peers * cap, e)
    y_slot = jnp.take(back, jnp.minimum(slot, n_peers * cap - 1), axis=0)
    y_slot = jnp.where(keep[:, None], y_slot, 0)
    y_slot = y_slot * w_s[:, None].astype(x.dtype)
    out = jnp.zeros((t, e), x.dtype).at[tok_s].add(y_slot)
    return out, aux


def moe_ffn_a2a(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                capacity_factor: float, mesh, dispatch_shuffle: bool = False):
    """x: (B, S, E). Returns (out (B,S,E), aux). shard_map over the mesh:
    batch -> dp axes, sequence -> model axis (sequence-parallel layout).
    ``dispatch_shuffle`` BMMC-permutes send slots within each peer block
    (neutral at equal capacity; rounds capacity to a power of two — see
    module docstring)."""
    from ..parallel.sharding import dp_axes as _dp
    dp = _dp(mesh)
    dp_entry = dp if len(dp) > 1 else dp[0]
    n_experts = router_w.shape[1]

    body = functools.partial(
        _device_moe, top_k=top_k, n_experts=n_experts,
        capacity_factor=capacity_factor, model_axis="model", dp_axes=dp,
        dispatch_shuffle=dispatch_shuffle)

    def fn(xg, rw, wgt, wupt, wdt):
        b, s, e = xg.shape
        out, aux = body(xg.reshape(b * s, e), rw, wgt, wupt, wdt)
        return out.reshape(b, s, e), aux

    emb_spec = dp_entry  # FSDP axis for the embed dim of expert weights
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp_entry, "model", None),       # x
                  P(None, None),                     # router (replicated)
                  P("model", emb_spec, None),        # w_gate (X, E, F)
                  P("model", emb_spec, None),        # w_up
                  P("model", None, emb_spec)),       # w_down (X, F, E)
        out_specs=(P(dp_entry, "model", None), P()),
        check_vma=False)
    return mapped(x, router_w, w_gate, w_up, w_down)
