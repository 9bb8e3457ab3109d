"""Expert parallelism — MoE layer with all_to_all token dispatch.

ABSENT in the reference (SURVEY.md §2.4: "build: expert-sharded FFN
with all_to_all token dispatch + capacity-based routing").  Top-1/top-2
router with capacity factor; tokens are dispatched to expert shards
over the `expert` mesh axis via all_to_all, processed by the local
expert FFN (one big MXU matmul per expert), and combined back weighted
by router probabilities.  Static shapes throughout (capacity-padded) —
XLA-friendly, no dynamic gathers.

This is capacity routing WITH dropping: a token past an expert's capacity
loses that expert.  The served programs' routed layer is the dropless one
(a sigmoid router over all experts, top-k by score + selection bias, every
pair whose expert this chip holds computed by the ``moe_experts`` kernel,
no capacity): `ops/moe_experts.py`, called from `serving/programs._layers`
for a decoder that describes one (`models.generation.MoeSpec`).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["moe_layer", "moe_layer_sharded", "top2_gating"]


def top2_gating(logits, capacity: int, second_expert: bool = True):
    """Switch/GShard-style router. logits: (T, E). Returns
    (dispatch (T, E, C) one-hot, combine (T, E, C) weights, aux_loss)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    g1 = jnp.argmax(probs, axis=-1)  # (T,)
    p1 = jnp.take_along_axis(probs, g1[:, None], axis=1)[:, 0]
    # load-balancing auxiliary loss (Switch Transformer eq. 4)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(g1, E), axis=0)
    aux = E * jnp.sum(me * ce)

    def one_expert_dispatch(g, p, priority_offset):
        oh = jax.nn.one_hot(g, E)  # (T, E)
        pos = jnp.cumsum(oh, axis=0) * oh - 1 + priority_offset  # slot per token
        keep = (pos < capacity) & (pos >= 0)
        pos_c = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        disp = jax.nn.one_hot(pos_c, capacity) * keep[..., None]  # (T, E, C)
        return disp, pos

    d1, pos1 = one_expert_dispatch(g1, p1, 0)
    combine = d1 * p1[:, None, None]
    dispatch = d1
    if second_expert:
        probs2 = probs * (1 - jax.nn.one_hot(g1, E))
        g2 = jnp.argmax(probs2, axis=-1)
        p2 = jnp.take_along_axis(probs, g2[:, None], axis=1)[:, 0]
        # second choices queue behind first choices
        used = jnp.max(pos1, axis=0) + 1  # (E,) slots consumed per expert
        d2, _ = one_expert_dispatch(g2, p2, used[None, :] * jax.nn.one_hot(g2, E))
        denom = jnp.maximum(p1 + p2, 1e-9)
        combine = d1 * (p1 / denom)[:, None, None] + d2 * (p2 / denom)[:, None, None]
        dispatch = jnp.maximum(d1, d2)
    return dispatch, combine, aux


def moe_layer(x, router_w, expert_ws, axis_name: str = "expert",
              capacity_factor: float = 1.25, second_expert: bool = True,
              activation=jax.nn.gelu):
    """Inside-shard_map MoE FFN.

    x: (Tlocal, D) local tokens; router_w: (D, E) replicated;
    expert_ws: (Elocal, D, Dff), (Elocal, Dff, D) — this shard's experts.
    Returns (Tlocal, D), aux_loss.
    """
    w_in, w_out = expert_ws
    n = lax.psum(1, axis_name)
    Elocal = w_in.shape[0]
    E = Elocal * n
    T, D = x.shape
    capacity = max(1, int(capacity_factor * T / E))

    logits = x @ router_w  # (T, E)
    dispatch, combine, aux = top2_gating(logits, capacity, second_expert)
    # local tokens → per-expert capacity slots: (E, C, D)
    slots = jnp.einsum("tec,td->ecd", dispatch, x)
    # all_to_all over experts: each shard keeps its Elocal experts but
    # gathers every device's slots for them.  Tiled all_to_all divides
    # split_axis by n and multiplies concat_axis by n, chunks ordered by
    # source rank: (E, C, D) → (Elocal, n·C, D).
    slots = lax.all_to_all(slots, axis_name, split_axis=0, concat_axis=1, tiled=True)
    # expert FFN (batched over local experts — MXU)
    h = activation(jnp.einsum("ecd,edf->ecf", slots, w_in))
    y = jnp.einsum("ecf,efd->ecd", h, w_out)
    # route back: exact inverse of the dispatch all_to_all
    y = lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0, tiled=True)
    out = jnp.einsum("tec,ecd->td", combine, y)
    return out, aux


def moe_layer_sharded(x, router_w, expert_ws, mesh: Mesh,
                      capacity_factor: float = 1.25, second_expert: bool = True,
                      axis_name: str = "expert"):
    """Top-level: x (B, T, D) replicated batch; expert weights sharded
    on their leading (expert) dim."""
    from jax import shard_map

    B, T, D = x.shape
    xf = x.reshape(B * T, D)

    def inner(xt, rw, ws):
        out, aux = moe_layer(xt, rw, ws, axis_name=axis_name,
                             capacity_factor=capacity_factor,
                             second_expert=second_expert)
        return out, lax.pmean(aux, axis_name)

    fn = shard_map(inner, mesh=mesh,
                   in_specs=(P(), P(), (P(axis_name), P(axis_name))),
                   out_specs=(P(), P()), check_vma=False)
    out, aux = fn(xf, router_w, expert_ws)
    return out.reshape(B, T, D), aux
