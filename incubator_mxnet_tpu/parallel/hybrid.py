"""5-axis hybrid parallelism — one train step over Mesh(data, model, pipe, seq, expert).

The reference's parallelism tops out at data-parallel KVStore plus
`group2ctx` manual placement (SURVEY.md §2.4); this module is the
TPU-native end-state: a single `shard_map`-jitted training step of a
transformer-MoE LM that composes every strategy at once —

  data   — batch sharded, grads averaged (DP; ref kvstore allreduce)
  model  — Megatron TP: per-head column-sharded QKV, row-sharded output
           projection with one `psum` (ref: none)
  pipe   — GPipe microbatch pipeline via `pipeline.pipeline_forward`
           (ref: none)
  seq    — ring attention over the sequence axis via `ring.ring_attention`
           (ref: none)
  expert — MoE FFN with `all_to_all` token dispatch via `moe.moe_layer`
           (ref: none)

Everything is explicit-collective SPMD inside one `shard_map`; XLA
overlaps the ppermutes/all_to_alls with compute on ICI.  Gradients of
the *global* mean loss are assembled from per-shard `jax.grad` with the
documented psum/pmean corrections per replication pattern (verified
numerically against a single-device reference in
tests/test_hybrid_parallel.py).

MoE router aux-loss is intentionally excluded from the differentiated
loss here (capacity/grouping semantics are shard-local; see moe.py).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as onp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .moe import moe_layer, top2_gating
from .pipeline import pipeline_forward
from .ring import ring_attention

__all__ = ["HybridConfig", "init_params", "param_specs", "make_train_step",
           "reference_loss", "mesh_for", "shard_params_to_mesh"]


class HybridConfig(NamedTuple):
    """Static model/schedule config. n_layers == number of pipeline
    stages × layers_per_stage; every stage runs `layers_per_stage`
    transformer-MoE blocks."""
    vocab: int = 64
    d_model: int = 16
    n_heads: int = 4
    d_head: int = 4
    n_stages: int = 2          # leading dim of stage params (pipe-sharded)
    layers_per_stage: int = 1
    n_experts: int = 2
    d_ff: int = 32
    microbatches: int = 2
    capacity_factor: float = 2.0   # == n_experts → top-2 never drops
    lr: float = 0.1


def _layer_keys():
    return ("wqkv", "wo", "ln1_g", "ln1_b", "router", "w_in", "w_out",
            "ln2_g", "ln2_b")


def init_params(key, cfg: HybridConfig) -> Dict[str, Any]:
    V, D, H, Dh = cfg.vocab, cfg.d_model, cfg.n_heads, cfg.d_head
    S, L, E, F = cfg.n_stages, cfg.layers_per_stage, cfg.n_experts, cfg.d_ff
    ks = jax.random.split(key, 8)
    s = lambda *shape: (S, L) + shape
    def init(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale)
    return {
        "embed": init(ks[0], (V, D), 0.02),
        "wqkv": init(ks[1], s(D, H, 3 * Dh), D ** -0.5),
        "wo": init(ks[2], s(H, Dh, D), (H * Dh) ** -0.5),
        "ln1_g": jnp.ones(s(D)), "ln1_b": jnp.zeros(s(D)),
        "router": init(ks[3], s(D, E), 0.02),
        "w_in": init(ks[4], s(E, D, F), D ** -0.5),
        "w_out": init(ks[5], s(E, F, D), F ** -0.5),
        "ln2_g": jnp.ones(s(D)), "ln2_b": jnp.zeros(s(D)),
        "lnf_g": jnp.ones((D,)), "lnf_b": jnp.zeros((D,)),
    }


def param_specs(cfg: HybridConfig) -> Dict[str, P]:
    """PartitionSpec per parameter: pipe on the stage dim, Megatron TP on
    heads (attention) and expert on the expert dim (MoE)."""
    return {
        "embed": P(),
        "wqkv": P("pipe", None, None, "model"),   # column parallel (per-head)
        "wo": P("pipe", None, "model"),           # row parallel → psum
        "ln1_g": P("pipe"), "ln1_b": P("pipe"),
        "router": P("pipe"),
        "w_in": P("pipe", None, "expert"),
        "w_out": P("pipe", None, "expert"),
        "ln2_g": P("pipe"), "ln2_b": P("pipe"),
        "lnf_g": P(), "lnf_b": P(),
    }


def _ln(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _block(lp, h, cfg: HybridConfig, *, distributed: bool):
    """One transformer-MoE block. h: (mb, T, D) local activations.
    lp: this stage's params for ONE layer (no leading dims)."""
    mb, T, D = h.shape
    # -- attention (TP over 'model' heads; ring over 'seq') --------------
    hn = _ln(h, lp["ln1_g"], lp["ln1_b"])
    qkv = jnp.einsum("btd,dhe->bthe", hn, lp["wqkv"])       # (mb,T,Hl,3Dh)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.transpose(0, 2, 1, 3)                             # (mb,Hl,T,Dh)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if distributed:
        att = ring_attention(q, k, v, axis_name="seq")
    else:
        from ..ops.flash_attention import flash_attention
        att = flash_attention(q, k, v)
    att = att.transpose(0, 2, 1, 3)                         # (mb,T,Hl,Dh)
    proj = jnp.einsum("bthe,hed->btd", att, lp["wo"])
    if distributed:
        proj = lax.psum(proj, "model")                      # row-parallel reduce
    h = h + proj
    # -- MoE FFN (EP over 'expert') --------------------------------------
    hn = _ln(h, lp["ln2_g"], lp["ln2_b"])
    xt = hn.reshape(mb * T, D)
    if distributed:
        out, _aux = moe_layer(xt, lp["router"], (lp["w_in"], lp["w_out"]),
                              axis_name="expert",
                              capacity_factor=cfg.capacity_factor)
    else:
        E = cfg.n_experts
        cap = max(1, int(cfg.capacity_factor * xt.shape[0] / E))
        disp, comb, _aux = top2_gating(xt @ lp["router"], cap)
        slots = jnp.einsum("tec,td->ecd", disp, xt)
        hmid = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", slots, lp["w_in"]))
        y = jnp.einsum("ecf,efd->ecd", hmid, lp["w_out"])
        out = jnp.einsum("tec,ecd->td", comb, y)
    return h + out.reshape(mb, T, D)


def _stage_fn(stage_params, h, cfg: HybridConfig, distributed: bool):
    """Apply this stage's `layers_per_stage` blocks sequentially.
    stage_params leaves: (L, ...) — one stage's slice."""
    for li in range(cfg.layers_per_stage):
        lp = {k: stage_params[k][li] for k in _layer_keys()}
        h = _block(lp, h, cfg, distributed=distributed)
    return h


def _local_loss(params, x, y, cfg: HybridConfig):
    """Per-device loss inside shard_map. x,y: (B_l, T_l) int32."""
    B, T = x.shape
    M = cfg.microbatches
    h = jnp.take(params["embed"], x, axis=0)                # (B_l,T_l,D)
    hm = h.reshape((M, B // M, T, h.shape[-1]))
    stage = {k: params[k] for k in _layer_keys()}           # (S_l, L, ...)
    local_stage = jax.tree_util.tree_map(lambda p: p[0], stage)
    fn = functools.partial(_stage_fn, cfg=cfg, distributed=True)
    out = pipeline_forward(fn, local_stage, hm, axis_name="pipe")
    out = out.reshape(B, T, -1)
    out = _ln(out, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum("btd,vd->btv", out, params["embed"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()
    # only the LAST pipe stage's logits are real; broadcast its loss so
    # every rank holds this (data,seq)-shard's local mean CE
    is_last = lax.axis_index("pipe") == lax.psum(1, "pipe") - 1
    return lax.psum(jnp.where(is_last, ce, 0.0), "pipe")


def pmean_axes(v, axes):
    for ax in axes:
        v = lax.pmean(v, ax)
    return v


def _correct_grads(grads, specs, mesh_size: int):
    """Per-shard jax.grad → gradient of the GLOBAL mean loss.

    Under shard_map, reverse AD of a per-device scalar computes
    ∂(Σ over ALL devices' scalars)/∂(local shard).  Our per-device
    scalar ℓ is the (data,seq)-shard's mean CE, replicated over
    model/expert/pipe, so Σ devices ℓ = mesh.size · L where
    L = global mean loss.  The gradient of L w.r.t. a param *shared*
    across its replicated axes is therefore uniformly:

        psum(local_grad, over axes NOT in the param's PartitionSpec)
        / mesh.size

    — one rule for every replication pattern (verified against the
    single-device oracle in tests/test_hybrid_parallel.py).
    """
    all_axes = ("data", "model", "pipe", "seq", "expert")
    out = {}
    for name, g in grads.items():
        spec_axes = set()
        for entry in specs[name]:
            if entry is None:
                continue
            spec_axes.update(entry if isinstance(entry, tuple) else (entry,))
        for ax in all_axes:
            if ax not in spec_axes:
                g = lax.psum(g, ax)
        out[name] = g / mesh_size
    return out


def make_train_step(mesh: Mesh, cfg: HybridConfig):
    """Build the jitted 5-axis SPMD train step:
    step(params, x, y) -> (new_params, loss). Params must be placed with
    `shard_params_to_mesh`; x,y are (B, T) int32 global arrays with
    B % (data·microbatches) == 0 and T % seq == 0."""
    from jax import shard_map

    specs = param_specs(cfg)
    if cfg.n_stages != mesh.shape["pipe"]:
        raise ValueError(
            f"cfg.n_stages ({cfg.n_stages}) must equal the 'pipe' axis size "
            f"({mesh.shape['pipe']}) — one stage slice per pipe rank")
    mesh_size = int(onp.prod(list(mesh.shape.values())))

    def device_step(params, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: _local_loss(p, x, y, cfg))(params)
        grads = _correct_grads(grads, specs, mesh_size)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - cfg.lr * g, params, grads)
        return new_params, pmean_axes(loss, ("data", "seq"))

    sharded = shard_map(
        device_step, mesh=mesh,
        in_specs=(specs, P("data", "seq"), P("data", "seq")),
        out_specs=(specs, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))


def mesh_for(n_devices: int, devices=None) -> Mesh:
    """Factor n_devices over all five axes (powers of two preferred),
    priority data → model → pipe → seq → expert."""
    sizes = {"data": 1, "model": 1, "pipe": 1, "seq": 1, "expert": 1}
    remaining = n_devices
    order = ["data", "model", "pipe", "seq", "expert"]
    i = 0
    while remaining % 2 == 0 and remaining > 1:
        sizes[order[i % len(order)]] *= 2
        remaining //= 2
        i += 1
    sizes["data"] *= remaining  # odd residue goes to data
    devs = list(devices or jax.devices())[:n_devices]
    arr = onp.asarray(devs).reshape(tuple(sizes[a] for a in order))
    return Mesh(arr, tuple(order))


def shard_params_to_mesh(params, mesh: Mesh, cfg: HybridConfig):
    specs = param_specs(cfg)
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()}


def reference_loss(params, x, y, cfg: HybridConfig):
    """Single-device oracle: same math, no sharding. Token grouping for
    MoE matches the distributed step only when capacity never binds
    (capacity_factor == n_experts with top-2 guarantees this)."""
    B, T = x.shape
    M = cfg.microbatches
    h = jnp.take(params["embed"], x, axis=0)
    # group tokens per microbatch exactly as the pipeline does
    hm = h.reshape(M, B // M, T, -1)
    outs = []
    for m in range(M):
        hcur = hm[m]
        for s in range(cfg.n_stages):
            stage = {k: params[k][s] for k in _layer_keys()}
            hcur = _stage_fn(stage, hcur, cfg, distributed=False)
        outs.append(hcur)
    out = jnp.stack(outs).reshape(B, T, -1)
    out = _ln(out, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum("btd,vd->btv", out, params["embed"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()
