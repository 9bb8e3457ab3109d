"""ArcFace — margin softmax with a model-parallel-sharded classifier.

BASELINE config #5: the InsightFace recipe the reference ecosystem ran
over KVStore dist_sync with per-GPU classifier shards (SURVEY.md §2.4
"Large-softmax hybrid parallel").  TPU-native: the (num_classes, emb)
FC weight is sharded over the `model` axis; logits stay sharded; the
softmax normalizer and the margin target row are resolved with
psum/pmax over ICI inside shard_map — no device ever holds the full
classifier.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["arcface_logits", "arcface_loss_sharded", "ArcFaceHead"]


def _margin_cos(cos_t, margin_m2, margin_m3):
    """cos(θ + m2) - m3 (ArcFace additive-angular + CosFace additive)."""
    theta = jnp.arccos(jnp.clip(cos_t, -1.0 + 1e-7, 1.0 - 1e-7))
    return jnp.cos(theta + margin_m2) - margin_m3


def arcface_logits(emb, weight, labels, scale=64.0, margin_m2=0.5, margin_m3=0.0):
    """Single-device reference: emb (B, D) L2-normed, weight (C, D)."""
    emb_n = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
    w_n = weight / jnp.linalg.norm(weight, axis=1, keepdims=True)
    cos = emb_n @ w_n.T
    target = _margin_cos(cos, margin_m2, margin_m3)
    onehot = jax.nn.one_hot(labels, weight.shape[0], dtype=cos.dtype)
    return scale * jnp.where(onehot.astype(bool), target, cos)


def _sharded_loss(emb, w_shard, labels, *, axis_name, scale, m2, m3):
    """Inside shard_map: w_shard (Clocal, D); labels global ids (B,)."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    c_local = w_shard.shape[0]
    lo = idx * c_local

    emb_n = emb / jnp.linalg.norm(emb, axis=1, keepdims=True)
    w_n = w_shard / jnp.linalg.norm(w_shard, axis=1, keepdims=True)
    cos = emb_n @ w_n.T  # (B, Clocal)

    local_lab = labels - lo
    in_shard = (local_lab >= 0) & (local_lab < c_local)
    lab_c = jnp.clip(local_lab, 0, c_local - 1)
    onehot = jax.nn.one_hot(lab_c, c_local, dtype=cos.dtype) * in_shard[:, None]
    target = _margin_cos(cos, m2, m3)
    logits = scale * jnp.where(onehot.astype(bool), target, cos)

    # distributed stable log-softmax: global max then global denom (psum/pmax).
    # stop_gradient: the max shift cancels in d(log-softmax) and pmax has no
    # VJP rule — without it the backward pass cannot be built at all
    local_max = jnp.max(logits, axis=1)
    gmax = lax.pmax(lax.stop_gradient(local_max), axis_name)
    e = jnp.exp(logits - gmax[:, None])
    denom = lax.psum(jnp.sum(e, axis=1), axis_name)
    # numerator: the target logit lives on exactly one shard
    tgt_logit = lax.psum(jnp.sum(logits * onehot, axis=1), axis_name)
    loss = -(tgt_logit - gmax - jnp.log(denom))
    return jnp.mean(loss)


def arcface_loss_sharded(emb, weight, labels, mesh: Mesh, scale=64.0,
                         margin_m2=0.5, margin_m3=0.0, axis_name: str = "model"):
    """Top-level: weight (C, D) sharded on classes over `axis_name`."""
    from jax import shard_map

    fn = shard_map(
        functools.partial(_sharded_loss, axis_name=axis_name, scale=scale,
                          m2=margin_m2, m3=margin_m3),
        mesh=mesh,
        in_specs=(P(), P(axis_name, None), P()),
        out_specs=P(),
        check_vma=False)
    return fn(emb, weight, labels)


class ArcFaceHead:
    """Stateful convenience head: owns the sharded classifier weight."""

    def __init__(self, num_classes, emb_dim, mesh: Optional[Mesh] = None,
                 scale=64.0, margin=0.5, seed=0):
        key = jax.random.PRNGKey(seed)
        self.weight = jax.random.normal(key, (num_classes, emb_dim), jnp.float32) * 0.01
        self.mesh = mesh
        self.scale = scale
        self.margin = margin
        if mesh is not None and "model" in mesh.axis_names:
            from jax.sharding import NamedSharding

            self.weight = jax.device_put(
                self.weight, NamedSharding(mesh, P("model", None)))

    def loss(self, emb, labels):
        if self.mesh is not None and "model" in self.mesh.axis_names:
            return arcface_loss_sharded(emb, self.weight, labels, self.mesh,
                                        self.scale, self.margin)
        logits = arcface_logits(emb, self.weight, labels, self.scale, self.margin)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
