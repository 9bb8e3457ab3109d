"""Ring attention — sequence/context parallelism over the `seq` axis.

ABSENT in the reference (SURVEY.md §2.4, §5.7) — built here as a
first-class TPU feature: Q/K/V are sharded over the `seq` mesh axis;
each device holds one sequence block and rotates its KV block around
the ICI ring with `lax.ppermute` (double-buffered so the permute
overlaps the local attention compute), accumulating the exact softmax
online (same math as flash attention, distributed).  Memory per device
is O(T/n · T/n) and the full sequence length never materializes.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_sharded"]


def _merge_blocks(o1, lse1, o2, lse2):
    """Exact combination of two attention partials via logsumexp stats."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(jnp.isfinite(lse1), jnp.exp(lse1 - m_safe), 0.0)
    w2 = jnp.where(jnp.isfinite(lse2), jnp.exp(lse2 - m_safe), 0.0)
    denom = w1 + w2
    o = (o1 * w1[..., None] + o2 * w2[..., None]) \
        / jnp.maximum(denom, 1e-30)[..., None]
    lse = jnp.where(denom > 0, m_safe + jnp.log(jnp.maximum(denom, 1e-30)),
                    -jnp.inf)
    return o, lse


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = False,
                   scale: Optional[float] = None, impl: str = "flash"):
    """Inside-shard_map ring attention.

    q,k,v: (B, H, Tlocal, D) — the local sequence block of each device
    on `axis_name`.  Returns the exact global attention output for the
    local queries.  For causal=True, blocks are assumed ordered by
    device index along the ring.

    impl='flash' (default): each local block-pair runs the fused Pallas
    kernel (flash_attention_with_lse) and partials merge via logsumexp
    stats — per-block compute is fused, memory stays O(T/n · D).
    impl='einsum' keeps the explicit online-softmax accumulation.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if impl == "flash":
        return _ring_attention_flash(q, k, v, axis_name, causal, scale)
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    qf = q.astype(jnp.float32) * scale

    def local_attn(k_blk, v_blk, src_idx, m, l, acc):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32))
        if causal:
            # global positions: row = my_idx*T + iq, col = src_idx*T + ik
            row = my_idx * T + jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
            col = src_idx * T + jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
            mask = (col <= row)[None, None]
            s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        return m_new, l_new, acc_new

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        k_cur, v_cur, m, l, acc = carry
        # double-buffer: kick off the rotation, compute on current block
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        src_idx = (my_idx - i) % n  # whose block we hold at step i
        m, l, acc = local_attn(k_cur, v_cur, src_idx, m, l, acc)
        return k_next, v_next, m, l, acc

    m0 = jnp.full((B, H, T, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, T, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, T, D), jnp.float32)
    _, _, m, l, acc = lax.fori_loop(0, n, body, (k, v, m0, l0, acc0))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis_name, causal, scale):
    """Flash-kernel-per-block ring: rotate KV, run the fused kernel on
    each (local Q, visiting KV) pair, merge partials by logsumexp.

    Causal masking decomposes per block-pair into three static modes
    (earlier block: full; same block: causal; later block: skip), so the
    kernel never needs traced position offsets."""
    from ..ops.flash_attention import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape

    def full_blk(kb, vb):
        o, l = flash_attention_with_lse(q, kb, vb, causal=False, scale=scale)
        return o.astype(jnp.float32), l

    def causal_blk(kb, vb):
        o, l = flash_attention_with_lse(q, kb, vb, causal=True, scale=scale)
        return o.astype(jnp.float32), l

    def skip_blk(kb, vb):
        return (jnp.zeros((B, H, T, D), jnp.float32),
                jnp.full((B, H, T), -jnp.inf, jnp.float32))

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        k_cur, v_cur, o, lse = carry
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        src_idx = (my_idx - i) % n
        if causal:
            mode = jnp.where(src_idx < my_idx, 0,
                             jnp.where(src_idx == my_idx, 1, 2))
            o_b, lse_b = lax.switch(mode, (full_blk, causal_blk, skip_blk),
                                    k_cur, v_cur)
        else:
            o_b, lse_b = full_blk(k_cur, v_cur)
        o, lse = _merge_blocks(o, lse, o_b, lse_b)
        return k_next, v_next, o, lse

    o0 = jnp.zeros((B, H, T, D), jnp.float32)
    lse0 = jnp.full((B, H, T), -jnp.inf, jnp.float32)
    _, _, o, _ = lax.fori_loop(0, n, body, (k, v, o0, lse0))
    return o.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, causal: bool = False,
                           scale: Optional[float] = None, axis_name: str = "seq",
                           impl: str = "flash", data_axis: Optional[str] = None):
    """Top-level entry: q,k,v are (B, H, T, D) global arrays; shards T
    over `axis_name` and runs the ring under shard_map.

    ``data_axis``: also shard the batch dim over this mesh axis (pass
    "data" when composing SP with DP — otherwise the batch would
    replicate across the data axis inside the attention region).  The
    ring collectives only span `axis_name`, so the data axis rides
    along for free."""
    from jax import shard_map

    b = data_axis if data_axis and data_axis in mesh.axis_names \
        and mesh.shape[data_axis] > 1 else None
    spec = P(b, None, axis_name, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                          scale=scale, impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)
