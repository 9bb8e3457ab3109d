"""Single-query paged attention over the serving KV pool.

The serving step program (serving/programs.py) decodes one token per
lane against that lane's block table.  PR 12 did this at
"gather+dense-attention speed": gather every page into a dense
``(B, H, max_seq_len, D)`` view, full-width fp32 masked softmax.  This
module is the kernel-speed replacement (ISSUE 15, the vLLM
PagedAttention recipe on TPU):

* ``paged_attention`` with ``impl="pallas"`` — a Pallas kernel with
  grid ``(lane, block)``, every head of a page in one step: the KV walk
  is the innermost grid axis and the index map reads each page DIRECTLY
  from the pool via the lane's block-table row (scalar-prefetched, the
  TPU paged-attention idiom) — no dense gather, nothing ``(B, H, max_seq_len)``-shaped is
  ever materialized.  Online-softmax state (m, l, acc) lives in VMEM
  scratch exactly like `flash_attention._fa_kernel_streamed`, and dead
  blocks (``block > pos // block_size``) skip their math the same way
  `_fa_kernel_resident` skips fully-masked causal blocks.
* ``impl="dense"`` — byte-for-byte the PR 12 recipe (fp32 scores,
  ``finfo.min`` mask, full-width `jax.nn.softmax`, fp32 PV).  This is
  the CPU fallback the eviction-bit-identity and greedy-parity
  contracts rest on: CPU engines keep EXACTLY the old numerics.

Both impls take an optional int8 KV pool (per-head symmetric int8 with
an fp32 scale per (block, head, slot) — `contrib.quantization`'s
per-channel recipe applied to the feature dim): the kernel dequantizes
pages in-register after the DMA, so the pool stays s8 in HBM and
roughly doubles resident sequences per HBM byte.

The pallas and dense impls agree to fp32 roundoff (online vs full-width
softmax re-associate the same sums), NOT bitwise — dispatch therefore
never mixes impls within one engine: tokens are reproducible per
(engine config), which is what the eviction contract needs.  On the TPU
that holds when both are traced under matmul precision "highest"
(2e-6 on a v5e); at the default precision the MXU rounds each impl's
fp32 softmax weights to bf16, and they agree to that rounding — within
2^-8 of the largest |v|, below the bf16 rounding of the output itself.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mosaic

__all__ = ["paged_attention", "paged_attention_dense", "default_impl"]


def default_impl(platform: Optional[str] = None) -> str:
    """Auto dispatch: the Pallas kernel on TPU, the dense gather
    everywhere else (the CPU test/serving surface keeps PR 12's exact
    numerics; interpret-mode kernel runs are opt-in via impl=)."""
    platform = platform or jax.default_backend()
    return "pallas" if platform == "tpu" else "dense"


def _dequant(pages, scales):
    """(..., bs, D) int8 pages × (..., bs) fp32 scales → fp32."""
    return pages.astype(jnp.float32) * scales[..., None]


def paged_attention_dense(q, pool_k, pool_v, tables, pos,
                          scale_k=None, scale_v=None):
    """The PR 12 dense-gather recipe, verbatim: gather the lane's pages
    into a (B, H, W, D) view, fp32 scores / sqrt(D), iota position mask
    at ``finfo(f32).min``, full-width fp32 softmax, fp32 PV — masked
    slots contribute exactly 0.0 and lanes never mix, the two facts
    behind docs/serving.md §"Why eviction is exact".  int8 pools are
    dequantized after the gather (fp32), same score math."""
    B, nbps = tables.shape
    H, bs, D = pool_k.shape[1], pool_k.shape[2], pool_k.shape[3]
    W = nbps * bs
    if scale_k is not None:
        gk = _dequant(pool_k[tables], scale_k[tables])
        gv = _dequant(pool_v[tables], scale_v[tables])
        gk = gk.transpose(0, 2, 1, 3, 4).reshape(B, H, W, D)
        gv = gv.transpose(0, 2, 1, 3, 4).reshape(B, H, W, D)
    else:
        gk = pool_k[tables].transpose(0, 2, 1, 3, 4).reshape(B, H, W, D)
        gv = pool_v[tables].transpose(0, 2, 1, 3, 4).reshape(B, H, W, D)
    s = jnp.einsum("bhd,bhkd->bhk", q, gk,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(kpos <= pos[:, None, None], s,
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p, gv,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  bs, kv_quant):
    """One grid step = one (lane, page), all heads at once.  The page
    arrived via the block-table index map; this body does the
    online-softmax update, `pl.when`-skipping pages past the lane's
    length bound.

    Blocks span the whole head axis so their trailing two dims equal
    the array's (Mosaic's block-shape rule), and both dots are plain
    2-D MXU matmuls: the page is flattened to ``(H*bs, D)`` and every
    head scores against every head's slots, then slots of OTHER heads
    are masked like future positions — ``exp`` underflows them to
    exactly 0.0, so each head's row reduces over its own page only."""
    from jax.experimental import pallas as pl

    if kv_quant:
        sk_ref, sv_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    t = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, jnp.finfo(jnp.float32).min)
        l_ref[...] = jnp.zeros_like(l_ref)

    # length bound: pages past the lane's current position hold no
    # visible slot — skip their math entirely (same trick as
    # _fa_kernel_resident's nk_live; the DMA still lands, compute
    # doesn't).  Page j==0 is always live (t >= 0), so m/l are finite
    # by emit time.
    @pl.when(j <= t // bs)
    def _update():
        h, d = q_ref.shape[-2:]
        q = q_ref[0].astype(jnp.float32)                # (H, D)
        if kv_quant:
            k = _dequant(k_ref[0], sk_ref[0])           # (H, bs, D) f32
            v = _dequant(v_ref[0], sv_ref[0])
        else:
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
        k = k.reshape(h * bs, d)
        v = v.reshape(h * bs, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            / math.sqrt(d)                              # (H, H*bs)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # column c holds slot c - row*bs of head c // bs
        slot = col - row * bs
        own = jnp.logical_and(slot >= 0, slot < bs)
        s = jnp.where(jnp.logical_and(own, j * bs + slot <= t), s,
                      jnp.finfo(jnp.float32).min)
        m_prev, l_prev = m_ref[...], l_ref[...]         # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)   # masked slots underflow to exactly 0.0
        acc_ref[...] = acc_ref[...] * alpha \
            + jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(j == nb - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_call(q, pools, tables, pos, interpret):
    """Shared pallas_call: ``pools`` is (pool_k, pool_v) or, for int8
    pages, (pool_k, pool_v, scale_k, scale_v)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    bs = pools[0].shape[2]
    nbps = tables.shape[1]
    kv_quant = len(pools) == 4
    kernel = functools.partial(_paged_kernel, bs=bs, kv_quant=kv_quant)
    lane = pl.BlockSpec((1, H, D), lambda b, j, t, p: (b, 0, 0))
    page = pl.BlockSpec((1, H, bs, D), lambda b, j, t, p: (t[b, j], 0, 0, 0))
    page_scale = pl.BlockSpec((1, H, bs), lambda b, j, t, p: (t[b, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nbps),
        in_specs=[lane, page, page] + [page_scale] * (2 * kv_quant),
        out_specs=lane,
        scratch_shapes=[pltpu.VMEM((H, D), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
        name="paged_attention_q8" if kv_quant else "paged_attention",
    )(tables, pos, q, *pools)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_core(q, pool_k, pool_v, tables, pos, interpret):
    return _paged_call(q, (pool_k, pool_v), tables, pos, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_core_q8(q, pool_k, pool_v, scale_k, scale_v, tables, pos,
                   interpret):
    return _paged_call(q, (pool_k, pool_v, scale_k, scale_v), tables, pos,
                       interpret)


def paged_attention(q, pool_k, pool_v, tables, pos, *,
                    scale_k=None, scale_v=None,
                    impl: Optional[str] = None,
                    interpret: Optional[bool] = None):
    """Single-query attention of ``q`` (B, H, D) against the paged KV
    pool (num_blocks, H, block_size, D) through per-lane block tables
    (B, blocks_per_seq) at positions ``pos`` (B,), attending slots
    ``<= pos`` — the serving decode-step attention.

    ``impl``: "pallas" (kernel; interpret-mode on CPU), "dense" (the
    PR 12 gather recipe), or None for `default_impl`.  Pass
    ``scale_k/scale_v`` (num_blocks, H, block_size) fp32 when the pool
    is int8 (per-head symmetric quantization).
    """
    impl = impl or default_impl()
    if impl == "dense":
        return paged_attention_dense(q, pool_k, pool_v, tables, pos,
                                     scale_k, scale_v)
    if impl != "pallas":
        raise ValueError(f"paged_attention impl {impl!r} (pallas|dense)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # lanes and heads are independent: per shard of both under a mesh
    # (ops/mosaic.py); every shard walks the whole pool of its heads
    lanes, heads = mosaic.split(q.shape[:2])
    lane, pool = P(lanes, heads), P(None, heads)
    pools = (pool_k, pool_v) if scale_k is None \
        else (pool_k, pool_v, scale_k, scale_v)
    core = functools.partial(_paged_core if scale_k is None
                             else _paged_core_q8, interpret=interpret)
    in_specs = (lane,) + (pool,) * len(pools) + (P(lanes), P(lanes))
    return mosaic.per_shard(core, in_specs, lane)(q, *pools, tables, pos)
