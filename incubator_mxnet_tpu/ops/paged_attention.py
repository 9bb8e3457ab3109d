"""Single-query paged attention over the serving KV pool.

The serving step program (serving/programs.py) decodes one token per
lane against that lane's block table.  PR 12 did this at
"gather+dense-attention speed": gather every page into a dense
``(B, H, max_seq_len, D)`` view, full-width fp32 masked softmax.  This
module is the kernel-speed replacement (ISSUE 15, the vLLM
PagedAttention recipe on TPU):

The pool is ``(num_blocks, block_size, H*D)``: a position is one row
of its page and a head a run of ``D`` lanes.  It is the one shape that
the buffer the engine donates, the programs' K/V write and this
kernel's page block all take as it lies — row-major and unpadded on
the device, so a served program holds no copy of a pool array
(docs/serving.md, "The pool's layout"; over ``(num_blocks, H,
block_size, D)`` it held three of each).

* ``paged_attention`` with ``impl="pallas"`` — a Pallas kernel with
  grid ``(lane, block)``, every head of a page in one step: the KV walk
  is the innermost grid axis and the index map reads each page DIRECTLY
  from the pool via the lane's block-table row (scalar-prefetched, the
  TPU paged-attention idiom) — no dense gather, nothing ``(B, H, max_seq_len)``-shaped is
  ever materialized.  The query is laid out block-diagonally over the
  page's ``H*D`` lanes, so both dots are plain 2-D matmuls over the
  page as it lies.  Online-softmax state (m, l, acc) lives in VMEM
  scratch exactly like `flash_attention._fa_kernel_streamed`, and dead
  blocks (``block > pos // block_size``) skip their math the same way
  `_fa_kernel_resident` skips fully-masked causal blocks.
* ``impl="dense"`` — byte-for-byte the PR 12 recipe (fp32 scores,
  ``finfo.min`` mask, full-width `jax.nn.softmax`, fp32 PV).  This is
  the CPU fallback the eviction-bit-identity and greedy-parity
  contracts rest on: CPU engines keep EXACTLY the old numerics.

Both impls take an optional int8 KV pool (per-head symmetric int8 with
an fp32 scale per (block, slot, head), scale pools ``(num_blocks,
block_size, H)`` — `contrib.quantization`'s per-channel recipe applied
to the feature dim): the kernel dequantizes in-register after the DMA
(the s8 page enters the dots as it is, its scales multiply the scores
and the softmax weights), so the pool stays s8 in HBM and roughly
doubles resident sequences per HBM byte.

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

__all__ = ["paged_attention", "paged_attention_dense", "default_impl",
           "pool_shapes", "write_rows"]


def default_impl(platform: Optional[str] = None) -> str:
    """Auto dispatch: the Pallas kernel on TPU, the dense gather
    everywhere else (the CPU test/serving surface keeps PR 12's exact
    numerics; interpret-mode kernel runs are opt-in via impl=)."""
    platform = platform or jax.default_backend()
    return "pallas" if platform == "tpu" else "dense"


def pool_shapes(num_blocks, block_size, heads, head_dim):
    """(shape of a K or V pool array, shape of its int8 scale pool): a
    position is a row of its page, a head a run of ``head_dim`` lanes."""
    return ((num_blocks, block_size, heads * head_dim),
            (num_blocks, block_size, heads))


def write_rows(pool, wblk, off, x):
    """The K/V write of every serving program: ``x`` — keys or values
    ``(..., H, D)``, or their scales ``(..., H)`` — into rows
    ``(wblk, off)`` of a page pool or a scale pool.  A position is one
    row, so the write updates the donated pool in place and the kernel
    reads the same bytes."""
    return pool.at[wblk, off].set(x.reshape(wblk.shape + pool.shape[2:]))


def _dequant(pages, scales):
    """(..., H, D) int8 pages × (..., H) fp32 scales → fp32."""
    return pages.astype(jnp.float32) * scales[..., None]


def paged_attention_dense(q, pool_k, pool_v, tables, pos,
                          scale_k=None, scale_v=None):
    """The PR 12 dense-gather recipe, verbatim: gather the lane's pages
    into a (B, H, W, D) view, fp32 scores / sqrt(D), iota position mask
    at ``finfo(f32).min``, full-width fp32 softmax, fp32 PV — masked
    slots contribute exactly 0.0 and lanes never mix, the two facts
    behind docs/serving.md §"Why eviction is exact".  int8 pools are
    dequantized after the gather (fp32), same score math.  The pool's
    layout only changes how the view is gathered: its values, and every
    operation on them, are those of the ``(num_blocks, H, bs, D)`` pool
    this recipe was written for, bit for bit."""
    B, nbps = tables.shape
    H, D = q.shape[1:]
    bs = pool_k.shape[1]
    W = nbps * bs

    def view(pool, scale):
        g = pool[tables].reshape(B, nbps, bs, H, D)
        if scale is not None:
            g = _dequant(g, scale[tables])
        return g.transpose(0, 3, 1, 2, 4).reshape(B, H, W, D)

    gk, gv = view(pool_k, scale_k), view(pool_v, scale_v)
    s = jnp.einsum("bhd,bhkd->bhk", q, gk,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(kpos <= pos[:, None, None], s,
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p, gv,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  bs, heads, kv_quant):
    """One grid step = one (lane, page), all heads at once.  The page
    arrived via the block-table index map as the pool holds it,
    ``(bs, H*D)``: a position a row, a head a run of ``D`` lanes.  This
    body does the online-softmax update, `pl.when`-skipping pages past
    the lane's length bound.

    Both dots are plain 2-D MXU matmuls over the page as it lies.  The
    query is laid out block-diagonally, ``q_bd[h] = q`` on head h's
    lanes and exact zeros elsewhere, so ``q_bd · page^T`` is each head's
    own ``(H, bs)`` scores; ``p · page`` weights every head's lanes by
    every head's row, and `_emit` keeps the diagonal blocks.  int8 pages
    enter the dots as they are and their fp32 scales multiply the
    scores and the softmax weights, one per (slot, head)."""
    from jax.experimental import pallas as pl

    if kv_quant:
        sk_ref, sv_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    t = pos_ref[b]
    d = q_ref.shape[-1] // heads

    def own():
        """(H, H*D): the lanes of row h that are head h's.  Built where
        it is used, so a skipped page pays nothing for it."""
        row = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
        return jnp.logical_and(col >= row * d, col < (row + 1) * d)

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
        q_bd = jnp.where(own(), q_ref[0].astype(jnp.float32), 0.0)
        s = jax.lax.dot_general(
            q_bd, k_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (H, bs)
        if kv_quant:
            s = s * sk_ref[0].T
        s = s / math.sqrt(d)
        slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(j * bs + slot <= t, s, jnp.finfo(jnp.float32).min)
        m_prev, l_prev = m_ref[...], l_ref[...]         # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)   # masked slots underflow to exactly 0.0
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if kv_quant:
            p = p * sv_ref[0].T
        acc_ref[...] = acc_ref[...] * alpha \
            + jnp.dot(p, v_ref[0].astype(jnp.float32),
                      preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _emit():
        o = jnp.where(own(), acc_ref[...] / l_ref[...], 0.0)
        o_ref[0] = jnp.sum(o, axis=0, keepdims=True).astype(o_ref.dtype)


def _paged_call(q, pools, tables, pos, interpret):
    """Shared pallas_call: ``pools`` is (pool_k, pool_v) or, for int8
    pages, (pool_k, pool_v, scale_k, scale_v).  Query and output cross
    the call with the head axis flattened, as the pages have it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    bs = pools[0].shape[1]
    nbps = tables.shape[1]
    kv_quant = len(pools) == 4
    kernel = functools.partial(_paged_kernel, bs=bs, heads=H,
                               kv_quant=kv_quant)
    lane = pl.BlockSpec((1, 1, H * D), lambda b, j, t, p: (b, 0, 0))
    page = pl.BlockSpec((1, bs, H * D), lambda b, j, t, p: (t[b, j], 0, 0))
    page_scale = pl.BlockSpec((1, bs, H), lambda b, j, t, p: (t[b, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nbps),
        in_specs=[lane, page, page] + [page_scale] * (2 * kv_quant),
        out_specs=lane,
        scratch_shapes=[pltpu.VMEM((H, H * D), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H * D), q.dtype),
        interpret=interpret,
        name="paged_attention_q8" if kv_quant else "paged_attention",
    )(tables, pos, q.reshape(B, 1, H * D), *pools)
    return out.reshape(B, H, D)


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
    pool (num_blocks, block_size, H*D) through per-lane block tables
    (B, blocks_per_seq) at positions ``pos`` (B,), attending slots
    ``<= pos`` — the serving decode-step attention.

    ``impl``: "pallas" (kernel; interpret-mode on CPU), "dense" (the
    PR 12 gather recipe), or None for `default_impl`.  Pass
    ``scale_k/scale_v`` (num_blocks, block_size, H) fp32 when the pool
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
    # (ops/mosaic.py); every shard walks the whole pool of its heads,
    # which are contiguous runs of the pool's last dimension
    lanes, heads = mosaic.split(q.shape[:2])
    lane, pool = P(lanes, heads), P(None, None, heads)
    pools = (pool_k, pool_v) if scale_k is None \
        else (pool_k, pool_v, scale_k, scale_v)
    core = functools.partial(_paged_core if scale_k is None
                             else _paged_core_q8, interpret=interpret)
    in_specs = (lane,) + (pool,) * len(pools) + (P(lanes), P(lanes))
    return mosaic.per_shard(core, in_specs, lane)(q, *pools, tables, pos)
