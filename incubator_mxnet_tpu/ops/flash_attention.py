"""Flash attention — Pallas TPU kernel with online softmax.

Replaces the reference's fused interleaved-matmul attention CUDA ops
(`src/operator/contrib/transformer.cu` [UNVERIFIED], SURVEY.md §2.3
"Attention / transformer kernels": "Pallas flash attention (the
marquee custom kernel)").

Design (per /opt/skills/guides/pallas_guide.md):
- grid = (batch*heads, ceil(Tq/BQ)); each program owns one query block
  in VMEM and streams key/value blocks with `pl.ds`, keeping the
  running (max, denom, acc) online-softmax state as fori_loop carry.
- both matmuls hit the MXU with fp32 accumulation
  (`preferred_element_type`); inputs may be bf16.
- causal masking via iota comparison; out-of-range tails masked the
  same way so ragged Tk works.
- `interpret=True` on CPU so the same kernel runs in the test suite
  (SURVEY.md §4: CPU is the reference implementation).
- batch and heads are independent: in a program traced over a mesh the
  kernels run per shard of (batch, heads) (`ops/mosaic.py`).

`attention_reference` is the jnp oracle used by the numeric tests.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mosaic

__all__ = ["flash_attention", "flash_attention_with_lse",
           "attention_reference", "attention_small_t"]


def _safe_softmax(s):
    """Softmax along -1 that returns 0 (not NaN) on fully-masked rows —
    the flash-kernel convention for queries with no visible keys."""
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def attention_reference(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Plain XLA softmax(QKᵀ)V oracle. q,k,v: (B, H, T, D).

    Causal masking is bottom-right aligned (query i sees keys j with
    j − (Tk − Tq) ≤ i), matching the Pallas kernel and the VJP."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = _safe_softmax(s)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _fwd_block_update(q, k_blk, v_blk, m, l, acc, qi, kb, *, causal, bq, bk,
                      tq, tk):
    """One online-softmax block update — THE shared numerics of both
    forward kernels (the backward factors its per-block math into
    `_bwd_block_terms` the same way).  `q` is pre-scaled f32; returns
    the updated (m, l, acc) carry."""
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    col = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = col < tk
    if causal:
        # bottom-right alignment (matches attention_reference & VJP):
        # query i attends keys j with j - (tk - tq) <= i
        row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        valid = jnp.logical_and(valid, col <= row + (tk - tq))
    s = jnp.where(valid, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # guard fully-masked rows (m_new == -inf) against exp(-inf - -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(jnp.where(valid, s - m_safe, -jnp.inf))
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
    alpha = jnp.where(jnp.isfinite(m), alpha, 0.0)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _emit_out_lse(m, l, acc, o_ref, lse_ref, bq):
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # row logsumexp for the fused backward (−inf on fully-masked rows);
    # stored 8-wide-broadcast: TPU block shapes need sublane-divisible dims
    lse = m[:, 0] + jnp.log(jnp.maximum(l[:, 0], 1e-30))
    lse = jnp.where(jnp.isfinite(m[:, 0]), lse, -jnp.inf)
    lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, bq))


def _fa_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                        bq, bk, nk, tq, tk):
    """Whole-KV-resident forward: K/V live in VMEM for the grid step and
    an in-kernel fori walks their blocks.  Fastest below the VMEM wall
    (measured 29.6 vs the streamed kernel's 38.6 ms fwd+bwd at T=8192
    B2 H16 D64); `_fa_kernel_streamed` takes over beyond it."""
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
    qi = pl.program_id(1)
    d = q.shape[-1]

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * bk, bk), :].astype(jnp.float32)
        return _fwd_block_update(q, k_blk, v_blk, m, l, acc, qi, kb,
                                 causal=causal, bq=bq, bk=bk, tq=tq, tk=tk)

    m0 = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    # causal: KV blocks past the diagonal are fully masked — bound the
    # walk at the last live block instead of visiting them (≈2× less
    # compute at long T; the skipped blocks contribute exactly nothing)
    if causal:
        last_row = (qi + 1) * bq - 1 + (tk - tq)
        nk_live = jnp.minimum(nk, last_row // bk + 1)
    else:
        nk_live = nk
    m, l, acc = jax.lax.fori_loop(0, nk_live, body, (m0, l0, acc0))
    _emit_out_lse(m, l, acc, o_ref, lse_ref, bq)


def _fa_kernel_streamed(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                        acc_ref, *, scale, causal, bq, bk, nk, tq, tk):
    """Streamed-KV forward: the KV walk is the INNERMOST grid axis, one
    (bk, d) block per step, with the online-softmax state (m, l, acc)
    in VMEM scratch — the same structure as the streaming backward
    kernels.  Nothing T-sized is ever VMEM-resident, so one chip runs
    T=32k+ (the whole-KV-resident design hits the 16 MB VMEM wall near
    T=8192 at H=16 D=64, where it remains the faster choice)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal (bottom-right aligned): block fully masked iff its lowest
    # column exceeds the block's highest row + (tk - tq) — skip its math
    # entirely (the grid still visits it; only compute is saved)
    live = True
    if causal:
        live = kb * bk <= (qi + 1) * bq - 1 + (tk - tq)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        m_new, l_new, acc_new = _fwd_block_update(
            q, k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            m_ref[...], l_ref[...], acc_ref[...], qi, kb,
            causal=causal, bq=bq, bk=bk, tq=tq, tk=tk)
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(kb == nk - 1)
    def _emit():
        _emit_out_lse(m_ref[...], l_ref[...], acc_ref[...], o_ref, lse_ref,
                      bq)


# one K (or V) tensor may keep this many bytes VMEM-resident in the
# forward; past it the streamed-KV kernel runs (measured boundary on the
# v5e: bf16 T=8192 D=64 = 1 MB fits, T=16384 OOMs the 16 MB VMEM once
# double-buffering and q/out blocks are accounted)
_KV_RESIDENT_MAX_BYTES = 1 << 20


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                                             "interpret"))
def _flash_core(q, k, v, causal, scale, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    # interpret (CPU tests): shrink blocks to the array; TPU: keep the
    # full tile and pad — Mosaic requires sublane/lane-divisible blocks
    bq = min(block_q, Tq) if interpret else block_q
    bk = min(block_k, Tk) if interpret else block_k
    pad_q = (-Tq) % bq
    pad_k = (-Tk) % bk
    qf = q.reshape(B * H, Tq, D)
    kf = k.reshape(B * H, Tk, D)
    vf = v.reshape(B * H, Tk, D)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    Tq_p, Tk_p = Tq + pad_q, Tk + pad_k
    nk = Tk_p // bk
    out_shape = [
        jax.ShapeDtypeStruct((B * H, Tq_p, D), q.dtype),
        jax.ShapeDtypeStruct((B * H, 8, Tq_p), jnp.float32),
    ]
    if Tk_p * D * k.dtype.itemsize <= _KV_RESIDENT_MAX_BYTES:
        # below the VMEM wall (PADDED extent — what the kernel actually
        # holds): whole KV resident, fastest
        kernel = functools.partial(_fa_kernel_resident, scale=scale,
                                   causal=causal, bq=bq, bk=bk, nk=nk,
                                   tq=Tq, tk=Tk)
        out, lse = pl.pallas_call(
            kernel,
            grid=(B * H, Tq_p // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, Tk_p, D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, Tk_p, D), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, 8, bq), lambda b, i: (b, 0, i)),
            ],
            out_shape=out_shape,
            interpret=interpret,
            name="flash_fwd",
        )(qf, kf, vf)
    else:
        # beyond it: stream KV via the innermost grid axis
        kernel = functools.partial(_fa_kernel_streamed, scale=scale,
                                   causal=causal, bq=bq, bk=bk, nk=nk,
                                   tq=Tq, tk=Tk)
        out, lse = pl.pallas_call(
            kernel,
            grid=(B * H, Tq_p // bq, nk),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
            ],
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd_streamed",
        )(qf, kf, vf)
    return (out[:, :Tq, :].reshape(B, H, Tq, D),
            lse[:, 0, :Tq].reshape(B, H, Tq))


def _bwd_block_terms(q_blk, k_blk, v_blk, do_blk, lse, delta, qb, kb, *,
                     scale, causal, bq, bk, tq, tk):
    """Shared per-(q-block, k-block) backward math: returns (p, ds)."""
    s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    row = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = jnp.logical_and(row < tq, col < tk)
    if causal:
        valid = jnp.logical_and(valid, col <= row + (tk - tq))
    # minor-dim insert on the f32 BEFORE any bool op: Mosaic only
    # relayouts 32-bit vectors when adding a lane dimension
    lse_col = lse[:, None]
    valid = jnp.logical_and(valid, jnp.isfinite(lse_col))
    p = jnp.where(valid,
                  jnp.exp(s - jnp.where(jnp.isfinite(lse_col), lse_col, 0.0)),
                  0.0)
    dp = jax.lax.dot_general(do_blk, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    return p, ds


def _fa_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, bq, bk, tq, tk):
    """grid (BH, nk, nq): q/do stream through VMEM one block per inner
    step; the dk/dv output block is revisited across the inner q loop
    (index map independent of the innermost dim) and accumulated in
    place — per-step VMEM stays O(block), any sequence length fits."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    # causal: a (q-block, k-block) pair is fully masked iff the block's
    # lowest key column exceeds its highest query row + (tk − tq) —
    # skip all five dots for it (≈2× less bwd compute at long T)
    live = True
    if causal:
        live = kb * bk <= (qb + 1) * bq - 1 + (tk - tq)

    @pl.when(live)
    def _accum():
        k_blk = k_ref[0].astype(jnp.float32)   # (bk, d)
        v_blk = v_ref[0].astype(jnp.float32)
        q_blk = q_ref[0].astype(jnp.float32)   # (bq, d) — streamed
        do_blk = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]      # (bq,)
        delta = delta_ref[0, 0]  # (bq,)
        p, ds = _bwd_block_terms(q_blk, k_blk, v_blk, do_blk, lse, delta,
                                 qb, kb, scale=scale, causal=causal, bq=bq,
                                 bk=bk, tq=tq, tk=tk)
        dv_ref[0] += jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_ref[0] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                  scale, causal, bq, bk, tq, tk):
    """grid (BH, nq, nk): k/v stream; dq block revisited/accumulated."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    live = True
    if causal:
        live = kb * bk <= (qi + 1) * bq - 1 + (tk - tq)

    @pl.when(live)
    def _accum():
        q_blk = q_ref[0].astype(jnp.float32)  # (bq, d)
        do_blk = do_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)  # (bk, d) — streamed
        v_blk = v_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        _p, ds = _bwd_block_terms(q_blk, k_blk, v_blk, do_blk, lse, delta,
                                  qi, kb, scale=scale, causal=causal, bq=bq,
                                  bk=bk, tq=tq, tk=tk)
        dq_ref[0] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def _flash_bwd_core(q, k, v, do, lse, delta, causal, scale, block_q, block_k,
                    interpret):
    """Fused Pallas backward: recompute-tiled dQ/dK/dV — O(T) memory,
    never materializes the (Tq, Tk) score matrix (SURVEY.md §2.3/§5.7:
    the long-context training enabler)."""
    from jax.experimental import pallas as pl

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq = min(block_q, Tq) if interpret else block_q
    bk = min(block_k, Tk) if interpret else block_k
    pad_q = (-Tq) % bq
    pad_k = (-Tk) % bk
    qf = q.reshape(B * H, Tq, D)
    kf = k.reshape(B * H, Tk, D)
    vf = v.reshape(B * H, Tk, D)
    dof = do.reshape(B * H, Tq, D)
    lsef = lse.reshape(B * H, Tq)
    deltaf = delta.reshape(B * H, Tq)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
        dof = jnp.pad(dof, ((0, 0), (0, pad_q), (0, 0)))
        # padded rows: -inf lse marks them fully masked in the kernels
        lsef = jnp.pad(lsef, ((0, 0), (0, pad_q)), constant_values=-jnp.inf)
        deltaf = jnp.pad(deltaf, ((0, 0), (0, pad_q)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    Tq_p, Tk_p = Tq + pad_q, Tk + pad_k
    nq, nk = Tq_p // bq, Tk_p // bk
    # 8-wide broadcast of the row stats (TPU sublane divisibility)
    lsef = jnp.broadcast_to(lsef[:, None, :], (B * H, 8, Tq_p))
    deltaf = jnp.broadcast_to(deltaf[:, None, :], (B * H, 8, Tq_p))

    # grid (BH, nk, nq): innermost q-steps stream q/do blocks; the dk/dv
    # block's index map ignores the inner dim so it stays resident in
    # VMEM and accumulates (fp32) — per-step VMEM is O(bq·D + bk·D)
    dkdv = functools.partial(_fa_dkdv_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, tq=Tq, tk=Tk)
    dk, dv = pl.pallas_call(
        dkdv,
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 8, bq), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tk_p, D), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Tk_p, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lsef, deltaf)

    dqk = functools.partial(_fa_dq_kernel, scale=scale, causal=causal,
                            bq=bq, bk=bk, tq=Tq, tk=Tk)
    dq = pl.pallas_call(
        dqk,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq_p, D), jnp.float32),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lsef, deltaf)

    return (dq[:, :Tq, :].reshape(B, H, Tq, D).astype(q.dtype),
            dk[:, :Tk, :].reshape(B, H, Tk, D).astype(k.dtype),
            dv[:, :Tk, :].reshape(B, H, Tk, D).astype(v.dtype))


# forward crossover, measured on v5e (BERT-large, T=128): XLA's fused
# attention beats the Pallas kernel ~62% vs ~56% MFU at short sequence —
# the kernel's win is the O(T²) memory it avoids, which only binds at
# long context.  Below this the XLA reference runs (identical numerics).
_PALLAS_FWD_MIN_SCORES = 512 * 512

# floor of the sub-crossover FUSED path (probs-in-bf16 XLA attention):
# between this and the Pallas crossover, bf16 TPU forwards keep Q/K/V
# bf16 into the MXU and cast the probs to bf16 for the PV matmul —
# halving the (B,H,T,T) probs HBM traffic that caps transformer-big
# T=256 (the weakest flagship row, 42.6% MFU).  Below the floor the
# score matrix fits cache and the fp32 reference costs nothing extra.
_SMALL_T_FUSED_MIN_SCORES = 128 * 128


def attention_small_t(q, k, v, causal: bool = False,
                      scale: Optional[float] = None):
    """Sub-crossover fused XLA attention for bf16 inputs: scores and
    softmax in fp32 (bf16 operands straight into the MXU — no fp32
    materialization of K), probs CAST TO THE INPUT DTYPE for the PV
    matmul with fp32 accumulation.  vs `attention_reference` this
    halves probs HBM traffic and skips two fp32 upcasts; numerics
    differ from the reference only by the bf16 rounding of the probs
    (|Δp| ≤ 2⁻⁸·p, tolerance-pinned in tests/test_paged_attention.py).
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = _safe_softmax(s).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _use_small_t(platform, tq, tk, dtype) -> bool:
    """TPU-only and bf16-only: CPU keeps the fp32 reference (the exact
    oracle the parity/eviction tests pin), fp32 inputs gain nothing
    from a bf16 probs cast."""
    return (platform == "tpu" and jnp.dtype(dtype) == jnp.bfloat16
            and _SMALL_T_FUSED_MIN_SCORES <= tq * tk
            < _PALLAS_FWD_MIN_SCORES)


def kernel_active(tq, tk, force_reference=False) -> bool:
    """Would flash_attention take the Pallas kernel at these sizes?
    Callers that stay on the XLA path can pick the layout-friendlier
    `attention_bthd` formulation instead of transposing to (B,H,T,D)."""
    return _use_pallas(jax.default_backend(), tq, tk, force_reference)


def attention_bthd(q, k, v, scale: Optional[float] = None):
    """Transpose-free XLA attention: q/k/v in (B, T, H, D) layout, the
    einsums carry the head transposition, scores accumulate in f32.

    Numerically equivalent to `attention_reference` for bf16-exact
    inputs and finite scores (non-causal, unmasked); avoids the four
    materialized (B,H,T,D) layout copies per call the transposed
    formulation costs below the flash crossover."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _per_shard(core, *arrays, **static):
    """Run a kernel core ((B, H, ...) arrays in and out) per shard of
    batch and heads."""
    spec = P(*mosaic.split(arrays[0].shape[:2]))
    return mosaic.per_shard(functools.partial(core, **static), spec, spec)(
        *arrays)


def _use_pallas(platform, tq, tk, force_reference: bool):
    if force_reference:
        return False
    if platform == "cpu":
        # interpreter is exact but slow — small shapes only (parity tests)
        return tq * tk <= 256 * 256
    return tq * tk >= _PALLAS_FWD_MIN_SCORES


# crossover for the backward: below this the XLA full-matrix backward is
# faster (the fused bwd recomputes scores twice — its win is the O(T²)
# memory it does NOT materialize, which only matters at long context)
_PALLAS_BWD_MIN_SCORES = 512 * 512


def _use_pallas_bwd(platform, tq, tk, force_reference: bool):
    if not _use_pallas(platform, tq, tk, force_reference):
        return False
    if platform == "cpu":
        return True  # interpret-mode parity tests exercise the kernels
    return tq * tk >= _PALLAS_BWD_MIN_SCORES


def _dispatch_fwd(q, k, v, causal, scale, block_q, block_k,
                  force_reference: bool):
    """Returns (out, lse); lse is None on the reference path."""
    platform = jax.default_backend()
    if _use_pallas(platform, q.shape[2], k.shape[2], force_reference):
        interp = platform == "cpu"
        bq = min(block_q, 64) if interp else block_q
        bk = min(block_k, 64) if interp else block_k
        return _per_shard(_flash_core, q, k, v, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, interpret=interp)
    if not force_reference and _use_small_t(platform, q.shape[2],
                                            k.shape[2], q.dtype):
        # sub-crossover fused path (lse=None → exact reference backward)
        return attention_small_t(q, k, v, causal, scale), None
    return attention_reference(q, k, v, causal, scale), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, force_reference):
    out, _ = _dispatch_fwd(q, k, v, causal, scale, block_q, block_k,
                           force_reference)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, force_reference):
    out, lse = _dispatch_fwd(q, k, v, causal, scale, block_q, block_k,
                             force_reference)
    return out, (q, k, v, out, lse)


def _flash_bwd_reference(q, k, v, do, causal, scale, delta=None):
    """Exact XLA backward (materializes the score matrix — reference
    path fallback; kept as the oracle for the fused kernel's tests).

    `delta` overrides the row term rowsum(dP∘P) — the lse-cotangent
    variant passes Δ − dlse here (same formula, one subtraction)."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = _safe_softmax(s)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    if delta is None:
        delta = jnp.sum(dp * p, axis=-1)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd(causal, scale, block_q, block_k, force_reference, res, do):
    """Fused Pallas backward (dQ/dK/dV, recompute tiling) when the
    forward ran the kernel; XLA full-matrix backward on the reference
    path (ref trains attention via cuDNN autograd — SURVEY.md §2.3)."""
    q, k, v, out, lse = res
    platform = jax.default_backend()
    if lse is None or not _use_pallas_bwd(platform, q.shape[2], k.shape[2],
                                          force_reference):
        return _flash_bwd_reference(q, k, v, do, causal, scale)
    interp = platform == "cpu"
    # bigger bwd blocks amortize the per-grid-step overhead of the
    # streaming kernels (measured 512 ≈ best on v5e at T≥2k)
    bq = min(block_q, 64) if interp else max(block_q, 512)
    bk = min(block_k, 64) if interp else max(block_k, 512)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return _per_shard(_flash_bwd_core, q, k, v, do, lse, delta,
                      causal=causal, scale=scale, block_q=bq, block_k=bk,
                      interpret=interp)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _reference_attention_lse(q, k, v, causal, scale):
    """(out, lse) from ONE score computation — the reference-path unit
    behind both flash_attention_with_lse and _reference_lse."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
    l = jnp.sum(e, axis=-1)
    lse = jnp.where(l > 0, m_safe + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)
    p = e / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
    return out, lse


def _reference_lse(q, k, causal, scale):
    B, H, Tq, D = q.shape
    v0 = jnp.zeros((B, H, k.shape[2], 1), jnp.float32)
    return _reference_attention_lse(q, k, v0, causal, scale)[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, force_reference):
    """(out, lse) variant — the composable unit for ring attention:
    per-block results merge exactly via their logsumexp stats."""
    platform = jax.default_backend()
    if _use_pallas(platform, q.shape[2], k.shape[2], force_reference):
        interp = platform == "cpu"
        bq = min(block_q, 64) if interp else block_q
        bk = min(block_k, 64) if interp else block_k
        return _per_shard(_flash_core, q, k, v, causal=causal, scale=scale,
                          block_q=bq, block_k=bk, interpret=interp)
    # reference path: ONE score computation yields both out and lse
    return _reference_attention_lse(q, k, v, causal, scale)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, force_reference):
    out, lse = _flash_lse(q, k, v, causal, scale, block_q, block_k,
                          force_reference)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, force_reference, res, cots):
    """d(lse)/ds = P, so the lse cotangent folds into the row term:
    dS = P ∘ (dP − (Δ − dlse)) — one extra subtraction, same kernels."""
    q, k, v, out, lse = res
    do, dlse = cots
    delta = (jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
             - dlse.astype(jnp.float32))
    platform = jax.default_backend()
    if _use_pallas_bwd(platform, q.shape[2], k.shape[2], force_reference):
        interp = platform == "cpu"
        bq = min(block_q, 64) if interp else max(block_q, 512)
        bk = min(block_k, 64) if interp else max(block_k, 512)
        return _per_shard(_flash_bwd_core, q, k, v, do, lse, delta,
                          causal=causal, scale=scale, block_q=bq, block_k=bk,
                          interpret=interp)
    return _flash_bwd_reference(q, k, v, do, causal, scale, delta=delta)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             force_reference: bool = False):
    """Differentiable (out, logsumexp) attention — ring building block.
    Blocks default to shape-derived sizes (`_auto_block`)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash_lse(q, k, v, causal, scale, _auto_block(q.shape[2], block_q),
                      _auto_block_k(k, block_k), force_reference)


def _auto_block(t: int, requested) -> int:
    """Largest of (512, 256, 128) dividing t, else 128 (the kernel's
    legacy fixed size).  Bigger forward blocks amortize per-grid-step
    overhead exactly like the backward's >=512 floor: at T=8192 the
    (512,512) forward measures 2.4x the (128,128) one (fwd+bwd
    29.6 vs 70.2 ms on one v5e, B2 H16 D64)."""
    if requested is not None:
        return requested
    for b in (512, 256, 128):
        if t % b == 0:
            return b
    return 128


def _auto_block_k(k, requested) -> int:
    """Default KV block.  On the STREAMED-KV path (per K/V tensor over
    the VMEM-resident budget) the per-grid-step work/DMA is one (bk, D)
    block, and 512-row blocks leave the MXU idle between 64 KB DMAs —
    1024 measures 47.9 vs 29.9 TF/s at T=16k D=64 (2048 regresses,
    4096 exceeds VMEM; benchmark/flash_profile.py sweep).  The bump
    applies only to DEFAULTED block_k and small head dims (the f32
    K+V double-buffered working set stays ≲2 MB at D≤128); explicit
    caller blocks are always honored."""
    if requested is not None:
        return requested
    t, d = k.shape[2], k.shape[3]
    b = _auto_block(t, None)
    itemsize = jnp.dtype(k.dtype).itemsize  # handles bfloat16 too
    if (t * d * itemsize > _KV_RESIDENT_MAX_BYTES and d <= 128
            and t >= 1024):
        b = max(b, 1024)
    return b


def flash_attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None, block_k: Optional[int] = None,
                    force_reference: bool = False):
    """Fused attention. q,k,v: (B, H, T, D) jax arrays (or NDArray).

    TPU → Pallas kernel; CPU → same kernel via the Pallas interpreter
    for small shapes, XLA reference otherwise (identical numerics).
    Differentiable via a custom VJP (exact softmax-attention backward).
    ``block_q``/``block_k`` default to shape-derived sizes (see
    `_auto_block`); pass explicit ints to pin them.
    """
    from ..ndarray.ndarray import NDArray, apply_op, raw

    was_nd = isinstance(q, NDArray)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    block_q = _auto_block(q.shape[2], block_q)
    block_k = _auto_block_k(k, block_k)
    if was_nd:
        # eager NDArray path: route through apply_op so autograd.record()
        # tapes the custom VJP like any other op
        return apply_op(
            lambda a, b, c: _flash(a, b, c, causal, scale, block_q, block_k,
                                   force_reference), q, k, v)
    return _flash(raw(q), raw(k), raw(v), causal, scale, block_q, block_k,
                  force_reference)
