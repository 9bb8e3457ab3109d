"""Fused sparse softmax cross-entropy over large vocabularies.

The XLA path for `-log_softmax(logits)[label]` on a (B*T, 30k) logits
tensor materializes the full fp32 log-probability tensor (measured on
the BERT-large flagship: a 500 MB fp32 write + re-reads ≈ 3 ms of the
step, `docs/performance.md`).  This kernel streams vocab chunks
through VMEM with the online-softmax recurrence (the flash-attention
trick applied to the loss): forward reads the logits ONCE and emits
only per-row lse; backward regenerates softmax from the saved lse and
writes d(logits) directly — no (N, V) fp32 tensor ever exists.

The forward kernel does ONLY the V-wide streaming work (max/exp/sum);
the O(N) `logits[label]` gather runs as an XLA gather on 4k elements,
keeping forward per-lane VPU work minimal (an in-kernel label
hit-accumulate across every block measured ~1.6x slower), and only the
ragged tail vocab block pays masking.  The backward keeps the label
compare IN-kernel: the alternative — an O(N) scatter of -g outside —
measured ~6 ms (TPU serializes scalar scatters), vs ~0.3 ms for the
per-lane compare.

Numerics match the unfused fp32 reference: chunks are upcast to f32 in
VMEM, max/sum accumulate in f32, and `lse = m + log(l)` is the same
quantity XLA's log_softmax computes.  The kernel uses no TPU-only
primitives, so interpret mode covers it on CPU in CI; non-TPU backends
take an equivalent jnp reference (ref: src/operator/nn/softmax.cc
SoftmaxOutput fused grad, SURVEY.md §2.3).  Rows are independent: in a
program traced over a mesh the kernels run per shard of rows
(`ops/mosaic.py`).

API: `fused_sparse_xent(logits, labels) -> nll` per row, custom VJP in
d(logits) only.  `logits`: (..., V); `labels`: int (...).

Per-row vectors ride as (BR, 1) blocks — Mosaic wants 2D tiled
operands (a bare s32[N] carries XLA's T(1024) layout, which kernel
block tilings cannot match).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mosaic

__all__ = ["fused_sparse_xent", "fused_smoothed_xent", "should_fuse",
           "FUSED_MIN_CLASSES"]

_BR = 128    # rows per block
_BV = 7680   # vocab lanes per block (60 * 128)

# below this class count the streamed kernel's per-call overhead
# outweighs the (N, V) fp32 log-prob tensor it avoids
FUSED_MIN_CLASSES = 512


def should_fuse(num_classes: int) -> bool:
    """THE gate both public xent entry points share (gluon loss and
    mx.nd.softmax_cross_entropy) — one constant, one backend list."""
    return num_classes >= FUSED_MIN_CLASSES and _kernel_backend()


def _ceil(a, b):
    return -(-a // b)


def _fwd_kernel(x_ref, *refs, V, bv, nv, want_sum):
    """want_sum=False: refs = (lse_ref, m_ref, l_ref) — the plain-xent
    forward, unchanged cost.  want_sum=True adds (xsum_ref out, s_ref
    scratch): the per-row raw-logit sum rides the same streaming pass
    (the label-smoothing term is lse - sum/V); only the smoothed path
    pays the extra per-lane add."""
    from jax.experimental import pallas as pl

    if want_sum:
        lse_ref, xsum_ref, m_ref, l_ref, s_ref = refs
    else:
        lse_ref, m_ref, l_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        if want_sum:
            s_ref[...] = jnp.zeros_like(s_ref)

    def update(x, xz):
        m_old = m_ref[...]  # (BR, 1)
        m_new = jnp.maximum(m_old, jnp.max(x, axis=1, keepdims=True))
        # exp(-inf - -inf) would be NaN before any real lane arrives
        corr = jnp.where(m_old == -jnp.inf, 0.0, jnp.exp(m_old - m_new))
        l_ref[...] = l_ref[...] * corr + jnp.sum(
            jnp.exp(x - m_new), axis=1, keepdims=True)
        m_ref[...] = m_new
        if want_sum:
            # xz = x with tail lanes zeroed (not -inf)
            s_ref[...] = s_ref[...] + jnp.sum(xz, axis=1, keepdims=True)

    ragged = V % bv != 0
    if ragged:
        # only the LAST vocab block has out-of-range lanes to mask
        @pl.when(j == nv - 1)
        def _tail():
            x = x_ref[...].astype(jnp.float32)
            vidx = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            update(jnp.where(vidx < V, x, -jnp.inf),
                   jnp.where(vidx < V, x, 0.0) if want_sum else None)

        @pl.when(j < nv - 1)
        def _body():
            x = x_ref[...].astype(jnp.float32)
            update(x, x)
    else:
        x = x_ref[...].astype(jnp.float32)
        update(x, x)

    @pl.when(j == nv - 1)
    def _emit():
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])
        if want_sum:
            xsum_ref[...] = s_ref[...]


def _bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref, *, bv, V, eps):
    # d(logits) = (softmax - target) * g with target = (1-eps)·onehot +
    # eps/V (eps=0 is the plain xent this kernel shipped with).  The
    # label compare runs in-kernel: an O(N) XLA scatter for the -g term
    # measured ~6 ms (4096 scalar updates serialize on TPU), the
    # per-lane compare ~0.3.  Out-of-range tail lanes write garbage
    # that the BlockSpec clips at the array boundary.
    from jax.experimental import pallas as pl

    x = x_ref[...].astype(jnp.float32)
    p = jnp.exp(x - lse_ref[...])  # (BR,1) broadcasts over lanes
    vidx = pl.program_id(1) * bv + jax.lax.broadcasted_iota(
        jnp.int32, x.shape, 1)
    hit = (vidx == lab_ref[...]).astype(jnp.float32)
    target = hit if eps == 0.0 else (1.0 - eps) * hit + eps / V
    dx_ref[...] = ((p - target) * g_ref[...]).astype(dx_ref.dtype)


def _block_rows(N):
    return _BR if N % _BR == 0 else (8 if N % 8 == 0 else 1)


def _pallas_fwd(x2, interpret, want_sum):
    """(lse,) — or, for the smoothed loss (want_sum), (lse, per-row
    logit sum) — in ONE streaming pass over (N, V)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, V = x2.shape
    br = _block_rows(N)
    bv = min(_BV, _ceil(V, 128) * 128)
    nv = _ceil(V, bv)
    out = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    row = jax.ShapeDtypeStruct((N, 1), jnp.float32)
    scratch = pltpu.VMEM((br, 1), jnp.float32)
    n_out = 2 if want_sum else 1
    res = pl.pallas_call(
        functools.partial(_fwd_kernel, V=V, bv=bv, nv=nv,
                          want_sum=want_sum),
        grid=(_ceil(N, br), nv),
        in_specs=[pl.BlockSpec((br, bv), lambda i, j: (i, j))],
        out_specs=(out,) * n_out if want_sum else out,
        out_shape=(row,) * n_out if want_sum else row,
        scratch_shapes=[scratch] * (n_out + 1),
        interpret=interpret,
        name="xent_fwd",
    )(x2)
    return tuple(r[:, 0] for r in (res if want_sum else (res,)))


def _pallas_bwd(x2, labels, lse, g, interpret, eps=0.0):
    from jax.experimental import pallas as pl

    N, V = x2.shape
    br = _block_rows(N)
    bv = min(_BV, _ceil(V, 128) * 128)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, bv=bv, V=V, eps=float(eps)),
        grid=(_ceil(N, br), _ceil(V, bv)),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
        name="xent_bwd",
    )(x2, labels.astype(jnp.int32).reshape(N, 1), lse.reshape(N, 1),
      g.astype(jnp.float32).reshape(N, 1))


def _label_logit(x2, labels):
    """logits[row, label] upcast to f32 — exact for bf16 inputs."""
    lab = labels.astype(jnp.int32)[:, None]
    return jnp.take_along_axis(x2, lab, axis=-1)[:, 0].astype(jnp.float32)


def _ref_lse(x2):
    return jax.scipy.special.logsumexp(x2.astype(jnp.float32), axis=-1)


def _kernel_backend() -> bool:
    return jax.default_backend() == "tpu"


def _row_spec(n_rows):
    # shards of rows stay a multiple of the 8-row block
    return mosaic.split((n_rows,), (8,))[0]


def _stats_of(x2, eps, interpret=False):
    """(lse, xsum-or-None): the plain path (eps=0) runs the lse-only
    kernel so it pays nothing for the smoothing machinery."""
    if _kernel_backend() or interpret:
        rows = _row_spec(x2.shape[0])
        fwd = functools.partial(_pallas_fwd, interpret=interpret,
                                want_sum=eps != 0.0)
        stats = mosaic.per_shard(fwd, (P(rows, None),), P(rows))(x2)
        return stats if eps != 0.0 else (stats[0], None)
    if eps == 0.0:
        return _ref_lse(x2), None
    return _ref_lse(x2), jnp.sum(x2.astype(jnp.float32), axis=-1)


def _kernel_bwd(x2, labels, lse, g, interpret, eps):
    rows = _row_spec(x2.shape[0])
    bwd = functools.partial(_pallas_bwd, interpret=interpret, eps=eps)
    return mosaic.per_shard(bwd, (P(rows, None),) + (P(rows),) * 3,
                            P(rows, None))(x2, labels, lse, g)


def _smooth_value(x2, labels, eps, lse, xsum):
    # loss = lse - (1-eps)·logits[label] - eps·mean_v(logits): the
    # exact jax.nn.log_softmax-based smoothed CE, reassociated so only
    # O(N) row statistics survive the (N, V) stream
    pick = _label_logit(x2, labels)
    if eps == 0.0:
        return lse - pick
    return lse - (1.0 - eps) * pick - (eps / x2.shape[-1]) * xsum


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _xent2d(x2, labels, eps):
    lse, xsum = _stats_of(x2, eps)
    return _smooth_value(x2, labels, eps, lse, xsum)


def _xent2d_fwd(x2, labels, eps):
    lse, xsum = _stats_of(x2, eps)
    return _smooth_value(x2, labels, eps, lse, xsum), (x2, labels, lse)


def _xent2d_bwd(eps, res, g):
    x2, labels, lse = res
    if _kernel_backend():
        return _kernel_bwd(x2, labels, lse, g, False, eps), None
    V = x2.shape[-1]
    p = jnp.exp(x2.astype(jnp.float32) - lse[:, None])
    oh = jax.nn.one_hot(labels.astype(jnp.int32), V, dtype=jnp.float32)
    tgt = oh if eps == 0.0 else (1.0 - eps) * oh + eps / V
    dx = ((p - tgt) * g.astype(jnp.float32)[:, None]).astype(x2.dtype)
    return dx, None


_xent2d.defvjp(_xent2d_fwd, _xent2d_bwd)


def fused_sparse_xent(logits, labels):
    """Per-element negative log-likelihood `lse - logits[label]`.

    logits: (..., V); labels: integer (...) matching the leading dims.
    Returns f32 (...) — differentiable in logits (streamed Pallas
    kernel on TPU; exact jnp reference elsewhere)."""
    V = logits.shape[-1]
    lead = logits.shape[:-1]
    x2 = logits.reshape(-1, V)
    nll = _xent2d(x2, labels.reshape(-1), 0.0)
    return nll.reshape(lead)


def fused_smoothed_xent(logits, labels, smoothing: float):
    """Label-smoothed CE `lse - (1-eps)·logits[label] - eps·mean(logits)`
    per element — the exact log_softmax-based smoothed loss, streamed so
    no (N, V) fp32 log-prob tensor ever materializes (the per-row logit
    sum rides the same online-softmax pass; the backward kernel folds
    the eps/V uniform target in).  smoothing=0 is `fused_sparse_xent`."""
    V = logits.shape[-1]
    lead = logits.shape[:-1]
    x2 = logits.reshape(-1, V)
    loss = _xent2d(x2, labels.reshape(-1), float(smoothing))
    return loss.reshape(lead)


def run_interpret(logits, labels, smoothing: float = 0.0):
    """Interpret-mode kernel run (CPU CI parity for the kernel math) —
    same want_sum selection as production: smoothing=0 exercises the
    lse-only kernel variant, smoothing>0 the (lse, xsum) one."""
    V = logits.shape[-1]
    x2 = logits.reshape(-1, V)
    eps = float(smoothing)
    lse, xsum = _stats_of(x2, eps, interpret=True)
    loss = _smooth_value(x2, labels.reshape(-1), eps, lse, xsum)
    return loss.reshape(logits.shape[:-1]), lse


def run_interpret_bwd(logits, labels, lse, g, smoothing: float = 0.0):
    V = logits.shape[-1]
    x2 = logits.reshape(-1, V)
    dx = _kernel_bwd(x2, labels.reshape(-1), lse.reshape(-1),
                     g.reshape(-1), True, float(smoothing))
    return dx.reshape(logits.shape)
