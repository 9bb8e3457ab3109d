"""Neural-network operators.

Re-design of `src/operator/nn/` (SURVEY.md §2.3 "Dense NN": ref files
`convolution.cc`, `fully_connected.cc`, `batch_norm.cc`,
`layer_norm.cc`, `softmax.cc`, `dropout.cc`, `pooling.cc`
[UNVERIFIED]).  All heavy ops lower to XLA MXU primitives:
``lax.conv_general_dilated`` and ``jnp.dot``; normalizations are
expressed so XLA fuses the elementwise chains around the matmuls.
Layouts follow the reference's NCHW API; XLA:TPU's layout assignment
re-tiles internally, so no user-visible transposes are needed.

BatchNorm is functional: it RETURNS updated running stats; the Gluon
layer writes them back (eagerly) or routes them through the cached-op
state channel (hybridized) — see gluon/block.py.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .ndarray import NDArray, apply_op, raw, wrap

__all__ = [
    "FullyConnected",
    "Convolution",
    "Deconvolution",
    "Pooling",
    "Activation",
    "LeakyReLU",
    "softmax",
    "log_softmax",
    "softmin",
    "masked_softmax",
    "masked_log_softmax",
    "SoftmaxOutput",
    "batch_norm_stats",
    "BatchNorm",
    "LayerNorm",
    "GroupNorm",
    "InstanceNorm",
    "L2Normalization",
    "Dropout",
    "DropoutAdd",
    "UpSampling",
    "RNN",
    "smooth_l1",
    "softmax_cross_entropy",
    "gelu",
]


def _pair(v, n=2):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


# ---------------------------------------------------------------------- #
# dense / conv — the MXU ops
# ---------------------------------------------------------------------- #
def FullyConnected(data, weight, bias=None, num_hidden: int = 0, flatten: bool = True, no_bias: bool = False):
    """y = x · Wᵀ + b  (ref: src/operator/nn/fully_connected.cc).

    The contraction maps directly onto the MXU; keep inputs bf16 under
    AMP for full systolic-array throughput.
    """

    def f(x, w, *rest):
        xx = x.reshape(x.shape[0], -1) if flatten else x
        if xx.dtype != w.dtype:  # mixed precision: follow the weight dtype
            xx = xx.astype(w.dtype)
        y = jnp.dot(xx, w.T, preferred_element_type=_acc_type(xx.dtype))
        y = y.astype(xx.dtype)
        if rest:
            y = y + rest[0].astype(y.dtype)
        return y

    args = (data, weight) if (no_bias or bias is None) else (data, weight, bias)
    return apply_op(f, *args)


def _acc_type(dt):
    if dt in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return dt


def _stem_s2d_applicable(x, w, nd, stride, dilate, pad, groups) -> bool:
    """The classic TPU stem rewrite (MLPerf ResNet): a 7x7 stride-2
    pad-3 conv on a thin-channel input (the ImageNet stem) runs ~1.5x
    faster expressed as a 4x4 stride-1 conv on 2x2 space-to-depth input
    — exact same math (measured r4, docs/resnet_train_profile.md).
    TPU-only (other backends keep the canonical conv); opt out with
    MXTPU_NO_S2D_STEM=1."""
    import os

    import jax

    return (nd == 2 and groups == 1
            and tuple(stride) == (2, 2) and tuple(dilate) == (1, 1)
            and tuple(pad) == (3, 3)
            and w.ndim == 4 and w.shape[2:] == (7, 7) and w.shape[1] <= 4
            and x.ndim == 4 and x.shape[2] % 2 == 0 and x.shape[3] % 2 == 0
            and jax.default_backend() == "tpu"
            # opt-out only on an explicit truthy value ("0" keeps it on)
            and os.environ.get("MXTPU_NO_S2D_STEM", "0").lower()
            not in ("1", "true", "yes"))


def _stem_conv_s2d(x, w):
    """y = conv7x7_s2_p3(x, w) computed as conv4x4_s1 on space-to-depth
    input.  Derivation: with xs[(c,r,q)][i'] = x[c][2i'+r], the 7x7 tap
    dy maps to (ky, r) via dy = 2*ky - 1 + r, giving a 4x4 kernel and
    asymmetric padding (2, 1).  The kernel transform is differentiable
    (pure gather), so training through it is exact."""
    N, C, H, W = x.shape
    O = w.shape[0]
    xs = x.reshape(N, C, H // 2, 2, W // 2, 2) \
        .transpose(0, 1, 3, 5, 2, 4).reshape(N, C * 4, H // 2, W // 2)
    w4 = jnp.zeros((O, C, 2, 2, 4, 4), w.dtype)
    for ky in range(4):
        for r in range(2):
            dy = 2 * ky - 1 + r
            if not 0 <= dy < 7:
                continue
            for kx in range(4):
                for q in range(2):
                    dx = 2 * kx - 1 + q
                    if not 0 <= dx < 7:
                        continue
                    w4 = w4.at[:, :, r, q, ky, kx].set(w[:, :, dy, dx])
    w4 = w4.reshape(O, C * 4, 4, 4)
    return lax.conv_general_dilated(
        xs, w4, (1, 1), [(2, 1), (2, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def Convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter: int = 0, num_group: int = 1, no_bias: bool = False,
                layout: str = "NCHW", **kwargs):
    """N-D convolution via lax.conv_general_dilated (ref: convolution.cc).

    MXNet layout NCHW / NCW / NCDHW; XLA assigns TPU-friendly tiled
    layouts internally, and grouped/depthwise conv maps to
    feature_group_count.
    """
    nd = len(kernel) if kernel is not None else 2
    stride = _pair(stride or 1, nd)
    dilate = _pair(dilate or 1, nd)
    pad = _pair(pad or 0, nd)

    def f(x, w, *rest):
        spatial = "DHW"[-nd:] if nd <= 3 else None
        lhs_spec = "NC" + spatial
        rhs_spec = "OI" + spatial
        out_spec = lhs_spec
        if x.dtype != w.dtype:  # mixed precision: follow the weight dtype
            x = x.astype(w.dtype)
        # NOTE: no preferred_element_type here — this JAX version's conv
        # TRANSPOSE rule feeds the fp32 accumulator cotangent back into a
        # bf16 conv and type-errors; the TPU MXU accumulates conv in fp32
        # in hardware regardless of the HLO output dtype
        if _stem_s2d_applicable(x, w, nd, stride, dilate, pad, num_group):
            y = _stem_conv_s2d(x, w)
        else:
            y = lax.conv_general_dilated(
                x, w,
                window_strides=stride,
                padding=[(p, p) for p in pad],
                rhs_dilation=dilate,
                dimension_numbers=(lhs_spec, rhs_spec, out_spec),
                feature_group_count=num_group,
            )
        if rest:
            b = rest[0].reshape((1, -1) + (1,) * nd)
            y = y + b.astype(y.dtype)
        return y

    args = (data, weight) if (no_bias or bias is None) else (data, weight, bias)
    return apply_op(f, *args)


def Deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter: int = 0, num_group: int = 1,
                  no_bias: bool = True, **kwargs):
    """Transposed convolution (ref: deconvolution.cc)."""
    nd = len(kernel) if kernel is not None else 2
    stride = _pair(stride or 1, nd)
    dilate = _pair(dilate or 1, nd)
    pad = _pair(pad or 0, nd)
    adj = _pair(adj or 0, nd)

    def f(x, w, *rest):
        spatial = "DHW"[-nd:]
        if x.dtype != w.dtype:  # mixed precision: follow the weight dtype
            x = x.astype(w.dtype)
        # Weight stored (Cin, Cout/g, *k) — the reference layout.  The
        # transposed conv is computed directly as a dilated conv: dilate
        # the input by `stride`, flip the kernel spatially and swap its
        # in/out channel roles (per group), then convolve stride-1.
        # out = stride*(i-1) + dilate*(k-1) + 1 - 2*pad + adj, matching
        # deconvolution-inl.h.
        cin, coutg = w.shape[0], w.shape[1]
        ksz = w.shape[2:]
        g = num_group
        wt = w.reshape((g, cin // g, coutg) + ksz)
        wt = jnp.swapaxes(wt, 1, 2).reshape((g * coutg, cin // g) + ksz)
        wt = jnp.flip(wt, axis=tuple(range(2, 2 + nd)))
        padding = [(dilate[i] * (ksz[i] - 1) - pad[i],
                    dilate[i] * (ksz[i] - 1) - pad[i] + adj[i])
                   for i in range(nd)]
        y = lax.conv_general_dilated(
            x, wt,
            window_strides=(1,) * nd,
            padding=padding,
            lhs_dilation=stride,
            rhs_dilation=dilate,
            dimension_numbers=("NC" + spatial, "OI" + spatial, "NC" + spatial),
            feature_group_count=g,
        )
        if rest:
            y = y + rest[0].reshape((1, -1) + (1,) * nd).astype(y.dtype)
        return y

    args = (data, weight) if (no_bias or bias is None) else (data, weight, bias)
    return apply_op(f, *args)


def Pooling(data, kernel=None, pool_type: str = "max", stride=None, pad=None,
            global_pool: bool = False, pooling_convention: str = "valid",
            count_include_pad: bool = True, **kwargs):
    """Max/avg/sum/lp pooling via lax.reduce_window (ref: pooling.cc)."""

    def f(x):
        nd = x.ndim - 2
        if global_pool:
            return jnp.mean(x, axis=tuple(range(2, x.ndim)), keepdims=True) \
                if pool_type == "avg" else (
                    jnp.max(x, axis=tuple(range(2, x.ndim)), keepdims=True)
                    if pool_type == "max"
                    else jnp.sum(x, axis=tuple(range(2, x.ndim)), keepdims=True))
        k = _pair(kernel, nd)
        s = _pair(stride or k, nd)
        p = _pair(pad or 0, nd)
        window = (1, 1) + k
        strides = (1, 1) + s
        pads = ((0, 0), (0, 0)) + tuple((pp, pp) for pp in p)
        if pooling_convention == "full":
            # ceil-mode: extend the upper padding so partial windows count
            extra = []
            for i in range(nd):
                size = x.shape[2 + i] + 2 * p[i] - k[i]
                rem = size % s[i]
                extra.append(0 if rem == 0 else s[i] - rem)
            pads = ((0, 0), (0, 0)) + tuple((pp, pp + e) for pp, e in zip(p, extra))
        if pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            return lax.reduce_window(x, init, lax.max, window, strides, pads)
        ssum = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return ssum
        if count_include_pad:
            denom = 1.0
            for kk in k:
                denom *= kk
            return ssum / denom
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return ssum / counts

    return apply_op(f, data)


def UpSampling(data, scale: int = 2, sample_type: str = "nearest", **kwargs):
    def f(x):
        n, c, h, w = x.shape
        if sample_type == "nearest":
            return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        return jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")

    return apply_op(f, data)


# ---------------------------------------------------------------------- #
# activations / softmax
# ---------------------------------------------------------------------- #
_ACTS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
}


def Activation(data, act_type: str = "relu"):
    return apply_op(_ACTS[act_type], data)


def gelu(data, approximate: bool = True):
    return apply_op(lambda x: jax.nn.gelu(x, approximate=approximate), data)


def LeakyReLU(data, gamma=None, act_type: str = "leaky", slope: float = 0.25,
              lower_bound: float = 0.125, upper_bound: float = 0.334):
    if act_type in ("leaky", "rrelu"):
        return apply_op(lambda x: jnp.where(x >= 0, x, slope * x), data)
    if act_type == "elu":
        return apply_op(lambda x: jnp.where(x >= 0, x, slope * (jnp.exp(x) - 1)), data)
    if act_type == "selu":
        return apply_op(lambda x: jax.nn.selu(x), data)
    if act_type == "gelu":
        return apply_op(jax.nn.gelu, data)
    if act_type == "prelu":
        def f(x, g):
            g = g.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 2 else g
            return jnp.where(x >= 0, x, g * x)

        return apply_op(f, data, gamma)
    raise ValueError(f"unknown act_type {act_type}")


def softmax(data, axis: int = -1, temperature: Optional[float] = None, length=None):
    if length is not None:
        return masked_softmax(data, _length_mask(data, length, axis), axis=axis)

    def f(x):
        xx = x / temperature if temperature else x
        return jax.nn.softmax(xx, axis=axis)

    return apply_op(f, data)


def log_softmax(data, axis: int = -1, temperature: Optional[float] = None):
    def f(x):
        xx = x / temperature if temperature else x
        return jax.nn.log_softmax(xx, axis=axis)

    return apply_op(f, data)


def softmin(data, axis: int = -1):
    return apply_op(lambda x: jax.nn.softmax(-x, axis=axis), data)


def _length_mask(data, length, axis):
    steps = jnp.arange(raw(data).shape[axis])
    shape = [1] * raw(data).ndim
    shape[axis] = -1
    lshape = [1] * raw(data).ndim
    lshape[0] = -1
    return NDArray((steps.reshape(shape) < raw(wrap(length)).reshape(lshape)).astype(jnp.float32))


def masked_softmax(data, mask, axis: int = -1, temperature: float = 1.0):
    def f(x, m):
        neg = jnp.finfo(x.dtype).min
        xx = jnp.where(m.astype(bool), x / temperature, neg)
        y = jax.nn.softmax(xx, axis=axis)
        return jnp.where(m.astype(bool), y, 0.0)

    return apply_op(f, data, wrap(mask))


def masked_log_softmax(data, mask, axis: int = -1):
    def f(x, m):
        neg = jnp.finfo(x.dtype).min
        xx = jnp.where(m.astype(bool), x, neg)
        return jax.nn.log_softmax(xx, axis=axis)

    return apply_op(f, data, wrap(mask))


def SoftmaxOutput(data, label=None, grad_scale: float = 1.0, ignore_label: float = -1.0,
                  use_ignore: bool = False, multi_output: bool = False, **kwargs):
    """Legacy fused softmax+CE-grad op; forward = softmax (ref:
    softmax_output.cc).  `label` only shapes the backward (handled by
    Module's implicit-CE loss), so it is optional here."""
    return softmax(data, axis=1 if multi_output else -1)


def softmax_cross_entropy(data, label):
    def f(x, y):
        from ..ops.xent_kernel import fused_sparse_xent, should_fuse

        if should_fuse(x.shape[-1]):
            # streamed kernel path: no (N, V) log-prob materialization
            # (ops/xent_kernel.py; same fp32 lse numerics).  one_hot
            # semantics for out-of-range labels (they contribute 0,
            # where the kernel's gather would clip) are preserved
            # explicitly.
            yi = y.astype(jnp.int32)
            nll = fused_sparse_xent(x, yi)
            valid = (yi >= 0) & (yi < x.shape[-1])
            return jnp.sum(jnp.where(valid, nll, 0.0)).astype(x.dtype)
        logp = jax.nn.log_softmax(x, axis=-1)
        oh = jax.nn.one_hot(y.astype(jnp.int32), x.shape[-1], dtype=x.dtype)
        return -jnp.sum(oh * logp)

    return apply_op(f, data, wrap(label))


def smooth_l1(data, scalar: float = 1.0):
    def f(x):
        s2 = scalar * scalar
        return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * x * x, jnp.abs(x) - 0.5 / s2)

    return apply_op(f, data)


# ---------------------------------------------------------------------- #
# normalization
# ---------------------------------------------------------------------- #
def _bn_stats_f32(x, axis: int = 1):
    """Per-channel (mean, var) in f32 via a TWO-STAGE reduction.

    Measured on the v5e (r4, docs/performance.md): XLA lowers a direct
    bf16 `jnp.mean(x, (0, 2, 3))` to a reduce running ~6x off the HBM
    roofline on ResNet-sized activations; reshaping to (N, C, S) and
    reducing S then N with f32 accumulation is 3-6x faster end-to-end
    (fwd+bwd) and is the difference between BN costing 13.8 ms and
    ~4 ms of a BS128 ResNet-50 train step.  The square stays in x's
    dtype (f32 accumulate) so autodiff never saves an upcast f32 copy
    of the activation."""
    cnt = x.size // x.shape[axis]
    if x.ndim >= 3 and axis == 1:
        xr = x.reshape(x.shape[0], x.shape[1], -1)
        s = jnp.sum(jnp.sum(xr, 2, dtype=jnp.float32), 0)
        q = jnp.sum(jnp.sum(xr * xr, 2, dtype=jnp.float32), 0)
    elif axis in (x.ndim - 1, -1):
        xr = x.reshape(-1, x.shape[-1])
        s = jnp.sum(xr, 0, dtype=jnp.float32)
        q = jnp.sum(xr * xr, 0, dtype=jnp.float32)
    else:
        axes = tuple(i for i in range(x.ndim) if i != axis)
        s = jnp.sum(x, axes, dtype=jnp.float32)
        q = jnp.sum(jnp.square(x), axes, dtype=jnp.float32)
    mean = s / cnt
    var = jnp.maximum(q / cnt - jnp.square(mean), 0.0)
    return mean, var


def batch_norm_stats(data, axis: int = 1):
    """Per-channel (mean, var) over all non-`axis` dims (ref:
    batch_norm.cc stats kernels).  Accepts NDArray like every exported
    op — it previously reached into `_bn_stats_f32` with the wrapper
    type and crashed on public inputs."""

    def f(x):
        mean, var = _bn_stats_f32(x, axis)
        return mean.astype(x.dtype), var.astype(x.dtype)

    return apply_op(f, data, n_out=2)


def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps: float = 1e-5,
              momentum: float = 0.9, axis: int = 1, use_global_stats: bool = False,
              fix_gamma: bool = False, training: bool = False):
    """Functional BatchNorm (ref: batch_norm.cc).

    Returns ``(out, new_moving_mean, new_moving_var)``; callers own the
    state write-back (eager: in-place rebind; hybridized: the cached-op
    state channel).
    """
    use_batch_stats = training and not use_global_stats

    def f(x, g, b, mm, mv):
        if fix_gamma:
            g = jnp.ones_like(g)
        if use_batch_stats:
            mean32, var32 = _bn_stats_f32(x, axis)
            new_mm = momentum * mm + (1 - momentum) * mean32.astype(mm.dtype)
            new_mv = momentum * mv + (1 - momentum) * var32.astype(mv.dtype)
        else:
            mean32, var32 = mm.astype(jnp.float32), mv.astype(jnp.float32)
            new_mm, new_mv = mm, mv
        shape = [1] * x.ndim
        shape[axis] = -1
        # normalize as ONE fused multiply-add: inv/shift precomputed in
        # f32 at (C,) size, cast once (see _bn_stats_f32 perf note)
        inv = lax.rsqrt(var32 + eps) * g.astype(jnp.float32)
        shift = b.astype(jnp.float32) - mean32 * inv
        out = x * inv.astype(x.dtype).reshape(shape) \
            + shift.astype(x.dtype).reshape(shape)
        return out, new_mm, new_mv

    out = apply_op(f, data, gamma, beta, moving_mean, moving_var, n_out=3)
    return out


def LayerNorm(data, gamma, beta, axis: int = -1, eps: float = 1e-5):
    """ref: layer_norm.cc — mean/var over `axis`, affine transform."""

    def f(x, g, b):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=axis, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=axis, keepdims=True)
        shape = [1] * x.ndim
        shape[axis] = -1
        y = (x32 - mean) * lax.rsqrt(var + eps)
        return (y.astype(x.dtype) * g.reshape(shape) + b.reshape(shape)).astype(x.dtype)

    return apply_op(f, data, gamma, beta)


def GroupNorm(data, gamma, beta, num_groups: int = 1, eps: float = 1e-5):
    def f(x, g, b):
        n, c = x.shape[:2]
        xg = x.reshape((n, num_groups, c // num_groups) + x.shape[2:])
        axes = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=axes, keepdims=True)
        var = jnp.var(xg, axis=axes, keepdims=True)
        y = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
        shape = (1, c) + (1,) * (x.ndim - 2)
        return y * g.reshape(shape) + b.reshape(shape)

    return apply_op(f, data, gamma, beta)


def InstanceNorm(data, gamma, beta, eps: float = 1e-5):
    def f(x, g, b):
        axes = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        y = (x - mean) * lax.rsqrt(var + eps)
        shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
        return y * g.reshape(shape) + b.reshape(shape)

    return apply_op(f, data, gamma, beta)


def L2Normalization(data, eps: float = 1e-10, mode: str = "instance"):
    def f(x):
        if mode == "channel":
            denom = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
        elif mode == "spatial":
            denom = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(2, x.ndim)), keepdims=True) + eps)
        else:
            denom = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1) + eps)
            denom = denom.reshape((-1,) + (1,) * (x.ndim - 1))
        return x / denom

    return apply_op(f, data)


# ---------------------------------------------------------------------- #
# dropout — RNG threaded via mx.random's trace-aware provider
# ---------------------------------------------------------------------- #
def Dropout(data, p: float = 0.5, mode: str = "training", axes=(),
            training=None):
    """ref: dropout.cc.  Keys come from `mx.random`'s provider, which is
    a concrete key eagerly and a traced key argument under hybridize —
    so the jitted program stays key-parametric (no baked-in constants).

    ``training=None`` (default) follows `autograd`'s train mode like the
    reference op (active inside ``record()``, identity outside); pass an
    explicit bool to override.
    """
    if training is None:
        from .. import _tape

        training = _tape.is_training()
    if not (training or mode == "always") or p <= 0.0:
        return wrap(data)
    from .. import random as _random

    key = _random.next_key()

    if not axes:
        # fused path on EVERY backend: on TPU the uint8 keep-mask comes
        # from the in-kernel Mosaic PRNG (1 byte/element, off the
        # critical path — the BERT "dropout tax", BASELINE.md) and the
        # apply fuses into neighboring XLA fusions; backward reuses the
        # saved mask.  Elsewhere a block-keyed threefry mask with the
        # same structure.  In a program traced over a mesh both are
        # drawn per shard, tile for tile the global mask
        # (ops/mosaic.py), so this path stays active on multi-device
        # meshes.
        from ..ops.dropout_kernel import fused_dropout

        seed_arr = _random.key_to_seed(key)
        return apply_op(lambda x: fused_dropout(x, seed_arr, float(p)), data)

    def f(x, k):
        shape = list(x.shape)
        for a in axes:
            shape[a] = 1
        keep = jax.random.bernoulli(k, 1.0 - p, shape=tuple(shape))
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)

    return apply_op(lambda x: f(x, key), data)


def DropoutAdd(data, residual, p: float = 0.5, mode: str = "training",
               training=None):
    """``residual + Dropout(data)`` — the transformer post-sublayer
    pattern; the masked apply and the add ride one XLA fusion.  Same
    mask bits, partitioning, AND train-mode default as `Dropout`
    (no-axes form; ``training=None`` follows `autograd`'s train mode);
    falls back to the plain sum when dropout is inactive."""
    if training is None:
        from .. import _tape

        training = _tape.is_training()
    if not (training or mode == "always") or p <= 0.0:
        return wrap(data) + wrap(residual)
    from .. import random as _random
    from ..ops.dropout_kernel import fused_dropout_add

    seed_arr = _random.key_to_seed(_random.next_key())
    return apply_op(
        lambda x, r: fused_dropout_add(x, r, seed_arr, float(p)),
        data, residual)


# ---------------------------------------------------------------------- #
# fused RNN op (ref: src/operator/rnn.cc — cuDNN RNN on GPU).
# TPU-native: lax.scan over fused cell matmuls; weights arrive packed
# exactly like the reference's single param blob.
# ---------------------------------------------------------------------- #
def RNN(data, parameters, state, state_cell=None, mode: str = "lstm",
        state_size: int = 0, num_layers: int = 1, bidirectional: bool = False,
        p: float = 0.0, state_outputs: bool = True, training: bool = False, **kwargs):
    from .rnn_impl import fused_rnn

    return fused_rnn(data, parameters, state, state_cell, mode=mode,
                     state_size=state_size, num_layers=num_layers,
                     bidirectional=bidirectional, dropout=p, training=training)
