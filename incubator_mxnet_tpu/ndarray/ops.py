"""Tensor-algebra op namespace with MXNet semantics.

Re-design of `src/operator/tensor/` (SURVEY.md §2.3 "Tensor algebra",
ref files `elemwise_binary_op_basic.cc`, `broadcast_reduce_op_value.cc`,
`dot.cc`, `matrix_op.cc`, `indexing_op.cc`, `ordering_op.cc`
[UNVERIFIED]).  Every function lowers to jax.numpy/lax — XLA fuses and
tiles these onto the VPU/MXU; there are no hand-written kernels here.
Names and argument conventions follow the reference's `mx.nd.*` surface
(e.g. ``concat(dim=)``, ``slice_axis``, explicit ``broadcast_*`` ops)
so reference user code ports unchanged.

Anything not explicitly defined falls through to `jax.numpy` via the
module-level ``__getattr__`` in the package ``__init__``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .ndarray import NDArray, apply_op, raw, wrap

__all__ = []  # populated at bottom


def _exported(fn):
    __all__.append(fn.__name__)
    return fn


# ---------------------------------------------------------------------- #
# elementwise unary
# ---------------------------------------------------------------------- #
def _unary(name, jfn):
    def op(data, **kwargs):
        return apply_op(jfn, data)

    op.__name__ = name
    op.__doc__ = f"Elementwise {name} (XLA fused)."
    __all__.append(name)
    return op


exp = _unary("exp", jnp.exp)
log = _unary("log", jnp.log)
log2 = _unary("log2", jnp.log2)
log10 = _unary("log10", jnp.log10)
log1p = _unary("log1p", jnp.log1p)
expm1 = _unary("expm1", jnp.expm1)
sqrt = _unary("sqrt", jnp.sqrt)
rsqrt = _unary("rsqrt", lax.rsqrt)
cbrt = _unary("cbrt", jnp.cbrt)
rcbrt = _unary("rcbrt", lambda x: 1.0 / jnp.cbrt(x))
square = _unary("square", jnp.square)
reciprocal = _unary("reciprocal", jnp.reciprocal)
abs = _unary("abs", jnp.abs)
sign = _unary("sign", jnp.sign)
floor = _unary("floor", jnp.floor)
ceil = _unary("ceil", jnp.ceil)
round = _unary("round", jnp.round)
rint = _unary("rint", jnp.rint)
trunc = _unary("trunc", jnp.trunc)
fix = _unary("fix", jnp.trunc)
negative = _unary("negative", jnp.negative)
sigmoid = _unary("sigmoid", jax.nn.sigmoid)
hard_sigmoid = _unary("hard_sigmoid", lambda x: jnp.clip(0.2 * x + 0.5, 0.0, 1.0))
relu = _unary("relu", jax.nn.relu)
softsign = _unary("softsign", jax.nn.soft_sign)
erf = _unary("erf", jax.scipy.special.erf)
erfinv = _unary("erfinv", jax.scipy.special.erfinv)
gamma = _unary("gamma", lambda x: jnp.exp(jax.scipy.special.gammaln(x)))
gammaln = _unary("gammaln", jax.scipy.special.gammaln)
sin = _unary("sin", jnp.sin)
cos = _unary("cos", jnp.cos)
tan = _unary("tan", jnp.tan)
arcsin = _unary("arcsin", jnp.arcsin)
arccos = _unary("arccos", jnp.arccos)
arctan = _unary("arctan", jnp.arctan)
sinh = _unary("sinh", jnp.sinh)
cosh = _unary("cosh", jnp.cosh)
tanh = _unary("tanh", jnp.tanh)
arcsinh = _unary("arcsinh", jnp.arcsinh)
arccosh = _unary("arccosh", jnp.arccosh)
arctanh = _unary("arctanh", jnp.arctanh)
degrees = _unary("degrees", jnp.degrees)
radians = _unary("radians", jnp.radians)
logical_not = _unary("logical_not", lambda x: (~(x.astype(bool))).astype(x.dtype))


@_exported
def clip(data, a_min, a_max):
    return apply_op(lambda x: jnp.clip(x, a_min, a_max), data)


@_exported
def identity(data):
    return apply_op(lambda x: x, data)


@_exported
def cast(data, dtype):
    return apply_op(lambda x: x.astype(jnp.dtype(dtype)), data)


@_exported
def isnan(data):
    return apply_op(lambda x: jnp.isnan(x).astype(jnp.float32), data)


@_exported
def isinf(data):
    return apply_op(lambda x: jnp.isinf(x).astype(jnp.float32), data)


@_exported
def isfinite(data):
    return apply_op(lambda x: jnp.isfinite(x).astype(jnp.float32), data)


# ---------------------------------------------------------------------- #
# elementwise binary (+ explicit broadcast_* parity aliases)
# ---------------------------------------------------------------------- #
def _binary(name, jfn):
    def op(lhs, rhs, **kwargs):
        return apply_op(jfn, lhs, rhs)

    op.__name__ = name
    __all__.append(name)
    return op


add = _binary("add", jnp.add)
subtract = _binary("subtract", jnp.subtract)
multiply = _binary("multiply", jnp.multiply)
divide = _binary("divide", jnp.divide)
modulo = _binary("modulo", jnp.mod)
power = _binary("power", jnp.power)
maximum = _binary("maximum", jnp.maximum)
minimum = _binary("minimum", jnp.minimum)
hypot = _binary("hypot", jnp.hypot)
arctan2 = _binary("arctan2", jnp.arctan2)
equal = _binary("equal", lambda a, b: (a == b).astype(jnp.result_type(a)))
not_equal = _binary("not_equal", lambda a, b: (a != b).astype(jnp.result_type(a)))
greater = _binary("greater", lambda a, b: (a > b).astype(jnp.result_type(a)))
greater_equal = _binary("greater_equal", lambda a, b: (a >= b).astype(jnp.result_type(a)))
lesser = _binary("lesser", lambda a, b: (a < b).astype(jnp.result_type(a)))
lesser_equal = _binary("lesser_equal", lambda a, b: (a <= b).astype(jnp.result_type(a)))
logical_and = _binary("logical_and", lambda a, b: jnp.logical_and(a, b).astype(jnp.result_type(a)))
logical_or = _binary("logical_or", lambda a, b: jnp.logical_or(a, b).astype(jnp.result_type(a)))
logical_xor = _binary("logical_xor", lambda a, b: jnp.logical_xor(a, b).astype(jnp.result_type(a)))

# MXNet exposes broadcasting binaries as broadcast_* ops; numpy-style
# broadcasting makes them the same function here.
for _n, _f in [
    ("broadcast_add", jnp.add), ("broadcast_plus", jnp.add),
    ("broadcast_sub", jnp.subtract), ("broadcast_minus", jnp.subtract),
    ("broadcast_mul", jnp.multiply), ("broadcast_div", jnp.divide),
    # mshadow_op::mod is divisor-sign (fmod + divisor correction when
    # signs differ) — i.e. python/numpy-style, same kernel `%` routes
    # through upstream; jnp.mod matches it
    ("broadcast_mod", jnp.mod), ("broadcast_power", jnp.power),
    ("broadcast_maximum", jnp.maximum), ("broadcast_minimum", jnp.minimum),
    ("broadcast_hypot", jnp.hypot),
    ("broadcast_equal", lambda a, b: (a == b).astype(jnp.result_type(a))),
    ("broadcast_not_equal", lambda a, b: (a != b).astype(jnp.result_type(a))),
    ("broadcast_greater", lambda a, b: (a > b).astype(jnp.result_type(a))),
    ("broadcast_greater_equal", lambda a, b: (a >= b).astype(jnp.result_type(a))),
    ("broadcast_lesser", lambda a, b: (a < b).astype(jnp.result_type(a))),
    ("broadcast_lesser_equal", lambda a, b: (a <= b).astype(jnp.result_type(a))),
    ("broadcast_logical_and", lambda a, b: jnp.logical_and(a, b).astype(jnp.result_type(a))),
    ("broadcast_logical_or", lambda a, b: jnp.logical_or(a, b).astype(jnp.result_type(a))),
    ("broadcast_logical_xor", lambda a, b: jnp.logical_xor(a, b).astype(jnp.result_type(a))),
]:
    globals()[_n] = _binary(_n, _f)

elemwise_add = _binary("elemwise_add", jnp.add)
elemwise_sub = _binary("elemwise_sub", jnp.subtract)
elemwise_mul = _binary("elemwise_mul", jnp.multiply)
elemwise_div = _binary("elemwise_div", jnp.divide)


@_exported
def broadcast_to(data, shape):
    return apply_op(lambda x: jnp.broadcast_to(x, tuple(shape)), data)


@_exported
def broadcast_like(lhs, rhs):
    return apply_op(lambda x, y: jnp.broadcast_to(x, y.shape), lhs, rhs)


@_exported
def broadcast_axis(data, axis, size):
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    sizes = (size,) if isinstance(size, int) else tuple(size)

    def f(x):
        tgt = list(x.shape)
        for a, s in zip(axes, sizes):
            tgt[a] = s
        return jnp.broadcast_to(x, tuple(tgt))

    return apply_op(f, data)


@_exported
def where(condition, x, y):
    return apply_op(lambda c, a, b: jnp.where(c.astype(bool), a, b), condition, x, y)


# ---------------------------------------------------------------------- #
# reductions
# ---------------------------------------------------------------------- #
def _reduce(name, jfn):
    def op(data, axis=None, keepdims=False, exclude=False, **kwargs):
        def f(x):
            ax = axis
            if isinstance(ax, list):
                ax = tuple(ax)
            if exclude and ax is not None:
                ax_t = (ax,) if isinstance(ax, int) else tuple(ax)
                ax = tuple(i for i in range(x.ndim) if i not in ax_t)
            return jfn(x, axis=ax, keepdims=keepdims)

        return apply_op(f, data)

    op.__name__ = name
    __all__.append(name)
    return op


sum = _reduce("sum", jnp.sum)
mean = _reduce("mean", jnp.mean)
prod = _reduce("prod", jnp.prod)
max = _reduce("max", jnp.max)
min = _reduce("min", jnp.min)
nansum = _reduce("nansum", jnp.nansum)
nanprod = _reduce("nanprod", jnp.nanprod)
sum_axis = _reduce("sum_axis", jnp.sum)
max_axis = _reduce("max_axis", jnp.max)
min_axis = _reduce("min_axis", jnp.min)


@_exported
def norm(data, ord=2, axis=None, keepdims=False):
    def f(x):
        if axis is None:
            return jnp.linalg.norm(x.reshape(-1), ord=ord, keepdims=keepdims)
        return jnp.linalg.norm(x, ord=ord, axis=axis if not isinstance(axis, list) else tuple(axis), keepdims=keepdims)

    return apply_op(f, data)


@_exported
def argmax(data, axis=None, keepdims=False):
    return apply_op(lambda x: jnp.argmax(x, axis=axis, keepdims=keepdims).astype(jnp.float32), data)


@_exported
def argmin(data, axis=None, keepdims=False):
    return apply_op(lambda x: jnp.argmin(x, axis=axis, keepdims=keepdims).astype(jnp.float32), data)


@_exported
def argmax_channel(data):
    return apply_op(lambda x: jnp.argmax(x, axis=-1).astype(jnp.float32), data)


# ---------------------------------------------------------------------- #
# dot products (MXNet semantics: reference src/operator/tensor/dot.cc)
# ---------------------------------------------------------------------- #
@_exported
def dot(lhs, rhs, transpose_a: bool = False, transpose_b: bool = False):
    """MXNet dot: contract last axis of lhs with first axis of rhs (MXU)."""

    def f(a, b):
        if transpose_a:
            a = jnp.transpose(a)
        if transpose_b:
            b = jnp.transpose(b)
        return jnp.tensordot(a, b, axes=1) if (a.ndim > 2 or b.ndim > 2) else a @ b

    return apply_op(f, lhs, rhs)


@_exported
def batch_dot(lhs, rhs, transpose_a: bool = False, transpose_b: bool = False):
    def f(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)

    return apply_op(f, lhs, rhs)


@_exported
def khatri_rao(*args):
    def f(*ms):
        out = ms[0]
        for m in ms[1:]:
            out = (out[:, None, :] * m[None, :, :]).reshape(-1, out.shape[-1])
        return out

    return apply_op(f, *args)


# ---------------------------------------------------------------------- #
# shape manipulation
# ---------------------------------------------------------------------- #
@_exported
def reshape(data, shape, reverse=False):
    return wrap(data).reshape(shape)


@_exported
def reshape_like(lhs, rhs):
    return apply_op(lambda x, y: jnp.reshape(x, y.shape), lhs, rhs)


@_exported
def flatten(data):
    return apply_op(lambda x: jnp.reshape(x, (x.shape[0], -1)), data)


Flatten = flatten
__all__.append("Flatten")


@_exported
def transpose(data, axes=None):
    return apply_op(lambda x: jnp.transpose(x, axes if axes else None), data)


@_exported
def swapaxes(data, dim1=0, dim2=1):
    return apply_op(lambda x: jnp.swapaxes(x, dim1, dim2), data)


SwapAxis = swapaxes
__all__.append("SwapAxis")


@_exported
def expand_dims(data, axis):
    return apply_op(lambda x: jnp.expand_dims(x, axis), data)


@_exported
def squeeze(data, axis=None):
    return apply_op(lambda x: jnp.squeeze(x, axis), data)


@_exported
def concat(*args, dim: int = 1):
    return apply_op(lambda *xs: jnp.concatenate(xs, axis=dim), *args)


Concat = concat
__all__.append("Concat")


@_exported
def concatenate(arrays, axis=0):
    return apply_op(lambda *xs: jnp.concatenate(xs, axis=axis), *arrays)


@_exported
def stack(*args, axis: int = 0):
    if len(args) == 1 and isinstance(args[0], (list, tuple)):
        args = tuple(args[0])
    return apply_op(lambda *xs: jnp.stack(xs, axis=axis), *args)


@_exported
def split(data, num_outputs, axis=1, squeeze_axis=False):
    def f(x):
        parts = jnp.split(x, num_outputs, axis=axis)
        if squeeze_axis:
            parts = [jnp.squeeze(p, axis=axis) for p in parts]
        return tuple(parts)

    out = apply_op(f, data, n_out=num_outputs)
    return list(out) if isinstance(out, tuple) else [out]


SliceChannel = split
__all__.append("SliceChannel")


@_exported
def split_v2(data, indices_or_sections, axis=0, squeeze_axis=False):
    def f(x):
        parts = jnp.split(x, indices_or_sections, axis=axis)
        if squeeze_axis:
            parts = [jnp.squeeze(p, axis=axis) for p in parts]
        return tuple(parts)

    n = indices_or_sections if isinstance(indices_or_sections, int) else len(indices_or_sections) + 1
    out = apply_op(f, data, n_out=n)
    return list(out) if isinstance(out, tuple) else [out]


@_exported
def tile(data, reps):
    return apply_op(lambda x: jnp.tile(x, reps), data)


@_exported
def repeat(data, repeats, axis=None):
    return apply_op(lambda x: jnp.repeat(x, repeats, axis=axis), data)


@_exported
def pad(data, mode="constant", pad_width=None, constant_value=0.0):
    """MXNet pad: pad_width is a flat tuple of (before, after) per axis."""

    def f(x):
        pw = [(pad_width[2 * i], pad_width[2 * i + 1]) for i in range(x.ndim)]
        m = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
        if m == "constant":
            return jnp.pad(x, pw, mode=m, constant_values=constant_value)
        return jnp.pad(x, pw, mode=m)

    return apply_op(f, data)


@_exported
def slice(data, begin, end, step=None):
    import builtins

    def f(x):
        steps = step or [None] * len(begin)
        idx = tuple(builtins.slice(b, e, s) for b, e, s in zip(begin, end, steps))
        return x[idx]

    return apply_op(f, data)


@_exported
def slice_axis(data, axis, begin, end):
    import builtins

    def f(x):
        e = end if end is not None else x.shape[axis]
        idx = [builtins.slice(None)] * x.ndim
        idx[axis] = builtins.slice(begin, e)
        return x[tuple(idx)]

    return apply_op(f, data)


@_exported
def slice_like(data, shape_like, axes=None):
    import builtins

    def f(x, y):
        axs = axes if axes is not None else range(x.ndim)
        idx = [builtins.slice(None)] * x.ndim
        for a in axs:
            idx[a] = builtins.slice(0, y.shape[a])
        return x[tuple(idx)]

    return apply_op(f, data, shape_like)


@_exported
def reverse(data, axis):
    return apply_op(lambda x: jnp.flip(x, axis=axis), data)


flip = reverse
__all__.append("flip")


@_exported
def depth_to_space(data, block_size):
    def f(x):
        n, c, h, w = x.shape
        b = block_size
        x = x.reshape(n, b, b, c // (b * b), h, w)
        x = jnp.transpose(x, (0, 3, 4, 1, 5, 2))
        return x.reshape(n, c // (b * b), h * b, w * b)

    return apply_op(f, data)


@_exported
def space_to_depth(data, block_size):
    def f(x):
        n, c, h, w = x.shape
        b = block_size
        x = x.reshape(n, c, h // b, b, w // b, b)
        x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
        return x.reshape(n, c * b * b, h // b, w // b)

    return apply_op(f, data)


# ---------------------------------------------------------------------- #
# indexing (reference src/operator/tensor/indexing_op.cc)
# ---------------------------------------------------------------------- #
@_exported
def take(a, indices, axis=0, mode="clip"):
    def f(x, idx):
        return jnp.take(x, idx.astype(jnp.int32), axis=axis, mode="clip" if mode == "clip" else "wrap")

    return apply_op(f, a, wrap(indices))


@_exported
def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    def f(x, idx):
        out = jnp.take_along_axis(x, jnp.expand_dims(idx.astype(jnp.int32), axis), axis=axis)
        return out if keepdims else jnp.squeeze(out, axis=axis)

    return apply_op(f, data, wrap(index))


@_exported
def gather_nd(data, indices):
    def f(x, idx):
        idx = idx.astype(jnp.int32)
        return x[tuple(idx[i] for i in range(idx.shape[0]))]

    return apply_op(f, data, wrap(indices))


@_exported
def scatter_nd(data, indices, shape):
    def f(d, idx):
        idx = idx.astype(jnp.int32)
        out = jnp.zeros(tuple(shape), dtype=d.dtype)
        return out.at[tuple(idx[i] for i in range(idx.shape[0]))].set(d)

    return apply_op(f, data, wrap(indices))


@_exported
def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    def f(idx):
        oh = jax.nn.one_hot(idx.astype(jnp.int32), depth, dtype=jnp.dtype(dtype))
        return oh * (on_value - off_value) + off_value

    return apply_op(f, wrap(indices))


@_exported
def embedding(data, weight, input_dim=None, output_dim=None, dtype="float32", sparse_grad=False):
    """Embedding lookup — gather from the table (TPU idiom for row_sparse)."""

    def f(idx, w):
        return jnp.take(w, idx.astype(jnp.int32), axis=0, mode="clip")

    return apply_op(f, wrap(data), weight)


Embedding = embedding
__all__.append("Embedding")


# ---------------------------------------------------------------------- #
# ordering (reference src/operator/tensor/ordering_op.cc)
# ---------------------------------------------------------------------- #
@_exported
def sort(data, axis=-1, is_ascend=True):
    def f(x):
        y = jnp.sort(x, axis=axis)
        return y if is_ascend else jnp.flip(y, axis=axis)

    return apply_op(f, data)


@_exported
def argsort(data, axis=-1, is_ascend=True, dtype="float32"):
    def f(x):
        y = jnp.argsort(x, axis=axis)
        if not is_ascend:
            y = jnp.flip(y, axis=axis)
        return y.astype(jnp.dtype(dtype))

    return apply_op(f, data)


@_exported
def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    def f(x):
        xt = jnp.moveaxis(x, axis, -1)
        vals, idx = lax.top_k(-xt if is_ascend else xt, k)
        if is_ascend:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis)
        if ret_typ == "value":
            return vals
        if ret_typ == "both":
            return (vals, idx.astype(jnp.dtype(dtype)))
        return idx.astype(jnp.dtype(dtype))

    if ret_typ == "both":
        return apply_op(f, data, n_out=2)
    return apply_op(f, data)


# ---------------------------------------------------------------------- #
# sequence ops (reference src/operator/sequence_*.cc)
# ---------------------------------------------------------------------- #
@_exported
def sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return wrap(data)

    def f(x, slen):
        steps = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        steps = steps.reshape(shape)
        batch_axis = 1 - axis if axis in (0, 1) else 0
        lshape = [1] * x.ndim
        lshape[batch_axis] = x.shape[batch_axis]
        mask = steps < slen.reshape(lshape)
        return jnp.where(mask, x, jnp.asarray(value, dtype=x.dtype))

    return apply_op(f, data, wrap(sequence_length))


@_exported
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    def f(x, *rest):
        if not use_sequence_length or not rest:
            return jnp.take(x, x.shape[axis] - 1, axis=axis)
        slen = rest[0].astype(jnp.int32)
        idx = jnp.maximum(slen - 1, 0)
        xt = jnp.moveaxis(x, axis, 0)
        return xt[idx, jnp.arange(xt.shape[1])]

    args = (data,) if sequence_length is None else (data, wrap(sequence_length))
    return apply_op(f, *args)


@_exported
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    def f(x, *rest):
        if not use_sequence_length or not rest:
            return jnp.flip(x, axis=axis)
        slen = rest[0].astype(jnp.int32)
        T = x.shape[axis]
        steps = jnp.arange(T)
        xt = jnp.moveaxis(x, axis, 0)  # (T, B, ...)
        lens = slen.reshape((1, -1) + (1,) * (xt.ndim - 2))
        sidx = jnp.where(steps.reshape((-1,) + (1,) * (xt.ndim - 1)) < lens,
                         lens - 1 - steps.reshape((-1,) + (1,) * (xt.ndim - 1)),
                         steps.reshape((-1,) + (1,) * (xt.ndim - 1)))
        out = jnp.take_along_axis(xt, sidx.astype(jnp.int32), axis=0)
        return jnp.moveaxis(out, 0, axis)

    args = (data,) if sequence_length is None else (data, wrap(sequence_length))
    return apply_op(f, *args)


SequenceMask = sequence_mask
SequenceLast = sequence_last
SequenceReverse = sequence_reverse
__all__ += ["SequenceMask", "SequenceLast", "SequenceReverse"]
