"""Gluon Parameter / ParameterDict.

Re-design of `python/mxnet/gluon/parameter.py` [UNVERIFIED]
(SURVEY.md §2.6 "Gluon core"): a Parameter owns ONE global `jax.Array`
(possibly sharded over a Mesh via `.sharding`) instead of per-context
copies — `list_data()`/`list_ctx()` return single-element lists for
API parity (the SPMD re-expression of MXNet's per-GPU replication,
SURVEY.md §2.4 DP row).  Deferred shape init (`shape` containing 0) is
kept: layers complete shapes at first forward.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as onp

from .. import initializer as init_mod
from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray
from ..telemetry.profiler import setup_phased

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its deferred shape was resolved."""


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default",
                 sharding=None):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self.sharding = sharding  # PartitionSpec-like axis names for pjit/TP
        self._data_nd: Optional[NDArray] = None
        self._deferred_init = None

    # ------------------------------------------------------------------ #
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise ValueError(f"grad_req must be write/add/null, got {req}")
        self._grad_req = req
        if self._data_nd is not None:
            self._data_nd.attach_grad(req)

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(s1 in (0, s2) for s1, s2 in zip(self._shape, new_shape)) \
            and len(self._shape) == len(new_shape)
        if not unknown_ok:
            raise AssertionError(
                f"Expected shape {new_shape} is incompatible with given shape {self._shape} "
                f"for Parameter {self.name}")
        self._shape = tuple(new_shape)

    def _shape_known(self):
        return self._shape is not None and all(s > 0 for s in self._shape)

    # ------------------------------------------------------------------ #
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit: bool = False):
        if self._data_nd is not None and not force_reinit:
            return
        default_init = default_init or init_mod.Uniform()
        if not self._shape_known():
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init)
                return
            raise DeferredInitializationError(
                f"Parameter {self.name} has unknown shape {self._shape} and "
                f"allow_deferred_init=False")
        self._finish_init(init, ctx, default_init)

    def _finish_init(self, init, ctx, default_init):
        arr = NDArray(jnp.zeros(self._shape, dtype=jnp.dtype(self.dtype)), ctx=_first_ctx(ctx))
        initializer = init or self.init or default_init
        if isinstance(initializer, str):
            initializer = init_mod.create(initializer)
        initializer(init_mod.InitDesc(self.name), arr)
        self._data_nd = arr
        self._deferred_init = None
        if self._grad_req != "null":
            arr.attach_grad(self._grad_req)

    @setup_phased("deferred_init")
    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        if not self._shape_known():
            raise DeferredInitializationError(
                f"Parameter {self.name} deferred init could not resolve shape {self._shape}")
        init, ctx, default_init = self._deferred_init
        self._finish_init(init, ctx, default_init)

    # ------------------------------------------------------------------ #
    def _check_initialized(self):
        if self._data_nd is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"Parameter {self.name} has not been initialized yet because "
                    f"initialization was deferred. Run a forward pass first")
            raise RuntimeError(
                f"Parameter {self.name} has not been initialized. "
                f"You should initialize parameters with Block.initialize()")

    def data(self, ctx=None) -> NDArray:
        self._check_initialized()
        return self._data_nd

    def list_data(self) -> List[NDArray]:
        return [self.data()]

    def grad(self, ctx=None) -> NDArray:
        self._check_initialized()
        if self._grad_req == "null":
            raise RuntimeError(f"Cannot get gradient array for Parameter {self.name} "
                               f"because grad_req='null'")
        return self._data_nd._grad

    def list_grad(self) -> List[NDArray]:
        return [self.grad()]

    def list_ctx(self) -> List[Context]:
        self._check_initialized()
        return [self._data_nd.context]

    @setup_phased("set_data")
    def set_data(self, data):
        arr = data if isinstance(data, NDArray) else NDArray(jnp.asarray(data))
        if self._data_nd is None:
            self.shape = arr.shape
            self._finish_init(init_mod.Constant(0.0), None, init_mod.Constant(0.0))
        self._data_nd._set_data(jnp.asarray(arr._data, dtype=self._data_nd._data.dtype)
                                .reshape(self._data_nd.shape))

    def zero_grad(self):
        if self._data_nd is not None and self._data_nd._grad is not None:
            self._data_nd._grad._data = jnp.zeros_like(self._data_nd._grad._data)

    def reset_ctx(self, ctx):
        pass  # single global array; placement handled by sharding

    def cast(self, dtype):
        self.dtype = dtype
        if self._data_nd is not None:
            self._data_nd._data = self._data_nd._data.astype(jnp.dtype(dtype))
            if self._data_nd._grad is not None:
                self._data_nd._grad._data = self._data_nd._grad._data.astype(jnp.dtype(dtype))

    def var(self):
        from .. import symbol

        return symbol.Symbol.var(self.name)

    def __repr__(self):
        return f"Parameter {self.name} (shape={self._shape}, dtype={self.dtype})"


class Constant(Parameter):
    """Non-differentiable constant parameter (parity: gluon.Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = NDArray(jnp.asarray(onp.asarray(value, dtype="float32")))
        self.value = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=str(value._data.dtype),
                         init=init_mod.Constant(0.0), differentiable=False)
        self._data_nd = value


def _first_ctx(ctx):
    if ctx is None:
        return None
    if isinstance(ctx, (list, tuple)):
        return ctx[0] if ctx else None
    return ctx


class ParameterDict:
    """Ordered name->Parameter mapping with a shared prefix."""

    def __init__(self, prefix="", shared: Optional["ParameterDict"] = None):
        self._prefix = prefix
        self._params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, key) -> Parameter:
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def get(self, name, **kwargs) -> Parameter:
        """Create-or-retrieve `prefix+name` (gluon semantics)."""
        name = self._prefix + name
        if self._shared is not None and name in self._shared._params:
            param = self._shared._params[name]
        elif name in self._params:
            param = self._params[name]
        else:
            param = Parameter(name, **kwargs)
            self._params[name] = param
            return param
        # verify/complete attributes of the re-retrieved parameter
        for k, v in kwargs.items():
            if k == "shape" and v is not None:
                param.shape = (v,) if isinstance(v, int) else tuple(v)
        self._params.setdefault(name, param)
        return param

    def get_constant(self, name, value=None) -> Constant:
        name = self._prefix + name
        if name in self._params:
            return self._params[name]
        c = Constant(name, value)
        self._params[name] = c
        return c

    def update(self, other: "ParameterDict"):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError(f"Cannot update self with other because they have different "
                                 f"Parameters with the same name {k}")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        default = init or init_mod.Uniform()
        for p in self._params.values():
            p.initialize(None, ctx, default_init=default, force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        pass

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, fname, strip_prefix=""):
        from ..utils import serialization

        arrays = {}
        for name, p in self._params.items():
            key = name[len(strip_prefix):] if name.startswith(strip_prefix) else name
            arrays[key] = p.data()
        serialization.save_ndarrays(fname, arrays)

    def load(self, fname, ctx=None, allow_missing=False, ignore_extra=False,
             restore_prefix=""):
        from ..utils import serialization

        loaded = serialization.load_ndarrays(fname)
        loaded = {restore_prefix + k.removeprefix("arg:").removeprefix("aux:"): v
                  for k, v in loaded.items()}
        for name, p in self._params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise IOError(f"Parameter {name} missing in file {fname}")
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise IOError(f"Parameters in file not in model: {sorted(extra)}")

    def __repr__(self):
        s = "\n".join(repr(p) for p in self._params.values())
        return f"ParameterDict(prefix={self._prefix!r})\n{s}"
