"""Gluon Block / HybridBlock — eager containers + the jit bridge.

Re-design of `python/mxnet/gluon/block.py` + `src/imperative/cached_op.cc`
[UNVERIFIED] (SURVEY.md §2.2 "CachedOp", §3.3): ``hybridize()`` does
NOT build an NNVM symbol — it wraps the block's forward in `jax.jit`.
The jitted program is parametric in (trainable params, aux state, RNG
key, inputs); jit's shape-keyed executor cache IS CachedOp's
per-shape cache ("the single most important equivalence in the whole
build", SURVEY.md §3.3).  `static_alloc`/`static_shape` flags are
accepted for parity and ignored: XLA is always static-shape +
pre-planned memory.

Backward through a hybridized block records ONE tape node whose vjp is
`jax.vjp` of the whole jitted function (CachedOp::Backward).
"""
from __future__ import annotations

import contextlib
import os
import re
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import _tape, autograd
from .. import ndarray as nd_mod
from .. import random as _random
from ..base import MXNetError
from ..engine import LazyRef
from ..ndarray.ndarray import NDArray, raw, wrap
from ..ops import mosaic
from ..telemetry.profiler import setup_phased
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nn_block_scope", "functionalize"]

# per-block LRU caps for the lazy-path aval-spec cache (one entry per
# distinct input signature) and the chained-composition cache (one
# _ChainedOp — holding four jitted programs — per upstream/treedef
# combination).  Unbounded, a shape-churning workload leaks specs and
# compiled programs for the process lifetime (ADVICE #3 / TPU010).
_AVAL_CACHE_CAP = int(os.environ.get("MXTPU_BLOCK_AVAL_CACHE", "64"))
_CHAIN_CACHE_CAP = int(os.environ.get("MXTPU_BLOCK_CHAIN_CACHE", "16"))


def _lru_hit(cache: "OrderedDict", key):
    """cache[key] refreshing recency, or None."""
    val = cache.get(key)
    if val is not None:
        cache.move_to_end(key)
    return val


def _lru_store(cache: "OrderedDict", key, val, cap: int):
    """Insert and evict least-recently-used entries beyond `cap`."""
    cache[key] = val
    while len(cache) > cap:
        cache.popitem(last=False)
    return val


class _BlockScope(threading.local):
    def __init__(self):
        self._current: Optional["Block"] = None
        self._counters: Dict[str, int] = {}


_scope = _BlockScope()


@contextlib.contextmanager
def nn_block_scope(block: "Block"):
    prev = _scope._current
    _scope._current = block
    try:
        yield
    finally:
        _scope._current = prev


def _make_prefix(hint: str) -> str:
    cur = _scope._current
    if cur is not None:
        counters = cur._child_counters
    else:
        counters = _scope._counters
    idx = counters.get(hint, 0)
    counters[hint] = idx + 1
    base = f"{hint}{idx}_"
    if cur is not None:
        return cur.prefix + base
    return base


class Block:
    """Base eager container (ref gluon.Block).

    Children are registered via attribute assignment; `collect_params`
    walks the tree.  `__call__` → `forward`.
    """

    def __init__(self, prefix: Optional[str] = None, params: Optional[ParameterDict] = None):
        hint = type(self).__name__.lower()
        self._prefix = prefix if prefix is not None else _make_prefix(hint)
        self._params = ParameterDict(self._prefix, shared=params)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._child_counters: Dict[str, int] = {}
        self._forward_hooks: List = []
        self._forward_pre_hooks: List = []
        self._monitors: List = []  # mx.mon.Monitor instances (install())

    # -- attribute magic ------------------------------------------------ #
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            if not hasattr(self, "_children"):
                raise RuntimeError("call super().__init__() before assigning child blocks")
            self._children[name] = value
        elif isinstance(value, Parameter):
            if hasattr(self, "_params"):
                self._params._params[value.name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def name(self) -> str:
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return nn_block_scope(self)

    # -- parameter management ------------------------------------------- #
    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            for name, p in self._params.items():
                if pat.match(name):
                    ret._params[name] = p
        for child in self._children.values():
            child_params = child.collect_params(select)
            for name, p in child_params.items():
                ret._params[name] = p
        return ret

    @setup_phased("initialize")
    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)
        return self

    @setup_phased("cast")
    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for c in self._children.values():
            c.cast(dtype)
        return self

    def zero_grad(self):
        self.collect_params().zero_grad()

    # -- (de)serialization ---------------------------------------------- #
    def _collect_params_with_prefix(self, prefix: str = "") -> "OrderedDict[str, Parameter]":
        """Structural names ('0.weight', 'encoder.layer1.bias') — the
        .params key scheme of the reference save_parameters, stable
        across instances regardless of global name counters."""
        if prefix:
            prefix += "."
        ret: "OrderedDict[str, Parameter]" = OrderedDict()
        for name, p in self._params.items():
            ret[prefix + _strip_prefix(name, self._prefix)] = p
        for key, child in self._children.items():
            if isinstance(child, Block):
                for k, p in child._collect_params_with_prefix(prefix + key).items():
                    ret.setdefault(k, p)
        return ret

    def save_parameters(self, filename, deduplicate: bool = False):
        from ..utils import serialization

        params = self._collect_params_with_prefix()
        arrays = {}
        seen = {}
        for name, p in params.items():
            if p._data_nd is None:
                continue
            if deduplicate and id(p) in seen:
                continue
            seen[id(p)] = name
            arrays[name] = p.data()
        serialization.save_ndarrays(filename, arrays)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from ..utils import serialization

        loaded = serialization.load_ndarrays(filename)
        loaded = {k.removeprefix("arg:").removeprefix("aux:"): v for k, v in loaded.items()}
        params = self._collect_params_with_prefix()
        for key, arr in loaded.items():
            if key in params:
                params[key].set_data(arr)
            elif not ignore_extra:
                raise IOError(f"Parameter {key} loaded from file is not present in the Block")
        if not allow_missing:
            missing = [k for k in params if k not in loaded]
            if missing:
                raise IOError(f"Parameters missing in file: {sorted(missing)}")

    # legacy aliases
    save_params = save_parameters

    def load_params(self, filename, ctx=None, **kwargs):
        self.load_parameters(filename, ctx, **kwargs)

    # -- hooks ----------------------------------------------------------- #
    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    def apply(self, fn):
        for c in self._children.values():
            c.apply(fn)
        fn(self)
        return self

    # -- execution ------------------------------------------------------- #
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary (parity: Block.summary)."""
        lines = []
        seen = set()

        def walk(block, indent=0):
            n_params = 0
            for p in block._params.values():
                if id(p) not in seen and p._data_nd is not None:
                    n_params += p.data().size
                    seen.add(id(p))
            lines.append("  " * indent + f"{type(block).__name__}({block.name}): {n_params} params")
            for c in block._children.values():
                walk(c, indent + 1)

        walk(self)
        out = "\n".join(lines)
        print(out)
        return out

    def __repr__(self):
        s = f"{type(self).__name__}(\n"
        for key, child in self._children.items():
            s += f"  ({key}): {type(child).__name__}\n"
        return s + ")"


_CHAIN_MISS = object()


def _program_jits(raw_fn):
    """The four compiled entry points every cached program exposes
    (plain blocks via `_build_cache`, compositions via `_ChainedOp`):
    fn, grad (remat flavor), fwd_record (saves residuals), bwd_record."""
    fn = jax.jit(raw_fn, static_argnums=(0, 1))

    def grad_fn(training, arg_tree, train_raws, aux_raws, rng, rng_ctr,
                input_raws, cots):
        def f(tr, ins):
            out, _new_aux = raw_fn(training, arg_tree, tr, aux_raws,
                                   rng, rng_ctr, *ins)
            return out

        _out, vjp = jax.vjp(f, tuple(train_raws), tuple(input_raws))
        d_train, d_ins = vjp(cots)
        return d_train, d_ins

    # CachedOp::Backward equivalence, remat flavor: the backward
    # graph recomputes the forward inside (jax.checkpoint-style
    # FLOPs-for-HBM trade, opt-in via hybridize(remat_backward=True))
    grad = jax.jit(grad_fn, static_argnums=(0, 1))

    def fwd_record_fn(training, arg_tree, train_raws, aux_raws, rng,
                      rng_ctr, input_raws):
        def f(tr, ins):
            return raw_fn(training, arg_tree, tr, aux_raws,
                          rng, rng_ctr, *ins)  # (out, new_aux)

        out, pullback, new_aux = jax.vjp(
            f, tuple(train_raws), tuple(input_raws), has_aux=True)
        # pullback is a jax.tree_util.Partial pytree: its leaves are
        # the forward residuals, so it round-trips through jit — the
        # backward jit below consumes them without recomputing the
        # forward (standard fwd+bwd FLOP budget, CachedOp::Backward
        # with saved intermediates)
        return out, new_aux, pullback

    fwd_record = jax.jit(fwd_record_fn, static_argnums=(0, 1))
    bwd_record = jax.jit(lambda pullback, cots: pullback(cots))
    return fn, grad, fwd_record, bwd_record


def _capture_raw(p):
    """Capture a parameter's raw array for a RECORDING forward without
    forcing a pending value: during Trainer multi-step chaining the
    param nd holds a LazyRef whose force flushes the whole chain — the
    recording path defers instead (the fused/chained program ignores
    these captures; any eager consumer resolves them via
    `_resolve_raws`, which flushes first and therefore sees the
    post-chain weights its step logically follows)."""
    nd = p._data_nd
    return nd._lazy if nd._lazy is not None else nd._raw


def _resolve_raws(raws):
    """Force any LazyRef captures (see `_capture_raw`) to concrete
    arrays.  No-op (and allocation-free-ish) for plain tuples."""
    if any(isinstance(r, LazyRef) for r in raws):
        return tuple(r.force() if isinstance(r, LazyRef) else r
                     for r in raws)
    return raws


def _aval_or_raw(r):
    """jax.eval_shape accepts ShapeDtypeStructs and arrays mixed."""
    return jax.ShapeDtypeStruct(r.aval.shape, r.aval.dtype) \
        if isinstance(r, LazyRef) else r


def _grads_not_kept():
    from ..base import MXNetError

    raise MXNetError(
        "This gradient was consumed inside a fused Trainer step and never "
        "materialized (Trainer(..., keep_grads=False)). Construct the "
        "Trainer with keep_grads=True to read p.grad() after step().")


class _PendingStep:
    """A deferred hybridized step (engine.py lazy composition).

    Holds everything needed to run the cached forward / backward jits
    later — or to let `Trainer.step` compile fwd+vjp+update as ONE
    program.  Values materialize through LazyRef cells on demand.
    """

    __slots__ = ("block", "training", "arg_tree", "train_raws", "aux_raws",
                 "rng", "rng_ctr", "input_raws", "out_treedef", "out_avals",
                 "out_cells", "aux_params", "aux_cells", "fwd_done", "pullback",
                 "bwd_requested", "bwd_done", "grad_cells", "n_train",
                 "out_nds", "head_positions")

    def __init__(self, block, training, arg_tree, train_raws, aux_raws, rng,
                 rng_ctr, input_raws, out_treedef, out_avals, aux_params):
        self.block = block
        self.training = training
        self.arg_tree = arg_tree
        self.train_raws = train_raws
        self.aux_raws = aux_raws
        self.rng = rng
        self.rng_ctr = rng_ctr
        self.input_raws = tuple(input_raws)
        self.out_treedef = out_treedef
        self.out_avals = list(out_avals)
        self.out_cells = [LazyRef(self.force_fwd, a) for a in out_avals]
        self.aux_params = aux_params
        self.aux_cells = []
        self.fwd_done = False
        self.pullback = None
        self.bwd_requested = False
        self.bwd_done = False
        self.grad_cells: Dict[int, LazyRef] = {}  # input position -> cell
        self.n_train = len(train_raws)
        self.out_nds: List = []        # NDArrays returned to the caller
        self.head_positions = None     # backward head out-leaf indices (None=all)

    # -- stage execution (the WaitForVar equivalences) ------------------- #
    def force_fwd(self):
        if self.fwd_done:
            return
        blk = self.block
        # resolve deferred weight/aux captures first (flushes any open
        # Trainer chain, so this step sees its true predecessor weights)
        self.train_raws = _resolve_raws(tuple(self.train_raws))
        self.aux_raws = _resolve_raws(tuple(self.aux_raws))
        # rebind aux params to their captured concrete values first —
        # apply_fn's save/rebind would otherwise force our own cells
        for p, cell, a in zip(self.aux_params, self.aux_cells, self.aux_raws):
            if p._data_nd._lazy is cell:
                p._data_nd._data = a
        out_raws, new_aux, pullback = blk._cached_fwd_record(
            self.training, self.arg_tree, self.train_raws, self.aux_raws,
            self.rng, self.rng_ctr, self.input_raws)
        leaves = jax.tree_util.tree_leaves(out_raws)
        for cell, v in zip(self.out_cells, leaves):
            cell.value = v
        for p, cell, v in zip(self.aux_params, self.aux_cells, new_aux):
            cell.value = v
            p._data_nd._data = v
        self.pullback = pullback
        self.fwd_done = True

    def request_bwd(self, targets):
        """targets: [(input_position, param_NDArray)] with grad_req='write'."""
        force = self.force_bwd
        cells = self.grad_cells
        for pos, nd in targets:
            g = nd._grad
            # reuse the existing grad buffer's aval (or a previous lazy
            # cell's) — constructing ShapeDtypeStructs per param per step
            # costs real milliseconds at BERT scale.  A grad buffer can
            # hold a plain numpy array (host-initialized zeros): build
            # the aval from shape/dtype then.
            if g._lazy is not None:
                aval = g._lazy.aval
            else:
                aval = getattr(g._raw, "aval", None)
                if aval is None:
                    aval = jax.ShapeDtypeStruct(tuple(g._raw.shape),
                                                g._raw.dtype)
            cell = LazyRef(force, aval)
            g._data = cell
            cells[pos] = cell
        self.bwd_requested = True

    def force_bwd(self):
        if self.bwd_done:
            return
        self.force_fwd()
        heads = self.head_positions
        cts = [jnp.ones(a.shape, a.dtype) if heads is None or i in heads
               else jnp.zeros(a.shape, a.dtype)
               for i, a in enumerate(self.out_avals)]
        cot_tree = jax.tree_util.tree_unflatten(self.out_treedef, cts)
        d_train, d_ins = self.block._cached_bwd_record(self.pullback, cot_tree)
        all_d = tuple(d_train) + tuple(d_ins)
        for pos, cell in self.grad_cells.items():
            cell.value = all_d[pos]
        self.bwd_done = True

    def fill_from_full_step(self, out_leaves, new_aux, grads):
        """Called by Trainer after the fused single-program step ran.

        ``grads=None`` means the Trainer ran with ``keep_grads=False``
        (gradients were consumed inside the fused program, never
        materialized): reading ``p.grad()`` afterwards raises."""
        for cell, v in zip(self.out_cells, out_leaves):
            cell.value = v
        for p, cell, v in zip(self.aux_params, self.aux_cells, new_aux):
            cell.value = v
            if p._data_nd._lazy is cell:
                p._data_nd._data = v
        for pos, cell in self.grad_cells.items():
            if pos < self.n_train:
                if grads is None:
                    cell.force_fn = _grads_not_kept
                else:
                    cell.value = grads[pos]
        self.fwd_done = True
        self.bwd_done = True
        self.pullback = None


class _ChainedOp:
    """Composition of an upstream pending program and a downstream
    hybridized block into ONE cached program.

    This is how the canonical MXNet loop
    ``L = loss_fn(net(x), y); L.backward(); trainer.step()`` — with the
    loss a SEPARATE block from the net — still compiles to a single
    fused fwd+bwd+update XLA program: calling a hybridized block on the
    lazy outputs of another pending step does not force that step, it
    splices both programs together (the dependency-engine composition
    one level up).  Exposes the same protocol `_PendingStep`/`Trainer`
    use on plain blocks: `_cached_fn/_cached_grad/_cached_fwd_record/
    _cached_bwd_record`, `_cached_param_order`, `_cache_version`.

    Output tree = (down_out, up_out): the upstream pending's existing
    output cells are re-pointed at the chained step, so values the user
    already holds (e.g. logits for the metric) materialize from the one
    fused program.
    """

    def __init__(self, up_block, down_block, lazy_map, n_up_inputs):
        up_tr, up_aux = up_block._cached_param_order
        down_tr, down_aux = down_block._cached_param_order

        def dedup(seq_up, seq_down):
            # a Parameter shared between the two blocks must appear ONCE
            # in the combined (donated!) buffer tuple; slots map each
            # original position to its deduped index, and jax.vjp sums
            # the shared param's gradient across both uses
            comb, index_of, slots = [], {}, []
            for p in list(seq_up) + list(seq_down):
                j = index_of.get(id(p))
                if j is None:
                    j = len(comb)
                    comb.append(p)
                    index_of[id(p)] = j
                slots.append(j)
            return comb, tuple(slots)

        comb_tr, tr_slots = dedup(up_tr, down_tr)
        comb_aux, aux_slots = dedup(up_aux, down_aux)
        self._cached_param_order = (comb_tr, comb_aux)
        self._cache_version = (up_block._cache_version,
                               down_block._cache_version)
        self._aval_cache: "OrderedDict" = OrderedDict()
        n_up_tr, n_up_aux = len(up_tr), len(up_aux)
        up_fn, down_fn = up_block._cached_fn, down_block._cached_fn
        # deterministic per-composition-depth RNG salt: nested chains
        # must give each stochastic block a distinct key stream
        depth = getattr(up_block, "chain_depth", 0) + 1
        self.chain_depth = depth
        # shared aux written by both halves: the DOWN half's new value
        # wins (it ran last), mirroring sequential eager execution
        n_aux_total = len(comb_aux)

        def raw_fn(training, token, train_raws, aux_raws, rng, rng_ctr,
                   *input_raws):
            up_tree, down_tree, lmap, n_up_in = token
            up_tr_raws = tuple(train_raws[tr_slots[i]]
                               for i in range(n_up_tr))
            up_aux_raws = tuple(aux_raws[aux_slots[i]]
                                for i in range(n_up_aux))
            up_out, up_new_aux = up_fn(
                training, up_tree, up_tr_raws, up_aux_raws, rng, rng_ctr,
                *input_raws[:n_up_in])
            up_leaves = jax.tree_util.tree_leaves(up_out)
            it = iter(input_raws[n_up_in:])
            d_leaves = [up_leaves[j] if j is not None else next(it)
                        for j in lmap]
            # independent RNG stream for the downstream program.
            # DIVERGENCE (documented): the eager/fallback path would
            # instead draw a fresh step key for the downstream block, so
            # a STOCHASTIC downstream block (dropout-bearing head) sees
            # different randomness depending on whether chaining engaged.
            # Distributions are identical; exact bits are not.  Chaining
            # is deterministic for a given program shape, so seeded runs
            # remain reproducible among themselves.
            rng_d = jax.random.fold_in(rng, 0xC4A1 + depth)
            # downstream sees upstream's aux updates for shared aux
            aux_after_up = list(aux_raws)
            for i in range(n_up_aux):
                aux_after_up[aux_slots[i]] = up_new_aux[i]
            down_tr_raws = tuple(train_raws[tr_slots[n_up_tr + i]]
                                 for i in range(len(down_tr)))
            down_aux_raws = tuple(aux_after_up[aux_slots[n_up_aux + i]]
                                  for i in range(len(down_aux)))
            down_out, down_new_aux = down_fn(
                training, down_tree, down_tr_raws, down_aux_raws, rng_d,
                rng_ctr, *d_leaves)
            new_aux = aux_after_up
            for i in range(len(down_aux)):
                new_aux[aux_slots[n_up_aux + i]] = down_new_aux[i]
            return ((down_out, up_out), tuple(new_aux[:n_aux_total]))

        (self._cached_fn, self._cached_grad, self._cached_fwd_record,
         self._cached_bwd_record) = _program_jits(raw_fn)
        self.lazy_map = tuple(lazy_map)
        self.n_up_inputs = n_up_inputs

        def src_map(slots, n_up, n_comb):
            # deduped index -> ("up", i) | ("down", i): first occurrence
            # decides where _try_chain reads the concrete value from
            # (upstream values come from the pending snapshot)
            src = [None] * n_comb
            for pos, j in enumerate(slots):
                if src[j] is None:
                    src[j] = ("up", pos) if pos < n_up \
                        else ("down", pos - n_up)
            return tuple(src)

        self.tr_src = src_map(tr_slots, n_up_tr, len(comb_tr))
        self.aux_src = src_map(aux_slots, n_up_aux, len(comb_aux))


class HybridBlock(Block):
    """Block that can be compiled: ``hybridize()`` → `jax.jit` cache."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._remat_backward = False
        self._jit_kwargs: Dict[str, Any] = {}
        self._cached_fn = None
        self._cached_param_order: Optional[List[Parameter]] = None
        self._aval_cache: "OrderedDict" = OrderedDict()
        self._cache_version = 0  # bumped on every _build_cache (Trainer key)
        # the mesh `parallel.shard_params` placed the parameters on: the
        # forward is traced with it in context (ops/mosaic.py)
        self._mesh = None
        # _ChainedOp compositions by key
        self._chain_cache: "OrderedDict" = OrderedDict()

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, remat_backward: bool = False,
                  **kwargs):
        """Enable compiled execution (CachedOp ≡ jax.jit, SURVEY.md §3.3).

        static_alloc/static_shape accepted for reference parity; XLA is
        always static — they are no-ops.

        remat_backward (TPU extension): when True, the cached backward
        recomputes the forward instead of saving residuals between the
        forward and backward jits (`jax.checkpoint`-style FLOPs-for-HBM
        trade — use for long-context / memory-bound training).  Default
        False: forward saves residuals, backward reuses them — the
        standard 1-fwd + 1-bwd FLOP budget.
        """
        self._active = active
        self._remat_backward = remat_backward
        self._invalidate_cached_program()
        for c in self._children.values():
            if isinstance(c, HybridBlock):
                c.hybridize(active, static_alloc=static_alloc,
                            static_shape=static_shape,
                            remat_backward=remat_backward, **kwargs)
        return self

    @setup_phased("cast")
    def cast(self, dtype):
        """Parameter dtype changes invalidate cached programs and avals."""
        super().cast(dtype)
        self._invalidate_cached_program()
        return self

    def _invalidate_cached_program(self):
        """Drop every cached compiled program/aval for THIS block — the
        single reset used by hybridize/cast and structural rewrites
        (e.g. contrib.quantization.quantize_net)."""
        self._cached_fn = None
        self._aval_cache = OrderedDict()
        self._chain_cache = OrderedDict()
        self._aux_cell_avals = None
        self._cache_version += 1

    def infer_shape(self, *args):
        """Run a shape-only forward to resolve deferred params."""
        self._ensure_shapes(args)

    def _ensure_shapes(self, args):
        """Resolve deferred param shapes with ONE eager (concrete) forward.

        Must run OUTSIDE any jax trace: initializers materialize real
        arrays into Parameter state (a tracer there would leak).
        """
        need = [p for p in self.collect_params().values() if p._deferred_init is not None]
        if not need:
            return
        rec = _tape.set_recording(False)
        try:
            self.forward(*[wrap(a) if isinstance(a, NDArray) or hasattr(a, "shape")
                           else a for a in args])
        finally:
            _tape.set_recording(rec)
        for p in self.collect_params().values():
            if p._deferred_init is not None:
                p._finish_deferred_init()

    # -- the CachedOp equivalence ---------------------------------------- #
    def _build_cache(self):
        self._cache_version += 1
        self._aval_cache = OrderedDict()
        params = self.collect_params()
        trainable = [p for p in params.values() if p.grad_req != "null" and p._data_nd is not None]
        aux = [p for p in params.values() if p.grad_req == "null" and p._data_nd is not None]
        self._cached_param_order = (trainable, aux)
        apply_fn = _make_apply_fn(self, trainable, aux, call_forward=True)

        def raw_fn(training: bool, arg_tree, train_raws: Tuple,
                   aux_raws: Tuple, rng_key, rng_ctr, *input_raws):
            # arg_tree is the treedef of the positional args — forward
            # may take nested lists/tuples/dicts of arrays (RNN state
            # lists, optional None args like token_types).  Static, part
            # of the jit cache key like any shape/dtype change.
            # rng_ctr is folded in HERE so callers pass a stable base key
            # + a python counter: zero eager RNG dispatches per step.
            full = jax.tree_util.tree_unflatten(arg_tree, list(input_raws))
            key = jax.random.fold_in(rng_key, rng_ctr)
            with mosaic.mesh_context(self._mesh):
                return apply_fn(train_raws, aux_raws, key, *full,
                                training=training)

        (self._cached_fn, self._cached_grad, self._cached_fwd_record,
         self._cached_bwd_record) = _program_jits(raw_fn)

    def _call_cached_op(self, *args):
        args_leaves, arg_tree = jax.tree_util.tree_flatten(args)
        input_nds = [wrap(a) for a in args_leaves]
        recording = _tape.is_recording()
        if recording and not self._remat_backward:
            # lazy inputs from another pending step: splice the two
            # programs instead of forcing (dependency-engine composition)
            out = self._try_chain(arg_tree, input_nds)
            if out is not _CHAIN_MISS:
                return out
        if self._cached_fn is None:
            self._ensure_shapes(args)
            self._build_cache()
        trainable, aux = self._cached_param_order
        input_raws = [a._data for a in input_nds]
        rng, rng_ctr = _random.step_key()
        training = _tape.is_training()
        fn = self._cached_fn
        if not recording or self._remat_backward:
            # eager/remat consumers need concrete values — the forcing
            # read flushes any open Trainer chain first
            train_raws = tuple(p._data_nd._data for p in trainable)
            aux_raws = tuple(p._data_nd._data for p in aux)
        else:
            # recording defers: an open chain's weight LazyRefs pass
            # through unforced (the fused program never reads them)
            train_raws = tuple(_capture_raw(p) for p in trainable)
            aux_raws = tuple(_capture_raw(p) for p in aux)
        if not recording:
            out_raws, new_aux = fn(training, arg_tree, train_raws, aux_raws,
                                   rng, rng_ctr, *input_raws)
            for p, r in zip(aux, new_aux):
                p._data_nd._data = r
            return jax.tree_util.tree_map(NDArray, out_raws)

        if self._remat_backward:
            return self._record_remat(training, arg_tree, trainable, aux,
                                      train_raws, aux_raws, rng, rng_ctr,
                                      input_nds, input_raws)

        # LAZY recording path (dependency-engine equivalence, engine.py):
        # do NOT dispatch — return LazyRef-backed NDArrays and register a
        # pending step.  Trainer.step() may compile the whole
        # fwd+backward+update as one donated program; any eager value
        # access instead forces the staged fwd/bwd jits.
        sig = (training, arg_tree,
               tuple((tuple(r.shape), str(r.dtype)) for r in input_raws))
        spec = _lru_hit(self._aval_cache, sig)
        if spec is None:
            import functools

            out_shape, aux_shape = jax.eval_shape(
                functools.partial(fn, training, arg_tree),
                tuple(_aval_or_raw(r) for r in train_raws),
                tuple(_aval_or_raw(r) for r in aux_raws),
                rng, rng_ctr, *input_raws)
            leaves_avals, treedef = jax.tree_util.tree_flatten(out_shape)
            spec = (treedef, leaves_avals)
            _lru_store(self._aval_cache, sig, spec, _AVAL_CACHE_CAP)
        treedef, out_avals = spec

        pending = _PendingStep(self, training, arg_tree, train_raws, aux_raws,
                               rng, rng_ctr, input_raws, treedef, out_avals, aux)
        # aux params go lazy too: they are rebound to cells the pending
        # fills (a read before the step forces the staged forward).
        # Cell avals are CACHED per block — building a ShapeDtypeStruct
        # per aux param per step measured ~5 ms/step of pure host
        # bookkeeping on ResNet-50's 106 BN stats
        cell_avals = getattr(self, "_aux_cell_avals", None)
        if cell_avals is None or len(cell_avals) != len(aux):
            cell_avals = tuple(
                jax.ShapeDtypeStruct(_aval_or_raw(a).shape,
                                     _aval_or_raw(a).dtype)
                for a in aux_raws)
            self._aux_cell_avals = cell_avals
        for p, av in zip(aux, cell_avals):
            cell = LazyRef(pending.force_fwd, av)
            pending.aux_cells.append(cell)
            p._data_nd._data = cell

        out_nds = []
        for cell in pending.out_cells:
            ndo = NDArray(cell)
            ndo._in_graph = True
            out_nds.append(ndo)

        tape_inputs = [p._data_nd for p in trainable] + input_nds
        cached_bwd = self._cached_bwd_record
        out_dtypes = [a.dtype for a in out_avals]

        def node_vjp(cotangents):
            # eager tape walk (multi-node tapes, custom head grads):
            # force the staged forward, then run the cached backward
            pending.force_fwd()
            cts = cotangents if isinstance(cotangents, tuple) else (cotangents,)
            cts = tuple(c.astype(dt) if c.dtype != dt else c
                        for c, dt in zip(cts, out_dtypes))
            cot_tree = jax.tree_util.tree_unflatten(treedef, list(cts))
            d_train, d_ins = cached_bwd(pending.pullback, cot_tree)
            return tuple(d_train) + tuple(d_ins)

        pending.out_nds = out_nds
        node = _tape.TapeNode(tape_inputs, out_nds, node_vjp, len(out_nds))
        node.pending = pending
        _tape.append_node(node)
        return jax.tree_util.tree_unflatten(treedef, out_nds)

    def _try_chain(self, arg_tree, input_nds):
        """Call-on-lazy-outputs: splice this block's program onto the
        owning pending (one fused XLA program for net → loss → update).

        Returns the downstream outputs (lazy), or `_CHAIN_MISS` when the
        inputs aren't all from one open pending step."""
        lazy_cells = [(i, nd._lazy) for i, nd in enumerate(input_nds)
                      if isinstance(nd, NDArray) and nd._lazy is not None]
        if not lazy_cells:
            return _CHAIN_MISS
        pend = None
        for _, cell in lazy_cells:
            owner = getattr(cell.force_fn, "__self__", None)
            if not isinstance(owner, _PendingStep):
                return _CHAIN_MISS
            if pend is None:
                pend = owner
            elif owner is not pend:
                return _CHAIN_MISS
        if pend.fwd_done or pend.bwd_requested:
            return _CHAIN_MISS
        tape = _tape.current_tape()
        if not tape or getattr(tape[-1], "pending", None) is not pend:
            return _CHAIN_MISS
        training = _tape.is_training()
        if training != pend.training:
            return _CHAIN_MISS
        cell_pos = {id(c): j for j, c in enumerate(pend.out_cells)}
        lazy_map = []
        concrete_nds = []
        for nd in input_nds:
            if isinstance(nd, NDArray) and nd._lazy is not None:
                j = cell_pos.get(id(nd._lazy))
                if j is None:
                    return _CHAIN_MISS
                lazy_map.append(j)
            else:
                lazy_map.append(None)
                concrete_nds.append(nd)
        if self._cached_fn is None:
            # building the cache must not force the upstream: only
            # proceed when no param shapes are deferred
            if any(p._deferred_init is not None
                   for p in self.collect_params().values()):
                return _CHAIN_MISS
            self._build_cache()

        up_block = pend.block
        key = ("chain", id(up_block), up_block._cache_version,
               self._cache_version, tuple(lazy_map), pend.arg_tree, arg_tree)
        chained = _lru_hit(self._chain_cache, key)
        if chained is None:
            chained = _ChainedOp(up_block, self, lazy_map,
                                 len(pend.input_raws))
            _lru_store(self._chain_cache, key, chained, _CHAIN_CACHE_CAP)

        comb_tr, comb_aux = chained._cached_param_order
        up_tr, up_aux = up_block._cached_param_order
        down_tr, down_aux = self._cached_param_order
        # upstream raws come from the pending snapshot (its aux params
        # are currently rebound to lazy cells — do NOT read them);
        # params shared between the halves appear once (tr_src/aux_src)
        train_raws = tuple(
            pend.train_raws[i] if where == "up"
            else _capture_raw(down_tr[i])
            for where, i in chained.tr_src)
        aux_raws = tuple(
            pend.aux_raws[i] if where == "up"
            else _capture_raw(down_aux[i])
            for where, i in chained.aux_src)
        input_raws = tuple(pend.input_raws) \
            + tuple(nd._data for nd in concrete_nds)
        token = (pend.arg_tree, arg_tree, chained.lazy_map,
                 chained.n_up_inputs)

        sig = (key, training,
               tuple((tuple(r.shape), str(r.dtype)) for r in input_raws))
        spec = _lru_hit(self._aval_cache, sig)
        if spec is None:
            import functools

            out_shape, _aux_shape = jax.eval_shape(
                functools.partial(chained._cached_fn, training, token),
                tuple(_aval_or_raw(r) for r in train_raws),
                tuple(_aval_or_raw(r) for r in aux_raws),
                pend.rng, pend.rng_ctr, *input_raws)
            down_shape, up_shape = out_shape
            d_leaves, d_treedef = jax.tree_util.tree_flatten(down_shape)
            leaves_avals, treedef = jax.tree_util.tree_flatten(out_shape)
            spec = (treedef, leaves_avals, d_treedef, len(d_leaves))
            _lru_store(self._aval_cache, sig, spec, _AVAL_CACHE_CAP)
        treedef, out_avals, down_treedef, n_down = spec
        if len(out_avals) - n_down != len(pend.out_cells):
            return _CHAIN_MISS  # upstream output arity changed underneath

        pending2 = _PendingStep(chained, training, token, train_raws,
                                aux_raws, pend.rng, pend.rng_ctr, input_raws,
                                treedef, out_avals, comb_aux)
        cell_avals = getattr(chained, "_aux_cell_avals", None)
        if cell_avals is None or len(cell_avals) != len(comb_aux):
            cell_avals = tuple(
                jax.ShapeDtypeStruct(_aval_or_raw(a).shape,
                                     _aval_or_raw(a).dtype)
                for a in aux_raws)
            chained._aux_cell_avals = cell_avals
        for p, av in zip(comb_aux, cell_avals):
            cell = LazyRef(pending2.force_fwd, av)
            pending2.aux_cells.append(cell)
            p._data_nd._data = cell
        # the upstream's existing output cells become the tail of this
        # pending's outputs — values the caller already holds fill from
        # the one chained program
        for j, old_cell in enumerate(pend.out_cells):
            old_cell.force_fn = pending2.force_fwd
            old_cell.value = None
            pending2.out_cells[n_down + j] = old_cell

        down_nds = []
        for cell in pending2.out_cells[:n_down]:
            ndo = NDArray(cell)
            ndo._in_graph = True
            down_nds.append(ndo)
        pending2.out_nds = down_nds + list(pend.out_nds)

        up_node = tape.pop()
        up_input_nds = up_node.inputs[len(up_tr):]
        tape_inputs = [p._data_nd for p in comb_tr] + list(up_input_nds) \
            + list(concrete_nds)
        cached_bwd = chained._cached_bwd_record
        out_dtypes = [a.dtype for a in out_avals]

        def node_vjp(cotangents):
            pending2.force_fwd()
            cts = cotangents if isinstance(cotangents, tuple) else (cotangents,)
            cts = tuple(c.astype(dt) if c.dtype != dt else c
                        for c, dt in zip(cts, out_dtypes))
            cot_tree = jax.tree_util.tree_unflatten(treedef, list(cts))
            d_train, d_ins = cached_bwd(pending2.pullback, cot_tree)
            return tuple(d_train) + tuple(d_ins)

        node = _tape.TapeNode(tape_inputs, pending2.out_nds, node_vjp,
                              len(pending2.out_nds))
        node.pending = pending2
        _tape.append_node(node)
        return jax.tree_util.tree_unflatten(down_treedef, down_nds)

    def _record_remat(self, training, arg_tree, trainable, aux, train_raws,
                      aux_raws, rng, rng_ctr, input_nds, input_raws):
        """Eager recording with rematerializing backward (long-context mode)."""
        out_raws, new_aux = self._cached_fn(training, arg_tree, train_raws,
                                            aux_raws, rng, rng_ctr, *input_raws)
        for p, r in zip(aux, new_aux):
            p._data_nd._data = r
        leaves, treedef = jax.tree_util.tree_flatten(out_raws)
        out_nds = []
        for o in leaves:
            ndo = NDArray(o)
            ndo._in_graph = True
            out_nds.append(ndo)

        tape_inputs = [p._data_nd for p in trainable] + input_nds
        cached_grad = self._cached_grad
        out_dtypes = [o.dtype for o in leaves]

        def node_vjp(cotangents):
            cts = cotangents if isinstance(cotangents, tuple) else (cotangents,)
            cts = tuple(c.astype(dt) if c.dtype != dt else c
                        for c, dt in zip(cts, out_dtypes))
            cot_tree = jax.tree_util.tree_unflatten(treedef, list(cts))
            d_train, d_ins = cached_grad(training, arg_tree, train_raws,
                                         aux_raws, rng, rng_ctr,
                                         tuple(input_raws), cot_tree)
            return tuple(d_train) + tuple(d_ins)

        _tape.append_node(_tape.TapeNode(tape_inputs, out_nds, node_vjp, len(out_nds)))
        return jax.tree_util.tree_unflatten(treedef, out_nds)

    # -- execution -------------------------------------------------------- #
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        # an activated Monitor forces the eager path so per-layer hooks
        # fire (the compiled cached-op never re-enters child Python)
        monitored = any(m.activated for m in self._monitors)
        if self._active and not kwargs and not monitored:
            out = self._call_cached_op(*args)
        else:
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        """Default: dispatch to `hybrid_forward(F, ...)` with params bound."""
        if type(self).hybrid_forward is not HybridBlock.hybrid_forward:
            self._resolve_deferred(args)
            bound = {}
            for name, p in self._params.items():
                short = _strip_prefix(name, self._prefix)
                bound[short] = p.data()
            return self.hybrid_forward(nd_mod, *args, **bound, **kwargs)
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward or hybrid_forward")

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError

    def _resolve_deferred(self, args):
        """Layers override `_infer_param_shapes(x)` for deferred-init."""
        pending = [p for p in self._params.values() if p._deferred_init is not None]
        if not pending:
            return
        if args and isinstance(args[0], NDArray):
            self._infer_param_shapes(*args)
        for p in pending:
            p._finish_deferred_init()

    def _infer_param_shapes(self, *args):
        pass

    def export(self, path: str, epoch: int = 0):
        """Save symbol JSON + params pair (parity: HybridBlock.export)."""
        from .. import symbol as sym_mod
        from ..utils import serialization

        sym_json = sym_mod.block_to_symbol_json(self)
        with open(f"{path}-symbol.json", "w") as f:
            f.write(sym_json)
        params = self.collect_params()
        arrays = {f"arg:{_strip_prefix(n, self._prefix)}": p.data()
                  for n, p in params.items() if p._data_nd is not None}
        serialization.save_ndarrays(f"{path}-{epoch:04d}.params", arrays)
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"


class SymbolBlock(HybridBlock):
    """Run a saved symbol graph as a Block (inference import path)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        self._outputs = outputs
        self._inputs = inputs

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod

        sym = sym_mod.load(symbol_file)
        block = SymbolBlock(sym, input_names)
        if param_file:
            from ..utils import serialization

            loaded = serialization.load_ndarrays(param_file)
            for k, v in loaded.items():
                key = k.removeprefix("arg:").removeprefix("aux:")
                p = Parameter(key, shape=v.shape)
                p.set_data(v)
                block._params._params[key] = p
        return block

    def forward(self, *args):
        from .. import symbol as sym_mod

        bindings = {name: wrap(a) for name, a in zip(
            self._inputs if isinstance(self._inputs, (list, tuple)) else [self._inputs], args)}
        for name, p in self._params.items():
            bindings[name] = p.data()
        return sym_mod.evaluate(self._outputs, bindings)


def _strip_prefix(name: str, prefix: str) -> str:
    return name[len(prefix):] if prefix and name.startswith(prefix) else name


def _make_apply_fn(block: Block, trainable: List[Parameter], aux: List[Parameter],
                   call_forward: bool = False):
    """Shared pure-function body for `functionalize` and `_build_cache`:
    temporarily rebinds param raws (restored in `finally`), disables the
    tape, installs a trace key provider, and returns
    ``(out_raws, new_aux)``.  `call_forward=True` invokes
    ``block.forward`` directly (cached-op path: skip the child-cache
    dispatch); else ``block.__call__``."""

    def apply_fn(train_raws, aux_raws, rng_key, *input_raws, training=False):
        # save WITHOUT forcing: an open Trainer chain leaves LazyRefs on
        # the param nds, and this save/restore is pure bookkeeping (the
        # values are never consumed) — the setter in `finally` re-binds
        # a LazyRef as-is
        t_saved = [_capture_raw(p) for p in trainable]
        a_saved = [_capture_raw(p) for p in aux]
        rec_saved = _tape.set_recording(False)
        trn_saved = _tape.set_training(training)
        try:
            for p, r in zip(trainable, train_raws):
                p._data_nd._data = r
            for p, r in zip(aux, aux_raws):
                p._data_nd._data = r
            with _random.TraceKeyProvider(rng_key):
                fn = block.forward if call_forward else block
                # args may be nested pytrees of raws (RNN state lists);
                # wrap every array leaf, preserve the structure
                outs = fn(*[jax.tree_util.tree_map(wrap, i)
                            for i in input_raws])
            out_raws = jax.tree_util.tree_map(
                raw, outs, is_leaf=lambda v: isinstance(v, NDArray))
            new_aux = tuple(p._data_nd._data for p in aux)
            return out_raws, new_aux
        finally:
            for p, r in zip(trainable, t_saved):
                p._data_nd._data = r
            for p, r in zip(aux, a_saved):
                p._data_nd._data = r
            _tape.set_recording(rec_saved)
            _tape.set_training(trn_saved)

    apply_fn.trainable_params = trainable
    apply_fn.aux_params = aux
    return apply_fn


def functionalize(block: Block, *example_args):
    """Extract a pure JAX function from an (initialized) Block.

    The SPMD bridge: once a Gluon model is a pure function of
    ``(trainable, aux, rng_key, *inputs)`` it composes with ``jax.jit``,
    ``jax.grad``, ``pjit`` shardings and ``shard_map`` — this is how the
    Trainer/bench/multichip paths compile full train steps (the
    CachedOp equivalence of SURVEY.md §3.3 taken to its conclusion).

    Returns ``(apply_fn, trainable_raws, aux_raws)`` where
    ``apply_fn(trainable, aux, rng_key, *input_raws, training=False)``
    → ``(out_raws, new_aux)``.  ``trainable``/``aux`` are tuples of raw
    `jax.Array` in `collect_params()` order (grad_req != 'null' first
    tuple, the rest in the second).
    """
    if example_args:
        if isinstance(block, HybridBlock):
            block._ensure_shapes(tuple(wrap(a) for a in example_args))
        else:
            block(*[wrap(a) for a in example_args])
    params = block.collect_params()
    trainable = [p for p in params.values() if p.grad_req != "null" and p._data_nd is not None]
    aux = [p for p in params.values() if p.grad_req == "null" and p._data_nd is not None]
    pending = [p.name for p in params.values() if p._data_nd is None]
    if pending:
        raise MXNetError(
            f"functionalize: parameters not initialized (pass example args): {pending}")
    apply_fn = _make_apply_fn(block, trainable, aux)
    train_raws = tuple(p._data_nd._data for p in trainable)
    aux_raws = tuple(p._data_nd._data for p in aux)
    return apply_fn, train_raws, aux_raws
