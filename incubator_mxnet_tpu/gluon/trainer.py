"""Gluon Trainer.

Re-design of `python/mxnet/gluon/trainer.py` [UNVERIFIED]
(SURVEY.md §2.6, §3.2): owns the optimizer + a KVStore facade.
`step(batch_size)` = allreduce_grads + update.

TPU-first fast path: when the configuration allows (no dist kvstore, no
server-side updater, no gradient compression), `step()` compiles ONE
jitted multi-tensor update over the whole parameter set — every
parameter's `optimizer.pure_update` stacked in a single XLA program
with the weight/state buffers donated.  This is the equivalent of the
reference's fused `multi_sgd_update`/`multi_lamb` multi-tensor ops
(SURVEY.md §2.3 "Optimizer ops"), generalized to all optimizers, and
is what lets the public `autograd.record()` → `trainer.step()` loop
run at hand-rolled-JAX speed instead of dispatching one kernel per
parameter.

On the slow (reference-parity) path, grads go per-key through the
KVStore facade (push/pull, compression, dist reduction) and the
optimizer runs per-parameter — identical observable semantics.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Union

import jax

from .. import kvstore as kvs_mod
from .. import optimizer as opt_mod
from .. import telemetry
from ..base import MXNetError
from ..ndarray.ndarray import raw
from ..ops import mosaic
from .parameter import Parameter, ParameterDict


def _wait_or_surface(leaf) -> None:
    """Block on a throttle leaf; a buffer donated into a later step is
    already consumed (benign), but a REAL async execution error (e.g.
    device OOM) must not be silently dropped."""
    try:
        jax.block_until_ready(leaf)  # tpulint: disable=TPU002 -- deliberate backpressure sync: bounds run-ahead to the throttle window
    except RuntimeError as e:
        if "deleted" not in str(e):
            raise


def _aval_bytes(a) -> int:
    import math

    import numpy as onp

    try:
        itemsize = int(onp.dtype(a.dtype).itemsize)
    except TypeError:
        itemsize = 2  # bfloat16 and friends
    return math.prod(a.shape) * itemsize if a.shape else itemsize

def _apply_constraints(new_w, new_s, constraints):
    """Pin fused-step outputs to their input shardings (ZeRO gspmd tier):
    new weights back to the original param layout, new states to the
    data-augmented state layout."""
    from jax.sharding import NamedSharding

    wsh, ssh = constraints
    wsc = jax.lax.with_sharding_constraint
    new_w = tuple(wsc(x, s) if isinstance(s, NamedSharding) else x
                  for x, s in zip(new_w, wsh))
    sdef = jax.tree_util.tree_structure(new_s)
    sl = [wsc(x, s) if isinstance(s, NamedSharding) else x
          for x, s in zip(jax.tree_util.tree_leaves(new_s), ssh)]
    return new_w, jax.tree_util.tree_unflatten(sdef, sl)


__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params: Union[ParameterDict, List[Parameter], Dict],
                 optimizer, optimizer_params: Optional[dict] = None,
                 kvstore="device", compression_params=None, update_on_kvstore=None,
                 fuse_step: bool = True, donate: bool = True,
                 keep_grads: bool = True,
                 max_inflight_steps: Optional[int] = None,
                 max_inflight_bytes: int = 6 << 30,
                 mesh=None, data_axis: str = "data",
                 chain_steps: int = 1, chain_unroll: bool = False,
                 zero_stage: Optional[int] = None,
                 zero_collectives: str = "auto",
                 zero_overlap: Optional[bool] = None,
                 zero_bucket_mb: Optional[float] = None):
        if isinstance(params, (dict, ParameterDict)):
            param_list = [params[k] for k in sorted(params.keys())] \
                if isinstance(params, dict) else list(params.values())
        elif isinstance(params, (list, tuple)):
            param_list = list(params)
        else:
            raise ValueError("First argument must be a list or dict of Parameters")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(param_list):
            if not isinstance(p, Parameter):
                raise ValueError(f"First argument must contain Parameters, got {type(p)}")
            self._param2idx[p.name] = i
            self._params.append(p)
        self._compression_params = compression_params
        self._contains_sparse = False
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_type = kvstore
        self._kvstore = kvs_mod.create(kvstore) if isinstance(kvstore, str) and kvstore else kvstore
        if self._kvstore is not None and compression_params:
            self._kvstore.set_gradient_compression(compression_params)
        self._update_on_kvstore = update_on_kvstore if update_on_kvstore is not None else False
        self._kv_initialized = False
        self._states: Dict[int, object] = {}
        # fused-step machinery
        self._fuse_step = fuse_step
        self._donate = donate
        self._fused_fn = None
        self._fused_key = None
        self._fullstep_ctx = None
        self._states_stale = False
        # keep_grads=False: the single-program step does NOT materialize
        # gradients as program outputs (saves one full-model HBM write
        # per step); reading p.grad() after step() then raises.
        self._keep_grads = keep_grads
        # Async dispatch run-ahead cap: every queued step holds its
        # output buffers (grads/new states) until it retires, so an
        # unbounded enqueue loop exhausts HBM.  The dependency-engine
        # equivalence of the reference's bounded engine queue.
        # explicit step cap (tight-HBM chips): honored by BOTH throttle
        # paths; None = default 8 for the eager-backward path, bytes-only
        # for the one-program path
        self._user_inflight_cap = None if max_inflight_steps is None \
            else max(1, int(max_inflight_steps))
        self._max_inflight = self._user_inflight_cap or 8
        # one-program path: run-ahead bounded by BYTES actually held per
        # in-flight step (non-donated program outputs), not step count —
        # a host sync stalls the dispatch queue, so programs with small
        # outputs must never pay it (see _throttle_bytes)
        self._max_inflight_bytes = int(max_inflight_bytes)
        from collections import deque

        self._inflight = deque()
        # SPMD: an explicit Mesh (or one inferred from already-sharded
        # params via parallel.sharding.shard_params) makes the fused
        # step a multi-device GSPMD program: optimizer states are
        # created on each param's sharding and unsharded batch inputs
        # are placed on the data axis.  The training loop is unchanged —
        # this is how "gluon.Trainer scales across a TPU pod"
        # (BASELINE.json north star) without a DataParallelExecutorGroup.
        self._mesh = mesh
        self._data_axis = data_axis
        # multi-step chaining: buffer K canonical steps and dispatch ONE
        # lax.scan program over the full train state — amortizes the
        # per-dispatch host overhead that otherwise sits between
        # device steps.  Reads of any chained value (loss, outputs,
        # params, grads) flush the chain first, so semantics match the
        # per-step path exactly; requires keep_grads=False.
        self._chain_steps = max(1, int(chain_steps))
        # unroll: python-loop the K bodies instead of lax.scan — longer
        # compile (K copies of the step), but no while-loop bookkeeping,
        # no input stacking, and per-step outputs come back as separate
        # arrays (no slicing on read)
        self._chain_unroll = bool(chain_unroll)
        self._chain_buf: list = []
        self._chain_state: Optional[dict] = None
        self._chain_weight_cells: list = []
        # ZeRO-1 sharded optimizer step (docs/performance.md "Sharded
        # optimizer"): None = auto (ON whenever a mesh with a non-trivial
        # data axis is active), 0 = off, 1 = forced.  zero_collectives
        # picks how the sharding is expressed: "explicit" (shard_map +
        # psum_scatter/all_gather — data-only meshes), "gspmd"
        # (NamedSharding state + sharding constraints — composes with
        # TP), or "auto" (explicit when eligible, else gspmd).
        if zero_stage not in (None, 0, 1):
            raise ValueError(f"zero_stage must be None, 0 or 1, got {zero_stage!r}")
        if zero_collectives not in ("auto", "gspmd", "explicit"):
            raise ValueError(
                f"zero_collectives must be 'auto', 'gspmd' or 'explicit', "
                f"got {zero_collectives!r}")
        self._zero_stage = zero_stage
        self._zero_collectives = zero_collectives
        # Backward-overlapped bucketed gradient sync (parallel/overlap.py):
        # None = env-resolved (MXTPU_ZERO_OVERLAP, default on).  Only the
        # explicit tier buckets; the result is bit-identical to the
        # monolithic per-param exchange (interleaved pack layout), so the
        # knob exists for A/B measurement, not numerics.
        if zero_bucket_mb is not None and float(zero_bucket_mb) <= 0:
            raise ValueError(
                f"zero_bucket_mb must be positive, got {zero_bucket_mb!r}")
        self._zero_overlap = zero_overlap
        self._zero_bucket_mb = zero_bucket_mb
        self._zero_overlap_broken = False  # sticky: bucketed build failed
        self._zero_warned: set = set()  # one-time warning keys
        self._capture_hlo = False       # tests/dryrun: keep last_step_hlo
        self.last_step_hlo: Optional[str] = None
        # lowered (pre-XLA) StableHLO of the same step: carries the
        # jax.buffer_donor markers hlolint's donation-coverage fact
        # holds the compiled input_output_alias header against
        self.last_step_stablehlo: Optional[str] = None
        # perf-attribution program name of the step path that last ran
        # (telemetry.perf roofline/MFU gauges key on it)
        self._perf_program: Optional[str] = None

    def _get_mesh(self):
        """Explicit mesh, else inferred from any NamedSharded param.
        Re-probes while None so `shard_params` called after Trainer
        construction (or after a warmup step) is still picked up."""
        if self._mesh is None:
            from jax.sharding import NamedSharding

            for p in self._params:
                if p._data_nd is None or p._data_nd._lazy is not None:
                    continue
                sh = getattr(p._data_nd._raw, "sharding", None)
                if isinstance(sh, NamedSharding):
                    self._mesh = sh.mesh
                    break
        return self._mesh

    def _step_mesh(self, pending):
        """The mesh the step program is traced under (ops/mosaic.py): the
        Trainer's, if any of the step's arguments is laid out over more
        than one device.  A mesh that nothing was placed on (params not
        sharded, no sharded state, a batch the data axis does not divide)
        leaves a one-device program, which has no mesh."""
        mesh = self._get_mesh()
        if mesh is None or mesh.size == 1:
            return None
        leaves = [p._data_nd._raw for p in self._params
                  if p._data_nd is not None and p._data_nd._lazy is None]
        leaves += jax.tree_util.tree_leaves(list(self._states.values()))
        leaves += list(self._shard_inputs(pending.input_raws))
        spans = any(len(getattr(getattr(x, "sharding", None), "device_set",
                                ())) > 1 for x in leaves)
        return mesh if spans else None

    # ------------------------------------------------------------------ #
    # ZeRO-1 sharded optimizer state (gluon/zero.py)
    # ------------------------------------------------------------------ #
    def _warn_zero_once(self, key: str, msg: str, use_logging: bool = False):
        if key in self._zero_warned:
            return
        self._zero_warned.add(key)
        if use_logging:
            import logging

            logging.getLogger(__name__).warning(msg)
        else:
            import warnings

            warnings.warn(msg, stacklevel=4)

    def _resolve_zero(self) -> Optional[dict]:
        """Resolve the ZeRO-1 configuration for the current step.

        Returns None (replicated optimizer path) or
        ``{"tier": "explicit"|"gspmd", "mesh", "axis", "D"}``.  ZeRO is
        auto-enabled when a mesh with a non-trivial data axis is active;
        stochastic optimizers and gradient compression opt out with a
        one-time warning naming the reason."""
        if self._zero_stage == 0:
            return None
        mesh = self._get_mesh()
        axis = self._data_axis
        D = int(mesh.shape[axis]) \
            if mesh is not None and axis in mesh.axis_names else 0
        if D <= 1:
            if self._zero_stage == 1:
                self._warn_zero_once(
                    "nomesh",
                    f"Trainer(zero_stage=1): no mesh with a non-trivial "
                    f"{axis!r} axis is active — running the replicated "
                    f"optimizer path")
            return None
        opt = self._optimizer
        if getattr(opt, "needs_rng", False):
            self._warn_zero_once(
                "rng",
                f"Trainer: ZeRO-1 disabled for stochastic optimizer "
                f"{type(opt).__name__}: a sharded update would draw "
                f"per-shard noise and diverge from the replicated rule")
            return None
        kv = self._kvstore
        comp = getattr(kv, "_compression", None) if kv is not None else None
        if comp is not None:
            reason = comp.reduce_scatter_incompatible_reason()
            if reason is not None:
                # one-time logging.warning naming the reason — the step
                # keeps the all-reduce gradient sync instead of silently
                # changing the compression numerics
                self._warn_zero_once(
                    "compression",
                    "Trainer: zero_stage=1 reduce-scatter gradient sync "
                    "disabled, falling back to the all-reduce path: "
                    + reason, use_logging=True)
                return None
        tier = self._zero_collectives
        explicit_ok = (tuple(mesh.axis_names) == (axis,)
                       and getattr(opt, "elementwise_update", True))
        if tier == "auto":
            tier = "explicit" if explicit_ok else "gspmd"
        elif tier == "explicit" and not explicit_ok:
            self._warn_zero_once(
                "explicit",
                "Trainer(zero_collectives='explicit') needs a data-only "
                "mesh and an elementwise optimizer rule — using the GSPMD "
                "sharding tier instead")
            tier = "gspmd"
        return {"tier": tier, "mesh": mesh, "axis": axis, "D": D}

    def _zero_sig(self):
        zr = self._resolve_zero()
        return None if zr is None else (zr["tier"], zr["axis"], zr["D"])

    def _overlap_sig(self) -> Optional[int]:
        """Bucket byte cap when the overlapped explicit exchange is
        live, else None (off / env-disabled / sticky-broken).  Part of
        the fullstep staleness signature so flipping the knob rebuilds."""
        if self._zero_overlap_broken:
            return None
        from ..parallel import overlap as overlap_mod

        if not overlap_mod.overlap_enabled(self._zero_overlap):
            return None
        return overlap_mod.resolve_bucket_bytes(self._zero_bucket_mb)

    def _canonicalize_states(self):
        """Convert any explicit-tier Zero1State entries back to the
        canonical full-shape layout (device-side slice+reshape of the
        global flat buffers — no host round-trip)."""
        from . import zero as zero_mod

        for k, st in list(self._states.items()):
            if isinstance(st, zero_mod.Zero1State):
                self._states[k] = zero_mod.canonical(st)

    def optimizer_state_bytes_per_device(self) -> int:
        """Per-device bytes held by the optimizer state (sharding
        metadata only, no sync) — the quantity ZeRO-1 divides by the
        data-axis size."""
        from . import zero as zero_mod

        self._sync_states()
        return sum(zero_mod.state_bytes_per_device(st)
                   for st in self._states.values())

    def host_states(self) -> dict:
        """Canonical full-shape host copy of every optimizer state,
        fetched one leaf at a time (a ZeRO-sharded state is never
        materialized as a full device-side replica to be saved)."""
        import numpy as onp

        from . import zero as zero_mod

        self._flush_chain()
        self._sync_states()
        out = {}
        for k, st in self._states.items():
            if isinstance(st, zero_mod.Zero1State):
                out[k] = zero_mod.host_canonical(st)
            else:
                out[k] = jax.tree_util.tree_map(
                    lambda x: onp.asarray(jax.device_get(x)), st)
        return out

    def device_states(self) -> dict:
        """Live device references to every optimizer state, post-flush
        and post-sync — NO copy, NO host fetch.  This is the async
        checkpoint hook: the CheckpointManager snapshots these with one
        on-device copy program, so the caller stalls only for the copy
        dispatch, never a device→host transfer.  Explicit-tier entries
        come back as ``Zero1State`` (shard-local; the worker re-assembles
        the canonical layout on host via ``zero.host_canonical``)."""
        self._flush_chain()
        self._sync_states()
        return dict(self._states)

    def adopt_restored_states(self) -> int:
        """Re-shard freshly-restored canonical optimizer state onto this
        trainer's CURRENT mesh (elastic resume: a checkpoint taken on
        data=8 restoring onto data=4 re-flat-pads + re-slices here).

        Checkpoints always store the canonical full-shape layout, and
        ``_canonicalize_states`` runs before every fullstep (re)build, so
        eagerly adopting is safe and also pre-places each leaf shard-
        local — the first step after restore never materializes a full
        replica per device.  Off the explicit ZeRO tier this is a no-op.
        Returns the number of states adopted."""
        from . import zero as zero_mod

        zr = self._resolve_zero()
        if zr is None or zr["tier"] != "explicit":
            return 0
        mesh, axis, D = zr["mesh"], zr["axis"], zr["D"]
        opt = self._optimizer
        adopted = 0
        for i, st in list(self._states.items()):
            p = self._params[i]
            if p._data_nd is None:
                continue
            w = p._data_nd._data
            try:
                if isinstance(st, zero_mod.Zero1State):
                    if st.meta.D == D:
                        continue
                    self._states[i] = zero_mod.reshard(st, D, mesh, axis)
                else:
                    mp = bool(opt.multi_precision
                              and w.dtype in (jnp.float16, jnp.bfloat16))
                    self._states[i] = zero_mod.adopt(st, w, D, mesh, axis, mp)
                adopted += 1
            except zero_mod.ZeroIncompatible:
                # the fullstep build will settle the tier (gspmd
                # fallback) — leave this state canonical
                continue
        self._fullstep_ctx = None
        return adopted

    def _shard_state_like(self, state, w):
        """Place same-shape optimizer-state leaves (momentum, fp32
        master, ...) on the weight's sharding — TP memory savings apply
        to the full train state, not just the weights.  With ZeRO-1
        active the leaf sharding additionally gains the data axis on the
        first free divisible dimension (gluon/zero.py), dividing state
        bytes per device by the data-axis size."""
        from jax.sharding import NamedSharding

        sh = getattr(w, "sharding", None)
        if not isinstance(sh, NamedSharding):
            return state
        zsh = None
        zr = self._resolve_zero()
        if zr is not None:
            from . import zero as zero_mod

            zsh = zero_mod.gspmd_state_sharding(w, zr["axis"], zr["D"])

        def put(leaf):
            if hasattr(leaf, "shape") and tuple(leaf.shape) == tuple(w.shape):
                return jax.device_put(leaf, zsh or sh)
            return leaf

        return jax.tree_util.tree_map(put, state)

    def _zero_constraints(self, idxs):
        """(weight shardings, flat state-leaf shardings) for the gspmd
        tier's output constraints — captured from the live arrays."""
        w_sh = tuple(getattr(self._params[i]._data_nd._data, "sharding", None)
                     for i in idxs)
        s_sh = tuple(getattr(l, "sharding", None)
                     for i in idxs
                     for l in jax.tree_util.tree_leaves(self._states[i]))
        return (w_sh, s_sh)

    @telemetry.span("trainer/shard_inputs")
    def _shard_inputs(self, input_raws):
        """Place uncommitted/unsharded batch inputs on the data axis.

        Inputs the user already NamedSharded (seq-parallel splits, ...)
        are left untouched.  Auto-placement applies ONLY to inputs whose
        leading dim equals the batch size (the leading dim of the FIRST
        array input, sharded or not — MXNet's data-first convention): lookup
        tables or (T, ...)-layout masks whose leading dim merely happens
        to divide the data axis are NOT batch-sharded, which would make
        GSPMD insert a reshard collective every step.  Pre-shard such
        inputs yourself (jax.device_put with a NamedSharding) to opt in
        to any other layout."""
        mesh = self._get_mesh()
        if mesh is None or self._data_axis not in mesh.axis_names:
            return input_raws
        from jax.sharding import NamedSharding

        from ..io.prefetcher import batch_sharding

        n = mesh.shape[self._data_axis]
        if n <= 1:
            return input_raws
        batch = None  # leading dim of the first array input (data-first)
        for r in input_raws:
            if hasattr(r, "shape") and r.ndim >= 1:
                batch = r.shape[0]
                break
        if batch is None or batch % n != 0:
            if batch is not None \
                    and not getattr(self, "_warned_noshard", False):
                import warnings

                self._warned_noshard = True
                warnings.warn(
                    f"Trainer: first input's leading dim {batch} is not "
                    f"divisible by the data axis ({n}) — auto data-"
                    f"sharding of inputs is OFF for this step shape. If "
                    f"the first argument is not the batch (data-first "
                    f"convention), pre-shard inputs with jax.device_put.",
                    stacklevel=3)
            return input_raws
        out = []
        for r in input_raws:
            # already-NamedSharded inputs (e.g. batches staged by the
            # io.prefetcher pipeline, or user-placed splits) pass
            # through untouched — prefetched feeds pay ZERO per-step
            # device_put here
            sh = getattr(r, "sharding", None)
            if (not isinstance(sh, NamedSharding) and hasattr(r, "shape")
                    and r.ndim >= 1 and r.shape[0] == batch):
                r = jax.device_put(
                    r, batch_sharding(mesh, r.ndim, self._data_axis))
            out.append(r)
        return tuple(out)

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            if optimizer_params:
                raise ValueError("optimizer_params must be None when optimizer is an instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt_mod.create(optimizer, param_dict=param_dict,
                                             **optimizer_params)

    def _init_kvstore(self):
        if self._kvstore is not None:
            for i, p in enumerate(self._params):
                if p._data_nd is not None:
                    self._kvstore.init(i, p.data())
        self._kv_initialized = True

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # ------------------------------------------------------------------ #
    # fused fast path
    # ------------------------------------------------------------------ #
    def _can_fuse(self) -> bool:
        if not self._fuse_step or self._update_on_kvstore:
            return False
        kv = self._kvstore
        if kv is not None:
            if kv._compression is not None or kv._updater is not None:
                return False
            if kv._is_dist and jax.process_count() > 1 \
                    and not self._dist_spmd_ready():
                # legacy dist contract: process-LOCAL params/batches rely
                # on the kvstore push/pull reduction — fusing would skip
                # it and silently diverge the replicas
                return False
            # dist multi-process with GLOBAL state IS fusable (SURVEY.md
            # §5.8): params were placed on a multi-process mesh
            # (shard_params) and the batch enters as a global array
            # (gluon.utils.shard_batch), so the gradient reduction
            # compiles into the jitted step (GSPMD psum over the data
            # axis, DCN between slices) — no per-key host path, comm/
            # compute overlap for free.
        if type(self._optimizer).pure_update is opt_mod.Optimizer.pure_update:
            return False  # custom optimizer without a pure rule
        return True

    def _iter_active_param_raws(self):
        """Raw arrays of every committed, grad-carrying param (the set
        both the SPMD-readiness probes and the kvstore bypass agree on)."""
        for p in self._params:
            if p.grad_req == "null" or p._data_nd is None \
                    or p._data_nd._lazy is not None:
                continue
            yield p._data_nd._raw

    def _has_global_params(self) -> bool:
        """Any managed param placed as a multi-process global array."""
        return any(
            hasattr(r, "is_fully_addressable") and not r.is_fully_addressable
            for r in self._iter_active_param_raws())

    def _dist_spmd_ready(self) -> bool:
        """True iff the training state is multi-process global: EVERY
        managed param's array spans beyond this process's devices (the
        signature `shard_params(block, global_mesh)` leaves).  A MIXED
        state (some params global, some process-local) is not fusable —
        the local params' grads would silently skip the cross-process
        reduction — and warns once."""
        n_global = n_local = 0
        for r in self._iter_active_param_raws():
            if hasattr(r, "is_fully_addressable") and not r.is_fully_addressable:
                n_global += 1
            else:
                n_local += 1
        if n_global and n_local and not getattr(self, "_warned_mixed", False):
            import warnings

            self._warned_mixed = True
            warnings.warn(
                f"Trainer: {n_global} params are multi-process global but "
                f"{n_local} are process-local — no reduction path serves "
                f"both (step() refuses this state when a kvstore is "
                f"attached). Apply shard_params to the WHOLE block.",
                stacklevel=3)
        return n_global > 0 and n_local == 0

    def _can_fuse_packed_compression(self) -> bool:
        """Dist + gradient compression: grads exchange as ONE bit-packed
        buffer (all params concatenated), then the stacked fused update
        runs — per-key DCN latency eliminated while keeping the 2-bit
        wire format and error feedback (VERDICT r2 #4)."""
        if not self._fuse_step or self._update_on_kvstore:
            return False
        kv = self._kvstore
        if kv is None or kv._compression is None or kv._updater is not None:
            return False
        if not (kv._is_dist and jax.process_count() > 1):
            return False  # single-process: per-key path is cheap, keep
            # the kvstore-store-visible semantics
        # Global (GSPMD-placed) params are already cross-process reduced
        # inside the SPMD step — packing and summing one decompressed
        # copy per process would scale grads by process_count (or fail
        # on non-addressable arrays).  step() skips the kvstore exchange
        # entirely for global state (see the bypass there).
        if self._has_global_params():
            return False
        return type(self._optimizer).pure_update \
            is not opt_mod.Optimizer.pure_update

    # -- shared machinery of the two fused paths ------------------------ #
    def _mults_key(self, idxs):
        """Per-param lr/wd multipliers + clip — recomputed every step and
        part of every fused cache key, so param.lr_mult / clip_gradient
        changes mid-run rebuild the program instead of being ignored."""
        opt = self._optimizer
        return (tuple(opt._lr_mult_for(i) for i in idxs),
                tuple(opt._wd_mult_for(i) for i in idxs),
                opt.clip_gradient)

    def _make_stacked_update(self, lr_mults, wd_mults, clip):
        """Stacked multi-tensor update over all params (one traced body —
        the reference's `multi_sgd_update`/`multi_lamb` generalization)."""
        opt = self._optimizer
        needs_rng = opt.needs_rng

        def stacked(weights, grads, states, ts, lr, wd, rescale, keys):
            # ts is a single stacked (N,) array and keys a stacked (N,2)
            # array — ONE host transfer each per step, not N tiny ones
            # (which dominate step latency over a remote device link)
            new_w, new_s = [], []
            for j in range(len(weights)):
                k = keys[j] if needs_rng else None
                nw, ns = opt.pure_update_multi_precision(
                    weights[j], grads[j], states[j], ts[j],
                    lr * lr_mults[j], wd * wd_mults[j], rescale, clip, k)
                new_w.append(nw)
                new_s.append(ns)
            return tuple(new_w), tuple(new_s)

        return stacked

    def _advance_scalars(self, idxs):
        """Advance host-side update counts (authoritative for
        save_states / ctx rebuilds); return (lr, keys) for this step."""
        import jax.numpy as jnp

        opt = self._optimizer
        for i in idxs:
            opt._update_count(i)
        lr = opt.lr_scheduler(opt.num_update) if opt.lr_scheduler is not None else opt.lr
        keys = None
        if opt.needs_rng:
            from .. import random as _random

            keys = jnp.stack([_random.next_key() for _ in idxs])
        return lr, keys

    def _step_scalars(self, idxs):
        """Advance update counts; return traced (per-index ts, lr, keys).

        ts/keys are stacked into single device arrays so each step pays
        one host→device transfer, not one per parameter (~400 for BERT).
        The one-program step only pays this on its FIRST call after a
        ctx (re)build — afterwards ts lives on device and increments
        inside the donated program."""
        import jax.numpy as jnp

        opt = self._optimizer
        lr, keys = self._advance_scalars(idxs)
        ts = jnp.asarray([float(opt._index_update_count[i]) for i in idxs],
                         jnp.float32)
        return ts, lr, keys

    def _throttle(self, leaf):
        """Bound async run-ahead: each queued step holds its output
        buffers until it retires, so an unthrottled enqueue loop OOMs.
        Blocks on the (max_inflight)-steps-old leaf; a leaf that was
        donated into a later step is already consumed — skip it."""
        self._inflight.append(leaf)
        if telemetry.enabled():
            telemetry.gauge("trainer_inflight_steps") \
                .set(len(self._inflight))
        if len(self._inflight) > self._max_inflight:
            with telemetry.span("trainer/throttle"):
                while len(self._inflight) > self._max_inflight:
                    old = self._inflight.popleft()
                    _wait_or_surface(old)

    def _throttle_bytes(self, leaf, held_bytes: int):
        """Byte-budgeted run-ahead bound for the one-program step.

        depth = budget // held_bytes steps may be in flight (capped by
        an EXPLICIT user max_inflight_steps).  A host sync
        (block_until_ready/device_get) stops the host from running
        ahead of the device, so: small-output programs (depth larger
        than any realistic run-ahead) never sync at all, and big-output
        programs drain HALF the queue with ONE sync every depth/2 steps
        instead of paying one sync per step."""
        self._inflight.append(leaf)
        if telemetry.enabled():
            # host ints only (held_bytes comes from aval metadata) — the
            # run-ahead HBM pressure this throttle exists to bound
            telemetry.gauge("throttle_held_bytes") \
                .set(int(held_bytes) * len(self._inflight))
            telemetry.gauge("trainer_inflight_steps") \
                .set(len(self._inflight))
        depth = max(2, self._max_inflight_bytes // max(int(held_bytes), 1))
        if self._user_inflight_cap is not None:
            depth = min(depth, self._user_inflight_cap)
        if self._user_inflight_cap is None \
                and int(held_bytes) * 4096 <= self._max_inflight_bytes:
            # truly-tiny outputs: even absurd run-ahead (4096 steps)
            # fits the budget — never sync, just stop the ref queue
            # growing (a dropped reference frees the retired scalar)
            if len(self._inflight) > 64:
                self._inflight.popleft()
            return
        if len(self._inflight) >= depth:
            with telemetry.span("trainer/throttle"):
                last = None
                while len(self._inflight) > depth // 2:
                    last = self._inflight.popleft()
                _wait_or_surface(last)

    # ------------------------------------------------------------------ #
    # multi-step chaining (chain_steps > 1): K canonical steps buffered
    # and dispatched as ONE lax.scan program over the full train state.
    # Values a user may touch mid-chain (loss/outputs/params/grads) are
    # LazyRefs whose force flushes the chain first — semantics match
    # the per-step path exactly; the win is K-1 avoided host
    # dispatch gaps (the dependency-engine run-ahead, one level up).
    # ------------------------------------------------------------------ #
    def _materialize_ts(self, ctx, idx_of):
        """Device step counter: steady-state device-resident, else ONE
        transfer from the authoritative host counts (int32: exact +1
        past 2^24; update rules get the f32 view in-program)."""
        import jax.numpy as jnp

        ts = ctx.get("ts_dev")
        if ts is None:
            opt = self._optimizer
            ts = jnp.asarray([int(opt._index_update_count[i])
                              for i in idx_of], jnp.int32)
        return ts

    def _chain_allowed(self) -> bool:
        if self._chain_steps <= 1:
            return False
        kv = self._kvstore
        reason = None
        if self._keep_grads or not self._donate:
            reason = "it requires keep_grads=False and donate=True"
        elif kv is not None and getattr(kv, "_is_dist", False):
            reason = "it is not supported with a distributed kvstore"
        if reason is not None:
            if not getattr(self, "_chain_warned", False):
                import warnings

                warnings.warn(
                    f"Trainer(chain_steps={self._chain_steps}) is being "
                    f"IGNORED: {reason}; steps dispatch one program each",
                    stacklevel=4)
                self._chain_warned = True
            return False
        return True

    def flush(self):
        """Dispatch any buffered chained steps (no-op when none)."""
        self._flush_chain()

    def _enqueue_chain(self, ctx, pending) -> bool:
        import jax.numpy as jnp

        from ..engine import LazyRef

        opt = self._optimizer
        idx_of = ctx["idx_of"]
        lr, keys = self._advance_scalars(idx_of)
        flush = self._flush_chain
        if self._chain_state is None:
            from .block import _resolve_raws

            self._chain_state = {
                "w": tuple(nd._data for nd in ctx["nds"]),
                "aux": _resolve_raws(pending.aux_raws),
                "states": ctx["states"],
                "ts": self._materialize_ts(ctx, idx_of),
                "ctx": ctx,
            }
            cells = []
            for nd, w in zip(ctx["nds"], self._chain_state["w"]):
                cell = LazyRef(flush,
                               jax.ShapeDtypeStruct(w.shape, w.dtype))
                nd._data = cell
                cells.append(cell)
            self._chain_weight_cells = cells
        self._chain_buf.append({
            "pending": pending,
            "rng": pending.rng, "ctr": pending.rng_ctr,
            # mesh runs: batch inputs placed on the data axis HERE, so
            # the chained program sees the same shardings the per-step
            # path would (GSPMD then shards the in-program stack too)
            "inputs": tuple(self._shard_inputs(pending.input_raws)),
            "lr": float(lr), "wd": float(opt.wd),
            "rescale": float(opt.rescale_grad),
            "keys": keys,
        })
        for cell in pending.out_cells:
            cell.force_fn = flush
        for cell in pending.aux_cells:
            cell.force_fn = flush
        for cell in pending.grad_cells.values():
            cell.force_fn = flush
        if len(self._chain_buf) >= self._chain_steps:
            self._flush_chain()
        return True

    def _get_chain_fn(self, ctx, has_keys: bool):
        key = ("chain_fn", has_keys, self._chain_unroll)
        fn = ctx.get(key)
        if fn is None:
            import jax.numpy as jnp
            from jax import lax

            pure = ctx["pure"]

            if self._chain_unroll:
                def chain_unrolled(w, aux, states, ts, per_step):
                    outs, auxs, sync = [], [], None
                    for x in per_step:
                        if has_keys:
                            rng, ctr, inp, lr, wd, rs, ky = x
                        else:
                            rng, ctr, inp, lr, wd, rs = x
                            ky = None
                        out_leaves, aux, _g, w, states, ts, sync = pure(
                            w, aux, states, rng, ctr, inp, ts, lr, wd,
                            rs, ky)
                        outs.append(out_leaves)
                        auxs.append(aux)
                    return w, aux, states, ts, tuple(outs), tuple(auxs), sync

                fn = jax.jit(chain_unrolled, donate_argnums=(0, 2, 3))
                ctx[key] = fn
                return fn

            def chain(w, aux, states, ts, per_step):
                # per_step: K per-step tuples — stacked HERE, inside the
                # one jitted program, so a flush costs exactly ONE
                # dispatch (each eager jnp.stack would be its own
                # dispatch)
                xs = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                            *per_step)

                def body(carry, x):
                    cw, caux, cst, cts = carry
                    if has_keys:
                        rng, ctr, inp, lr, wd, rs, ky = x
                    else:
                        rng, ctr, inp, lr, wd, rs = x
                        ky = None
                    out_leaves, new_aux, _g, new_w, new_s, new_ts, sync = \
                        pure(cw, caux, cst, rng, ctr, inp,
                             cts, lr, wd, rs, ky)
                    return ((new_w, new_aux, new_s, new_ts),
                            (out_leaves, new_aux, sync))

                carry, ys = lax.scan(body, (w, aux, states, ts), xs)
                outs, auxs, syncs = ys
                return carry + (outs, auxs, syncs[-1])

            # aux (arg 1) deliberately NOT donated — the single-step fn
            # never donates it either, so user-held aux references (e.g.
            # a captured running_mean array) stay readable, parity with
            # the per-step path
            fn = jax.jit(chain, donate_argnums=(0, 2, 3))
            ctx[key] = fn
        return fn

    @staticmethod
    def _chain_step_lost():
        raise MXNetError(
            "this value belonged to a chained Trainer step whose flush "
            "failed; the step never executed (see the raised flush error)")

    def _flush_chain(self):
        if not self._chain_buf:
            return
        with telemetry.span("trainer/chain_flush"):
            self._flush_chain_impl()

    def _flush_chain_impl(self):
        buf, st = self._chain_buf, self._chain_state
        if not buf:
            return
        import jax.numpy as jnp

        self._chain_buf = []
        self._chain_state = None
        wcells, self._chain_weight_cells = self._chain_weight_cells, []
        ctx = st["ctx"]
        opt = self._optimizer
        K = len(buf)
        done = 0  # steps whose update definitely applied before a failure
        live = (st["w"], st["aux"], st["states"], st["ts"])
        try:
            if K >= 2 and K == self._chain_steps:
                has_keys = buf[0]["keys"] is not None
                import numpy as onp

                # host scalars ride along as plain numpy scalars — they
                # transfer with the one call, never as their own dispatch
                per_step = tuple(
                    (r["rng"], onp.int32(r["ctr"]), r["inputs"],
                     onp.float32(r["lr"]), onp.float32(r["wd"]),
                     onp.float32(r["rescale"]))
                    + ((r["keys"],) if has_keys else ())
                    for r in buf)
                fn = self._get_chain_fn(ctx, has_keys)
                new_w, new_aux, new_s, new_ts, outs, auxs, sync = fn(
                    st["w"], st["aux"], st["states"], st["ts"], per_step)
                if self._chain_unroll:
                    # per-step outputs are separate arrays — fill direct
                    for k, r in enumerate(buf):
                        r["pending"].fill_from_full_step(outs[k], auxs[k],
                                                         None)
                        done += 1
                else:
                    for k, r in enumerate(buf):
                        self._fill_pending_sliced(
                            r["pending"], outs, auxs, k,
                            final_aux=new_aux if k == K - 1 else None)
            else:
                # tail/partial flush: reuse the compiled single-step fn
                w, aux, states, ts = live
                for r in buf:
                    out_leaves, aux, _g, w, states, ts, sync = ctx["fn"](
                        w, aux, states, r["rng"], r["ctr"], r["inputs"],
                        ts, r["lr"], r["wd"], r["rescale"], r["keys"])
                    r["pending"].fill_from_full_step(out_leaves, aux, None)
                    done += 1
                    live = (w, aux, states, ts)
                new_w, new_aux, new_s, new_ts = w, aux, states, ts
        except Exception:
            # A dispatch failure leaves its own donation unapplied, so
            # `live` — the carry after the last SUCCESSFUL step (the
            # original st for done=0) — is intact: restore it to the
            # nds, mark only the steps that never ran as lost, and roll
            # back exactly their count advances.
            w_live, aux_live, s_live, ts_live = live
            for nd, cell, w in zip(ctx["nds"], wcells, w_live):
                cell.value = w
                if nd._lazy is cell:
                    nd._data = w
            last = buf[-1]["pending"]
            for p, cell, a in zip(last.aux_params, last.aux_cells,
                                  aux_live):
                cell.value = a
                if p._data_nd._lazy is cell:
                    p._data_nd._data = a
            for r in buf[done:]:
                for cell in (list(r["pending"].out_cells)
                             + list(r["pending"].grad_cells.values())):
                    if cell.value is None:
                        cell.force_fn = self._chain_step_lost
            for i in ctx["idx_of"]:
                opt._index_update_count[i] -= (K - done)
            opt.num_update = max(
                [opt.begin_num_update] + list(
                    opt._index_update_count.values()))
            if done:
                ctx["states"] = s_live
                ctx["ts_dev"] = ts_live
                self._states_stale = True
            try:
                self._sync_states()  # while ctx is still attached
            except Exception:
                pass
            self._fullstep_ctx = None
            raise
        for nd, cell, w in zip(ctx["nds"], wcells, new_w):
            cell.value = w
            if nd._lazy is cell:
                nd._data = w
        ctx["states"] = new_s
        ctx["ts_dev"] = new_ts
        self._states_stale = True
        if telemetry.enabled():
            self._count_collective_bytes(ctx, K)
        try:
            self._throttle_bytes(sync, ctx["held_bytes"] * K)
        except Exception:
            # async execution error of an in-flight program: see the
            # single-step handler — unrecoverable in-process, counts
            # deliberately kept; recovery is a checkpoint restore
            self._fullstep_ctx = None
            raise

    @staticmethod
    def _fill_pending_sliced(pending, outs, auxs, k, final_aux=None):
        """Fill a chained pending from the scan-stacked outputs without
        dispatching K×leaves slice programs: out/aux cells get per-cell
        force_fns that slice ON READ.  The LAST pending's aux must be
        concrete (the aux nds are rebound to its cells) — `final_aux`
        passes the scan carry (identical to auxs[:, -1], no slicing)."""
        from .block import _grads_not_kept

        def slicer(cell, stacked):
            def fill():
                cell.value = stacked[k]
            return fill

        for cell, stacked in zip(pending.out_cells, outs):
            if cell.value is None:
                cell.force_fn = slicer(cell, stacked)
        if final_aux is not None:
            for p, cell, v in zip(pending.aux_params, pending.aux_cells,
                                  final_aux):
                cell.value = v
                if p._data_nd._lazy is cell:
                    p._data_nd._data = v
        else:
            for cell, stacked in zip(pending.aux_cells, auxs):
                if cell.value is None:
                    cell.force_fn = slicer(cell, stacked)
        for pos, cell in pending.grad_cells.items():
            if cell.value is None:
                cell.force_fn = _grads_not_kept
        pending.fwd_done = True
        pending.bwd_done = True
        pending.pullback = None

    @telemetry.span("trainer/fused_step")
    def _fused_step(self):
        opt = self._optimizer
        self._sync_states()
        # this path donates/replaces the state buffers the fullstep ctx
        # still references — drop the ctx so the next full step re-reads
        self._fullstep_ctx = None
        self._canonicalize_states()
        idxs = [i for i, p in enumerate(self._params)
                if p.grad_req != "null" and p._data_nd is not None]
        lr_mults, wd_mults, clip = self._mults_key(idxs)
        key = (tuple(idxs), lr_mults, wd_mults, clip, self._zero_sig())
        if self._fused_fn is None or self._fused_key != key:
            self._fused_key = key
            for i in idxs:
                if i not in self._states:
                    self._states[i] = self._shard_state_like(
                        opt.create_state_multi_precision(
                            i, self._params[i].data()),
                        self._params[i]._data_nd._data)
            stacked = self._make_stacked_update(lr_mults, wd_mults, clip)
            # ZeRO gspmd tier: pin outputs to the (data-sharded) state /
            # original weight shardings so the partitioner keeps the
            # layout across the donated update
            constraints = self._zero_constraints(idxs) \
                if self._resolve_zero() is not None else None
            donate = (0, 2) if self._donate else ()

            def stacked_with_sync(*a):
                import jax.numpy as jnp

                nw, ns = stacked(*a)
                if constraints is not None:
                    nw, ns = _apply_constraints(nw, ns, constraints)
                # tiny NON-donated output depending on the update: the
                # throttle's sync leaf (every other output is a donated
                # alias, which block_until_ready can't wait on)
                sync = nw[0].ravel()[0].astype(jnp.float32) if nw \
                    else jnp.float32(0)
                return nw, ns, sync

            self._fused_fn = jax.jit(stacked_with_sync, donate_argnums=donate)
            if telemetry.enabled():
                telemetry.gauge("optimizer_state_bytes_per_device") \
                    .set(self.optimizer_state_bytes_per_device())
        ts, lr, keys = self._step_scalars(idxs)
        weights = tuple(self._params[i]._data_nd._data for i in idxs)
        grads = tuple(raw(self._params[i].grad()) for i in idxs)
        states = tuple(self._states[i] for i in idxs)
        self._perf_program = "trainer_fused_step"
        if telemetry.enabled():
            # captured once per program name (AOT; the jit call cache is
            # untouched) — repeat calls are a dict lookup
            telemetry.perf.capture("trainer_fused_step", self._fused_fn,
                                   weights, grads, states, ts, lr, opt.wd,
                                   opt.rescale_grad, keys)
        new_w, new_s, sync = self._fused_fn(weights, grads, states, ts, lr,
                                            opt.wd, opt.rescale_grad, keys)
        for i, nw, ns in zip(idxs, new_w, new_s):
            self._params[i]._data_nd._data = nw
            # tpulint: disable-next=TPU010 -- keyed by parameter index: bounded by the model's parameter count, not by shapes/configs
            self._states[i] = ns
        # this path always materializes grads (backward wrote them), so
        # run-ahead always holds model-sized buffers: always throttle
        self._throttle(sync)

    # ------------------------------------------------------------------ #
    # public step API
    # ------------------------------------------------------------------ #
    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce + optimizer update; grads rescaled by 1/batch_size.

        With telemetry enabled, each call opens a ``trainer/step`` span
        (sub-spans mark which path ran), advances the telemetry step
        index, and records `trainer_step_seconds` — the HOST-side
        dispatch latency of the step; device execution overlaps
        asynchronously, so end-to-end step time is what the throttle
        sub-span absorbs once run-ahead saturates (no forced sync —
        see docs/observability.md)."""
        if not telemetry.enabled():
            return self._step_impl(batch_size, ignore_stale_grad)
        telemetry.mark_step()
        t0 = time.perf_counter()
        with telemetry.span("trainer/step"):
            self._step_impl(batch_size, ignore_stale_grad)
        dt = time.perf_counter() - t0
        telemetry.histogram("trainer_step_seconds").observe(dt)
        telemetry.counter("trainer_steps_total").inc()
        # roofline/MFU attribution: fold this step's host wall time into
        # the program_* gauges of whichever compiled step path ran (a
        # no-op when that program's costs were never captured)
        telemetry.perf.note_timing(self._perf_program, dt)

    def _step_impl(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        if self._can_fuse():
            pending = self._detect_pending()
            if pending is not None and self._try_full_step(pending):
                return
            self._fused_step()
            return
        if self._can_fuse_packed_compression():
            with telemetry.span("trainer/allreduce_packed"):
                self._allreduce_grads_packed()
            self._fused_step()
            return
        with telemetry.span("trainer/allreduce"):
            self._allreduce_grads()
        with telemetry.span("trainer/update"):
            self._update(ignore_stale_grad)

    # ------------------------------------------------------------------ #
    # single-program step: fwd + vjp + update in ONE donated jit
    # (the dependency-engine composition, engine.py)
    # ------------------------------------------------------------------ #
    def _detect_pending(self):
        """All managed grads must be LazyRefs of ONE unforced pending step."""
        pending = None
        for p in self._params:
            if p.grad_req == "null" or p._data_nd is None:
                continue
            g = p._data_nd._grad
            if g is None or g._lazy is None:
                return None
            pend = getattr(g._lazy.force_fn, "__self__", None)
            if pend is None or (pending is not None and pend is not pending):
                return None
            pending = pend
        if (pending is None or pending.fwd_done or pending.bwd_done
                or not pending.bwd_requested):
            return None
        # non-parameter graph inputs wanting grads (x.attach_grad()) need
        # the staged bwd path — the full-step program differentiates
        # w.r.t. parameters only and would leave their cells unfillable
        for pos in pending.grad_cells:
            if pos >= pending.n_train:
                return None
        return pending

    @telemetry.span("trainer/full_step")
    def _try_full_step(self, pending) -> bool:
        opt = self._optimizer
        block = pending.block
        ctx = self._fullstep_ctx
        idx_of = ctx["idx_of"] if ctx is not None else None
        mults = self._mults_key(idx_of) if idx_of is not None else None
        sig = (id(block), block._cache_version, pending.training,
               pending.arg_tree, pending.head_positions,
               tuple((r.shape, str(r.dtype)) for r in pending.input_raws),
               self._overlap_sig())
        zsig = self._zero_sig()
        stale = (ctx is None or ctx["sig"] != sig or ctx["mults"] != mults
                 or ctx.get("zero_sig") != zsig)
        if self._chain_buf and stale:
            # shape/block/zero-mode change mid-chain: flush before
            # rebuilding so the rebuild sees real (post-chain) weights
            self._flush_chain()
            ctx = self._fullstep_ctx
            stale = (ctx is None or ctx["sig"] != sig
                     or ctx["mults"] != mults
                     or ctx.get("zero_sig") != zsig)
        if stale:
            ctx = self._prepare_full_step(pending, sig)
            if ctx is None:
                return False
            self._fullstep_ctx = ctx
        self._perf_program = ctx.get("perf_program")
        if self._chain_allowed():
            return self._enqueue_chain(ctx, pending)
        import jax.numpy as jnp

        idx_of = ctx["idx_of"]
        prev_num_update = opt.num_update
        lr, keys = self._advance_scalars(idx_of)
        ts = self._materialize_ts(ctx, idx_of)
        states = ctx["states"]
        from .block import _resolve_raws

        try:
            input_raws = self._shard_inputs(pending.input_raws)
            out_leaves, new_aux, grads, new_w, new_s, new_ts, sync = ctx["fn"](
                _resolve_raws(pending.train_raws),
                _resolve_raws(pending.aux_raws), states, pending.rng,
                pending.rng_ctr, input_raws, ts, lr, opt.wd,
                opt.rescale_grad, keys)
        except Exception:
            # Pre-dispatch / trace-time failure (bad input transfer,
            # compile error, synchronous OOM at dispatch): nothing was
            # donated, so full rollback is SOUND — preserve the latest
            # live states, drop the ctx so the next step rebuilds from
            # authoritative host state, and undo the count advance so a
            # retry doesn't run one step ahead.
            try:
                self._sync_states()
            except Exception:
                pass  # states themselves invalidated: rebuild will surface it
            self._fullstep_ctx = None
            for i in idx_of:
                opt._index_update_count[i] -= 1
            opt.num_update = prev_num_update
            raise
        ctx["ts_dev"] = new_ts
        if telemetry.enabled():
            self._count_collective_bytes(ctx, 1)
        pending.fill_from_full_step(out_leaves, new_aux,
                                    grads if self._keep_grads else None)
        for nd, nw in zip(ctx["nds"], new_w):
            nd._data = nw
        ctx["states"] = new_s
        self._states_stale = True  # dict synced lazily (save_states/fallback)
        # ALWAYS bound the dispatch queue: even with keep_grads=False the
        # non-donated forward outputs (e.g. a (B,T,V) logits leaf in the
        # canonical net→loss chain) are held by every in-flight step, so
        # unbounded run-ahead still exhausts HBM.  The sync leaf is a
        # dedicated non-donated scalar — waiting on it never touches the
        # donated buffers.  Byte-budgeted: programs with small outputs
        # never pay the host sync.
        try:
            self._throttle_bytes(sync, ctx["held_bytes"])
        except Exception:
            # ASYNC execution error of an in-flight step surfacing at the
            # throttle's host sync.  The failed program already consumed
            # its donated inputs and its outputs (which params/states now
            # reference) are poisoned — the step chain is UNRECOVERABLE
            # in-process, and whether any given step's update applied is
            # unknowable, so counts are deliberately NOT rolled back.
            # Drop the ctx and re-raise the true device error; recovery
            # is a checkpoint restore (utils.checkpoint / autoresume).
            self._fullstep_ctx = None
            raise
        return True

    def _prepare_full_step(self, pending, sig):
        """Resolve block→trainer param mapping, states, and the jitted fn."""
        opt = self._optimizer
        block = pending.block
        trainable, _aux = block._cached_param_order
        nd2idx = {id(p._data_nd): i for i, p in enumerate(self._params)}
        idx_of = []
        for bp in trainable:
            i = nd2idx.get(id(bp._data_nd))
            if i is None:
                return None  # block param not managed by this trainer
            idx_of.append(i)
        managed = {i for i, p in enumerate(self._params)
                   if p.grad_req != "null" and p._data_nd is not None}
        if set(idx_of) != managed:
            return None  # stale grads would go unnoticed — fall back
        self._sync_states()
        self._canonicalize_states()
        for i in idx_of:
            if i not in self._states:
                self._states[i] = self._shard_state_like(
                    opt.create_state_multi_precision(i, self._params[i].data()),
                    self._params[i]._data_nd._data)
        mults = self._mults_key(idx_of)
        fn = pure = None
        zero_bytes = None
        zero_buckets = None
        zr = self._resolve_zero()
        if zr is not None and zr["tier"] == "explicit":
            built = self._try_build_zero_explicit(pending, mults, zr, idx_of)
            if built is None:
                zr = self._resolve_zero()  # sticky fallback → gspmd
            else:
                fn, pure, zstates, zero_bytes, zero_buckets = built
                for i, st in zip(idx_of, zstates):
                    self._states[i] = st
        if fn is None:
            constraints = self._zero_constraints(idx_of) \
                if zr is not None else None
            fn, pure = self._build_full_step(pending, mults, constraints)
            if zr is not None:
                # gspmd tier: the data-axis gradient sync stays an
                # all-reduce (plan-level estimate for telemetry)
                zero_bytes = {"all-reduce": sum(
                    _aval_bytes(self._params[i]._data_nd._data)
                    for i in idx_of)}
        zsig = None if zr is None else (zr["tier"], zr["axis"], zr["D"])

        held = sum(_aval_bytes(a) for a in pending.out_avals)
        held += sum(_aval_bytes(a) for a in pending.aux_raws)  # new_aux outputs
        if self._keep_grads:
            held += sum(_aval_bytes(self._params[i]._data_nd._data)
                        for i in idx_of)
        if not self._donate:
            # un-donated programs copy weights+states per step and hold
            # the batch inputs too
            held += sum(_aval_bytes(self._params[i]._data_nd._data)
                        for i in idx_of)
            held += sum(_aval_bytes(l)
                        for i in idx_of
                        for l in jax.tree_util.tree_leaves(self._states[i]))
            held += sum(_aval_bytes(a) for a in pending.input_raws)
        # roofline/MFU attribution name of this one-program step path:
        # telemetry.perf keys its program_* gauges on it, and step()
        # feeds each step's wall time back under the same name
        pname = "trainer_full_step"
        if zsig is not None:
            pname += "_zero_bucketed" if zero_buckets is not None \
                else f"_zero_{zsig[0]}"
        ctx = {
            "sig": sig,
            "mults": mults,
            "idx_of": idx_of,
            "nds": [self._params[i]._data_nd for i in idx_of],
            "states": tuple(self._states[i] for i in idx_of),
            "fn": fn,
            "pure": pure,
            "held_bytes": held,
            "zero_sig": zsig,
            "zero_bytes": zero_bytes,
            "zero_buckets": zero_buckets,
            "perf_program": pname,
            "lower_avals": None,
        }
        if telemetry.enabled():
            telemetry.gauge("optimizer_state_bytes_per_device") \
                .set(self.optimizer_state_bytes_per_device())
        if self._capture_hlo or telemetry.enabled():
            try:
                args = self._step_lower_args(pending, ctx)
                # retention-free skeleton for capture_step_costs() —
                # callers that enable telemetry after the build
                ctx["lower_avals"] = self._avalize(args)
                self._capture_step_artifacts(fn, ctx, args)
            except Exception:
                if self._capture_hlo:
                    self.last_step_hlo = None
        return ctx

    def _sync_states(self):
        """Write the fullstep ctx's states back into the per-index dict."""
        ctx = self._fullstep_ctx
        if ctx is not None and self._states_stale:
            self._states.update(zip(ctx["idx_of"], ctx["states"]))
        self._states_stale = False

    def _build_full_step(self, pending, mults, constraints=None):
        import jax.numpy as jnp

        block = pending.block
        raw_fn_jit = block._cached_fn  # jitted; inlines when traced inside jit
        training, arg_tree = pending.training, pending.arg_tree
        stacked = self._make_stacked_update(*mults)
        keep_grads = self._keep_grads
        heads = pending.head_positions  # out-leaf indices seeded with ones
        mesh = self._step_mesh(pending)

        def full(train_raws, aux_raws, states, rng, rng_ctr, input_raws, ts,
                 lr, wd, rescale, keys):
            def f(tr):
                out, new_aux = raw_fn_jit(training, arg_tree, tr, aux_raws,
                                          rng, rng_ctr, *input_raws)
                return out, new_aux

            # forward and backward are traced with the mesh in context so
            # that their Pallas kernels are emitted per shard
            with mosaic.mesh_context(mesh):
                out, pullback, new_aux = jax.vjp(f, tuple(train_raws),
                                                 has_aux=True)
                leaves, tdef = jax.tree_util.tree_flatten(out)
                cts = [jnp.ones_like(l) if heads is None or i in heads
                       else jnp.zeros_like(l) for i, l in enumerate(leaves)]
                cot = jax.tree_util.tree_unflatten(tdef, cts)
                (grads,) = pullback(cot)
            # int32 device counter: exact +1 at any step count; update
            # rules see the f32 view they expect
            new_w, new_s = stacked(train_raws, grads, states,
                                   ts.astype(jnp.float32), lr, wd,
                                   rescale, keys)
            if constraints is not None:
                # ZeRO gspmd tier: keep new states data-sharded and new
                # weights on the original param layout across donation
                new_w, new_s = _apply_constraints(new_w, new_s, constraints)
            out_leaves = jax.tree_util.tree_leaves(out)
            out_grads = tuple(grads) if keep_grads else ()
            # tiny NON-donated output depending on the update: the
            # throttle's sync target (donated aliases can't be waited
            # on, and with keep_grads=False the forward outputs still
            # include logits-sized buffers each in-flight step holds)
            sync = new_w[0].ravel()[0].astype(jnp.float32) if new_w \
                else jnp.float32(0)
            # device-resident step counter: the caller feeds new_ts back
            # instead of re-uploading host counts every step
            new_ts = ts + 1
            return (tuple(out_leaves), new_aux, out_grads, new_w, new_s,
                    new_ts, sync)

        donate = (0, 2, 6) if self._donate else ()
        return jax.jit(full, donate_argnums=donate), full

    # ------------------------------------------------------------------ #
    # ZeRO-1 explicit tier: the whole step (fwd + vjp + sharded update)
    # under a fully-manual shard_map over the data axis, so the gradient
    # sync is a REAL reduce-scatter and the updated params come back
    # with one all-gather (gluon/zero.py module docstring)
    # ------------------------------------------------------------------ #
    def _zero_fallback_gspmd(self, reason: str):
        """Sticky fallback: later _zero_sig()/_resolve_zero() calls keep
        answering 'gspmd', so the fullstep ctx stays cache-stable."""
        self._zero_collectives = "gspmd"
        self._warn_zero_once(
            "explicit_fallback",
            f"Trainer ZeRO-1: explicit reduce-scatter tier unavailable "
            f"({reason}) — using the GSPMD sharding tier")

    def _zero_overlap_fail(self, reason: str):
        """Sticky fallback one level SHALLOWER than gspmd: the bucketed
        (overlapped) exchange failed, keep the PR-4 monolithic explicit
        tier — later _overlap_sig() calls answer None, so the fullstep
        ctx stays cache-stable."""
        self._zero_overlap_broken = True
        self._warn_zero_once(
            "overlap_fallback",
            f"Trainer ZeRO-1: overlapped bucketed gradient sync "
            f"unavailable ({reason}) — using the monolithic per-param "
            f"exchange")

    def _zero_overlap_plan(self, zstates, idx_of, D):
        """Bucket plan for the overlapped exchange, or None when off.
        Buckets group only same-(dtype, multi-precision) params so the
        packed buffers never promote a dtype (bit-parity)."""
        cap = self._overlap_sig()
        if cap is None:
            return None
        from ..parallel import overlap as overlap_mod

        try:
            npads, items, keys = [], [], []
            for z, i in zip(zstates, idx_of):
                w = self._params[i]._data_nd._data
                npads.append(z.meta.npad)
                items.append(_aval_bytes(w) // max(1, w.size) if w.size else 1)
                keys.append((str(z.meta.w_dtype), z.meta.mp))
            buckets = overlap_mod.partition_buckets(npads, items, keys, D, cap)
        except Exception as e:
            self._zero_overlap_fail(
                f"bucket partitioning failed: {type(e).__name__}: "
                f"{str(e)[:200]}")
            return None
        if telemetry.enabled():
            h = telemetry.histogram("grad_bucket_bytes")
            for b in buckets:
                h.observe(float(b.nbytes))
            # plan-level estimate: the last bucket in backward order is
            # the one with no backward compute left to hide behind
            total = sum(b.nbytes for b in buckets)
            if total:
                telemetry.gauge("overlap_fraction",
                                labels={"source": "plan"}) \
                    .set(1.0 - buckets[-1].nbytes / total)
        return buckets

    def _count_collective_bytes(self, ctx, k: int):
        zb = ctx.get("zero_bytes")
        if not zb:
            return
        for op, b in zb.items():
            telemetry.counter("collective_bytes_total",
                              labels={"op": op}).inc(int(b) * k)

    def _step_lower_args(self, pending, ctx):
        """The argument tuple the full-step program lowers against —
        shared by the HLO-text capture (tests/dryrun gates) and the
        telemetry.perf cost/memory capture."""
        import jax.numpy as jnp

        from .block import _resolve_raws

        opt = self._optimizer
        # only shapes/dtypes matter for lowering: the update counts
        # may not exist yet at prepare time, so feed a zero vector
        return (_resolve_raws(pending.train_raws),
                _resolve_raws(pending.aux_raws), ctx["states"],
                pending.rng, pending.rng_ctr,
                tuple(self._shard_inputs(pending.input_raws)),
                jnp.zeros((len(ctx["idx_of"]),), jnp.int32),
                float(opt.learning_rate), float(opt.wd),
                float(opt.rescale_grad), None)

    @staticmethod
    def _avalize(args):
        """Shape/dtype/sharding skeleton of a lowering-argument tree —
        retention-free (holds no device buffers), so the fullstep ctx
        can keep it for a LATER AOT capture (bench's post-loop roofline
        phase) without pinning forward-output-sized arrays."""
        def to_aval(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                sh = getattr(x, "sharding", None)
                try:
                    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
                except Exception:
                    return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        return jax.tree_util.tree_map(to_aval, args)

    def _capture_step_artifacts(self, fn, ctx, args):
        """AOT lower+compile of the full-step program (the regular jit
        call cache is untouched) feeding every consumer of the ONE
        compile: compiled-HLO + lowered-StableHLO text when
        `_capture_hlo`, telemetry.perf cost/memory analysis (and, when
        its text capture is on, the hlolint contract-gate feed) when
        telemetry is enabled."""
        try:
            lowered = fn.lower(*args)
            compiled = lowered.compile()
        except Exception:
            if self._capture_hlo:
                self.last_step_hlo = None
                self.last_step_stablehlo = None
            return
        if self._capture_hlo:
            try:
                self.last_step_hlo = compiled.as_text()
            except Exception:
                self.last_step_hlo = None
            try:
                self.last_step_stablehlo = lowered.as_text()
            except Exception:
                self.last_step_stablehlo = None
        if telemetry.enabled():
            telemetry.perf.capture_compiled(ctx["perf_program"], compiled,
                                            sig=ctx["sig"], lowered=lowered)

    def _lower_step_hlo(self, fn, pending, ctx):
        """Compiled-HLO text of the fused step (tests/dryrun gates:
        reduce-scatter > 0, per-axis all-reduce attribution)."""
        try:
            args = self._step_lower_args(pending, ctx)
            return fn.lower(*args).compile().as_text()
        except Exception:
            return None

    def capture_step_costs(self):
        """Re-run the telemetry.perf cost/memory capture for the CURRENT
        full-step program from the retention-free aval skeleton stored
        at prepare time — for callers (bench.py's post-loop roofline
        phase) that enable telemetry only after the program was built.
        Returns the program name, or None (no ctx / telemetry off /
        analysis unavailable)."""
        ctx = self._fullstep_ctx
        if ctx is None or not telemetry.enabled():
            return None
        avals = ctx.get("lower_avals")
        if avals is None:
            return None
        try:
            compiled = ctx["fn"].lower(*avals).compile()
        except Exception:
            return None
        pc = telemetry.perf.capture_compiled(ctx["perf_program"], compiled,
                                             sig=ctx["sig"])
        return None if pc is None else ctx["perf_program"]

    def _try_build_zero_explicit(self, pending, mults, zr, idx_of):
        """Build the explicit-tier step, or None (sticky gspmd fallback)
        when this pending/mesh/optimizer combination can't take it."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from . import zero as zero_mod
        from .block import _resolve_raws

        mesh, axis, D = zr["mesh"], zr["axis"], zr["D"]
        opt = self._optimizer
        batch = None
        for r in pending.input_raws:
            if hasattr(r, "shape") and getattr(r, "ndim", 0) >= 1:
                batch = int(r.shape[0])
                break
        if batch is None or batch % D != 0:
            self._zero_fallback_gspmd(
                f"leading batch dim {batch} is not divisible by the "
                f"data axis ({D})")
            return None

        def on_data(r):
            sh = getattr(r, "sharding", None)
            return isinstance(sh, NamedSharding) and any(
                s == axis or (isinstance(s, tuple) and axis in s)
                for s in sh.spec)

        train_raws = _resolve_raws(pending.train_raws)
        aux_raws = _resolve_raws(pending.aux_raws)
        if any(on_data(r) for r in train_raws) \
                or any(on_data(r) for r in aux_raws):
            self._zero_fallback_gspmd(
                "some parameters are already sharded on the data axis")
            return None
        input_specs = []
        for r in pending.input_raws:
            if hasattr(r, "shape") and getattr(r, "ndim", 0) >= 1 \
                    and r.shape[0] == batch:
                input_specs.append(P(axis, *([None] * (r.ndim - 1))))
            elif on_data(r):
                self._zero_fallback_gspmd(
                    "a non-batch input is sharded on the data axis")
                return None
            else:
                input_specs.append(P())
        out_batch = tuple(
            getattr(a, "ndim", 0) >= 1 and tuple(a.shape)[0] == batch
            for a in pending.out_avals)
        try:
            zstates = []
            for i in idx_of:
                w = self._params[i]._data_nd._data
                mp = bool(opt.multi_precision
                          and w.dtype in (jnp.float16, jnp.bfloat16))
                zstates.append(
                    zero_mod.adopt(self._states[i], w, D, mesh, axis, mp))
            zstates = tuple(zstates)
            zinfo = {"mesh": mesh, "axis": axis, "D": D, "zstates": zstates,
                     "out_batch": out_batch,
                     "input_specs": tuple(input_specs), "buckets": None}

            def build(zinfo):
                fn, pure = self._build_full_step_zero(pending, mults, zinfo)
                # trace-level validation BEFORE anything can be donated:
                # the global output shapes must match the replicated
                # path's (catches batch-flag mis-inference and rules/ops
                # that don't trace under the manual mesh)
                outs = jax.eval_shape(
                    pure, tuple(train_raws), tuple(aux_raws), zstates,
                    pending.rng, pending.rng_ctr, tuple(pending.input_raws),
                    jnp.zeros((len(idx_of),), jnp.int32),
                    jnp.float32(0), jnp.float32(0), jnp.float32(1), None)
                got = [tuple(a.shape) for a in outs[0]]
                want = [tuple(a.shape) for a in pending.out_avals]
                if got != want:
                    raise zero_mod.ZeroIncompatible(
                        f"output shapes {got} != replicated {want}")
                return fn, pure

            buckets = self._zero_overlap_plan(zstates, idx_of, D)
            if buckets is not None:
                try:
                    zinfo["buckets"] = buckets
                    fn, pure = build(zinfo)
                except Exception as e:
                    # bucketed segmentation failed: sticky fallback to
                    # the PR-4 monolithic exchange, NOT all the way to
                    # gspmd — the explicit tier itself is fine
                    self._zero_overlap_fail(
                        f"bucketed build failed: {type(e).__name__}: "
                        f"{str(e)[:200]}")
                    zinfo["buckets"] = buckets = None
                    fn, pure = build(zinfo)
            else:
                fn, pure = build(zinfo)
        except Exception as e:
            self._zero_fallback_gspmd(
                f"explicit-tier build failed: {type(e).__name__}: "
                f"{str(e)[:300]}")
            return None
        rs_bytes = ag_bytes = 0
        for z, i in zip(zstates, idx_of):
            w = self._params[i]._data_nd._data
            item = _aval_bytes(w) // max(1, w.size) if w.size else 1
            rs_bytes += z.meta.npad * item
            ag_bytes += z.meta.npad * item
            if self._keep_grads:
                ag_bytes += z.meta.npad * item
        zero_bytes = {"reduce-scatter": rs_bytes, "all-gather": ag_bytes}
        return fn, pure, zstates, zero_bytes, buckets

    def _build_full_step_zero(self, pending, mults, zinfo):
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..parallel import overlap as overlap_mod
        from jax import shard_map
        from . import zero as zero_mod

        mesh, axis, D = zinfo["mesh"], zinfo["axis"], zinfo["D"]
        buckets = zinfo.get("buckets")  # None = monolithic per-param sync
        metas = tuple(z.meta for z in zinfo["zstates"])
        out_batch = zinfo["out_batch"]
        block = pending.block
        raw_fn_jit = block._cached_fn
        training, arg_tree = pending.training, pending.arg_tree
        lr_mults, wd_mults, clip = mults
        opt = self._optimizer
        keep_grads = self._keep_grads
        heads = pending.head_positions
        inv_d = 1.0 / D
        n_train = len(metas)

        def body(train_raws, aux_raws, states, rng, rng_ctr, input_raws, ts,
                 lr, wd, rescale, keys):
            def f(tr):
                out, new_aux = raw_fn_jit(training, arg_tree, tr, aux_raws,
                                          rng, rng_ctr, *input_raws)
                return out, new_aux

            out, pullback, new_aux = jax.vjp(f, tuple(train_raws),
                                             has_aux=True)
            leaves, tdef = jax.tree_util.tree_flatten(out)
            cts = []
            for i, l in enumerate(leaves):
                if heads is not None and i not in heads:
                    cts.append(jnp.zeros_like(l))
                elif out_batch[i]:
                    # batch-sharded head: local ones == the global ones
                    # cotangent restricted to this shard — exact
                    cts.append(jnp.ones_like(l))
                else:
                    # reduced (scalar) head under the batch-MEAN loss
                    # convention: global mean = mean of per-shard means,
                    # so each shard contributes 1/D of the cotangent
                    cts.append(jnp.full_like(l, inv_d))
            (grads,) = pullback(jax.tree_util.tree_unflatten(tdef, cts))
            tsf = ts.astype(jnp.float32)
            shard_idx = lax.axis_index(axis)
            # -- exchange: sum+shard every gradient ------------------- #
            g_pad = []
            for j in range(n_train):
                g = grads[j].reshape(-1)
                if metas[j].npad != metas[j].n:
                    g = jnp.pad(g, (0, metas[j].npad - metas[j].n))
                g_pad.append(g)
            g_shard = [None] * n_train
            if buckets is None:
                # THE ZeRO-1 exchange: one psum_scatter per parameter
                for j in range(n_train):
                    g_shard[j] = lax.psum_scatter(g_pad[j], axis, tiled=True)
            else:
                # overlapped tier: one psum_scatter per BUCKET, issued
                # in backward order — bucket 0's cotangents are complete
                # while earlier layers are still backpropagating, so the
                # latency-hiding scheduler floats each collective over
                # the remaining backward matmuls.  The interleaved pack
                # keeps every shard bit-identical to the per-param ops
                # (parallel/overlap.py module docstring).
                for b in buckets:
                    packed = overlap_mod.pack_bucket(
                        [g_pad[j] for j in b.idxs], D)
                    sh = lax.psum_scatter(packed, axis, tiled=True)
                    for j, seg in zip(b.idxs,
                                      overlap_mod.unpack_shards(sh, b.chunks)):
                        g_shard[j] = seg
            # -- shard-local optimizer update ------------------------- #
            new_s, nw_locs = [], []
            for j in range(n_train):
                m = metas[j]
                w = train_raws[j]
                st = states[j]
                if m.mp:
                    # fp32 master (canonical leaf 0) doubles as the
                    # local weight — no extra copy
                    w_loc = st.leaves[0].astype(w.dtype)
                else:
                    # slice this device's weight shard out of the
                    # replicated parameter (pad keeps it aligned with
                    # the reduce-scattered gradient)
                    w_pad = w.reshape(-1)
                    if m.npad != m.n:
                        w_pad = jnp.pad(w_pad, (0, m.npad - m.n))
                    chunk = m.npad // D
                    w_loc = lax.dynamic_slice(w_pad, (shard_idx * chunk,),
                                              (chunk,))
                inner = jax.tree_util.tree_unflatten(m.treedef, st.leaves)
                nw_l, ns = opt.pure_update_multi_precision(
                    w_loc, g_shard[j], inner, tsf[j], lr * lr_mults[j],
                    wd * wd_mults[j], rescale, clip, None)
                ns_leaves = tuple(jax.tree_util.tree_leaves(ns))
                new_s.append(zero_mod.Zero1State(ns_leaves, m))
                nw_locs.append(nw_l)

            # -- gather: rebuild full params (and grads) -------------- #
            def finish_w(j, w_full):
                m = metas[j]
                wf = w_full[:m.n].reshape(m.w_shape)
                if wf.dtype != train_raws[j].dtype:
                    wf = wf.astype(train_raws[j].dtype)
                return wf

            def finish_g(j, g_full):
                m = metas[j]
                return g_full[:m.n].reshape(m.w_shape).astype(grads[j].dtype)

            new_w = [None] * n_train
            out_grads = [None] * n_train if keep_grads else []
            if buckets is None:
                for j in range(n_train):
                    wf = lax.all_gather(nw_locs[j], axis, tiled=True, axis=0)
                    new_w[j] = finish_w(j, wf)
                    if keep_grads:
                        gf = lax.all_gather(g_shard[j], axis, tiled=True,
                                            axis=0)
                        out_grads[j] = finish_g(j, gf)
            else:
                # symmetric bucketed return trip: one all_gather per
                # bucket of updated weight shards (and grad shards)
                for b in buckets:
                    wt = lax.all_gather(
                        overlap_mod.pack_shards([nw_locs[j] for j in b.idxs]),
                        axis, tiled=True, axis=0)
                    for j, wp in zip(b.idxs, overlap_mod.unpack_gathered(
                            wt, b.chunks, D)):
                        new_w[j] = finish_w(j, wp)
                    if keep_grads:
                        gt = lax.all_gather(
                            overlap_mod.pack_shards(
                                [g_shard[j] for j in b.idxs]),
                            axis, tiled=True, axis=0)
                        for j, gp in zip(b.idxs, overlap_mod.unpack_gathered(
                                gt, b.chunks, D)):
                            out_grads[j] = finish_g(j, gp)
            out_leaves = list(leaves)
            for i, l in enumerate(out_leaves):
                if not out_batch[i] and jnp.issubdtype(l.dtype, jnp.floating):
                    # reduced heads/outputs: report the global (batch-
                    # mean) value, not this shard's local reduction
                    out_leaves[i] = lax.pmean(l, axis)
            new_aux = jax.tree_util.tree_map(
                lambda a: lax.pmean(a, axis)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, new_aux)
            sync = new_w[0].ravel()[0].astype(jnp.float32) if new_w \
                else jnp.float32(0)
            new_ts = ts + 1
            return (tuple(out_leaves), new_aux, tuple(out_grads),
                    tuple(new_w), tuple(new_s), new_ts, sync)

        state_specs = tuple(zero_mod.spec_state(m, axis) for m in metas)
        in_specs = (
            tuple(P() for _ in range(n_train)),          # train_raws
            P(),                                          # aux_raws
            state_specs,                                  # Zero1States
            P(), P(),                                     # rng, rng_ctr
            zinfo["input_specs"],                         # batch inputs
            P(), P(), P(), P(), P(),                      # ts/lr/wd/rescale/keys
        )
        out_specs = (
            tuple(P(axis, *([None] * (max(0, a.ndim - 1)))) if out_batch[i]
                  else P() for i, a in enumerate(pending.out_avals)),
            P(),                                          # new_aux
            tuple(P() for _ in range(n_train)) if keep_grads else (),
            tuple(P() for _ in range(n_train)),           # new_w
            state_specs,                                  # new states
            P(), P(),                                     # new_ts, sync
        )
        shmapped = shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

        def full_zero(*a):
            return shmapped(*a)

        donate = (0, 2, 6) if self._donate else ()
        return jax.jit(full_zero, donate_argnums=donate), shmapped

    def _allreduce_grads_packed(self):
        """ONE compressed exchange for the whole model: concat all grads
        flat → 2-bit pack (error feedback on the flat buffer) → single
        process_allgather → decompress+sum → scatter back into the grad
        buffers.  Elementwise quantization makes this bit-identical to
        the per-key path, minus ~#params DCN round-trips."""
        import jax.numpy as jnp
        from jax.experimental import multihost_utils

        comp = self._kvstore._compression
        ps = [p for p in self._params
              if p.grad_req != "null" and p._data_nd is not None]
        grads = [raw(p.grad()) for p in ps]
        flat = jnp.concatenate([g.reshape(-1).astype(jnp.float32)
                                for g in grads])
        # residual key includes the layout: if the managed set changes
        # (freeze/unfreeze), a fresh residual starts instead of applying
        # old error feedback at the wrong offsets
        rkey = ("__trainer_packed__",
                tuple(self._param2idx[p.name] for p in ps), int(flat.size))
        packed = comp.compress_packed(rkey, flat)
        gathered = multihost_utils.process_allgather(packed)
        summed = sum(comp.decompress(gathered[r], flat.shape)
                     for r in range(gathered.shape[0]))
        off = 0
        for p, g in zip(ps, grads):
            n = g.size
            p._data_nd._grad._data = summed[off:off + n] \
                .reshape(g.shape).astype(g.dtype)
            off += n

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        if self._has_global_params():
            # Grads of global (shard_params) arrays are already reduced
            # in-step by GSPMD; the per-key kvstore exchange would crash
            # on non-addressable arrays (and double-reduce otherwise) —
            # skip it.  Guarded HERE (not in step()) so the public
            # gradient-accumulation pattern allreduce_grads()+update()
            # gets the same protection.
            if not self._dist_spmd_ready():
                # mixed global/local: the local params' grads DO need
                # the kvstore exchange, which global arrays cannot ride
                # — refuse loudly rather than silently diverge replicas
                raise RuntimeError(
                    "Trainer: params are a MIX of multi-process "
                    "global (shard_params) and process-local arrays — "
                    "global grads reduce in-step but local ones need "
                    "the kvstore exchange, and no single path serves "
                    "both. Apply shard_params to the WHOLE block.")
            skipped = [
                s for s, active in (
                    ("gradient compression",
                     self._kvstore._compression is not None),
                    ("the kvstore server-side optimizer (set_optimizer)",
                     self._kvstore._updater is not None),
                ) if active]
            if skipped and not getattr(self, "_warned_global_nocomp", False):
                import warnings

                self._warned_global_nocomp = True
                warnings.warn(
                    f"Trainer: {' and '.join(skipped)} inactive for "
                    "multi-process global (shard_params) arrays — the "
                    "reduction happens inside the SPMD step and the "
                    "Trainer's own optimizer applies the update.",
                    stacklevel=2)
            return
        for i, p in enumerate(self._params):
            if p.grad_req != "null" and p._data_nd is not None:
                g = p.grad()
                self._kvstore.push(i, [g])
                out = [g]
                self._kvstore.pull(i, out)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        self._sync_states()
        self._fullstep_ctx = None  # eager updates replace ctx-held states
        self._canonicalize_states()  # per-key rules need full-shape leaves
        for i, p in enumerate(self._params):
            if p.grad_req == "null" or p._data_nd is None:
                continue
            if i not in self._states:
                self._states[i] = self._shard_state_like(
                    self._optimizer.create_state_multi_precision(i, p.data()),
                    p._data_nd._data)
            self._states[i] = self._optimizer.update_multi_precision(
                i, p.data(), p.grad(), self._states[i])
            # grads are left in place (reference semantics): with
            # grad_req='write' the next backward overwrites them anyway

    def save_states(self, fname):
        import pickle

        self._flush_chain()
        self._sync_states()
        with open(fname, "wb") as f:
            # host_states fetches leaf-at-a-time and converts any ZeRO-
            # sharded layout to canonical full shapes — a sharded state
            # is never materialized as a full device replica to be saved
            states_host = self.host_states()
            pickle.dump({"states": states_host,
                         "num_update": self._optimizer.num_update,
                         "index_update_count": self._optimizer._index_update_count},
                        f)

    def load_states(self, fname):
        import pickle

        self._flush_chain()
        with open(fname, "rb") as f:
            blob = pickle.load(f)
        self._states = {k: _to_device(v) for k, v in blob["states"].items()}
        self._optimizer.num_update = blob["num_update"]
        self._optimizer._index_update_count = blob["index_update_count"]
        self._fullstep_ctx = None  # loaded states invalidate the cached tuple
        self._states_stale = False


def _to_device(v):
    import jax
    import numpy as onp

    return jax.tree_util.tree_map(
        lambda x: jax.numpy.asarray(x) if isinstance(x, onp.ndarray) else x, v)
