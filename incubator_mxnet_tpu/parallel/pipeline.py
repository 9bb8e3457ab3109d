"""Pipeline parallelism — GPipe and 1F1B microbatch schedules over the
`pipe` axis.

ABSENT in the reference (SURVEY.md §2.4: "build: shard_map stage mesh +
microbatch lax.scan").  Implementation: every device holds ONE stage's
params; a loop over ticks keeps all stages busy; activations move
stage→stage with a single ppermute per tick (ICI neighbor transfer).

Two schedules:

- `pipeline_apply` (GPipe): forward-only schedule; under jax.grad XLA
  differentiates through the loop, replaying ticks in reverse AFTER all
  forward ticks — activation memory O(M · per-tick residuals), reduced
  to O(M · activation) by `remat_stage`.
- `pipeline_train_1f1b`: the REAL 1F1B tick order — each stage
  alternates one-forward/one-backward in steady state, holding at most
  `n_stages` microbatches of residuals in a circular buffer regardless
  of M.  This is the schedule, not an emulation: backward of microbatch
  m runs while later microbatches are still going forward.

Collective safety (both schedules): every branch predicate (`active`,
fwd/bwd tick parity) is a function of (tick, pipe index) ONLY, so it is
uniform across the members of any collective group that does not span
the `pipe` axis — in-stage TP/DP collectives (psum over 'model'/'data')
therefore cannot diverge across their group and `skip_inactive`/1F1B
branching is deadlock-free with them.  A collective spanning `pipe`
inside a stage remains unsupported (members would sit in different
branches).

GRADIENT correctness with in-stage collectives is a separate property:
only `pipeline_train_1f1b` provides it (it runs under vma checking,
which transposes collectives correctly).  `pipeline_apply` runs
check_vma=False, where `jax.grad` THROUGH a psum-bearing stage scales
gradients by the axis size — use it for forward/inference composition
and collective-free training stages; TRAIN PP×TP pipelines with
`pipeline_train_1f1b`.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry

__all__ = ["pipeline_forward", "pipeline_apply", "pipeline_train_1f1b"]


def _vma_of(z) -> set:
    """Varying-manual-axes of ``z`` under shard_map."""
    return set(jax.typeof(z).vma)


def _record_schedule(schedule: str, n_stages: int, n_micro: int) -> None:
    """Publish the schedule's analytic shape as gauges (host ints only).

    The bubble is a property of the tick grid — GPipe: ``n-1`` idle
    ticks per stage of ``M+n-1``; 1F1B: ``2(n-1)`` of ``2(M+n-1)`` —
    so the FRACTION is exact without timing anything on device.
    Device-side per-tick times belong to the XLA trace
    (profiler.device_op_table); multiplying the fraction into a
    host-measured step time is done where a step clock exists
    (GluonPipeline.train_step)."""
    lab = {"schedule": schedule}
    idle_per_stage = (n_stages - 1) * (2 if schedule == "1f1b" else 1)
    total_ticks = (n_micro + n_stages - 1) * (2 if schedule == "1f1b" else 1)
    telemetry.gauge("pipeline_stages", labels=lab).set(n_stages)
    telemetry.gauge("pipeline_microbatches", labels=lab).set(n_micro)
    telemetry.gauge("pipeline_bubble_ticks", labels=lab).set(idle_per_stage)
    telemetry.gauge("pipeline_bubble_fraction", labels=lab).set(
        idle_per_stage / max(total_ticks, 1))


def pipeline_forward(stage_fn: Callable, stage_params, x_microbatches,
                     axis_name: str = "pipe", skip_inactive: bool = False,
                     remat_stage: bool = False):
    """Inside-shard_map GPipe forward.

    stage_fn(params, x) -> y : one stage's compute (same signature all
    stages — heterogeneous stages dispatch on params).
    stage_params: this device's stage params (pytree).
    x_microbatches: (M, mb, ...) — the M microbatches, REPLICATED input;
    stage 0 consumes them, later stages ignore and take the ring input.
    Returns (M, mb, ...) outputs valid on the LAST stage.

    skip_inactive: wrap the stage compute in `lax.cond(active, ...)` so
    bubble ticks skip the FLOPs instead of computing-and-masking (the
    r1 review's PP-efficiency gap).  Safe with in-stage collectives
    whose group does NOT span the pipe axis (TP/DP psum): `active`
    depends only on (tick, pipe index), so all members of such a group
    take the same branch (see module docstring; proven by the PP×TP
    composed test).  Unsafe only for collectives spanning `pipe`.

    remat_stage: recompute the stage in the backward instead of saving
    its internals per tick.  Under jax.grad the scan otherwise stores
    every tick's stage residuals (GPipe's O(M) activation memory —
    the problem 1F1B schedules exist to fix); with remat only the
    per-tick INPUT survives, so activation memory drops from
    O(M · stage_residuals) to O(M · activation) + one in-flight
    recompute — the 1F1B memory profile with XLA's reverse pipeline.
    """
    if remat_stage:
        stage_fn = jax.checkpoint(stage_fn)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    total = M + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    mb_shape = x_microbatches.shape[1:]
    state = jnp.zeros(mb_shape, x_microbatches.dtype)  # activation in flight
    outputs = jnp.zeros((M,) + mb_shape, x_microbatches.dtype)

    def tick(t, carry):
        state, outputs = carry
        # stage 0 injects microbatch t (if any remain); others take ring input
        inject = x_microbatches[jnp.minimum(t, M - 1)]
        x_in = jnp.where(idx == 0, inject, state)
        active = jnp.logical_and(t - idx >= 0, t - idx < M)
        if skip_inactive:
            y = lax.cond(active,
                         lambda xi: stage_fn(stage_params, xi),
                         lambda xi: state, x_in)
        else:
            y = stage_fn(stage_params, x_in)
            y = jnp.where(active, y, state)
        # last stage writes its finished microbatch t-(n-1)
        out_slot = t - (n - 1)
        is_last = idx == n - 1
        write = jnp.logical_and(is_last, jnp.logical_and(out_slot >= 0, out_slot < M))
        outputs = lax.cond(
            write,
            lambda o: lax.dynamic_update_index_in_dim(o, y, jnp.maximum(out_slot, 0), 0),
            lambda o: o,
            outputs)
        # rotate activations to the next stage
        state = lax.ppermute(y, axis_name, perm)
        return state, outputs

    _, outputs = lax.fori_loop(0, total, tick, (state, outputs))
    # broadcast final outputs from the last stage to all (psum of masked)
    mask = (idx == n - 1).astype(outputs.dtype)
    return lax.psum(outputs * mask, axis_name)


def pipeline_apply(stage_fn: Callable, all_stage_params, x, mesh: Mesh,
                   num_microbatches: int, axis_name: str = "pipe",
                   skip_inactive: bool = False, remat_stage: bool = False):
    """Top-level: split batch into microbatches, shard stage params over
    `axis_name` (leading axis = stage), run the GPipe schedule.

    all_stage_params: pytree whose leaves have leading dim = n_stages.
    x: (B, ...) global batch.

    NOTE: runs check_vma=False — `jax.grad` through a stage containing
    a psum over another mesh axis mis-scales gradients by that axis
    size (module docstring).  For PP×TP TRAINING use
    `pipeline_train_1f1b`.
    """
    from jax import shard_map

    B = x.shape[0]
    if B % num_microbatches:
        raise ValueError(
            f"pipeline_apply: batch {B} not divisible by "
            f"num_microbatches {num_microbatches}")
    mb = B // num_microbatches
    xm = x.reshape((num_microbatches, mb) + x.shape[1:])

    def inner(params, xmb):
        local = jax.tree_util.tree_map(lambda p: p[0], params)  # this stage's slice
        return pipeline_forward(stage_fn, local, xmb, axis_name,
                                skip_inactive=skip_inactive,
                                remat_stage=remat_stage)

    param_spec = jax.tree_util.tree_map(lambda _: P(axis_name), all_stage_params)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(param_spec, P()), out_specs=P(), check_vma=False)
    if telemetry.enabled():
        _record_schedule("gpipe", mesh.shape[axis_name], num_microbatches)
        with telemetry.span("pipeline/gpipe_apply"):
            out = fn(all_stage_params, xm)
    else:
        out = fn(all_stage_params, xm)
    return out.reshape((B,) + out.shape[2:])


# --------------------------------------------------------------------- #
# true 1F1B (PipeDream-flush) schedule
# --------------------------------------------------------------------- #
def _1f1b_device(stage_fn, loss_fn, params, xm, targets, axis_name,
                 n_static, recompute_stage=True, loss_params=(),
                 want_dx=False):
    """One device's 1F1B train step (inside shard_map over `axis_name`).

    Tick times (n stages, idx = this stage, m = microbatch):
      forward(m)  at t = idx + 2m
      backward(m) at t = 2n − 1 − idx + 2m
    — opposite parities, so each tick a stage does one fwd OR one bwd.
    Residency of microbatch m at stage idx = 2(n−idx)−1 ticks →
    ≤ n microbatches in flight: state lives in a circular buffer of
    n slots (fwd(m+n) lands strictly after bwd(m): t gap = 2·idx+1 > 0),
    the 1F1B memory bound GPipe lacks.

    recompute_stage=True (default): the buffer holds only each in-flight
    microbatch's stage INPUT; the backward tick re-runs the stage vjp —
    O(n·activation) memory, one extra stage forward per microbatch
    (XLA's vjp residuals would otherwise duplicate the weight arrays
    into every slot; measured in docs/pipeline_1f1b.md).
    recompute_stage=False: full residuals are buffered — standard
    fwd+bwd FLOP budget, O(n·residuals) memory.

    loss_params: optional replicated pytree of TRAINABLE loss-side
    parameters (e.g. an LM head applied inside loss_fn(y, t, lp)) —
    their summed grads are returned alongside the stage grads, enabling
    full-model pipelines where embedding/head live outside the stages.

    Returns (loss_sum on the last stage, stage param grads,
    loss_params grads, per-microbatch input cotangents dx (M, mb, ...)
    valid on stage 0).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    M = xm.shape[0]
    mb_shape = xm.shape[1:]
    dt = xm.dtype
    total = 2 * (M + n_static - 1)
    fwd_perm = [(i, (i + 1) % n_static) for i in range(n_static)]
    bwd_perm = [(i, (i - 1) % n_static) for i in range(n_static)]

    # varying-manual-axes discipline: under shard_map with vma checking
    # ON (which is what makes the AD of in-stage collectives CORRECT —
    # with check_vma=False psum transposes to psum and grads come out
    # axis_size× too large), every cond branch pair must agree in vma,
    # and cotangents must carry exactly the vma of the value they are
    # cotangents OF (a psum-ending stage yields outputs invariant in the
    # TP axis).  We track: activation/ring vma (fixpoint of the stage's
    # output vma), per-residual-leaf vma, and per-param-grad vma.
    def _vma(z):
        return _vma_of(z)

    def cast_to(z, target):
        need = tuple(a for a in sorted(set(target) - _vma(z)))
        return lax.pcast(z, need, to="varying") if need else z

    act_vma = {axis_name}
    y_t = pull_t = None
    for _ in range(3):  # fixpoint: output vma feeds back as input vma
        y_t, pull_t = jax.vjp(stage_fn, params,
                              cast_to(jnp.zeros(mb_shape, dt), act_vma))
        new_vma = act_vma | _vma(y_t)
        # tpulint: disable-next=TPU004 -- vma sets are trace-time host metadata (axis-name frozensets), not tracer values
        if new_vma == act_vma:
            break
        act_vma = new_vma
    xm = cast_to(xm, act_vma)
    targets = cast_to(targets, act_vma)
    # loss params must be VARYING over the pipe axis before use inside
    # the loop: an unvarying operand's cotangent would trigger an
    # automatic psum over `pipe` INSIDE the cond branches — exactly the
    # forbidden pipe-spanning collective.  Promote here; the cross-stage
    # reduction happens outside the loop (caller's psum of the masked
    # accumulator).
    loss_params = jax.tree_util.tree_map(
        lambda p: cast_to(p, act_vma), loss_params)

    if recompute_stage:
        # buffer only the stage inputs; bwd re-derives residuals
        res_leaves_t = [cast_to(jnp.zeros(mb_shape, dt), act_vma)]
        res_treedef = None
    else:
        res_leaves_t, res_treedef = jax.tree_util.tree_flatten(pull_t)
    res_buf0 = tuple(cast_to(jnp.zeros((n_static,) + l.shape, l.dtype),
                             _vma(l) | {axis_name})
                     for l in res_leaves_t)
    # y buffer only needed when residuals are stored (recompute mode
    # re-derives y at the bwd tick)
    y_buf0 = cast_to(jnp.zeros((1,) if recompute_stage
                               else (n_static,) + mb_shape, dt), act_vma)
    dacc0 = jax.tree_util.tree_map(
        lambda p: cast_to(jnp.zeros(p.shape, jnp.float32),
                          _vma(p) | {axis_name}), params)
    dlp0 = jax.tree_util.tree_map(
        lambda p: cast_to(jnp.zeros(p.shape, jnp.float32), act_vma),
        loss_params)
    # dx collection costs a full-batch buffer + a pipe psum — only pay
    # for it when the caller asked (want_dx)
    dx_buf0 = cast_to(jnp.zeros((M,) + mb_shape if want_dx else (1,),
                                jnp.float32), act_vma)

    def pv(z):  # activations/scalars promote to the ring vma
        return cast_to(z, act_vma)

    def tick(t, carry):
        (ring_f, ring_b, res_buf, y_buf, dacc, dlp, dx_buf,
         loss_sum) = carry
        tf = t - idx
        m_f = tf // 2
        do_f = jnp.logical_and(jnp.logical_and(tf >= 0, tf % 2 == 0), m_f < M)
        tb = t - (2 * n - 1 - idx)
        m_b = tb // 2
        do_b = jnp.logical_and(jnp.logical_and(tb >= 0, tb % 2 == 0), m_b < M)

        def fwd_branch(op):
            ring_f, res_buf, y_buf = op
            mclip = jnp.clip(m_f, 0, M - 1)
            x_in = jnp.where(idx == 0, xm[mclip], ring_f)
            slot = mclip % n
            if recompute_stage:
                y = stage_fn(params, x_in)
                leaves = [x_in]
            else:
                y, pull = jax.vjp(stage_fn, params, x_in)
                leaves = jax.tree_util.tree_leaves(pull)
            res_buf = tuple(
                lax.dynamic_update_index_in_dim(b, pv(l).astype(b.dtype),
                                                slot, 0)
                for b, l in zip(res_buf, leaves))
            if not recompute_stage:
                y_buf = lax.dynamic_update_index_in_dim(
                    y_buf, pv(y).astype(dt), slot, 0)
            return pv(y).astype(dt), res_buf, y_buf

        def fwd_skip(op):
            ring_f, res_buf, y_buf = op
            return pv(jnp.zeros(mb_shape, dt)), res_buf, y_buf

        y_out, res_buf, y_buf = lax.cond(do_f, fwd_branch, fwd_skip,
                                         (ring_f, res_buf, y_buf))

        def bwd_branch(op):
            ring_b, dacc, dlp, dx_buf, loss_sum = op
            mclip = jnp.clip(m_b, 0, M - 1)
            slot = mclip % n
            leaves = [lax.dynamic_index_in_dim(b, slot, 0, keepdims=False)
                      for b in res_buf]
            if recompute_stage:
                y_m, pull = jax.vjp(stage_fn, params, leaves[0])
            else:
                pull = jax.tree_util.tree_unflatten(res_treedef, leaves)
                y_m = lax.dynamic_index_in_dim(y_buf, slot, 0, keepdims=False)
            tgt = targets[mclip]
            l_m, pl = jax.vjp(lambda yy, lp: loss_fn(yy, tgt, lp),
                              y_m, loss_params)
            dy_loss, dlp_m = pl(jnp.ones_like(l_m))
            is_last = idx == n - 1
            cot = jnp.where(is_last, pv(dy_loss).astype(dt), ring_b)
            loss_sum = loss_sum + jnp.where(is_last,
                                            pv(l_m).astype(jnp.float32), 0.0)
            dlp = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(is_last,
                                           pv(g).astype(jnp.float32), 0.0),
                dlp, dlp_m)
            dparams_m, dx_m = pull(cot)
            dacc = jax.tree_util.tree_map(
                lambda a, g: a + pv(g).astype(jnp.float32), dacc, dparams_m)
            # stage 0's dx is the pipeline-input cotangent for microbatch
            # m — recorded here, masked to stage 0 by the final psum
            if want_dx:
                dx_buf = lax.dynamic_update_index_in_dim(
                    dx_buf, pv(dx_m).astype(jnp.float32), mclip, 0)
            return pv(dx_m).astype(dt), dacc, dlp, dx_buf, loss_sum

        def bwd_skip(op):
            ring_b, dacc, dlp, dx_buf, loss_sum = op
            return (pv(jnp.zeros(mb_shape, dt)), dacc, dlp, dx_buf,
                    loss_sum)

        dx_out, dacc, dlp, dx_buf, loss_sum = lax.cond(
            do_b, bwd_branch, bwd_skip,
            (ring_b, dacc, dlp, dx_buf, loss_sum))

        ring_f = lax.ppermute(y_out, axis_name, fwd_perm)
        ring_b = lax.ppermute(dx_out, axis_name, bwd_perm)
        return (ring_f, ring_b, res_buf, y_buf, dacc, dlp, dx_buf,
                loss_sum)

    carry0 = (pv(jnp.zeros(mb_shape, dt)), pv(jnp.zeros(mb_shape, dt)),
              res_buf0, y_buf0, dacc0, dlp0, dx_buf0, pv(jnp.float32(0)))
    out = lax.fori_loop(0, total, tick, carry0)
    _, _, _, _, dacc, dlp, dx_buf, loss_sum = out
    # mask the dx rows to stage 0's contributions (other stages wrote
    # their own dx_m into their local buffer)
    if want_dx:
        dx_buf = dx_buf * (idx == 0).astype(jnp.float32)
    return loss_sum, dacc, dlp, dx_buf


def pipeline_train_1f1b(stage_fn: Callable, loss_fn: Callable,
                        all_stage_params, x, targets, mesh: Mesh,
                        num_microbatches: int, axis_name: str = "pipe",
                        recompute_stage: bool = True,
                        loss_params=None, return_dx: bool = False):
    """True 1F1B pipeline train step.

    stage_fn(params, x) -> y (uniform activation shape across stages;
    in-stage collectives over non-`pipe` axes are allowed — see module
    docstring).  loss_fn(y, target) -> scalar per microbatch, evaluated
    on the LAST stage — or loss_fn(y, target, loss_params) when
    ``loss_params`` is given (trainable head/readout living OUTSIDE the
    stages; its grads are returned too).

    return_dx: also return the cotangent w.r.t. the pipeline INPUT
    (B, ...) — this is what lets an embedding (or any front-end) live
    outside the pipeline and still train: run it forward eagerly, feed
    its output here, then apply its vjp to the returned dx.

    Returns ``(mean_loss, grads[, dloss_params][, dx])`` — grads has
    the stages' leading dim; all gradients correspond to the MEAN
    per-microbatch loss.

    Memory note: ``x`` and ``targets`` enter the shard_map replicated
    (in_specs P()) — every pipe device holds the full global batch even
    though only stage 0 consumes x and the last stage consumes targets.
    Activations stay O(n_stages)-bounded, but for very large inputs the
    replicated batch itself can dominate per-device memory; feed the
    pipeline microbatch-by-microbatch (or pre-shard x along a data axis
    composed with pipe) if that bites.
    """
    from jax import shard_map

    B = x.shape[0]
    M = num_microbatches
    if B % M:
        raise ValueError(
            f"pipeline_train_1f1b: batch {B} not divisible by "
            f"num_microbatches {M}")
    mb = B // M
    xm = x.reshape((M, mb) + x.shape[1:])
    tm = targets.reshape((M, mb) + targets.shape[1:])
    n_static = mesh.shape[axis_name]

    lp = () if loss_params is None else loss_params
    lf = (lambda y, t, _lp: loss_fn(y, t)) if loss_params is None \
        else loss_fn

    def _deflate(v):
        # reduce to an unvarying (out_specs P()) value: psum over pipe,
        # pmean over any leftover TP axes (values replicated there)
        v = lax.psum(v, axis_name)
        for ax in sorted(_vma_of(v)):
            v = lax.pmean(v, ax)
        return v

    def inner(params_stacked, xmb, tmb, lp_in):
        params = jax.tree_util.tree_map(lambda p: p[0], params_stacked)
        loss_sum, dacc, dlp, dx_buf = _1f1b_device(
            stage_fn, lf, params, xmb, tmb, axis_name, n_static,
            recompute_stage=recompute_stage, loss_params=lp_in,
            want_dx=return_dx)
        loss = _deflate(loss_sum) / M  # only last stage non-zero
        grads = jax.tree_util.tree_map(lambda g: (g / M)[None], dacc)
        dlp = jax.tree_util.tree_map(lambda g: _deflate(g) / M, dlp)
        # want_dx=False leaves a (1,) dummy — deflating it is free and
        # keeps the out_specs P() replication provable
        dx = _deflate(dx_buf) / M
        return loss, grads, dlp, dx

    param_spec = jax.tree_util.tree_map(lambda _: P(axis_name),
                                        all_stage_params)
    # vma checking ON: it is what makes in-stage collective AD correct
    # (see _1f1b_device); TP'd stages compose by calling _1f1b_device
    # under your own shard_map with pipe×model in_specs — the PP×TP test
    # shows the pattern.
    lp_spec = jax.tree_util.tree_map(lambda _: P(), lp)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(param_spec, P(), P(), lp_spec),
                   out_specs=(P(), param_spec, lp_spec, P()))
    if telemetry.enabled():
        _record_schedule("1f1b", n_static, M)
        with telemetry.span("pipeline/train_1f1b"):
            loss, grads, dlp, dx = fn(all_stage_params, xm, tm, lp)
    else:
        loss, grads, dlp, dx = fn(all_stage_params, xm, tm, lp)
    out = (loss, grads)
    if loss_params is not None:
        out += (dlp,)
    if return_dx:
        out += (dx.reshape((B,) + x.shape[1:]),)
    return out if len(out) > 2 else (loss, grads)
