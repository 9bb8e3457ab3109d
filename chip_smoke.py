"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: train, flash, serve
    python chip_smoke.py --chips 4   # one host, four chips: mesh + ZeRO-1

One process, no child that needs JAX.  Drives the two paths users depend
on — the public Gluon training loop and `ServingEngine` — at published
widths with seeded random weights, checks what comes out by the repo's
own means, and fails (traceback, non-zero exit) on the first phase that
fails.  It refuses any platform but ``tpu`` before building a model:
there is no CPU mode, rehearsals are made from a scratch driver that
imports the phase functions at tiny sizes (see the verify skill).

Each phase prints one JSON line (compile seconds are the backend compile
time JAX reports, so a cold and a warm cache can be told apart); the
last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Every number printed here is a smoke reading on the named device, not a
benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

# BERT-large phase 1 at published widths and depth: V, D, Dff, L, H, B, T
BERT_LARGE = (30522, 1024, 4096, 24, 16, 32, 128)
# two greedy tokens whose log-probs differ by at most this many bf16 ulps
# (at the log-probs' magnitude) count as a tie: `net.score` returns them
# from bf16 logits, and batch-16 and batch-1 programs round differently
TIE_ULPS = 2
# pallas-vs-dense paged attention on fp32 copies of the live pool, both
# traced under matmul precision "highest": fp32 softmax-attention over
# identical values, so fp32 roundoff
PAGED_ATOL_FP32 = 2e-5
# the kernel as the engine traces it: the MXU's default precision rounds
# the fp32 softmax weights to bf16 (relative 2^-9 each, weights sum to 1),
# so the output may be off by 2^-8 of the largest |v|
PAGED_REL_DEFAULT = 2.0 ** -8
# one-device vs TP×DP loss trajectory: same bf16 math re-associated by
# the partitioner, fp32 master weights
MESH_RTOL = 1e-2
# one-device vs ZeRO-1 with dropout on: each data shard draws the mask of
# its local batch, so the masks differ; the mean loss over 4096 tokens
# moves little with the mask, a wrong gradient scale moves it 2% a step
ZERO_RTOL = 2e-2


class _Clock:
    """Wall seconds of a phase, split into what JAX spent in the backend
    compiler (or fetching from the persistent cache) and the rest."""

    def __init__(self):
        from jax import monitoring

        self._compile = 0.0
        self._hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self._compile += secs

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self._hits += 1

    def start(self):
        self._t0 = time.perf_counter()
        self._c0, self._h0 = self._compile, self._hits

    def split(self) -> dict:
        wall = time.perf_counter() - self._t0
        comp = self._compile - self._c0
        return {"compile_s": round(comp, 2), "run_s": round(wall - comp, 2),
                "cache_hits": self._hits - self._h0}


def _emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _device_record() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _hbm_in_use_gb():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    used = stats.get("bytes_in_use")
    return None if used is None else round(used / 2**30, 2)


def _n_kernels(trainer) -> int:
    return (trainer.last_step_hlo or "").count("tpu_custom_call")


def _finite_losses(step, n):
    """Run ``step`` n times, fetching the loss each time."""
    import math

    losses = [float(step().asnumpy()) for _ in range(n)]
    assert all(math.isfinite(x) for x in losses), losses
    return losses


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #
def phase_device(want_count: int) -> dict:
    """Refuse anything but the TPU, at once; the peaks table must know
    the chip."""
    import jax

    from incubator_mxnet_tpu import callback

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; jax found {dev.platform!r} "
                 f"({dev.device_kind})")
    if len(jax.devices()) != want_count:
        sys.exit(f"chip_smoke.py was asked for {want_count} chip(s); jax "
                 f"found {len(jax.devices())}")
    rec = _device_record()
    _emit("device", **rec,
          peak_bf16_flops=callback.device_peak_flops(dev),
          peak_hbm_bytes_per_s=callback.device_peak_hbm_bytes_per_s(dev))
    return rec


def phase_train(clock, shapes=BERT_LARGE, steps=15, kernels=True):
    """BERT pretraining through record/backward/Trainer.step on one fixed
    batch: finite loss every step, the last below the first, the fused
    full-step program, and the Mosaic dropout + cross-entropy kernels
    inside it."""
    import bench

    clock.start()
    built = bench.build_pretrain(0.1, shapes, capture_hlo=True)
    losses = _finite_losses(built.train_step, 1 + steps)
    trainer = built.trainer
    assert trainer._fullstep_ctx is not None, \
        "the trainer took the staged path, not the fused full-step program"
    assert losses[-1] < losses[0], losses
    n_kernels = _n_kernels(trainer)
    if kernels:
        # dropout mask + xent forward + xent backward at the least; the
        # threefry / XLA references would leave no custom call at all
        assert n_kernels >= 3, f"{n_kernels} Mosaic kernels in the step"
    _emit("train", shapes=list(shapes), n_params=int(built.n_params),
          steps=1 + steps, first_loss=losses[0], last_loss=losses[-1],
          losses=[round(x, 4) for x in losses],
          fused_full_step=True, mosaic_kernels_in_step=n_kernels,
          hbm_in_use_gb=_hbm_in_use_gb(), **clock.split())


def phase_flash(clock, T=2048, B=8, steps=2, kernels=True, size=None):
    """The long-context LM at T=2048 through the same public loop, so
    the causal flash forward and both backward kernels compile and
    run."""
    from benchmark import longctx_bench

    clock.start()
    size = size or longctx_bench.SIZE
    built = longctx_bench.build(T, B, capture_hlo=True, size=size)
    losses = _finite_losses(built.step, steps)
    assert built.trainer._fullstep_ctx is not None
    n_kernels = _n_kernels(built.trainer)
    if kernels:
        # per layer: flash fwd + dk/dv + dq; plus dropout and xent
        n_layers = len(built.net._layers)
        assert n_kernels >= 3 * n_layers, \
            f"{n_kernels} Mosaic kernels in a {n_layers}-layer step"
    _emit("flash", size=list(size), T=T, B=B, steps=steps, losses=losses,
          mosaic_kernels_in_step=n_kernels,
          hbm_in_use_gb=_hbm_in_use_gb(), **clock.split())


def _paged_vs_dense(engine, num_heads, seed=0):
    """max |pallas - dense| of single-query attention over the engine's
    LIVE KV pool (layer 0), through block tables drawn from the blocks
    the run wrote, at ragged positions.  Float pools are compared on
    fp32 copies (values unchanged, bf16-exact) so that output rounding
    does not mask the difference.  The reference is the dense gather at
    matmul precision "highest"; the kernel is held to it twice — traced
    at "highest" too (fp32 roundoff: its math is right on the chip) and
    as the engine traces it (the MXU's default precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from incubator_mxnet_tpu.ops.paged_attention import (
        paged_attention, paged_attention_dense)

    pool_k, pool_v, scale_k, scale_v = engine._programs.kv_pools
    pk, pv = pool_k[0], pool_v[0]
    nb, bs, HD = pk.shape                  # a position a row of H*D
    H, D = num_heads, HD // num_heads
    written = onp.flatnonzero(onp.asarray(jnp.any(pk != 0, axis=(1, 2))))
    assert written.size >= 4, "the run left no KV pages behind"
    B, nbps = engine._B, engine._nbps
    rng = onp.random.RandomState(seed)
    tables = jnp.asarray(rng.choice(written, (B, nbps)), jnp.int32)
    pos = jnp.asarray(rng.randint(0, nbps * bs, (B,)), jnp.int32)
    pos = pos.at[0].set(0).at[1].set(nbps * bs - 1)  # both ends
    q = jax.random.normal(jax.random.PRNGKey(seed), (B, H, D),
                          jnp.bfloat16).astype(jnp.float32)
    if engine.kv_dtype == "int8":
        scales = (scale_k[0], scale_v[0])
        v = pv[written].astype(jnp.float32).reshape(-1, bs, H, D) \
            * scales[1][written][..., None]
        # dequantized K (8-bit integer × fp32 scale) is not bf16-exact
        # either: the scores are rounded as well as the weights
        rel = 4 * PAGED_REL_DEFAULT
    else:
        scales = (None, None)
        pk, pv = pk.astype(jnp.float32), pv.astype(jnp.float32)
        v = pv[written]
        rel = PAGED_REL_DEFAULT
    atol_default = rel * float(jnp.max(jnp.abs(v)))

    def kernel():
        return paged_attention(q, pk, pv, tables, pos, scale_k=scales[0],
                               scale_v=scales[1], impl="pallas")

    with jax.default_matmul_precision("highest"):
        dense = paged_attention_dense(q, pk, pv, tables, pos, *scales)
        err_fp32 = float(jnp.max(jnp.abs(kernel() - dense)))
    err_default = float(jnp.max(jnp.abs(kernel() - dense)))
    assert err_fp32 <= PAGED_ATOL_FP32, \
        f"paged kernel vs dense at precision highest: {err_fp32}"
    assert err_default <= atol_default, \
        f"paged kernel as the engine runs it: {err_default} > {atol_default}"
    return {"paged_vs_dense_max_abs_fp32": err_fp32,
            "paged_vs_dense_max_abs_default_precision": err_default,
            "default_precision_atol": atol_default,
            "pages_written": int(written.size)}


def _greedy_parity(net, prompt, got):
    """Engine tokens vs `net.generate` for one prompt.  The two attention
    impls agree to roundoff, not bitwise, so a near-tie may flip: report
    the first diverging position and both tokens' log-probs under the
    decode stack (`net.score`), and fail only above TIE_ULPS."""
    import math

    import jax.numpy as jnp
    import numpy as onp

    P, N = len(prompt), len(got)
    want = onp.asarray(net.generate(jnp.asarray(prompt)[None], N))[0, P:]
    diverged = onp.flatnonzero(want != onp.asarray(got))
    if diverged.size == 0:
        return {"greedy_vs_generate": "identical", "tokens_compared": N}
    i = int(diverged[0])
    prefix = list(prompt) + list(got[:i])
    both = jnp.asarray([prefix + [int(got[i])], prefix + [int(want[i])]],
                       jnp.int32)
    lp_engine, lp_generate = (float(x) for x in
                              onp.asarray(net.score(both))[:, -1])
    gap = abs(lp_engine - lp_generate)
    # bf16 keeps 8 significant bits: one ulp at this magnitude
    ulp = 2.0 ** (math.floor(math.log2(max(abs(lp_engine), abs(lp_generate),
                                           2.0 ** -126))) - 7)
    out = {"greedy_vs_generate": "diverged", "first_diverging_position": i,
           "logp_engine_token": lp_engine, "logp_generate_token": lp_generate,
           "gap_nats": gap, "gap_bf16_ulps": gap / ulp,
           "tie_threshold_ulps": TIE_ULPS}
    assert gap <= TIE_ULPS * ulp, out
    return out


def _serve_once(net, requests, vocab, num_heads, **engine_kw):
    """Submit ``requests`` = [(prompt_len, max_new_tokens)] to a fresh
    engine, run its normal loop until all finish; returns (engine
    checks, the first request's prompt and tokens)."""
    import numpy as onp

    from incubator_mxnet_tpu.serving import ServingEngine

    rng = onp.random.RandomState(0)
    prompts = [rng.randint(0, vocab, (p,)).astype(onp.int32)
               for p, _ in requests]
    with ServingEngine(net, **engine_kw) as engine:
        handles = [engine.submit(p, n, block=True)
                   for p, (_, n) in zip(prompts, requests)]
        outs = [h.result(timeout=600) for h in handles]
        assert engine.drain(timeout=60)
        for h, out, (_, n) in zip(handles, outs, requests):
            # eos_id=-1: every request must end by max_new_tokens
            assert h.status == "done" and h.finish_reason is None, \
                (h.status, h.finish_reason)
            assert len(out) == n and all(0 <= t < vocab for t in out)
        checks = {"attn_impl": engine.attn_impl,
                  "kv_dtype": engine.kv_dtype or "model",
                  "requests": len(requests),
                  "tokens": sum(len(o) for o in outs),
                  "decode_steps": engine.stats()["steps"],
                  **_paged_vs_dense(engine, num_heads)}
    return checks, prompts[0], outs[0]


def phase_serve(clock, size=None, max_batch=16, max_seq_len=512,
                kernels=True):
    """The long-context LM's configuration in bf16 behind
    `ServingEngine` with default attn_impl and kv_dtype, then a short
    int8-KV run."""
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from benchmark import longctx_bench
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    clock.start()
    V, D, Dff, L, H = size = size or longctx_bench.SIZE
    mx.random.seed(0)
    net = TransformerLM(vocab=V, units=D, hidden_size=Dff, num_layers=L,
                        num_heads=H, max_len=max_seq_len, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 16), jnp.int32)))
    net.cast("bfloat16")

    # default prefill chunk is 32: 100/150/200 span more than two chunks
    requests = [(40, 24), (5, 16), (17, 32), (33, 8), (64, 40), (100, 12),
                (150, 20), (200, 28)]
    requests = [(p, n) for p, n in requests if p + n <= max_seq_len]
    engine_kw = {"max_batch": max_batch, "max_seq_len": max_seq_len}
    checks, prompt, got = _serve_once(net, requests, V, H, **engine_kw)
    if kernels:
        assert checks["attn_impl"] == "pallas", checks
    checks.update(_greedy_parity(net, prompt, got))
    n_params = sum(p.data().size for p in net.collect_params().values())
    _emit("serve", size=list(size), n_params=int(n_params), **checks,
          hbm_in_use_gb=_hbm_in_use_gb(), **clock.split())

    clock.start()
    checks, _, _ = _serve_once(net, requests[:3], V, H,
                               kv_dtype="int8", **engine_kw)
    if kernels:
        assert checks["attn_impl"] == "pallas", checks
    assert checks["kv_dtype"] == "int8"
    _emit("serve_int8_kv", **checks, hbm_in_use_gb=_hbm_in_use_gb(),
          **clock.split())


def _mask_parity(mesh, shapes=((4096, 1024), (4096, 4096))):
    """The dropout keep-mask of a bf16 activation, drawn by one device
    and by the shards of ``mesh``: every shard draws its own tiles of
    the global mask, so the bits must be the same."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_mxnet_tpu.ops import mosaic
    from incubator_mxnet_tpu.ops.dropout_kernel import dropout_mask

    def under_mesh(x, seed):
        with mosaic.mesh_context(mesh):
            return dropout_mask(x, seed, 0.1)

    seed = jnp.array([7], jnp.int32)
    keep = []
    for shape in shapes:
        x = jnp.zeros(shape, jnp.bfloat16)
        one = jax.jit(lambda x, seed: dropout_mask(x, seed, 0.1))(x, seed)
        xs = jax.device_put(x, NamedSharding(mesh, P(*mesh.axis_names)))
        got = jax.jit(under_mesh)(xs, jax.device_put(
            seed, NamedSharding(mesh, P())))
        assert len(one.sharding.device_set) == 1
        assert len(got.sharding.device_set) == mesh.size, got.sharding
        one, got = onp.asarray(one), onp.asarray(got)
        assert (one == got).all(), \
            f"{shape}: {(one != got).mean():.3f} of the mask bits differ"
        keep.append(round(float(got.mean()), 4))
    return {"shapes": [list(s) for s in shapes], "rate": 0.1,
            "keep_fraction": keep, "identical_bits": True}


def phase_mesh(clock, shapes=BERT_LARGE, steps=3, kernels=True):
    """Four chips, dropout 0.1 throughout.  The dropout mask drawn per
    shard against one device's.  TP×DP: the same model and batch on
    `create_mesh(data=2, model=2)` against one device of this process —
    with identical masks the loss trajectories must agree.  Then ZeRO-1
    on `create_mesh(data=4)`: the explicit reduce-scatter tier, a
    quarter of the optimizer state per device, every leaf laid out over
    all four.  Both mesh programs must hold the Mosaic dropout and
    cross-entropy kernels."""
    import numpy as onp

    import bench
    from incubator_mxnet_tpu.parallel import create_mesh

    mesh = create_mesh(data=2, model=2)
    clock.start()
    _emit("mesh_dropout_mask", **_mask_parity(mesh), **clock.split())

    clock.start()
    one = bench.build_pretrain(0.1, shapes)
    want = _finite_losses(one.train_step, steps)
    replicated_bytes = int(one.trainer.optimizer_state_bytes_per_device())
    del one
    gc.collect()

    tp = bench.build_pretrain(0.1, shapes, mesh=mesh, capture_hlo=True)
    got = _finite_losses(tp.train_step, steps)
    assert tp.trainer._fullstep_ctx is not None
    n_kernels = _n_kernels(tp.trainer)
    if kernels:
        # dropout masks + xent forward + xent backward at the least
        assert n_kernels >= 3, f"{n_kernels} Mosaic kernels in the step"
    onp.testing.assert_allclose(got, want, rtol=MESH_RTOL)
    _assert_spread(tp, 4)
    _emit("mesh_tp2_dp2", shapes=list(shapes), dropout=0.1, steps=steps,
          one_device_losses=want, mesh_losses=got, rtol=MESH_RTOL,
          max_rel_diff=float(onp.max(onp.abs(onp.asarray(got) / want - 1))),
          mosaic_kernels_in_step=n_kernels, **clock.split())
    del tp
    gc.collect()

    clock.start()
    zero = bench.build_pretrain(0.1, shapes, mesh=create_mesh(data=4),
                                capture_hlo=True, zero_stage=1)
    zlosses = _finite_losses(zero.train_step, steps)
    onp.testing.assert_allclose(zlosses, want, rtol=ZERO_RTOL)
    trainer = zero.trainer
    # a build error falls back to GSPMD and sticks: neither may have
    # happened
    assert trainer._zero_sig() == ("explicit", "data", 4), trainer._zero_sig()
    assert trainer._fullstep_ctx is not None
    assert not trainer._zero_overlap_broken
    n_kernels = _n_kernels(trainer)
    if kernels:
        assert n_kernels >= 3, f"{n_kernels} Mosaic kernels in the step"
    # how the compiler spelt the exchange is its choice: report, don't pin
    hlo = trainer.last_step_hlo or ""
    collectives = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                   for op in ("reduce-scatter", "all-reduce", "all-gather")}
    per_device = int(trainer.optimizer_state_bytes_per_device())
    ratio = replicated_bytes / per_device
    assert 3.9 <= ratio <= 4.0, (replicated_bytes, per_device)
    _assert_spread(zero, 4)
    _emit("zero1_dp4", tier="explicit", dropout=0.1, steps=steps,
          losses=zlosses, rtol_vs_one_device=ZERO_RTOL,
          collectives_in_step=collectives,
          mosaic_kernels_in_step=n_kernels,
          optimizer_state_bytes_per_device=per_device,
          optimizer_state_bytes_replicated=replicated_bytes,
          ratio=round(ratio, 3), **clock.split())


def _assert_spread(built, n):
    """Every parameter and optimizer-state leaf is laid out over ``n``
    devices (not everything on device 0)."""
    import jax

    trainer = built.trainer
    trainer._sync_states()
    leaves = [p.data()._data for p in built.net.collect_params().values()]
    leaves += jax.tree_util.tree_leaves(list(trainer._states.values()))
    for x in leaves:
        assert len(x.sharding.device_set) == n, (x.shape, x.sharding)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip path and what it "
                         "is compared with")
    args = ap.parse_args(argv)

    device = phase_device(args.chips)
    from incubator_mxnet_tpu import runtime

    _emit("compile_cache", dir=runtime.use_compile_cache())
    clock = _Clock()
    phases = (phase_mesh,) if args.chips == 4 else \
        (phase_train, phase_flash, phase_serve)
    for phase in phases:
        phase(clock)
        gc.collect()  # drop the phase's model before the next one loads
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
