"""Flagship benchmark: BERT-large MLM pretraining step throughput → MFU.

Mirrors the reference's headline BERT-large phase-1 (seq 128) training
benchmark (BASELINE.md; GluonNLP `scripts/bert` era) — driven ENTIRELY
through the framework's public Gluon path (VERDICT r1 #2):

    with autograd.record():
        loss = model(tokens, labels)     # hybridized net+loss, one jit
    loss.backward()                      # cached residual-sharing bwd jit
    trainer.step(1)                      # fused multi-tensor update jit

bf16 params with fp32 master weights (multi_precision), momentum SGD,
buffer donation in the fused Trainer step.  North star (BASELINE.json):
≥40% MFU — `vs_baseline` = measured_MFU / 0.40.

The FLAGSHIP number is the REALISTIC one: dropout=0.1, as a real
phase-1 pretrain runs (VERDICT r2 Weak #1) — dropout masks generated by
the in-kernel TPU PRNG (`ops/dropout_kernel.py`), not threefry.  The
dropout-free figure is also reported in `detail` for round-over-round
comparison.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""
from __future__ import annotations

import json
import sys
import time


def _peak_flops(device) -> float:
    from incubator_mxnet_tpu.callback import device_peak_flops

    return device_peak_flops(device)


def _hlo_facts(hlo_text, stablehlo_text=None, axis_order=None,
               axis_sizes=None):
    """Compact hlolint fact summary for BENCH detail: the compiled
    program's collective inventory, dtype mix, donation aliasing and
    control-flow shape — list-valued facts collapsed to counts so the
    one-line JSON stays small."""
    if not hlo_text:
        return None
    from tools import hlolint

    mod = hlolint.parse_hlo(hlo_text)
    smod = hlolint.parse_stablehlo(stablehlo_text) if stablehlo_text else None
    s = hlolint.fact_summary(mod, stablehlo=smod, axis_order=axis_order,
                             axis_sizes=axis_sizes)
    donation = {k: v for k, v in s["donation"].items()
                if k != "aliased_params"}
    out = {
        "collectives": s["collectives"],
        "dtypes": sorted(s["dtypes"]["dtypes"]),
        "has_f64": s["dtypes"]["has_f64"],
        "sub_f32_accumulators": len(s["sub_f32_accumulators"]),
        "host_transfers": s["host_transfers"]["count"],
        "donation": donation,
        "stats": s["stats"],
        "entry": s["entry"],
    }
    return out


def _input_wait_phase(model, trainer, shapes, steps: int = 8):
    """Feed the train loop through the async device-feed pipeline
    (io.prefetcher.DevicePrefetcher) from a synthetic host dataset and
    report the input-wait fraction straight from the new pipeline
    metrics: data_wait_seconds.sum / wall — ~0 means compute-bound,
    →1 means the accelerator is starving on input."""
    import time as _t

    import numpy as onp

    from incubator_mxnet_tpu import autograd, telemetry
    from incubator_mxnet_tpu.io.prefetcher import DevicePrefetcher
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    V, D, Dff, L, H, B, T = shapes
    rng = onp.random.RandomState(0)

    def host_batches():
        for _ in range(steps):
            # host fetch/batchify stage: fresh numpy batches, as a real
            # tokenizer/loader would hand over
            yield (rng.randint(0, V, (B, T)).astype(onp.int32),
                   rng.randint(0, V, (B, T)).astype(onp.int32))

    was_on = telemetry.enabled()
    telemetry.enable()
    wait_h = telemetry.histogram("data_wait_seconds")
    h2d = telemetry.counter("h2d_bytes_total")
    base_wait, base_h2d = wait_h.sum, h2d.value
    t0 = _t.perf_counter()
    loss = None
    for tokens, labels in DevicePrefetcher(host_batches(), depth=2):
        with autograd.record():
            loss = model(NDArray(tokens), NDArray(labels))
        loss.backward()
        trainer.step(1)
    trainer.flush()
    float(loss.asnumpy())
    wall = _t.perf_counter() - t0
    waited = wait_h.sum - base_wait
    if not was_on:
        telemetry.disable()
    return {"steps": steps,
            "wall_seconds": round(wall, 4),
            "data_wait_seconds": round(waited, 4),
            "input_wait_fraction": round(waited / wall, 4) if wall else 0.0,
            "h2d_bytes": int(h2d.value - base_h2d)}


def build_pretrain(dropout: float, shapes, *, mesh=None, capture_hlo=False,
                   **trainer_kw):
    """BERT pretraining (MLM + NSP) at ``shapes`` = (V, D, Dff, L, H, B, T)
    through the public Gluon path: bf16 params with fp32 master weights,
    momentum SGD, one fixed random batch.  With ``mesh`` the params are
    placed by `shard_params` and the batch by `shard_batch`.  Returns a
    namespace with ``net``, ``model``, ``trainer``, ``n_params`` and
    ``train_step()`` (one record/backward/step, returns the loss).

    `chip_smoke.py` drives the same builder, so the smoke and the
    benchmark cannot drift apart."""
    import os
    import types

    import jax
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import Trainer
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.gluon.utils import shard_batch
    from incubator_mxnet_tpu.models import bert
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.parallel.sharding import shard_params

    V, D, Dff, L, H, B, T = shapes

    class PretrainWithLoss(HybridBlock):
        """net + MLM/NSP cross-entropy so the whole step traces into one
        jit.  The MLM loss goes through the public gluon loss, which on
        TPU streams the (B·T, 30k) softmax-xent through the Pallas
        kernel (ops/xent_kernel.py) instead of materializing the fp32
        log-prob tensor; numerics are the same fp32-lse quantity."""

        def __init__(self, net_, **kw):
            super().__init__(**kw)
            self.net = net_
            self.mlm_loss = mx.gluon.loss.SoftmaxCrossEntropyLoss()

        def forward(self, tokens, labels):
            mlm_logits, nsp_logits = self.net(tokens)
            mlm = self.mlm_loss(mlm_logits, labels).mean()
            nsp_logp = mx.nd.log_softmax(nsp_logits.astype("float32"))
            nsp = -(nsp_logp[:, 0].mean())
            return mlm + nsp

    mx.random.seed(0)
    net = bert.BERTForPretraining(vocab_size=V, units=D, hidden_size=Dff,
                                  num_layers=L, num_heads=H, dropout=dropout)
    net.initialize()
    # materialize deferred shapes, then cast params to bf16 compute
    net(NDArray(jnp.ones((B, T), jnp.int32)))
    net.cast("bfloat16")
    if mesh is not None:
        shard_params(net, mesh, warn=False)

    model = PretrainWithLoss(net)
    model.hybridize()

    n_params = sum(p.data().size for p in net.collect_params().values()
                   if p.grad_req != "null")

    # keep_grads=False: grads are consumed inside the one fused step
    # program, never written back to HBM (the documented perf knob —
    # the analogue of the reference's hybridize(static_alloc=True))
    chain = max(1, int(os.environ.get("MXTPU_BENCH_CHAIN", "1")))
    trainer = Trainer(model.collect_params(), "sgd",
                      {"learning_rate": 1e-3, "momentum": 0.9,
                       "multi_precision": True},
                      mesh=mesh, keep_grads=False, chain_steps=chain,
                      **trainer_kw)
    # the compiled step's text (last_step_hlo) from the same AOT compile
    trainer._capture_hlo = capture_hlo

    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    tokens = jax.random.randint(kx, (B, T), 0, V, dtype=jnp.int32)
    labels = jax.random.randint(ky, (B, T), 0, V, dtype=jnp.int32)
    if mesh is not None:
        tokens, labels = shard_batch(tokens, mesh), shard_batch(labels, mesh)
    else:
        tokens, labels = NDArray(tokens), NDArray(labels)

    def train_step():
        with autograd.record():
            loss = model(tokens, labels)
        loss.backward()
        trainer.step(1)
        return loss

    return types.SimpleNamespace(net=net, model=model, trainer=trainer,
                                 n_params=n_params, chain=chain,
                                 train_step=train_step)


def measure(dropout: float, shapes, steps: int, warmup: int,
            percentile_phase: bool = False, input_wait: bool = False):
    import jax

    V, D, Dff, L, H, B, T = shapes
    dev = jax.devices()[0]
    # flagship run also records the compiled step's hlolint facts
    # (detail.hlo_facts)
    built = build_pretrain(dropout, shapes, capture_hlo=percentile_phase)
    model, trainer, train_step = built.model, built.trainer, built.train_step
    n_params, chain = built.n_params, built.chain

    # chained mode: warm a whole flush cycle so the chain program
    # compiles outside the timed region, and time whole chains
    warmup = max(warmup, 2 * chain + 1)
    steps = -(-steps // chain) * chain
    for _ in range(warmup):
        loss = train_step()
    trainer.flush()
    float(loss.asnumpy())

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train_step()
    final_loss = float(loss.asnumpy())  # steps serialized by the params dependency
    dt = time.perf_counter() - t0

    tokens_per_s = B * T * steps / dt
    # train FLOPs/token ≈ 6·N_matmul + attention 12·L·T·D; embedding
    # lookups are gathers, not matmuls — exclude their tables
    n_embed = V * D + 512 * D + 2 * D
    flops_per_token = 6 * (n_params - n_embed) + 12 * L * T * D
    mfu = tokens_per_s * flops_per_token / _peak_flops(dev)
    result = {"mfu": mfu, "tokens_per_s": tokens_per_s, "n_params": n_params,
              "final_loss": final_loss}

    if percentile_phase:
        # SEPARATE short phase AFTER the headline loop: telemetry on and
        # a per-step value fetch, so bench_step_seconds measures real
        # step latency (not dispatch) without perturbing the MFU number
        from incubator_mxnet_tpu import telemetry

        was_on = telemetry.enabled()
        telemetry.enable()
        hist = telemetry.histogram("bench_step_seconds")
        # the step program was built before telemetry came on: re-run
        # the cost/memory capture from the ctx's stored aval skeleton so
        # the roofline attribution below has compile-time analysis
        perf_program = trainer.capture_step_costs()
        for _ in range(max(3, min(steps, 10))):
            t0 = time.perf_counter()
            loss = train_step()
            trainer.flush()
            float(loss.asnumpy())
            dt_sync = time.perf_counter() - t0
            hist.observe(dt_sync)
            # attribute the SYNCED wall time (value fetched): in this
            # serialized phase it is true end-to-end step latency, so
            # the MFU/roofline rows reflect achieved rates
            telemetry.perf.note_timing(perf_program, dt_sync)
        paths = telemetry.dump()
        result["step_seconds"] = {k: round(v, 6)
                                  for k, v in hist.percentiles().items()}
        result["telemetry_jsonl"] = paths["jsonl"]
        roofline = telemetry.perf.roofline_table()
        if roofline:
            result["roofline"] = roofline
        result["hlo_facts"] = _hlo_facts(
            trainer.last_step_hlo, getattr(trainer, "last_step_stablehlo",
                                           None))
        if not was_on:
            telemetry.disable()

    if input_wait:
        # SEPARATE phase after the headline loop: same model/trainer,
        # batches fed through the device prefetcher instead of the
        # resident arrays — reports how input-bound this config is
        result["input_wait"] = _input_wait_phase(model, trainer, shapes)
    return result


def main():
    import jax

    from incubator_mxnet_tpu import runtime

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a CPU timing is not a measurement of this system: no fallback
        sys.exit(f"bench.py needs a TPU; jax found {dev.platform!r} "
                 f"({dev.device_kind})")
    runtime.use_compile_cache()
    # BERT-large, phase-1 shapes
    shapes = (30522, 1024, 4096, 24, 16, 32, 128)
    steps, warmup = 30, 3

    # `input-wait`: feed a post-loop phase through the async device-feed
    # pipeline and report data_wait_seconds/wall (input-bound fraction)
    want_input_wait = "input-wait" in sys.argv

    # FLAGSHIP: realistic dropout=0.1 (in-kernel PRNG masks); also runs
    # the post-loop telemetry percentile phase (JSONL next to results)
    r_drop = measure(0.1, shapes, steps, warmup, percentile_phase=True,
                     input_wait=want_input_wait)
    # round-over-round comparison: dropout-free
    r_nodrop = measure(0.0, shapes, steps, warmup)

    V, D, Dff, L, H, B, T = shapes
    print(json.dumps({
        "metric": "bert_large_pretrain_mfu",
        "value": round(r_drop["mfu"] * 100, 2),
        "unit": "%MFU",
        "vs_baseline": round(r_drop["mfu"] / 0.40, 4),
        "detail": {
            "dropout": 0.1,
            "tokens_per_s": round(r_drop["tokens_per_s"], 1),
            "dropout_off_mfu": round(r_nodrop["mfu"] * 100, 2),
            "dropout_off_tokens_per_s": round(r_nodrop["tokens_per_s"], 1),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "path": "gluon: autograd.record + backward + Trainer.step(fused)",
            "n_params": int(r_drop["n_params"]),
            "batch": B, "seq": T, "steps_timed": steps,
            "final_loss": round(r_drop["final_loss"], 4),
            "step_seconds": r_drop.get("step_seconds"),
            "telemetry_jsonl": r_drop.get("telemetry_jsonl"),
            "hlo_facts": r_drop.get("hlo_facts"),
            "input_wait": r_drop.get("input_wait"),
        },
    }))


if __name__ == "__main__":
    main()
