"""CI gate: compiled-program contracts over the repo's flagship programs.

Compiles the programs whose compiled-artifact properties the repo
stakes perf claims on, extracts hlolint fact summaries from the SAME
AOT compile that feeds the roofline (telemetry.perf text capture — no
extra compilation beyond what trainer/generation already do), and
evaluates the committed `.hlolint_contracts.json`:

* ``trainer_full_step``               — monolithic data-parallel step
* ``trainer_full_step_zero_bucketed`` — ZeRO explicit tier, bucketed
  overlapped gradient sync (one reduce-scatter per bucket)
* ``decode_float`` / ``decode_int8``  — generation's bf16 and
  int8-weight greedy decode programs
* ``checkpoint_snapshot``             — the async checkpointer's
  on-device copy (must stay pure per-shard copies: no collectives,
  no host transfers)
* ``serving_prefill_chunk_float`` / ``serving_step_float`` and their
  ``_int8`` twins — the continuous-batching engine's paged-KV
  programs (donation must hold so eviction never doubles the pool;
  the int8 path must not materialize bf16 weight copies).  Prefill is
  the ISSUE 20 fixed-width chunk program — ONE per engine, no pow2
  bucket ladder
* ``serving_*_float_kv8`` — the int8-KV-pool family (``kv_dtype=
  "int8"``): the pool must actually carry s8 pages and keep donation
* ``serving_*_float_pallas`` — the forced paged-attention-kernel
  family: the decode step must NOT materialize the fp32
  ``(B, H, max_seq_len)`` attention-probs buffer the dense-gather
  path streams (that buffer is the whole point of the kernel)
* ``serving_draft_step_float`` / ``serving_spec_verify_float`` /
  ``serving_draft_prefill_chunk_float`` — the speculative-decoding
  family (``speculate_k > 0``): draft k-token proposer, batched target
  verifier, and the draft-pool chunk prefill.  Donation must hold on BOTH
  pool sets and everything stays on-device / collective-free /
  f64-free — speculation is a throughput lever, not a numerics change

* ``serving_*_float_ssm`` — the same two programs for a decoder with
  recurrent (Mamba) layers: a second donated state, the float32
  recurrent state and the conv window a layer, rides beside the pools
  and must be donated with them (a miss doubles the state's memory
  every step)

Contract context (``ctx``) carries the run's ground truth: the mesh
size ``D``, the bucket count ``n_buckets``, the global gradient bytes
``grad_bytes``, and the quantized weight shapes — so contracts can say
``collective_count('reduce-scatter') == ctx['n_buckets']`` instead of
hard-coding numbers that drift with the smoke model.

The gate fails on any contract violation AND on any captured program
with no contract (tpulint-style: new programs must either get a
contract or be listed under ``accepted``).  Bootstrap or refresh with

    JAX_PLATFORMS=cpu python ci/hlolint_gate.py --write-contracts

then review + tighten the pinned bounds before committing.

Run via ci/lint.sh; standalone:  JAX_PLATFORMS=cpu python ci/hlolint_gate.py
"""
import argparse
import json
import os
import sys
import tempfile

# runnable as `python ci/hlolint_gate.py` from the repo root
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

# env must be set BEFORE the package import: the virtual device count is
# read at backend init, telemetry config at package import
_FLAGS = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if not f.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    _FLAGS + ["--xla_force_host_platform_device_count=8"])
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("MXTPU_TELEMETRY_DUMP", None)
os.environ["MXTPU_TELEMETRY_DIR"] = tempfile.mkdtemp(prefix="mxtpu_hlolint_")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import incubator_mxnet_tpu as mx  # noqa: E402
from incubator_mxnet_tpu import autograd, gluon, telemetry  # noqa: E402
from incubator_mxnet_tpu.gluon import nn  # noqa: E402
from incubator_mxnet_tpu.models import generation as G  # noqa: E402
from incubator_mxnet_tpu.models.transformer import TransformerLM  # noqa: E402
from incubator_mxnet_tpu.ndarray.ndarray import NDArray  # noqa: E402
from incubator_mxnet_tpu.parallel import create_mesh  # noqa: E402
from tools import hlolint  # noqa: E402

CONTRACTS_PATH = os.path.join(_ROOT, ".hlolint_contracts.json")

# decode smoke model (small: the contract is about program structure,
# not quality)
V, C, DFF, L, H, MAXLEN = 31, 16, 32, 1, 2, 16
B, P, N = 1, 4, 6


class MLPWithLoss(gluon.nn.HybridBlock):
    def __init__(self):
        super().__init__()
        self.d1 = nn.Dense(64, activation="relu", in_units=32)
        self.d2 = nn.Dense(64, activation="relu", in_units=64)
        self.d3 = nn.Dense(8, in_units=64)
        self.loss = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(self, x, y):
        return self.loss(self.d3(self.d2(self.d1(x))), y).mean()


def _train_program(zero, checkpoint_dir=None):
    """One 2-step train; telemetry.perf captures the step program's HLO
    under its perf name.  With ``checkpoint_dir``, a synchronous
    checkpoint save afterwards additionally captures the
    ``checkpoint_snapshot`` on-device copy program.  Returns
    (n_buckets, grad_bytes)."""
    np.random.seed(0)
    mx.random.seed(0)
    mesh = create_mesh(data=len(jax.devices()))
    net = MLPWithLoss()
    net.initialize(force_reinit=True)
    net.hybridize()
    kw = dict(zero_stage=1, zero_overlap=True, zero_bucket_mb=0.01) \
        if zero else dict(zero_stage=0)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2}, mesh=mesh, **kw)
    with mesh:
        for s in range(2):
            rs = np.random.RandomState(s)
            x = rs.randn(16, 32).astype(np.float32)
            y = rs.randint(0, 8, (16,)).astype(np.int32)
            with autograd.record():
                loss = net(mx.nd.array(x), mx.nd.array(y))
            loss.backward()
            trainer.step(16)
    if checkpoint_dir is not None:
        from incubator_mxnet_tpu.utils.checkpoint import CheckpointManager

        with CheckpointManager(checkpoint_dir, async_save=False) as mgr:
            mgr.save(2, net=net, trainer=trainer)
    bks = (trainer._fullstep_ctx or {}).get("zero_buckets")
    grad_bytes = sum(
        int(np.prod(p.data().shape)) * 4
        for p in net.collect_params().values() if p.grad_req != "null")
    return (len(bks) if bks else None), grad_bytes


def _decode_programs():
    """Compile decode_float and decode_int8; returns the quantized
    weight shapes."""
    mx.random.seed(0)
    net = TransformerLM(vocab=V, units=C, hidden_size=DFF, num_layers=L,
                        num_heads=H, max_len=MAXLEN, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), jnp.int32)))
    net.cast("bfloat16")
    prompt = np.zeros((B, P), dtype="int32")
    net.generate(prompt, N)                   # decode_float
    net.quantize_for_decode(act_quant="none")
    net.generate(prompt, N)                   # decode_int8
    qc = net._decode_quant
    return sorted(tuple(qc.packed(d)["w8"].shape)
                  for d in qc._targets.values())


def _serving_programs():
    """Compile the continuous-batching engine's program families
    (float / int8-KV / forced-pallas / int8-weight, x prefill/step) by
    running one request through each engine flavour on a fresh tiny
    net.  Returns the decode-step attention-probs shape
    ``(max_batch, H, max_seq_len)`` — the fp32 buffer the paged kernel
    must NOT materialize."""
    from incubator_mxnet_tpu.serving import ServingEngine

    mx.random.seed(0)
    net = TransformerLM(vocab=V, units=C, hidden_size=DFF, num_layers=L,
                        num_heads=H, max_len=MAXLEN, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), jnp.int32)))
    net.cast("bfloat16")
    prompt = np.zeros((P,), dtype="int32")
    # prefill_chunk=5: the weight census counts f32/bf16 buffers SHAPED
    # like an s8 weight, and a chunk width of 16/32/48 would make the
    # chunk program's (chunk, C)-family activations alias the smoke
    # model's weight shapes — 5 aliases nothing
    kws = dict(max_batch=1, block_size=4, poll_interval=0.001,
               prefill_chunk=5)
    with ServingEngine(net, **kws) as eng:
        eng.submit(prompt, N).result(timeout=60)   # serving_*_float
    with ServingEngine(net, kv_dtype="int8", **kws) as eng:
        eng.submit(prompt, N).result(timeout=60)   # serving_*_float_kv8
    with ServingEngine(net, attn_impl="pallas", **kws) as eng:
        eng.submit(prompt, N).result(timeout=60)   # serving_*_float_pallas
    mx.random.seed(99)
    draft = TransformerLM(vocab=V, units=8, hidden_size=16, num_layers=1,
                          num_heads=1, max_len=MAXLEN, dropout=0.0)
    draft.initialize()
    draft(NDArray(jnp.ones((1, 4), jnp.int32)))
    with ServingEngine(net, speculate_k=2, draft_net=draft, **kws) as eng:
        # serving_draft_prefill_chunk_float + serving_draft_step_float
        # + serving_spec_verify_float
        eng.submit(prompt, N).result(timeout=60)
    net.quantize_for_decode(act_quant="none")
    with ServingEngine(net, **kws) as eng:
        eng.submit(prompt, N).result(timeout=60)   # serving_*_int8
    from incubator_mxnet_tpu.models.hybrid_ssm import HybridSSMDecoder

    mx.random.seed(7)
    hybrid = HybridSSMDecoder(
        vocab_size=V, hidden_size=C, intermediate_size=DFF,
        num_hidden_layers=2, num_attention_heads=H, num_key_value_heads=1,
        attn_layer_period=2, attn_layer_offset=1, mamba_d_state=4,
        mamba_dt_rank=4, max_position_embeddings=MAXLEN, dtype="bfloat16")
    hybrid.initialize()
    with ServingEngine(hybrid, **kws) as eng:
        eng.submit(prompt, N).result(timeout=60)   # serving_*_float_ssm
    return (1, H, MAXLEN)


def collect_facts():
    """Compile the sixteen programs and return (facts_by_program, ctx)."""
    telemetry.enable()
    telemetry.perf.set_hlo_text_capture(True)
    _, _ = _train_program(zero=False)
    n_buckets, grad_bytes = _train_program(
        zero=True,
        checkpoint_dir=tempfile.mkdtemp(prefix="mxtpu_hlolint_ckpt_"))
    assert n_buckets and n_buckets >= 2, \
        f"bucket cap did not split the grads: {n_buckets}"
    weight_shapes = _decode_programs()
    probs_shape = _serving_programs()

    D = len(jax.devices())
    texts = telemetry.perf.hlo_texts()
    want = ("trainer_full_step", "trainer_full_step_zero_bucketed",
            "decode_float", "decode_int8", "checkpoint_snapshot",
            "serving_prefill_chunk_float", "serving_step_float",
            "serving_prefill_chunk_float_kv8", "serving_step_float_kv8",
            "serving_prefill_chunk_float_pallas",
            "serving_step_float_pallas",
            "serving_draft_prefill_chunk_float",
            "serving_draft_step_float",
            "serving_spec_verify_float",
            "serving_prefill_chunk_int8", "serving_step_int8",
            "serving_prefill_chunk_float_ssm", "serving_step_float_ssm")
    missing = [p for p in want if p not in texts]
    assert not missing, \
        f"programs not captured (telemetry text capture broken?): " \
        f"{missing}; have {sorted(texts)}"

    facts = {}
    for name in want:
        t = texts[name]
        module = hlolint.parse_hlo(t["hlo"])
        smod = hlolint.parse_stablehlo(t["stablehlo"]) \
            if "stablehlo" in t else None
        kw = {}
        if name.startswith("trainer"):
            kw = dict(axis_order=["data"], axis_sizes={"data": D})
        if name.endswith("int8"):
            kw = dict(weight_shapes=weight_shapes)
        if name in ("serving_step_float", "serving_step_float_pallas"):
            # "weight" census repurposed as a probs census: any f32
            # buffer shaped (B, H, max_seq_len) is the dense-gather
            # score/softmax materialization the kernel path eliminates
            kw = dict(weight_shapes=[probs_shape],
                      weight_float_dtypes=("f32",))
        facts[name] = hlolint.fact_summary(module, stablehlo=smod, **kw)
    ctx = {"D": D, "n_buckets": n_buckets, "grad_bytes": grad_bytes,
           "weight_shapes": [list(w) for w in weight_shapes],
           "probs_shape": list(probs_shape)}
    return facts, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--write-contracts", action="store_true",
                    help="bootstrap/refresh the contract file from the "
                         "current programs instead of gating")
    ap.add_argument("--facts-out",
                    help="also dump the fact summaries (JSON) here")
    args = ap.parse_args(argv)

    facts, ctx = collect_facts()
    if args.facts_out:
        with open(args.facts_out, "w", encoding="utf-8") as fh:
            json.dump({"facts": facts, "ctx": ctx}, fh, indent=2,
                      sort_keys=True)

    if args.write_contracts:
        doc = hlolint.bootstrap_contracts(facts, ctx=ctx)
        with open(CONTRACTS_PATH, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"hlolint gate: wrote bootstrap contracts for "
              f"{len(doc['programs'])} program(s) to {CONTRACTS_PATH} — "
              "review and tighten before committing")
        return 0

    contracts = hlolint.load_contracts(CONTRACTS_PATH)
    violations, uncontracted = hlolint.evaluate(contracts, facts, ctx=ctx)
    for v in violations:
        print(v.render())
    for name in uncontracted:
        print(f"{name}: HLO000 ({hlolint.RULES['HLO000']}) — add a "
              "contract under 'programs' or list it under 'accepted' "
              f"in {os.path.basename(CONTRACTS_PATH)}")
    n_checks = sum(len(p.get("checks", ()))
                   for p in contracts.get("programs", {}).values())
    if violations or uncontracted:
        print(f"hlolint gate: FAIL — {len(violations)} violation(s), "
              f"{len(uncontracted)} un-contracted program(s)")
        return 1
    print(f"hlolint gate: OK ({len(facts)} programs, {n_checks} "
          f"contract checks, ctx={ctx})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
