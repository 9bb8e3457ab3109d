"""KV-cache decode throughput — single chip, one compiled program.

    python benchmark/generate_bench.py [B] [P] [N] [--no-quant] [--act-quant=auto|none|dynamic]

TransformerLM at the longctx-bench size (12L/1024D/V=32k); reports
prefill+decode wall time and decoded tokens/s for the bf16 path AND
the int8 weight-quantized path (`quantize_for_decode` — per-channel
int8 weights streamed through the decode matmuls, dequant in the
epilogue), plus the per-step weight bytes each path streams
(`decode_weight_bytes` telemetry).  Small-batch decode is
weight-streaming-bound, so the quantized column is the headline: the
ISSUE 7 target is B=1 step time <= 0.6x bf16.

The inference-side counterpart of `benchmark/longctx_bench.py`'s
training rows.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp

V, D, DFF, L, H = 32000, 1024, 4096, 12, 16


def _time_generate(net, prompt, N, reps, **kw):
    import numpy as onp

    out = net.generate(prompt, N, **kw)  # compile
    onp.asarray(out)
    t0 = time.perf_counter()
    for i in range(reps):
        out = net.generate(prompt, N, seed=i, **kw)
        onp.asarray(out[:, -1])
    return (time.perf_counter() - t0) / reps


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    B = int(args[0]) if len(args) > 0 else 8
    P = int(args[1]) if len(args) > 1 else 128
    N = int(args[2]) if len(args) > 2 else 128
    with_quant = "--no-quant" not in sys.argv
    aq = next((a.split("=", 1)[1] for a in sys.argv[1:]
               if a.startswith("--act-quant=")), "auto")

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    mx.random.seed(0)
    net = TransformerLM(vocab=V, units=D, hidden_size=DFF, num_layers=L,
                        num_heads=H, max_len=P + N, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((B, 16), jnp.int32)))
    net.cast("bfloat16")

    prompt = jax.random.randint(jax.random.PRNGKey(0), (B, P), 0, V,
                                dtype=jnp.int32)
    reps = 3
    telemetry.enable()
    reg = telemetry.get_registry()

    dt = _time_generate(net, prompt, N, reps)
    w_f = reg.get("decode_weight_bytes", {"path": "float"}).value
    print(f"TransformerLM {L}L/{D}D V={V} bf16, B={B} P={P} N={N}: "
          f"{dt*1e3:.1f} ms/gen = {B*N/dt:.0f} decoded tok/s "
          f"({dt/N*1e3:.2f} ms/token-step, batch {B}; "
          f"streams {w_f/1e6:.0f} MB weights/step)")
    if not with_quant:
        return

    net.quantize_for_decode(act_quant=aq)
    qdt = _time_generate(net, prompt, N, reps)
    w_q = reg.get("decode_weight_bytes", {"path": "int8"}).value
    qc = net._decode_quant
    print(f"TransformerLM {L}L/{D}D V={V} int8-weight "
          f"(act_quant={qc.act_quant}), B={B} P={P} N={N}: "
          f"{qdt*1e3:.1f} ms/gen = {B*N/qdt:.0f} decoded tok/s "
          f"({qdt/N*1e3:.2f} ms/token-step, batch {B}; "
          f"streams {w_q/1e6:.0f} MB weights/step)")
    print(f"quantized/bf16 step-time ratio: {qdt/dt:.2f}x "
          f"(target <= 0.60x at B=1); weight bytes {w_q/w_f:.2f}x")


if __name__ == "__main__":
    main()
