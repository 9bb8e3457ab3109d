"""Long-context training benchmark — single chip, flash-attention path.

SURVEY.md §5.7 makes long context a first-class capability; this
measures it END-TO-END through the public Gluon loop (same path as
bench.py): a decoder-only TransformerLM at T=32768 — 64x the
reference's fused-attention ceiling (T<=512, BASELINE.md) — trains on
ONE v5e chip because the Pallas flash kernels keep attention memory
O(T) and the streamed xent kernel never materializes the (B*T, 32k)
fp32 log-prob tensor.

    python benchmark/longctx_bench.py [T ...]   (default 2048 8192 32768)

Prints tok/s and MFU per config (attention FLOPs 12*L*T*D dominate at
long T, so MFU here exercises the flash kernels, not the matmuls).

The forward dispatches between a whole-KV-VMEM-resident kernel (below
~1 MB per K/V tensor — fastest) and a streamed-KV grid kernel beyond
it, so a single chip trains T=32k+; sequence sharding (ring attention,
docs/long_context.md §2) scales past a chip's HBM.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp

SIZE = V, D, DFF, L, H = 32000, 1024, 4096, 12, 16
STEPS, WARMUP = 10, 2


def build(T: int, B: int, dropout: float = 0.1, *, capture_hlo=False,
          size=SIZE):
    """The long-context LM, its Trainer and one fixed random batch,
    through the public Gluon loop.  Returns a namespace with ``net``,
    ``trainer`` and ``step()`` (one record/backward/step, returns the
    loss).  `chip_smoke.py` drives the same builder."""
    import types

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import Trainer
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    v, d, dff, n_layers, heads = size
    mx.random.seed(0)
    net = TransformerLM(vocab=v, units=d, hidden_size=dff,
                        num_layers=n_layers, num_heads=heads, max_len=T,
                        dropout=dropout)
    net.initialize()
    # materialize deferred shapes with a SHORT sequence: the params are
    # still f32 here, and an f32 flash kernel at T=8192 exceeds VMEM
    net(NDArray(jnp.ones((B, min(T, 128)), jnp.int32)))
    net.cast("bfloat16")

    class LMWithLoss(HybridBlock):
        def __init__(self, net_, **kw):
            super().__init__(**kw)
            self.net = net_
            self.loss = SoftmaxCrossEntropyLoss()

        def forward(self, tokens, labels):
            return self.loss(self.net(tokens), labels).mean()

    model = LMWithLoss(net)
    # beyond T=32k the saved-activation set (12 layers of (1, T, 4096)
    # bf16 FFN hiddens alone = T/32k * 6 GB) exceeds one chip's HBM:
    # rematerialize the forward inside the backward (docs/long_context.md
    # §3) — FLOPs for memory, the standard long-context trade
    model.hybridize(remat_backward=T > 32768)
    trainer = Trainer(model.collect_params(), "sgd",
                      {"learning_rate": 1e-3, "momentum": 0.9,
                       "multi_precision": True}, keep_grads=False)
    trainer._capture_hlo = capture_hlo
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    tokens = NDArray(jax.random.randint(kx, (B, T), 0, v, dtype=jnp.int32))
    labels = NDArray(jax.random.randint(ky, (B, T), 0, v, dtype=jnp.int32))

    def step():
        with autograd.record():
            loss = model(tokens, labels)
        loss.backward()
        trainer.step(1)
        return loss

    return types.SimpleNamespace(net=net, trainer=trainer, step=step)


def measure(T: int, B: int, dropout: float = 0.1):
    from incubator_mxnet_tpu.callback import device_peak_flops

    built = build(T, B, dropout)
    net, step = built.net, built.step

    for _ in range(WARMUP):
        loss = step()
    float(loss.asnumpy())
    t0 = time.perf_counter()
    for _ in range(STEPS):
        loss = step()
    final = float(loss.asnumpy())
    dt = time.perf_counter() - t0

    toks = B * T * STEPS / dt
    n_params = sum(p.data().size for p in net.collect_params().values()
                   if p.grad_req != "null")
    n_embed = V * D  # the output head is a real matmul, counted
    flops_per_token = 6 * (n_params - n_embed) + 12 * L * T * D
    mfu = toks * flops_per_token / device_peak_flops(jax.devices()[0])
    return toks, mfu, final, flops_per_token


def main():
    Ts = [int(a) for a in sys.argv[1:]] or [2048, 8192, 32768]
    print(f"TransformerLM V={V} D={D} L={L} H={H}, bf16 + fp32 masters, "
          f"dropout=0.1, public Gluon loop")
    for T in Ts:
        B = max(1, 16384 // T)
        toks, mfu, loss, fpt = measure(T, B)
        print(f"T={T:6d} B={B}: {toks:8.0f} tok/s  {mfu*100:5.2f}% MFU  "
              f"(attn share of FLOPs {12*L*T*D/fpt*100:.0f}%, "
              f"final_loss {loss:.3f})", flush=True)


if __name__ == "__main__":
    main()
