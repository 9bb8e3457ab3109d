"""Attention-core formulation probe at flagship shapes (B32 H16 T128 D64).

The bert_ablate.py noattn variant shows the attention core (scores +
softmax + PV, NOT the QKV/out projections) costs ~8 ms of the 70 ms
flagship step.  The product XLA path (`attention_reference`) upcasts
q/k/v to fp32 — fp32 einsums run the MXU at a fraction of the bf16
rate — and the model materializes (B,T,H,D)->(B,H,T,D) transposes.
This probe measures candidate formulations fwd+bwd, K iterations
chained in one jit (conv_probe methodology), with max|Δ| vs the fp32
oracle so wins can be adopted with eyes open:

  ref       product path today: transpose to (B,H,T,D), fp32 einsums
  bf16acc   (B,H,T,D) layout, bf16 einsum inputs + f32 accumulation
            (preferred_element_type) — exact for bf16-exact inputs
  bf16p     bf16acc + P cast to bf16 for the PV einsum (flash-kernel
            convention; rounds P at ~2^-9)
  notrans   bf16p formulated directly on (B,T,H,D) — no transposes
  pallas    the Pallas flash kernel forced on (below its crossover)
"""
import functools
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
from jax import lax

B, H, T, D = 32, 16, 128, 64
C = H * D
K = 96  # chained iterations per timed program (amortizes the fetch;
        # the `null` row measures it)
REPS = 5
SCALE = 1.0 / math.sqrt(D)


def ref_core(qkv):
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * SCALE
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(qkv.dtype)
    return o.transpose(0, 2, 1, 3).reshape(B, T, C)


def bf16acc_core(qkv):
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * SCALE
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(qkv.dtype)
    return o.transpose(0, 2, 1, 3).reshape(B, T, C)


def bf16p_core(qkv):
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * SCALE
    p = jax.nn.softmax(s, axis=-1).astype(qkv.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v,
                   preferred_element_type=jnp.float32).astype(qkv.dtype)
    return o.transpose(0, 2, 1, 3).reshape(B, T, C)


def notrans_core(qkv):
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, D)
    k = k.reshape(B, T, H, D)
    v = v.reshape(B, T, H, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * SCALE
    p = jax.nn.softmax(s, axis=-1).astype(qkv.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   preferred_element_type=jnp.float32).astype(qkv.dtype)
    return o.reshape(B, T, C)


def pallas_core(qkv):
    import incubator_mxnet_tpu.ops.flash_attention  # noqa: F401 — module
    fa = sys.modules["incubator_mxnet_tpu.ops.flash_attention"]
    fa._PALLAS_FWD_MIN_SCORES = 0
    fa._PALLAS_BWD_MIN_SCORES = 0

    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, H, D).transpose(0, 2, 1, 3)
    o = fa.flash_attention(q, k, v, causal=False, block_q=128, block_k=128)
    return o.transpose(0, 2, 1, 3).reshape(B, T, C)


def null_core(qkv):
    # dispatch/fetch overhead baseline: same signature, trivial compute
    return qkv[..., :C] * 1.0000001


CORES = {"null": null_core, "ref": ref_core, "bf16acc": bf16acc_core,
         "bf16p": bf16p_core, "notrans": notrans_core, "pallas": pallas_core}


def measure(name):
    core = CORES[name]

    def one(qkv, dy):
        # loss = <attend(qkv), dy> gives grad wrt qkv == full bwd pass
        out, vjp = jax.vjp(core, qkv)
        (dqkv,) = vjp(dy)
        return out, dqkv

    @jax.jit
    def chained(qkv, dy):
        def body(carry, _):
            q = carry
            out, dq = one(q, dy)
            # feed outputs forward so nothing is dead-code eliminated
            nq = jnp.concatenate([out, out, out], -1) * 1e-6 + q + dq * 1e-6
            return nq, ()

        final, _ = lax.scan(body, qkv, None, length=K)
        # scalar result: one small value fetch ends the timed region
        return final.astype(jnp.float32).sum()

    key = jax.random.PRNGKey(0)
    qkv = jax.random.normal(key, (B, T, 3 * C), jnp.bfloat16)
    dy = jax.random.normal(jax.random.PRNGKey(1), (B, T, C), jnp.bfloat16)

    float(chained(qkv, dy))  # compile + warm
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        float(chained(qkv, dy))
        best = min(best, time.perf_counter() - t0)
    ms = best / K * 1e3

    # numerics vs the fp32 oracle (fwd only, single call)
    o = jax.jit(core)(qkv)
    o_ref = jax.jit(ref_core)(qkv)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - o_ref.astype(jnp.float32))))
    return ms, err


def main():
    names = sys.argv[1:] or list(CORES)
    print(f"B={B} H={H} T={T} D={D}  K={K} chained, per-layer fwd+bwd ms")
    overhead = 0.0
    base = None
    for n in names:
        ms, err = measure(n)
        if n == "null":
            overhead = ms
            print(f"{n:>8}: {ms:6.3f} ms/iter dispatch+fetch overhead",
                  flush=True)
            continue
        net = ms - overhead
        if base is None:
            base = net
        print(f"{n:>8}: {net:6.3f} ms/layer  x24={net*24:6.2f} ms  "
              f"maxerr={err:.2e}  vs ref {net/base*100:5.1f}%", flush=True)


if __name__ == "__main__":
    main()
