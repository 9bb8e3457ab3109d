"""Per-kernel flash-attention device profile at long T (r4 VERDICT #2).

Times each Pallas kernel (fwd resident/streamed, bwd dK/dV, bwd dQ) in
isolation with the chained-scan methodology (K invocations inside one
jit, one value fetch) and reports achieved TF/s against the causal
attention FLOPs each kernel actually performs:

    fwd:    2·B·H·T²·D  (QKᵀ + PV, ×½ causal)
    dK/dV:  4·B·H·T²·D  (S, dP, dV, dK dots, ×½ causal)
    dQ:     3·B·H·T²·D  (S, dP, dS·K dots, ×½ causal)

Run ON THE TPU, one T per process (HBM fragmentation accumulates):

    python benchmark/flash_profile.py 8192
    python benchmark/flash_profile.py 16384 32768
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

H, D = 16, 64
REPS, K = 3, 32


def _time_chained(fn, args, flops, program=None):
    """K invocations chained in one jit; fetch once.  Returns (ms, tfs).

    The body DEPENDS on the scan carry (q is perturbed by a zero that
    XLA cannot prove zero-valued at trace time), so the kernel cannot
    be hoisted out of the loop; K=32 amortizes the d2h fetch, which
    the null variant subtracts.

    With telemetry enabled and a `program` name, the chained program's
    cost/memory analysis and best measured wall land in the
    telemetry.perf roofline attribution (tools/roofline_report.py's
    table format; one scan-body execution per the XLA cost model)."""

    @jax.jit
    def multi(*a):
        def body(c, _):
            perturbed = (a[0] + c.astype(a[0].dtype),) + tuple(a[1:])
            out = fn(*perturbed)[0]
            return out[0, 0, 0, 0].astype(jnp.float32) * 0.0, ()

        c, _ys = lax.scan(body, jnp.float32(0.0), None, length=K)
        return c

    @jax.jit
    def null(*a):  # same fetch + loop skeleton, no kernel
        def body(c, _):
            return c * 1.0000001, ()

        c, _ys = lax.scan(body, jnp.float32(0.0), None, length=K)
        return c + a[0][0, 0, 0, 0].astype(jnp.float32) * 0

    float(multi(*args))
    float(null(*args))
    t_null = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        float(null(*args))
        t_null = min(t_null, time.perf_counter() - t0)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        float(multi(*args))
        best = min(best, (time.perf_counter() - t0 - t_null) / K)
    best = max(best, 1e-6)  # fetch jitter must never yield <=0
    if program is not None:
        from incubator_mxnet_tpu import telemetry

        if telemetry.enabled():
            telemetry.perf.capture(program, multi, *args)
            telemetry.perf.note_timing(program, best)
    return best * 1e3, flops / best / 1e12


def main():
    import importlib

    fa = importlib.import_module("incubator_mxnet_tpu.ops.flash_attention")

    Ts = [int(a) for a in sys.argv[1:]] or [8192]
    for T in Ts:
        B = max(1, 2 * 8192 // T)
        scale = 1.0 / math.sqrt(D)
        key = jax.random.PRNGKey(0)
        q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i),
                                         (B, H, T, D), jnp.bfloat16)
                       for i in range(4))
        causal_flops = B * H * T * T * D  # 2·T²·D·BH × ½ causal

        bq = fa._auto_block(T, None)
        resident = T * D * 2 <= fa._KV_RESIDENT_MAX_BYTES
        fwd = functools.partial(fa._flash_core, causal=True, scale=scale,
                                block_q=bq, block_k=bq, interpret=False)
        ms, tfs = _time_chained(lambda a, b, c: fwd(a, b, c),
                                (q, k, v), 2 * causal_flops,
                                program=f"flash_fwd_T{T}")
        print(f"T={T} B={B} fwd[{'resident' if resident else 'streamed'}] "
              f"bq=bk={bq}: {ms:.2f} ms  {tfs:.1f} TF/s", flush=True)

        out, lse = fwd(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        bqb = max(bq, 512)

        def bwd(qq, kk, vv, dd):
            return fa._flash_bwd_core(qq, kk, vv, dd, lse, delta,
                                      causal=True, scale=scale, block_q=bqb,
                                      block_k=bqb, interpret=False)

        ms, tfs = _time_chained(lambda a, b, c, d: (bwd(a, b, c, d)[1],),
                                (q, k, v, do), 7 * causal_flops,
                                program=f"flash_bwd_T{T}")
        print(f"T={T} B={B} bwd[dkdv+dq] bq=bk={bqb}: {ms:.2f} ms  "
              f"{tfs:.1f} TF/s (combined)", flush=True)


if __name__ == "__main__":
    main()
