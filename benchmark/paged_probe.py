"""On-chip probe of the paged-attention kernel alone, at the serve
phase's widths (B=32 lanes, H=16, D=64, block 16, 512 positions).

    python benchmark/paged_probe.py          # needs one TPU chip

Prints two JSON lines, each naming the device:

* ``numerics`` — max |x − truth| on random bf16 data, where truth is the
  dense gather on fp32 copies at matmul precision "highest": the kernel
  traced at "highest" (its math), the kernel at the default precision on
  fp32 copies and on the bf16 pool itself (what the engine runs), the
  dense gather at the default precision (what the kernel replaced), and
  the kernel over an int8 pool against the dequantized truth.
* ``time`` — median milliseconds of one call over ``REPS`` calls, host
  clock around `block_until_ready`, for the kernel and the dense gather
  on the bf16 pool, with the bytes of the pages the lanes attend to.

A reading of one run, not a benchmark: no cell, no gate.
"""
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as onp

from incubator_mxnet_tpu.contrib.quantization import quantize_kv
from incubator_mxnet_tpu.ops.paged_attention import (paged_attention,
                                                     paged_attention_dense)

B, H, D, BS, NBPS = 32, 16, 64, 16, 32
REPS = 50


def _device():
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))


def _ms(fn, *args):
    fn(*args).block_until_ready()
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_probe.py needs a TPU")
    nb = B * NBPS + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    pk = jax.random.normal(kk, (nb, H, BS, D), jnp.bfloat16)
    pv = jax.random.normal(kv, (nb, H, BS, D), jnp.bfloat16)
    tables = jnp.asarray(onp.random.RandomState(0).permutation(nb - 1)
                         .reshape(B, NBPS) + 1, jnp.int32)
    pos = jnp.full((B,), NBPS * BS - 1, jnp.int32)   # every page is live
    q32, pk32, pv32 = (x.astype(jnp.float32) for x in (q, pk, pv))

    def kernel(*a, **kw):
        return paged_attention(*a, tables, pos, impl="pallas", **kw)

    with jax.default_matmul_precision("highest"):
        truth = paged_attention_dense(q32, pk32, pv32, tables, pos)
        at_highest = _err(kernel(q32, pk32, pv32), truth)
    k8, sk = quantize_kv(pk32)
    v8, sv = quantize_kv(pv32)
    with jax.default_matmul_precision("highest"):
        truth8 = paged_attention_dense(q32, k8, v8, tables, pos, sk, sv)
    print(json.dumps({"probe": "numerics", "device": _device(),
                      "kernel_fp32_in_highest": at_highest,
                      "kernel_fp32_in_default": _err(
                          kernel(q32, pk32, pv32), truth),
                      "kernel_bf16_in_default": _err(kernel(q, pk, pv), truth),
                      "dense_bf16_in_default": _err(
                          paged_attention_dense(q, pk, pv, tables, pos),
                          truth),
                      "kernel_int8_pool_default": _err(
                          kernel(q32, k8, v8, scale_k=sk, scale_v=sv),
                          truth8)}), flush=True)

    dense = jax.jit(paged_attention_dense)
    print(json.dumps({"probe": "time", "device": _device(), "reps": REPS,
                      "median_ms_kernel_bf16": _ms(
                          jax.jit(lambda *a: kernel(*a)), q, pk, pv),
                      "median_ms_dense_bf16": _ms(dense, q, pk, pv, tables,
                                                  pos),
                      "page_bytes_attended": 2 * B * NBPS * H * BS * D * 2}),
          flush=True)


if __name__ == "__main__":
    main()
