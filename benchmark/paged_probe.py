"""On-chip probe of the paged-attention kernel alone, at the served
cell's widths (B=32 lanes, H=16, D=64, block 16, 64 blocks a sequence,
a pool of 1025 blocks laid out ``(num_blocks, block_size, H*D)``).

    python benchmark/paged_probe.py [seed]   # needs one TPU chip

Prints three JSON lines, each naming the device:

* ``numerics`` — max |x − truth| on random bf16 data with every page
  live, where truth is the dense gather on fp32 copies at matmul
  precision "highest": the kernel traced at "highest" (its math), the
  kernel at the default precision on fp32 copies and on the bf16 pool
  itself (what the engine runs), the dense gather at the default
  precision (what the kernel replaced), and the kernel over an int8 pool
  against the dequantized truth.
* ``time`` — median milliseconds of one call over ``REPS`` calls, host
  clock around `block_until_ready`, every page live, for the kernel and
  the dense gather on the bf16 pool, with the bytes of the pages the
  lanes attend to.
* ``time_in_a_program`` — milliseconds a call when ``CALLS`` calls feed
  one another inside one program, as a served program's 24 layers do,
  with lanes placed like the cell's (prompt and output lengths
  log-uniform over the ranges of ``perf/traffic/serve-batch.json``, a
  lane somewhere along its output, blocks reserved for the whole
  request): what `decode_step_ms` holds of the kernel.

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

B, H, D, BS, NBPS, NB = 32, 16, 64, 16, 64, 1025
REPS, CALLS = 50, 24
TRAFFIC = os.path.join(os.path.dirname(__file__), "..", "perf", "traffic",
                       "serve-batch.json")


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


def _cell_lanes(rs):
    """Block tables and positions as the cell's traffic leaves them."""
    with open(TRAFFIC) as f:
        mix = json.load(f)
    prompt, output = (
        onp.exp(rs.uniform(onp.log(mix[key]["lo"]), onp.log(mix[key]["hi"]),
                           B)).astype(int)
        for key in ("prompt_len", "output_len"))
    pos = prompt + (output * rs.uniform(0, 1, B)).astype(int)
    reserved = onp.minimum(-(-(prompt + output) // BS), NBPS)
    ids = rs.permutation(NB - 1) + 1
    ends = onp.cumsum(reserved)
    tables = onp.zeros((B, NBPS), onp.int32)   # the rest: the scratch block
    for lane, (lo, hi) in enumerate(zip(ends - reserved, ends)):
        tables[lane, :hi - lo] = ids[lo:hi]
    return (jnp.asarray(tables), jnp.asarray(pos.clip(0, NBPS * BS - 1),
                                             jnp.int32))


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_probe.py needs a TPU")
    rs = onp.random.RandomState(int(sys.argv[1]) if len(sys.argv) > 1 else 0)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    pk = jax.random.normal(kk, (NB, BS, H * D), jnp.bfloat16)
    pv = jax.random.normal(kv, (NB, BS, H * D), jnp.bfloat16)
    # every page live: half a lane's blocks its own, walked twice
    own = rs.permutation(NB - 1)[:B * NBPS // 2].reshape(B, NBPS // 2) + 1
    tables = jnp.asarray(onp.concatenate([own, own], axis=1), jnp.int32)
    pos = jnp.full((B,), NBPS * BS - 1, jnp.int32)
    q32, pk32, pv32 = (x.astype(jnp.float32) for x in (q, pk, pv))

    def kernel(*a, **kw):
        return paged_attention(*a, tables, pos, impl="pallas", **kw)

    with jax.default_matmul_precision("highest"):
        truth = paged_attention_dense(q32, pk32, pv32, tables, pos)
        at_highest = _err(kernel(q32, pk32, pv32), truth)
    heads = (NB, BS, H, D)               # one scale a (slot, head)
    k8, sk = quantize_kv(pk32.reshape(heads))
    v8, sv = quantize_kv(pv32.reshape(heads))
    k8, v8 = k8.reshape(pk.shape), v8.reshape(pv.shape)
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

    cell_tables, cell_pos = _cell_lanes(rs)

    @jax.jit
    def program(q, pk, pv):
        for _ in range(CALLS):
            q = paged_attention(q, pk, pv, cell_tables, cell_pos,
                                impl="pallas")
        return q

    print(json.dumps({"probe": "time_in_a_program", "device": _device(),
                      "reps": REPS, "calls_a_program": CALLS,
                      "pages_live": int((cell_pos // BS + 1).sum()),
                      "pages_walked": B * NBPS,
                      "ms_a_call_kernel_bf16": _ms(program, q, pk, pv)
                      / CALLS}), flush=True)


if __name__ == "__main__":
    main()
