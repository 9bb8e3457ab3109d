"""On-chip probe of the paged-attention kernel alone, at the widths of
the two served cells:

* ``gpt2-medium`` (`gpt2-medium.serve-batch`): 32 lanes, 16 heads of 64,
  block 16, 64 blocks a sequence, a pool of 1025 blocks laid out
  ``(num_blocks, block_size, H*D)``;
* ``jamba2-3b`` (`jamba2-3b.serve-docs`): 64 lanes, 20 query heads on one
  KV head of 128, block 64, 44 blocks a sequence, a pool of 2817.

    python benchmark/paged_probe.py [seed]   # needs one TPU chip

Prints JSON lines, each naming the device:

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
* ``pages_per_step`` — one line a shape and a run length ``n`` in
  ``SWEEP`` (the kernel's rule `ops.paged_attention.pages_per_step`
  replaced by the constant for the line, and the line the rule itself
  picks marked ``"rule": true``): milliseconds a call, ``CALLS`` chained,
  with every run live, with one run a lane live, the same over a table
  twice as long (as many dead runs again and nothing else), and with
  lanes placed like the cell's; from them **microseconds a live run**
  (every run live: its copies land under the run before it) **and a
  dead run** (what the longer table adds), what a lane's one live run
  costs with nothing to hide its copies under, and beside them the
  bytes' floor of a live run (its K and V at the chip's peak,
  ``perf/peaks.json``).

A reading of one run, not a benchmark: no cell, no gate.
"""
import importlib
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

# ops/__init__ re-exports the function under the module's name
_paged = importlib.import_module("incubator_mxnet_tpu.ops.paged_attention")

B, H, D, BS, NBPS, NB = 32, 16, 64, 16, 64, 1025
REPS, CALLS = 50, 24
SWEEP = (1, 2, 4, 8, 16, 32)
PERF = os.path.join(os.path.dirname(__file__), "..", "perf")
TRAFFIC = os.path.join(PERF, "traffic", "serve-batch.json")
# (lanes, query heads, KV heads, head size, block, blocks a sequence,
#  blocks in the pool, the cell's traffic)
SHAPES = {
    "gpt2-medium": (B, H, H, D, BS, NBPS, NB, "serve-batch"),
    "jamba2-3b": (64, 20, 1, 128, 64, 44, 2817, "serve-docs"),
}


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


def _cell_lanes(rs, traffic=TRAFFIC, B=B, BS=BS, NBPS=NBPS, NB=NB):
    """Block tables and positions as the cell's traffic leaves them."""
    with open(traffic) as f:
        mix = json.load(f)
    prompt, output = (
        onp.exp(rs.uniform(onp.log(mix[key]["lo"]), onp.log(mix[key]["hi"]),
                           B)).astype(int)
        for key in ("prompt_len", "output_len"))
    output = onp.minimum(output, mix["max_total"] - prompt)
    pos = prompt + (output * rs.uniform(0, 1, B)).astype(int)
    reserved = onp.minimum(-(-(prompt + output) // BS), NBPS)
    ids = rs.permutation(NB - 1) + 1
    ends = onp.cumsum(reserved)
    tables = onp.zeros((B, NBPS), onp.int32)   # the rest: the scratch block
    for lane, (lo, hi) in enumerate(zip(ends - reserved, ends)):
        tables[lane, :hi - lo] = ids[lo:hi]
    return (jnp.asarray(tables), jnp.asarray(pos.clip(0, NBPS * BS - 1),
                                             jnp.int32))


def _sweep(rs, name):
    """The ``pages_per_step`` lines of one shape."""
    B, H, Hkv, D, BS, NBPS, NB, traffic = SHAPES[name]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
    pk = jax.random.normal(kk, (NB, BS, Hkv * D), jnp.bfloat16)
    pv = jax.random.normal(kv, (NB, BS, Hkv * D), jnp.bfloat16)
    # distinct pages as far as the pool has them, then the same again
    own = jnp.asarray(rs.permutation(B * NBPS) % (NB - 1) + 1,
                      jnp.int32).reshape(B, NBPS)
    last = jnp.full((B,), NBPS * BS - 1, jnp.int32)
    first = jnp.zeros((B,), jnp.int32)
    placed = {
        "all_live": (own, last),
        "one_run_live": (own, first),
        "one_run_live_table_twice": (jnp.concatenate([own, own], 1), first),
        "cell": _cell_lanes(rs, os.path.join(PERF, "traffic",
                                             traffic + ".json"),
                            B, BS, NBPS, NB)}
    with open(os.path.join(PERF, "peaks.json")) as f:
        hbm = json.load(f)["devices"][jax.devices()[0].device_kind][
            "hbm_bytes_per_s"]
    rule = _paged.pages_per_step
    picked = rule(BS, NBPS, Hkv * D * 2)
    try:
        for n in SWEEP:
            _paged.pages_per_step = lambda *shape, n=n: n

            @jax.jit
            def program(q, pk, pv, tables, pos):
                for _ in range(CALLS):
                    q = _paged._paged_call(q, (pk, pv), tables, pos, False)
                return q

            ms = {key: _ms(program, q, pk, pv, tables, pos) / CALLS
                  for key, (tables, pos) in placed.items()}
            runs, runs_twice = -(-NBPS // n), -(-2 * NBPS // n)
            dead_us = 1e3 * (ms["one_run_live_table_twice"]
                             - ms["one_run_live"]) / (B * (runs_twice - runs))
            print(json.dumps({
                "probe": "pages_per_step", "shape": name, "n": n,
                "rule": n == picked, "device": _device(),
                "grid_steps_a_call": B * runs, "ms_a_call": ms,
                "cell_pages_live": int((placed["cell"][1] // BS + 1).sum()),
                "us_a_live_run": 1e3 * ms["all_live"] / (B * runs),
                "us_a_dead_run": dead_us,
                "us_a_lone_live_run":
                    1e3 * ms["one_run_live"] / B - (runs - 1) * dead_us,
                "us_bytes_floor_a_live_run":
                    1e6 * 2 * n * BS * Hkv * D * 2 / hbm}),
                flush=True)
    finally:
        _paged.pages_per_step = rule


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

    for name in SHAPES:
        _sweep(rs, name)


if __name__ == "__main__":
    main()
