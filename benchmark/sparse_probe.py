"""On-chip probe of the two kernels of `ops/sparse_attention.py` that walk
a sequence's runs of pages, `index_scores` and `paged_attention_sparse`,
alone at the widths of `keye-vl-2-30b-a3b.serve-longdocs`: block 64, 528
blocks a sequence, a pool of 8,449; an index of 16 heads of 64 (rows of
128 lanes); 32 query heads on 4 KV heads of 128; a decode step's 16 lanes
and a chunk of 512 queries; bf16 pools, the index's head weights float32.

    python benchmark/sparse_probe.py   # needs one TPU chip

Prints JSON lines, each naming the device:

* ``time`` — milliseconds a call, ``CALLS`` calls of one kernel in one
  program on inputs that differ, median of ``REPS``, host clock around
  `block_until_ready`: the step at the cell's contexts (16 lanes,
  log-spaced 2,176-32,081, mean 11.5k); then the same live work over a
  table of 528 and of 264 entries (contexts 6,000-16,800, mean 11.4k; a
  chunk ending at 6,143), whose difference over the grid steps the
  longer table adds is **microseconds a dead grid step** of a form whose
  grid has a run axis (a form that loops over the live runs alone reads
  the same on both tables).
* ``digest`` — sha256 of each kernel's output on inputs made from a fixed
  key: the index's scores at or before each query's position (what lies
  behind it is unspecified), the attention's whole output.  Two trees
  that print the same digests return the same bits there.

A reading of one run, not a benchmark: no cell, no gate.
"""
import hashlib
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

_sp = importlib.import_module("incubator_mxnet_tpu.ops.sparse_attention")

N, BS, NBPS, NB = 16, 64, 528, 8449
HI, DI, H, HKV, D, TOPK, CHUNK = 16, 64, 32, 4, 128, 2048, 512
REPS, CALLS = 30, 8
CELL = onp.geomspace(2176, 32081, N).astype(int)
SHORT = onp.linspace(6000, 16800, N).astype(int)
CHUNK_LAST = 6143


def _device():
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _ms(fn, *args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times) / CALLS


def _inputs():
    ks = jax.random.split(jax.random.PRNGKey(43), 8)
    pool_i = jax.random.normal(ks[0], (NB, BS, _sp.index_row(DI)),
                               jnp.bfloat16).at[:, :, DI:].set(0)
    pool_k = jax.random.normal(ks[1], (NB, BS, HKV * D), jnp.bfloat16)
    pool_v = jax.random.normal(ks[2], (NB, BS, HKV * D), jnp.bfloat16)
    ids = onp.random.default_rng(43).permutation(NB - 1) + 1
    tables = jnp.asarray(ids[:N * NBPS].reshape(N, NBPS), jnp.int32)
    step = {"qi": jax.random.normal(ks[3], (CALLS, N, 1, HI, DI),
                                    jnp.bfloat16),
            "w": jax.random.normal(ks[4], (CALLS, N, 1, HI), jnp.float32),
            "q": jax.random.normal(ks[5], (CALLS, N, 1, H, D), jnp.bfloat16)}
    chunk = {"qi": jax.random.normal(ks[6], (CALLS, 1, CHUNK, HI, DI),
                                     jnp.bfloat16),
             "w": jax.random.normal(ks[7], (CALLS, 1, CHUNK, HI),
                                    jnp.float32),
             "q": jax.random.normal(ks[5], (CALLS, 1, CHUNK, H, D),
                                    jnp.bfloat16)}
    return pool_i, pool_k, pool_v, tables, step, chunk


def _positions(last, T):
    return last[:, None] - (T - 1) + jnp.arange(T, dtype=jnp.int32)[None]


@jax.jit
def _index(qi, w, pool_i, tables, last):
    return [_sp.index_scores(qi[c], w[c], pool_i, tables, last)
            for c in range(qi.shape[0])]


@jax.jit
def _select(scores, last):
    return _sp.select_positions(scores, _positions(last, scores.shape[1]),
                                TOPK)


@jax.jit
def _attend(q, pool_k, pool_v, tables, last, seen):
    return [_sp.paged_attention_sparse(q[c], pool_k, pool_v, tables, last,
                                       seen)
            for c in range(q.shape[0])]


def _digest(scores, out, last):
    s = onp.asarray(scores)
    s = onp.where(onp.arange(s.shape[-1]) <= onp.asarray(
        _positions(last, s.shape[1]))[..., None], s, 0.0)
    return {"index_scores": hashlib.sha256(s.tobytes()).hexdigest(),
            "paged_attention_sparse": hashlib.sha256(
                onp.asarray(out.astype(jnp.float32)).tobytes()).hexdigest()}


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("sparse_probe: no TPU")
    pool_i, pool_k, pool_v, tables, step, chunk = _inputs()
    dev = _device()
    cases = {
        "step_cell": (step, tables, CELL),
        "step_528": (step, tables, SHORT),
        "step_264": (step, tables[:, :NBPS // 2], SHORT),
        "chunk_528": (chunk, tables[:1], [CHUNK_LAST]),
        "chunk_264": (chunk, tables[:1, :NBPS // 2], [CHUNK_LAST]),
    }
    for name, (x, tb, last) in cases.items():
        last = jnp.asarray(last, jnp.int32)
        scores = _index(x["qi"], x["w"], pool_i, tb, last)
        seen = _select(scores[0], last)
        out = _attend(x["q"], pool_k, pool_v, tb, last, seen)
        if tb.shape[1] == NBPS:
            print(json.dumps({"digest": name, "device": dev,
                              **_digest(scores[0], out[0], last)}),
                  flush=True)
        print(json.dumps({
            "time": name, "device": dev, "table": tb.shape[1],
            "mean_context": float(onp.mean(onp.asarray(last) + 1)),
            "index_scores_ms": _ms(_index, x["qi"], x["w"], pool_i, tb,
                                   last),
            "paged_attention_sparse_ms": _ms(_attend, x["q"], pool_k,
                                             pool_v, tb, last, seen)}),
              flush=True)


if __name__ == "__main__":
    main()
