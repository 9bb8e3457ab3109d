"""Decode-side paged-attention kernel stack (`ops/paged_attention.py`,
ISSUE 15): single-query Pallas kernel + int8 KV pools + small-T fused
attention.

The load-bearing contracts:

* **Kernel/dense parity** — the online-softmax Pallas kernel (grid over
  (lane, run of pages), KV pages read straight from the pool) agrees
  with the dense-gather reference to fp32 roundoff for ragged per-lane
  lengths and permuted block tables, in f32 and bf16, with and without
  int8 pages, with one run a lane and with several, whole or padded.
* **Runs of pages** — a run's pages past the lane's position are never
  read, and a position is the same bits as a step's lane and as a lane
  of a chunk, wherever the chunk began.
* **One layout** — the pool is ``(num_blocks, block_size, H*D)``; the
  serving programs' write, the kernel and the dense recipe read a
  position as the same bytes, and the dense recipe's output is bit for
  bit what it was over the ``(num_blocks, H, block_size, D)`` pool.
* **Path isolation** — an engine runs ONE attention impl for its whole
  life; within the forced-pallas path eviction bit-identity holds
  exactly, and across paths greedy tokens agree (dispatch never mixes
  impls, so the cheaper CPU contract — byte-identity on the dense
  default — is pinned in test_serving.py and untouched here).
* **int8 KV quality/capacity** — engine-level greedy parity >= 95% vs
  the float-KV engine, teacher-forced perplexity delta <= 0.5% under
  KV fake-quant, and >= 1.8x resident sequences at equal pool bytes vs
  bf16 KV.
* **Small-T fused path** — `attention_small_t` matches the reference
  within bf16 tolerance and its dispatch gate only opens on TPU below
  the Pallas crossover.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.contrib.quantization import quantize_kv
from incubator_mxnet_tpu.models import generation as G
from incubator_mxnet_tpu.models.transformer import TransformerLM
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.ops.flash_attention import (_use_small_t,
                                                     attention_reference,
                                                     attention_small_t,
                                                     flash_attention)
from incubator_mxnet_tpu.ops.paged_attention import (default_impl,
                                                     paged_attention,
                                                     paged_attention_dense)
from incubator_mxnet_tpu.serving import ServingEngine

V, C, DFF, L, H, MAXLEN = 61, 16, 32, 1, 2, 64
P1 = onp.array([3, 7, 11, 2, 9], onp.int32)
P2 = onp.array([5, 1, 2], onp.int32)
_POLL = 0.001


# --------------------------------------------------------------------- #
# kernel-level parity vs the dense-gather reference
# --------------------------------------------------------------------- #
def _rand_pool(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


def _pages(x):
    """Keys or values ``(num_blocks, H, bs, D)``, or their scales
    ``(num_blocks, H, bs)``, laid out as the pool holds them: a position
    a row, ``(num_blocks, bs, H*D)`` or ``(num_blocks, bs, H)``."""
    x = jnp.swapaxes(x, 1, 2)
    return x.reshape(x.shape[:2] + (-1,))


def _paged_case(seed, B=3, heads=2, D=16, bs=8, nbps=4, dtype=jnp.float32):
    """Random K/V by (block, head, slot) + permuted tables + ragged
    per-lane positions."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    nblocks = B * nbps + 3  # spare blocks hold garbage the walk must skip
    k = _rand_pool(keys[0], (nblocks, heads, bs, D), dtype)
    v = _rand_pool(keys[1], (nblocks, heads, bs, D), dtype)
    q = _rand_pool(keys[2], (B, heads, D), dtype)
    tables = jax.random.permutation(keys[3],
                                    jnp.arange(B * nbps, dtype=jnp.int32))
    tables = tables.reshape(B, nbps)
    # ragged: lane 0 one token, lane 1 mid-block, lane 2 pool-full
    pos = jnp.array([0, bs + 3, bs * nbps - 1][:B], jnp.int32)
    return q, k, v, tables, pos


def _former_dense(q, k, v, tables, pos, scale_k=None, scale_v=None):
    """`paged_attention_dense` as it stood while the pool was
    ``(num_blocks, H, bs, D)``, kept here word for word: what the CPU
    engines' eviction and greedy-parity contracts were pinned on."""
    B, nbps = tables.shape
    H, bs, D = k.shape[1], k.shape[2], k.shape[3]
    W = nbps * bs
    if scale_k is not None:
        gk = k[tables].astype(jnp.float32) * scale_k[tables][..., None]
        gv = v[tables].astype(jnp.float32) * scale_v[tables][..., None]
        gk = gk.transpose(0, 2, 1, 3, 4).reshape(B, H, W, D)
        gv = gv.transpose(0, 2, 1, 3, 4).reshape(B, H, W, D)
    else:
        gk = k[tables].transpose(0, 2, 1, 3, 4).reshape(B, H, W, D)
        gv = v[tables].transpose(0, 2, 1, 3, 4).reshape(B, H, W, D)
    s = jnp.einsum("bhd,bhkd->bhk", q, gk,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(kpos <= pos[:, None, None], s,
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p, gv,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_pallas_kernel_matches_dense_ragged(dtype, tol):
    q, k, v, tables, pos = _paged_case(0, dtype=dtype)
    pk, pv = _pages(k), _pages(v)
    dense = paged_attention(q, pk, pv, tables, pos, impl="dense")
    pallas = paged_attention(q, pk, pv, tables, pos, impl="pallas",
                             interpret=True)
    assert pallas.dtype == q.dtype and pallas.shape == q.shape
    onp.testing.assert_allclose(onp.asarray(pallas, onp.float32),
                                onp.asarray(dense, onp.float32), atol=tol)


def test_pallas_kernel_matches_dense_int8_pages():
    q, k, v, tables, pos = _paged_case(1)
    qk, sk = quantize_kv(k)
    qv, sv = quantize_kv(v)
    pools = (_pages(qk), _pages(qv))
    scales = dict(scale_k=_pages(sk), scale_v=_pages(sv))
    dense = paged_attention(q, *pools, tables, pos, impl="dense", **scales)
    pallas = paged_attention(q, *pools, tables, pos, impl="pallas",
                             interpret=True, **scales)
    onp.testing.assert_allclose(onp.asarray(pallas), onp.asarray(dense),
                                atol=2e-5)
    # quantization error itself stays small vs the float pool
    ref = paged_attention(q, _pages(k), _pages(v), tables, pos, impl="dense")
    onp.testing.assert_allclose(onp.asarray(dense), onp.asarray(ref),
                                atol=0.05)


@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
def test_dense_recipe_is_bit_identical_to_its_former_output(kind):
    """The pool's layout changes how the dense recipe gathers its view
    and nothing else: on the same K/V it returns the bits it returned
    over the ``(num_blocks, H, bs, D)`` pool."""
    dtype = jnp.bfloat16 if kind == "bfloat16" else jnp.float32
    q, k, v, tables, pos = _paged_case(4, dtype=dtype)
    if kind == "int8":
        (k, sk), (v, sv) = quantize_kv(k), quantize_kv(v)
        former = _former_dense(q, k, v, tables, pos, sk, sv)
        now = paged_attention_dense(q, _pages(k), _pages(v), tables, pos,
                                    _pages(sk), _pages(sv))
    else:
        former = _former_dense(q, k, v, tables, pos)
        now = paged_attention_dense(q, _pages(k), _pages(v), tables, pos)
    assert now.dtype == former.dtype
    assert onp.array_equal(onp.asarray(now, onp.float32),
                           onp.asarray(former, onp.float32))


def _plain_attention(q, k, v):
    """One query (H, D) over one sequence's keys and values (T, H, D),
    in float64 on the host: no pool, no table, no mask."""
    q, k, v = (onp.asarray(x, onp.float64) for x in (q, k, v))
    s = onp.einsum("hd,thd->ht", q, k) / math.sqrt(q.shape[-1])
    p = onp.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return onp.einsum("ht,thd->hd", p, v)


@pytest.mark.parametrize("impl", ["dense", "pallas"])
@pytest.mark.parametrize("group,kv_heads", [(1, 4), (4, 1), (4, 2), (20, 1)],
                         ids=["1x4kv", "4x1kv", "4x2kv", "20x1kv"])
def test_grouped_heads_match_plain_attention(group, kv_heads, impl):
    """``Hq = group x Hkv`` query heads over a pool whose row is ``Hkv *
    D`` wide: query head h reads KV head ``h // group``.  Both impls
    against plain attention with the keys and values repeated over the
    group; with as many KV heads as query heads the dense recipe returns
    the bits it returned before heads could be grouped."""
    B, D, bs, nbps = 3, 16, 8, 4
    q, k, v, tables, pos = _paged_case(7 + group, B=B, heads=kv_heads, D=D,
                                       bs=bs, nbps=nbps)
    q = _rand_pool(jax.random.PRNGKey(group), (B, group * kv_heads, D),
                   jnp.float32)
    out = paged_attention(q, _pages(k), _pages(v), tables, pos, impl=impl,
                          interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype
    onp.testing.assert_allclose(
        onp.asarray(out), _plain_lanes(q, k, v, tables, pos, group),
        atol=2e-5)
    if group == 1 and impl == "dense":
        assert onp.array_equal(onp.asarray(out),
                               onp.asarray(_former_dense(q, k, v, tables,
                                                         pos)))


# --- several pages a grid step (`pages_per_step`) ------------------------ #
def _run_case(seed, heads, kv_heads, D, bs, nbps, pos, dtype=jnp.float32):
    """K/V by (block, head, slot) in a pool whose block 0 is the scratch
    block and holds real K/V; a lane a position of ``pos`` over a permuted
    table reserved as far as its position needs and scratch beyond, and
    one more lane that is idle: position 0, every entry scratch."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    B = len(pos) + 1
    nblocks = 1 + len(pos) * nbps
    k = _rand_pool(keys[0], (nblocks, kv_heads, bs, D), dtype)
    v = _rand_pool(keys[1], (nblocks, kv_heads, bs, D), dtype)
    q = _rand_pool(keys[2], (B, heads, D), dtype)
    tables = onp.zeros((B, nbps), onp.int32)
    ids = 1 + onp.asarray(jax.random.permutation(keys[3], len(pos) * nbps))
    for lane, t in enumerate(pos):
        live = t // bs + 1
        tables[lane, :live] = ids[lane * nbps:lane * nbps + live]
    return (q, k, v, jnp.asarray(tables),
            jnp.asarray(list(pos) + [0], jnp.int32))


def _plain_lanes(q, k, v, tables, pos, group):
    """Every lane by `_plain_attention` over its own sequence: (T, Hkv,
    D) as the sequence holds them, then a copy a query head."""
    nb, kv_heads, bs, D = k.shape
    out = []
    for b in range(q.shape[0]):
        T = int(pos[b]) + 1
        kb, vb = (onp.asarray(x, onp.float32)[onp.asarray(tables[b])]
                  .transpose(0, 2, 1, 3).reshape(-1, kv_heads, D)[:T]
                  .repeat(group, axis=1) for x in (k, v))
        out.append(_plain_attention(q[b], kb, vb))
    return onp.stack(out)


# (heads, KV heads, D, block, blocks a sequence): the rule's run length
# over float32 pages
_RUN_SHAPES = {
    "mha_bs16_nbps32": (2, 2, 16, 16, 32, 16),      # two whole runs
    "20x1kv_bs64_nbps44": (20, 1, 128, 64, 44, 16),  # the hybrid cell's table
    "4x2kv_bs32_nbps19": (4, 2, 16, 32, 19, 16),    # a prime: padded to 32
    "mha_bs8_nbps37": (2, 2, 16, 8, 37, 16),        # a prime: padded to 48
    "mha_bs128_nbps19": (2, 2, 128, 128, 19, 8),    # VMEM: 8 pages, to 24
}


@pytest.mark.parametrize("shape,kv8", [
    (shape, kv8) for shape, dims in _RUN_SHAPES.items()
    for kv8 in (False, True)
    if not kv8 or dims[0] == dims[1]],   # no int8 pages for grouped heads
    ids=lambda x: x if isinstance(x, str) else ("int8" if x else "float"))
def test_kernel_walks_runs_of_pages(shape, kv8):
    """The kernel with several pages a grid step against plain attention
    and the dense recipe: a lane at position 0, at a run's last slot, at
    a run's first slot, inside a run's second page, at the sequence's
    last position, and an idle lane on the scratch block; tables whose
    length is no whole number of runs; grouped heads; int8 pages."""
    from incubator_mxnet_tpu.ops.paged_attention import pages_per_step

    heads, kv_heads, D, bs, nbps, n = _RUN_SHAPES[shape]
    if kv8:     # a row a quarter as wide: VMEM may hold more of them
        n = pages_per_step(bs, nbps, kv_heads * D)
    assert pages_per_step(bs, nbps, kv_heads * D * (1 if kv8 else 4)) == n > 1
    run = n * bs
    pos = [0, run - 1, run, run + bs + 1, nbps * bs - 1]
    q, k, v, tables, pos = _run_case(5, heads, kv_heads, D, bs, nbps, pos)
    kw = {}
    if kv8:
        (k8, sk), (v8, sv) = quantize_kv(k), quantize_kv(v)
        kw = dict(scale_k=_pages(sk), scale_v=_pages(sv))
        pools = (_pages(k8), _pages(v8))
        k, v = (x8.astype(jnp.float32) * sx[..., None]
                for x8, sx in ((k8, sk), (v8, sv)))
    else:
        pools = (_pages(k), _pages(v))
    got = paged_attention(q, *pools, tables, pos, impl="pallas",
                          interpret=True, **kw)
    assert got.shape == q.shape and got.dtype == q.dtype
    dense = paged_attention(q, *pools, tables, pos, impl="dense", **kw)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(dense),
                                atol=2e-5)
    onp.testing.assert_allclose(
        onp.asarray(got), _plain_lanes(q, k, v, tables, pos,
                                       heads // kv_heads), atol=2e-5)


def test_kernel_with_runs_matches_dense_in_bf16():
    heads, kv_heads, D, bs, nbps, n = _RUN_SHAPES["mha_bs16_nbps32"]
    pos = [0, n * bs - 1, n * bs, nbps * bs - 1]
    q, k, v, tables, pos = _run_case(6, heads, kv_heads, D, bs, nbps, pos,
                                     dtype=jnp.bfloat16)
    pk, pv = _pages(k), _pages(v)
    dense = paged_attention(q, pk, pv, tables, pos, impl="dense")
    got = paged_attention(q, pk, pv, tables, pos, impl="pallas",
                          interpret=True)
    assert got.dtype == jnp.bfloat16
    onp.testing.assert_allclose(onp.asarray(got, onp.float32),
                                onp.asarray(dense, onp.float32), atol=2e-2)


@pytest.mark.parametrize("kv8", [False, True], ids=["float", "int8"])
def test_pages_past_a_lanes_position_are_never_read(kv8):
    """A live run ends in pages that hold no visible position (reserved
    for the request's later tokens, or another sequence's by now): the
    kernel fetches the run's visible pages alone, so what those pages
    hold, NaN included, is nothing to the lane; within the last visible
    page the slots past the position weigh exactly 0.0."""
    heads, kv_heads, D, bs, nbps, n = _RUN_SHAPES["mha_bs16_nbps32"]
    run = n * bs
    pos = [3, run - bs - 1, run + 1, 2 * run + bs]
    q, k, v, tables, pos = _run_case(8, heads, kv_heads, D, bs, nbps, pos)
    # every entry a page of its own, so that the pages past a position
    # can be spoiled: lane b's entry e is block 1 + b*nbps + e
    tables = 1 + jnp.arange((len(pos) - 1) * nbps, dtype=jnp.int32) \
        .reshape(-1, nbps)
    tables = jnp.concatenate([tables, jnp.zeros((1, nbps), jnp.int32)])
    dead = onp.zeros(k.shape[0], bool)
    for b, t in enumerate(onp.asarray(pos[:-1])):
        dead[1 + b * nbps + t // bs + 1:1 + (b + 1) * nbps] = True
    kw, kw_bad = {}, {}
    if kv8:
        (k, sk), (v, sv) = quantize_kv(k), quantize_kv(v)
        kw = dict(scale_k=_pages(sk), scale_v=_pages(sv))
        kw_bad = {name: _pages(jnp.where(dead[:, None, None], jnp.nan, sx))
                  for name, sx in (("scale_k", sk), ("scale_v", sv))}
        k_bad, v_bad = k, v
    else:
        k_bad, v_bad = (jnp.where(dead[:, None, None, None], jnp.nan, x)
                        for x in (k, v))
    want = paged_attention(q, _pages(k), _pages(v), tables, pos,
                           impl="pallas", interpret=True, **kw)
    got = paged_attention(q, _pages(k_bad), _pages(v_bad), tables, pos,
                          impl="pallas", interpret=True, **kw_bad)
    assert onp.isfinite(onp.asarray(got)).all()
    assert onp.array_equal(onp.asarray(got), onp.asarray(want))


def test_pages_per_step_follows_the_shapes():
    from incubator_mxnet_tpu.ops.paged_attention import pages_per_step

    assert pages_per_step(16, 64, 2048) == 16        # gpt2-medium's pool
    assert pages_per_step(64, 44, 256) == 16         # jamba2-3b's
    assert pages_per_step(8, 4, 128) == 4            # no more than there are
    assert pages_per_step(8, 1, 128) == 1
    assert pages_per_step(16, 64, 16 * 1024) == 4    # VMEM: what it holds
    assert pages_per_step(128, 16, 2048) == 4
    assert pages_per_step(16, 64, 128 * 1024) == 1   # nothing larger fits
    assert pages_per_step(256, 16, 8192) == 1


@pytest.mark.parametrize("chunk_start", [0, 240, 256],
                         ids=["from_0", "across_runs", "at_a_run"])
def test_position_as_a_lane_of_a_chunk_equals_the_step_bit_for_bit(
        chunk_start):
    """Runs are aligned to the table's index, not to a chunk's start: a
    position attended as one lane of a chunk (every position of the chunk
    a lane over the same table row, the chunk's K/V already in the pool)
    is, bit for bit, the position attended by a step beside strangers
    (the later positions not yet written), wherever the chunk began."""
    heads, kv_heads, D, bs, nbps, n = _RUN_SHAPES["mha_bs16_nbps32"]
    CH = 32
    end = chunk_start + CH - 1
    q1, k, v, tables, _ = _run_case(9, heads, kv_heads, D, bs, nbps,
                                    [end, nbps * bs - 1])
    q = _rand_pool(jax.random.PRNGKey(chunk_start), (CH, heads, D),
                   jnp.float32)
    pk, pv = _pages(k), _pages(v)
    posw = chunk_start + jnp.arange(CH, dtype=jnp.int32)
    chunk = paged_attention(q, pk, pv, jnp.broadcast_to(tables[0], (CH, nbps)),
                            posw, impl="pallas", interpret=True)
    for i in (0, 7, CH - 1):
        t = chunk_start + i
        # the step: position t's K/V are the last this sequence has, the
        # slots after them hold other bits, the other lanes are strangers
        blk, off = tables[0, (t + 1) // bs], (t + 1) % bs
        spk = pk.at[blk, off:].set(3.0) if t < end else pk
        spv = pv.at[blk, off:].set(-2.0) if t < end else pv
        qs = q1.at[0].set(q[i])
        step = paged_attention(qs, spk, spv, tables,
                               jnp.asarray([t, 5, 0], jnp.int32),
                               impl="pallas", interpret=True)
        assert onp.array_equal(onp.asarray(step[0]), onp.asarray(chunk[i])), i


def _window_case(seed, T, heads, kv_heads, Dk, Dv, bs, nbps,
                 dtype=jnp.float32):
    """One sequence's pools (block 0 the scratch block, every entry of the
    row a page of its own, permuted) and a chunk's queries."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = _rand_pool(keys[0], (1 + nbps, kv_heads, bs, Dk), dtype)
    v = _rand_pool(keys[1], (1 + nbps, kv_heads, bs, Dv), dtype)
    q = _rand_pool(keys[2], (T, heads, Dk), dtype)
    row = 1 + jax.random.permutation(keys[3], nbps).astype(jnp.int32)
    return q, _pages(k), _pages(v), row


# (heads, KV heads, key width, value width, queries, block, blocks a
#  sequence, first position, value scale)
_WINDOW_CASES = {
    "4x1kv_d16": (4, 1, 16, 16, 16, 8, 6, 0, 1.0),
    "20x1kv_d128": (20, 1, 128, 128, 16, 8, 6, 5, 1.0),
    "4x2kv_d128": (4, 2, 128, 128, 16, 8, 6, 17, 1.0),
    "mha_d128": (2, 2, 128, 128, 16, 8, 6, 9, 1.0),
    # what the form takes since it cuts the heads out of the run itself
    "mha16_d64": (16, 16, 64, 64, 32, 16, 8, 70, 1.0),
    "8x2kv_k48_v32_scaled": (8, 2, 48, 32, 16, 8, 6, 21, 0.707),
    # a row of three runs of 16 pages, the chunk across the first's edge
    "across_a_run": (4, 2, 16, 16, 32, 8, 37, 110, 1.0),
    # 20 x 64 rows against runs of 1,024 positions: two tiles of 10 heads
    "tiles_of_heads": (20, 1, 16, 16, 64, 64, 20, 1000, 1.0),
    # 2,048 queries of one head: two tiles of 1,024 of them
    "tiles_of_queries": (1, 1, 16, 16, 2048, 64, 34, 100, 1.0),
}


@pytest.mark.parametrize("case", list(_WINDOW_CASES))
def test_window_kernel_matches_every_position_as_a_lane(case):
    """A chunk's queries against each page ONCE (`paged_attention_window`,
    interpret mode) against the same queries as lanes of the dense recipe
    over the same table: positions ``start .. start+T-1``, pages permuted,
    the window ending mid-page; heads of any width side by side in a row,
    values narrower than keys, a value scale, several runs, several row
    tiles."""
    from incubator_mxnet_tpu.ops.paged_attention import (
        _window_rows, pages_per_step, paged_attention_window,
        window_kernel_fits)

    heads, kv_heads, Dk, Dv, T, bs, nbps, start, scale = _WINDOW_CASES[case]
    assert window_kernel_fits(T, heads, kv_heads, Dk, Dv)
    n = pages_per_step(bs, nbps, kv_heads * (Dk + Dv) * 2)
    tiles = heads // kv_heads * T // _window_rows(T, heads // kv_heads,
                                                   n * bs)
    assert (tiles > 1) == case.startswith("tiles"), tiles
    assert (nbps > n) == (case in ("across_a_run", "tiles_of_heads",
                                   "tiles_of_queries"))
    q, pk, pv, row = _window_case(3 + heads, T, heads, kv_heads, Dk, Dv, bs,
                                  nbps)
    pos = start + jnp.arange(T, dtype=jnp.int32)
    want = paged_attention(q, pk, pv, jnp.broadcast_to(row, (T, nbps)), pos,
                           value_scale=scale, impl="dense")
    got = paged_attention_window(q, pk, pv, row, jnp.int32(start),
                                 value_scale=scale, interpret=True)
    assert got.shape == (T, heads, Dv) and got.dtype == q.dtype
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                atol=2e-5)


def test_window_kernel_takes_bf16_queries_and_keys_as_they_are():
    """bf16 pools and queries: ``q . k^T`` takes them as they are stored
    (every product of two bf16 values is exact in float32, so these are
    the float32 dot's products), everything behind it stays float32; the
    result agrees with the dense recipe to bf16's rounding of the output."""
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention_window

    heads, kv_heads, Dk, Dv, T, bs, nbps, start, scale = \
        _WINDOW_CASES["8x2kv_k48_v32_scaled"]
    q, pk, pv, row = _window_case(4, T, heads, kv_heads, Dk, Dv, bs, nbps,
                                  dtype=jnp.bfloat16)
    pos = start + jnp.arange(T, dtype=jnp.int32)
    want = paged_attention(q, pk, pv, jnp.broadcast_to(row, (T, nbps)), pos,
                           value_scale=scale, impl="dense")
    got = paged_attention_window(q, pk, pv, row, jnp.int32(start),
                                 value_scale=scale, interpret=True)
    assert got.dtype == jnp.bfloat16
    onp.testing.assert_allclose(onp.asarray(got, onp.float32),
                                onp.asarray(want, onp.float32), atol=2e-2)


@pytest.mark.parametrize("first,second", [(0, 16), (224, 240), (240, 256)],
                         ids=["from_0", "across_a_run", "at_a_run"])
def test_window_position_is_the_same_bits_wherever_its_chunk_began(first,
                                                                   second):
    """Runs are aligned to the table's index, not to the chunk's start,
    and a row's arithmetic reads nothing of another row: a position
    attended by the chunk that began at ``first`` (the positions behind
    that chunk's end not yet written: other bits lie there) is, bit for
    bit, the position attended by the chunk that began at ``second``,
    which holds it too.  What makes a prefix-cache hit, whose first chunk
    begins where the hit ends, bit-identical to a cold prefill."""
    from incubator_mxnet_tpu.ops.paged_attention import (
        pages_per_step, paged_attention_window)

    heads, kv_heads, D, bs, nbps, CH = 4, 2, 16, 16, 32, 32
    assert pages_per_step(bs, nbps, kv_heads * D * 4) * bs == 256
    q, pk, pv, row = _window_case(11, second + CH - first, heads, kv_heads,
                                  D, D, bs, nbps)
    # the earlier chunk runs before the later one's own positions exist
    end = first + CH
    early_k = pk.at[row[end // bs:]].set(3.0)
    early_v = pv.at[row[end // bs:]].set(-2.0)
    a = paged_attention_window(q[:CH], early_k, early_v, row,
                               jnp.int32(first), interpret=True)
    b = paged_attention_window(q[second - first:], pk, pv, row,
                               jnp.int32(second), interpret=True)
    shared = first + CH - second
    assert shared == 16
    assert onp.array_equal(onp.asarray(a[-shared:]), onp.asarray(b[:shared]))


def test_window_kernel_never_reads_pages_past_the_chunk():
    """Of the sequence's table row the window form fetches the pages that
    hold a position some query of the chunk sees, and no other: a live
    run's pages past the chunk's last position, and every later run, may
    hold anything, NaN included (they are reserved for the request's later
    tokens, or another sequence's by now)."""
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention_window

    heads, kv_heads, D, bs, nbps, CH = 4, 2, 16, 16, 40, 32
    q, pk, pv, row = _window_case(12, CH, heads, kv_heads, D, D, bs, nbps)
    for start in (0, 100, 250):     # in run 0, in its middle, across its end
        dead = row[(start + CH - 1) // bs + 1:]
        want = paged_attention_window(q, pk, pv, row, jnp.int32(start),
                                      interpret=True)
        got = paged_attention_window(q, pk.at[dead].set(jnp.nan),
                                     pv.at[dead].set(jnp.nan), row,
                                     jnp.int32(start), interpret=True)
        assert onp.isfinite(onp.asarray(got)).all(), start
        assert onp.array_equal(onp.asarray(got), onp.asarray(want)), start


def test_window_kernel_says_what_it_has_no_sizes_for():
    from incubator_mxnet_tpu.ops.paged_attention import (
        paged_attention_window, window_kernel_fits)

    # any head width: the kernel cuts a KV head's lanes out of a run of
    # whole pages itself (64-wide heads side by side: gpt2-medium; one KV
    # head of 128: jamba2-3b; keys 192 and values 128 wide: mimo-v2-flash)
    assert window_kernel_fits(32, 16, 16, 64)
    assert window_kernel_fits(256, 20, 1, 128)
    assert window_kernel_fits(512, 64, 4, 192, 128)
    assert window_kernel_fits(65536, 20, 1, 128)          # in row tiles
    # VMEM: the smallest tile holds a row of every KV head
    assert not window_kernel_fits(32, 64, 64, 8192)
    assert not window_kernel_fits(32, 3, 2, 64)           # no whole group
    S = jax.ShapeDtypeStruct
    pool = S((9, 8, 64 * 8192), jnp.float32)
    with pytest.raises(ValueError, match="window_kernel_fits"):
        jax.eval_shape(
            lambda *a: paged_attention_window(*a, jnp.int32(0),
                                              interpret=True),
            S((32, 64, 8192), jnp.float32), pool, pool, S((8,), jnp.int32))
    q, k, v, tables, _ = _paged_case(2, heads=2)
    with pytest.raises(ValueError, match="KV heads"):
        paged_attention_window(q[:, :1, :12], _pages(k), _pages(v),
                               tables[0], jnp.int32(0), interpret=True)


def test_grouped_heads_refuse_what_is_not_built():
    q, k, v, tables, pos = _paged_case(2, heads=2)
    q3 = jnp.zeros((q.shape[0], 3, q.shape[2]), q.dtype)
    with pytest.raises(ValueError, match="KV heads"):
        paged_attention(q3, _pages(k), _pages(v), tables, pos, impl="pallas",
                        interpret=True)
    (k8, sk), (v8, sv) = quantize_kv(k), quantize_kv(v)
    q4 = jnp.zeros((q.shape[0], 4, q.shape[2]), q.dtype)
    with pytest.raises(ValueError, match="int8"):
        paged_attention(q4, _pages(k8), _pages(v8), tables, pos,
                        scale_k=_pages(sk), scale_v=_pages(sv),
                        impl="pallas", interpret=True)
    # the dense recipe has them
    out = paged_attention(q4, _pages(k8), _pages(v8), tables, pos,
                          scale_k=_pages(sk), scale_v=_pages(sv),
                          impl="dense")
    assert out.shape == q4.shape


@pytest.mark.parametrize("kv8", [False, True], ids=["float", "int8"])
def test_pool_written_by_the_programs_write_reads_back(kv8):
    """The write, the kernel and the dense recipe agree on which bytes
    a position is: K/V go into the pool position by position through
    the serving programs' own write — a chunk's window, then single
    steps — and both impls are held to a plain attention over each
    sequence's K/V at position 0, a block's last slot, a block's first
    slot and the sequence's last position, with an inactive lane
    writing to the scratch block beside them."""
    from incubator_mxnet_tpu.ops.paged_attention import write_rows

    H, D, bs, nbps, chunk = 2, 16, 8, 3, 8
    T = nbps * bs
    ends = [0, bs - 1, bs, T - 1]         # last written position per lane
    B = len(ends) + 1                     # + the inactive lane
    nb = 1 + len(ends) * nbps
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    k = jax.random.normal(keys[0], (B, T, H, D))
    v = jax.random.normal(keys[1], (B, T, H, D))
    q = jax.random.normal(keys[2], (B, H, D))
    tables = onp.zeros((B, nbps), onp.int32)      # inactive lane: scratch
    tables[:len(ends)] = 1 + onp.random.RandomState(0).permutation(
        len(ends) * nbps).reshape(len(ends), nbps)
    pools = [jnp.zeros((nb, bs, H * D), jnp.int8 if kv8 else jnp.float32)
             for _ in range(2)]
    scales = [jnp.ones((nb, bs, H), jnp.float32) for _ in range(2)]

    def write(wblk, off, kx, vx):
        for i, x in enumerate((kx, vx)):
            if kv8:
                x, sx = quantize_kv(x)
                scales[i] = write_rows(scales[i], wblk, off, sx)
            pools[i] = write_rows(pools[i], wblk, off, x)

    # the first block of every lane as one chunk's window (positions
    # past a lane's end land in scratch, as `serving_prefill_chunk`
    # points them), the rest as decode steps over all lanes at once
    for lane, end in enumerate(ends):
        posw = onp.arange(chunk)
        ok = posw <= end
        wblk = onp.where(ok, tables[lane, posw // bs], 0)
        write(jnp.asarray(wblk), jnp.asarray(posw % bs),
              k[lane, :chunk], v[lane, :chunk])
    for t in range(chunk, T):
        active = onp.array([t <= end for end in ends] + [False])
        wblk = onp.where(active, tables[:, t // bs], 0)
        write(jnp.asarray(wblk), jnp.full((B,), t % bs, jnp.int32),
              k[:, t], v[:, t])

    pos = jnp.asarray(ends + [0], jnp.int32)
    kw = dict(scale_k=scales[0], scale_v=scales[1]) if kv8 else {}
    got = {impl: onp.asarray(paged_attention(
        q, pools[0], pools[1], jnp.asarray(tables), pos, impl=impl,
        interpret=True, **kw)) for impl in ("pallas", "dense")}
    for lane, end in enumerate(ends):
        kk, vv = k[lane, :end + 1], v[lane, :end + 1]
        if kv8:   # what the pool holds: the quantized values, dequantized
            kk, vv = (x8.astype(jnp.float32) * sx[..., None]
                      for x8, sx in (quantize_kv(kk), quantize_kv(vv)))
        want = _plain_attention(q[lane], kk, vv)
        for impl in got:
            onp.testing.assert_allclose(got[impl][lane], want, atol=2e-5,
                                        err_msg=f"{impl} lane {lane}")
    # every lane's pages hold its own rows and nothing past its end
    rows = onp.asarray(pools[0]).reshape(nb, bs, H, D)
    for lane, end in enumerate(ends):
        blk, slot = tables[lane, end // bs], end % bs
        want = quantize_kv(k[lane, end])[0] if kv8 else k[lane, end]
        assert onp.array_equal(rows[blk, slot], onp.asarray(want))
        assert not rows[blk, slot + 1:].any()
    # the inactive lane's writes, and the windows' tails, went to scratch
    assert rows[0].any()
    assert all(onp.isfinite(g[-1]).all() for g in got.values())


def test_paged_attention_validates_impl():
    q, k, v, tables, pos = _paged_case(2, B=1, nbps=1)
    pk, pv = _pages(k), _pages(v)
    with pytest.raises(ValueError):
        paged_attention(q, pk, pv, tables, pos, impl="banana")
    assert default_impl("tpu") == "pallas"
    assert default_impl("cpu") == "dense"


def test_quantize_kv_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 2, 8, 16)) * 4.0
    qx, scale = quantize_kv(x)
    assert qx.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == x.shape[:-1]
    back = qx.astype(jnp.float32) * scale[..., None]
    err = onp.abs(onp.asarray(back - x))
    # symmetric per-vector int8: error bounded by half a quant step
    bound = onp.asarray(scale)[..., None] * 0.5 + 1e-7
    assert (err <= bound).all()
    # all-zero vectors survive (amax clamp, no division blow-up)
    qz, sz = quantize_kv(jnp.zeros((2, 3)))
    assert (onp.asarray(qz) == 0).all() and onp.isfinite(onp.asarray(sz)).all()


# --------------------------------------------------------------------- #
# engine-level: forced-pallas path
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def net():
    mx.random.seed(0)
    n = TransformerLM(vocab=V, units=C, hidden_size=DFF, num_layers=L,
                      num_heads=H, max_len=MAXLEN, dropout=0.0)
    n.initialize()
    n(NDArray(jnp.ones((1, 4), jnp.int32)))
    return n


@pytest.fixture(scope="module")
def pallas_engine(net):
    eng = ServingEngine(net, max_batch=2, block_size=8,
                        attn_impl="pallas", poll_interval=_POLL)
    assert eng.attn_impl == "pallas"
    yield eng
    try:
        eng.close()
    except Exception:
        pass


def _slow_step(seconds):
    def hook(phase):
        if phase == "step":
            time.sleep(seconds)
    return hook


def _wait(pred, timeout=30.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(0.002)
    return False


def test_pallas_engine_cobatched_matches_dense_engine(net, pallas_engine):
    """Co-batched prefill+decode under the kernel path agrees with the
    dense-gather engine on greedy tokens (fp32-roundoff softmax
    differences may flip a near-tie, hence >= rather than ==)."""
    with net.serve(max_batch=2, block_size=8, poll_interval=_POLL) as ref:
        assert ref.attn_impl == "dense"
        ra, rb = ref.submit(P1, 10), ref.submit(P2, 10)
        base_a, base_b = ra.result(timeout=60), rb.result(timeout=60)
    pa, pb = pallas_engine.submit(P1, 10), pallas_engine.submit(P2, 10)
    got_a, got_b = pa.result(timeout=60), pb.result(timeout=60)
    pallas_engine.drain(timeout=30)
    hits = sum(x == y for x, y in zip(got_a + got_b, base_a + base_b))
    assert hits / 20 >= 0.9, (got_a, got_b, base_a, base_b)


def test_engine_says_how_many_pages_a_grid_step_walks(net, pallas_engine):
    """`varz_config()["paged_pages_per_step"]`: the kernel's own rule at
    the engine's shapes, static for an engine; 0 where no kernel runs."""
    from incubator_mxnet_tpu.ops.paged_attention import pages_per_step

    cfg = pallas_engine.varz_config()
    assert cfg["attn_impl"] == "pallas"
    nbps = cfg["max_seq_len"] // cfg["block_size"]
    assert cfg["paged_pages_per_step"] == nbps > 1        # one run a lane
    assert cfg["paged_pages_per_step"] == pages_per_step(
        cfg["block_size"], nbps, C * 4)
    with net.serve(max_batch=2, block_size=8, poll_interval=_POLL) as dense:
        assert dense.varz_config()["paged_pages_per_step"] == 0


def test_eviction_bit_identity_under_pallas(pallas_engine):
    """The eviction-exactness contract survives the kernel path: a
    cancelled neighbour leaves the survivor byte-identical (within the
    SAME impl — the guarantee dispatch must not silently break)."""
    from incubator_mxnet_tpu.serving import RequestCancelled
    eng = pallas_engine
    ra, rb = eng.submit(P1, 10), eng.submit(P2, 10)
    base = ra.result(timeout=60)
    rb.result(timeout=60)
    assert eng.drain(timeout=30)
    eng.set_fault_hook(_slow_step(0.02))
    ra, rb = eng.submit(P1, 10), eng.submit(P2, 10)
    assert _wait(lambda: len(rb.tokens) >= 3)
    rb.cancel()
    assert ra.result(timeout=60) == base
    with pytest.raises(RequestCancelled):
        rb.result(timeout=60)
    eng.set_fault_hook(None)
    assert eng.submit(P1, 10).result(timeout=60) == base
    eng.drain(timeout=30)


# --------------------------------------------------------------------- #
# int8 KV pools: quality + capacity
# --------------------------------------------------------------------- #
def test_int8_kv_engine_greedy_parity(net):
    prompts = [P1, P2, onp.array([2, 9, 4, 1], onp.int32)]
    with net.serve(max_batch=2, block_size=8, poll_interval=_POLL) as ref:
        base = [ref.submit(p, 12).result(timeout=60) for p in prompts]
    kv8 = ServingEngine(net, max_batch=2, block_size=8,
                        kv_dtype="int8", poll_interval=_POLL)
    try:
        assert kv8.kv_dtype == "int8"
        got = [kv8.submit(p, 12).result(timeout=60) for p in prompts]
    finally:
        kv8.close()
    tot = sum(len(t) for t in base)
    hits = sum(a == b for ta, tb in zip(base, got) for a, b in zip(ta, tb))
    assert hits / tot >= 0.95, f"int8-KV greedy parity {hits}/{tot}"


def test_int8_kv_perplexity_delta():
    """Teacher-forced fake-quant of K/V (exactly what the pool stores)
    moves held-out perplexity by <= 0.5%."""
    mx.random.seed(1)
    net = TransformerLM(vocab=97, units=32, hidden_size=64, num_layers=2,
                        num_heads=4, max_len=64, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), jnp.int32)))
    held = onp.array(jax.random.randint(jax.random.PRNGKey(17), (4, 32),
                                        0, 97), dtype="int32")
    acts = tuple(lyr.ffn._act for lyr in net._layers)

    def tf_logits(fake):
        p = G._gather_params(net, held.shape[1])
        dt = p["embed"].dtype
        B, T = held.shape
        units = p["embed"].shape[1]
        h = p["embed"][held].astype(dt) * math.sqrt(units) \
            + p["pe"][:T].astype(dt)
        for lp, act in zip(p["layers"], acts):
            x = G._ln(h, *lp["ln1"])
            q, k, v = G._qkv_heads(G._dense(x, *lp["qkv"]), 4)
            kt = k.transpose(0, 2, 1, 3)
            vt = v.transpose(0, 2, 1, 3)
            if fake:
                qk, sk = quantize_kv(kt)
                qv, sv = quantize_kv(vt)
                kt = (qk.astype(jnp.float32) * sk[..., None]).astype(dt)
                vt = (qv.astype(jnp.float32) * sv[..., None]).astype(dt)
            a = flash_attention(q.transpose(0, 2, 1, 3), kt, vt,
                                causal=True).transpose(0, 2, 1, 3)
            h = h + G._dense(a.astype(dt).reshape(B, T, units), *lp["proj"])
            h = h + G._ffn_fwd(G._ln(h, *lp["ln2"]), lp, act)
        return G._logits_of(p, h.reshape(B * T, units)).reshape(B, T, -1)

    def ppl(logits):
        lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(
            lp, jnp.asarray(held[:, 1:, None]), axis=-1).mean()
        return float(jnp.exp(nll))

    ppl_f, ppl_q = ppl(tf_logits(False)), ppl(tf_logits(True))
    delta = abs(ppl_q - ppl_f) / ppl_f
    assert delta <= 0.005, \
        f"KV-quant perplexity delta {delta:.4%} > 0.5% " \
        f"(float {ppl_f:.3f}, int8-KV {ppl_q:.3f})"


def test_int8_kv_capacity_vs_bf16_at_equal_bytes():
    """ISSUE 15 acceptance: at equal pool bytes, int8 KV holds >= 1.8x
    the resident sequences of bf16 KV (D=64 so the per-vector fp32
    scale amortizes: 128 B vs 64+4 B per head-token)."""
    mx.random.seed(2)
    net = TransformerLM(vocab=31, units=128, hidden_size=64, num_layers=1,
                        num_heads=2, max_len=64, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((1, 4), jnp.int32)))
    net.cast("bfloat16")
    bf = ServingEngine(net, max_batch=1, block_size=8)
    q8 = ServingEngine(net, max_batch=1, block_size=8, kv_dtype="int8")
    try:
        budget = bf.kv_pool_bytes
        nbps = bf.max_seq_len // 8
        res_bf = bf.stats()["blocks_total"] // nbps
        # blocks an int8 pool fits into the SAME byte budget
        res_q8 = (budget // q8.kv_block_bytes) // nbps
        ratio = res_q8 / res_bf
        assert ratio >= 1.8, \
            f"int8 KV fits only {ratio:.2f}x bf16 residents " \
            f"({bf.kv_bytes_per_token} vs {q8.kv_bytes_per_token} B/token)"
        assert bf.kv_bytes_per_token / q8.kv_bytes_per_token >= 1.8
    finally:
        bf.close()
        q8.close()


# --------------------------------------------------------------------- #
# small-T fused attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("causal", [False, True])
def test_small_t_fused_matches_reference(causal):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    shape = (2, 2, 160, 32)  # 160^2 sits inside [128^2, 512^2)
    q = jax.random.normal(k1, shape).astype(jnp.bfloat16)
    k = jax.random.normal(k2, shape).astype(jnp.bfloat16)
    v = jax.random.normal(k3, shape).astype(jnp.bfloat16)
    ref = attention_reference(q, k, v, causal=causal)
    got = attention_small_t(q, k, v, causal=causal)
    assert got.dtype == q.dtype
    onp.testing.assert_allclose(onp.asarray(got, onp.float32),
                                onp.asarray(ref, onp.float32),
                                atol=3e-2, rtol=3e-2)


def test_small_t_dispatch_gate():
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert _use_small_t("tpu", 160, 160, bf16)
    assert _use_small_t("tpu", 128, 128, bf16)          # lower edge in
    assert not _use_small_t("tpu", 64, 64, bf16)        # tiny: XLA wins
    assert not _use_small_t("tpu", 512, 512, bf16)      # Pallas crossover
    assert not _use_small_t("cpu", 160, 160, bf16)      # never on CPU
    assert not _use_small_t("tpu", 160, 160, f32)       # bf16-only path


# --- keys wider than values, a window, a sink logit, a value scale -------- #
def _plain_windowed(q, k, v, pos, group, first=None, sink=None, scale=1.0):
    """Every lane in numpy: q (B, Hq, Dk), its sequence's k (B, T, Hkv, Dk)
    and v (B, T, Hkv, Dv); slots ``first .. pos`` are seen, the sink logit
    a head joins the denominator alone, the result is scaled."""
    q, k, v = (onp.asarray(x, onp.float64) for x in (q, k, v))
    B, Hq, Dk = q.shape
    out = onp.zeros((B, Hq, v.shape[-1]))
    for b in range(B):
        lo = 0 if first is None else int(first[b])
        for h in range(Hq):
            kv = h // group
            s = k[b, lo:int(pos[b]) + 1, kv] @ q[b, h] / math.sqrt(Dk)
            e = onp.exp(s - s.max())
            den = e.sum() + (0.0 if sink is None
                             else math.exp(float(sink[h]) - s.max()))
            out[b, h] = scale * (e / den) @ v[b, lo:int(pos[b]) + 1, kv]
    return out


def _wide_case(seed, Hq, Hkv, Dk, Dv, bs, nbps, pos, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    B = len(pos)
    nb = 1 + B * nbps
    k = _rand_pool(keys[0], (nb, Hkv, bs, Dk), dtype)
    v = _rand_pool(keys[1], (nb, Hkv, bs, Dv), dtype)
    q = _rand_pool(keys[2], (B, Hq, Dk), dtype)
    tables = 1 + jax.random.permutation(keys[3], B * nbps).reshape(B, nbps)
    sink = jax.random.normal(keys[4], (Hq,))
    seqs = [onp.asarray(x, onp.float32)[onp.asarray(tables)]
            .transpose(0, 1, 3, 2, 4).reshape(B, nbps * bs, Hkv, -1)
            for x in (k, v)]
    return (q, _pages(k), _pages(v), tables.astype(jnp.int32),
            jnp.asarray(pos, jnp.int32), sink, seqs)


_OPTION_CASES = {
    # (Hq, Hkv, Dk, Dv): groups of 16 and of 8 query heads a KV head
    "k24_v16_16x4kv": (64, 4, 24, 16, ()),
    "k24_v16_8x8kv": (64, 8, 24, 16, ()),
    "window": (8, 2, 16, 16, ("first",)),
    "sink": (8, 2, 16, 16, ("sink",)),
    "value_scale": (8, 2, 16, 16, ("scale",)),
    "all_16x4kv": (64, 4, 24, 16, ("first", "sink", "scale")),
    "all_8x8kv": (64, 8, 24, 16, ("first", "sink", "scale")),
    "all_mha": (4, 4, 24, 16, ("first", "sink", "scale")),
}


@pytest.mark.parametrize("impl", ["dense", "pallas"])
@pytest.mark.parametrize("case", list(_OPTION_CASES))
def test_keys_wider_than_values_window_sink_and_scale(case, impl):
    """The options a decoder with window layers asks of the single-query
    attention, each alone and all together, both impls against plain
    attention and the kernel against `paged_attention_dense`: a value row
    of another width than the key row, a first visible position a lane
    (in the row's first entry: the caller hands the row from its first
    visible block on), a sink logit a head, a value scale."""
    Hq, Hkv, Dk, Dv, opts = _OPTION_CASES[case]
    bs, nbps = 8, 4
    pos = [0, 5, 13, 22, 31]
    q, pk, pv, tables, pos, sink, (ks, vs) = _wide_case(
        3, Hq, Hkv, Dk, Dv, bs, nbps, pos)
    first = jnp.asarray([0, 3, 7, 6, 2], jnp.int32)     # each < bs
    kw = {}
    if "first" in opts:
        kw["first"] = first
    if "sink" in opts:
        kw["sink"] = sink
    if "scale" in opts:
        kw["value_scale"] = 0.707
    out = paged_attention(q, pk, pv, tables, pos, impl=impl, interpret=True,
                          **kw)
    assert out.shape == (len(pos), Hq, Dv) and out.dtype == q.dtype
    want = _plain_windowed(q, ks, vs, pos, Hq // Hkv, kw.get("first"),
                           kw.get("sink"), kw.get("value_scale", 1.0))
    onp.testing.assert_allclose(onp.asarray(out), want, atol=3e-5)
    if impl == "pallas":
        dense = paged_attention_dense(q, pk, pv, tables, pos, **kw)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(dense),
                                    atol=3e-5)


@pytest.mark.parametrize("impl", ["dense", "pallas"])
def test_dropping_the_sink_or_the_window_changes_the_result(impl):
    q, pk, pv, tables, pos, sink, _ = _wide_case(
        5, 8, 2, 24, 16, 8, 4, [9, 20, 31])
    first = jnp.asarray([4, 5, 1], jnp.int32)
    full = paged_attention(q, pk, pv, tables, pos, first=first, sink=sink,
                           impl=impl, interpret=True)
    for kw in (dict(first=first), dict(sink=sink)):
        less = paged_attention(q, pk, pv, tables, pos, impl=impl,
                               interpret=True, **kw)
        assert float(jnp.abs(full - less).max()) > 1e-2, sorted(kw)


def test_options_are_not_built_for_int8_pages():
    q, k, v, tables, pos = _paged_case(2)
    k8, ks = quantize_kv(jnp.swapaxes(k, 1, 2))
    v8, vs = quantize_kv(jnp.swapaxes(v, 1, 2))
    flat = [x.reshape(x.shape[:2] + (-1,)) for x in (k8, v8, ks, vs)]
    with pytest.raises(ValueError, match="int8"):
        paged_attention(q, flat[0], flat[1], tables, pos, scale_k=flat[2],
                        scale_v=flat[3], first=jnp.zeros_like(pos),
                        impl="dense")


@pytest.mark.parametrize("width,base", [(4, 10000.0), (8, 5000000.0),
                                        (16, 10000.0), (16, 100.0)])
def test_rotary_positions_turn_the_leading_lanes(width, base):
    """`generation._rope`, the rotary form of every served program:
    lane j < width/2 of a head pairs with lane j + width/2 and turns by
    ``pos * base**(-2j/width)``, the other lanes pass; so the product of a
    rotated query and key depends on their distance alone."""
    _rope = G._rope

    rng = onp.random.default_rng(width)
    D, half = 16, width // 2
    x = rng.normal(size=(5, 3, D)).astype(onp.float32)
    pos = onp.array([0, 1, 7, 130, 9000])
    got = onp.asarray(_rope(jnp.asarray(x), jnp.asarray(pos), width, base))
    theta = base ** (-2.0 * onp.arange(half) / width)
    z = (x[..., :half] + 1j * x[..., half:width]) \
        * onp.exp(1j * pos[:, None, None] * theta)
    onp.testing.assert_allclose(got[..., :half], z.real, atol=2e-3)
    onp.testing.assert_allclose(got[..., half:width], z.imag, atol=2e-3)
    onp.testing.assert_array_equal(got[..., width:], x[..., width:])
    # q at t+d against k at t: the same for every t
    q, k = jnp.asarray(x[:1, :1]), jnp.asarray(x[1:2, :1])
    dots = [float(jnp.sum(_rope(q, jnp.asarray([t + 5]), width, base)
                          * _rope(k, jnp.asarray([t]), width, base)))
            for t in (0, 3, 200)]
    assert max(dots) - min(dots) < 1e-3
    other = float(jnp.sum(_rope(q, jnp.asarray([9]), width, base)
                          * _rope(k, jnp.asarray([0]), width, base)))
    assert abs(other - dots[0]) > 1e-3
