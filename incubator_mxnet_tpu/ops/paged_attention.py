"""Single-query paged attention over the serving KV pool.

The serving step program (serving/programs.py) decodes one token per
lane against that lane's block table.  PR 12 did this at
"gather+dense-attention speed": gather every page into a dense
``(B, H, max_seq_len, D)`` view, full-width fp32 masked softmax.  This
module is the kernel-speed replacement (ISSUE 15, the vLLM
PagedAttention recipe on TPU):

The pool is ``(num_blocks, block_size, H*D)``: a position is one row
of its page and a head a run of ``D`` lanes.  It is the one shape that
the buffer the engine donates, the programs' K/V write and this
kernel's page block all take as it lies — row-major and unpadded on
the device, so a served program holds no copy of a pool array
(docs/serving.md, "The pool's layout"; over ``(num_blocks, H,
block_size, D)`` it held three of each).

* ``paged_attention`` with ``impl="pallas"`` — a Pallas kernel with
  grid ``(lane, run)``, a run `pages_per_step` consecutive entries of
  the lane's block-table row and every head of its pages in one step:
  the KV walk is the innermost grid axis, the pools stay in HBM as they
  lie and the kernel copies each visible page of a run DIRECTLY from the
  pool via the lane's block-table row (scalar-prefetched, the TPU
  paged-attention idiom), the next live run's pages under this run's
  math — no dense gather, nothing ``(B, H, max_seq_len)``-shaped is
  ever materialized.  The query is laid out block-diagonally over the
  pages' ``H*D`` lanes, so both dots are plain 2-D matmuls over the
  run as it lies.  Online-softmax state (m, l, acc) lives in VMEM
  scratch exactly like `flash_attention._fa_kernel_streamed`, and dead
  runs (``run * n * block_size > pos``) skip their copies and their
  math the same way `_fa_kernel_resident` skips fully-masked causal
  blocks.  A grid step's fixed cost is that of some ten pages' bytes,
  so one page a step (2,048 steps a call at the served widths) ran at
  a fifteenth of the bytes' pace: `pages_per_step` says how many.
* ``impl="dense"`` — byte-for-byte the PR 12 recipe (fp32 scores,
  ``finfo.min`` mask, full-width `jax.nn.softmax`, fp32 PV).  This is
  the CPU fallback the eviction-bit-identity and greedy-parity
  contracts rest on: CPU engines keep EXACTLY the old numerics.

Grouped heads: a query of ``Hq`` heads may attend a pool of ``Hkv``
heads, ``Hq`` a multiple of ``Hkv`` (a row of the pool is ``Hkv * D``
wide; query head ``h`` reads KV head ``h // (Hq // Hkv)``).  With ``Hq ==
Hkv`` both impls are what they were, operation for operation.  Otherwise
the kernel takes the query as ``(Hq, D)`` rows, lays row ``h`` on the
lanes of its KV head (for one KV head: as it is), and emits ``(Hq, D)``;
the dense recipe folds the group into the einsums.

A prefill chunk is many queries of ONE sequence: as lanes of the
single-query kernel its ``T`` positions walk the same pages ``T`` times
and multiply them ``T`` times.  `paged_attention_window` walks them once
for a whole tile of the chunk's query rows: grid ``(row tile, run)``, the
runs fetched exactly as the single-query kernel fetches them (the pools
in HBM, whole pages copied by the kernel itself, the next live run's
under this run's math: `_run_copies`, one routine for both kernels) and
the heads separated in VMEM: a KV head's ``G*T`` query rows meet that
head's lanes of the run, a static slice of the buffer, and no other
head's (the block-diagonal query of the single-query kernel would cost a
chunk ``Hkv`` times the work).  So it takes heads of any width side by
side in a row, values narrower than keys and a value scale; where a KV
head's rows do not fit VMEM at once they go in tiles, each tile reading
the pages again (`_window_rows`; `window_kernel_fits` says whether a
tiling exists).  Both kernels share one online-softmax update
(`_softmax_update`).  A chunk stays lanes of the single-query kernel
where the pages are int8 (the window form has no scales).

Both impls take an optional int8 KV pool (per-head symmetric int8 with
an fp32 scale per (block, slot, head), scale pools ``(num_blocks,
block_size, H)`` — `contrib.quantization`'s per-channel recipe applied
to the feature dim): the kernel dequantizes in-register after the DMA
(the s8 page enters the dots as it is, its scales multiply the scores
and the softmax weights), so the pool stays s8 in HBM and roughly
doubles resident sequences per HBM byte.

The pallas and dense impls agree to fp32 roundoff (online vs full-width
softmax re-associate the same sums), NOT bitwise — dispatch therefore
never mixes impls within one engine: tokens are reproducible per
(engine config), which is what the eviction contract needs.  On the TPU
that holds when both are traced under matmul precision "highest"
(2e-6 on a v5e); at the default precision the MXU rounds each impl's
fp32 softmax weights to bf16, and they agree to that rounding — within
2^-8 of the largest |v|, below the bf16 rounding of the output itself.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mosaic

__all__ = ["paged_attention", "paged_attention_dense", "default_impl",
           "pool_shapes", "write_rows", "paged_attention_window",
           "window_kernel_fits", "softmax_with_sink"]


def default_impl(platform: Optional[str] = None) -> str:
    """Auto dispatch: the Pallas kernel on TPU, the dense gather
    everywhere else (the CPU test/serving surface keeps PR 12's exact
    numerics; interpret-mode kernel runs are opt-in via impl=)."""
    platform = platform or jax.default_backend()
    return "pallas" if platform == "tpu" else "dense"


def pool_shapes(num_blocks, block_size, heads, head_dim):
    """(shape of a K or V pool array, shape of its int8 scale pool): a
    position is a row of its page, a head a run of ``head_dim`` lanes."""
    return ((num_blocks, block_size, heads * head_dim),
            (num_blocks, block_size, heads))


def write_rows(pool, wblk, off, x):
    """The K/V write of every serving program: ``x`` — keys or values
    ``(..., H, D)``, or their scales ``(..., H)`` — into rows
    ``(wblk, off)`` of a page pool or a scale pool.  A position is one
    row, so the write updates the donated pool in place and the kernel
    reads the same bytes."""
    return pool.at[wblk, off].set(x.reshape(wblk.shape + pool.shape[2:]))


def _dequant(pages, scales):
    """(..., H, D) int8 pages × (..., H) fp32 scales → fp32."""
    return pages.astype(jnp.float32) * scales[..., None]


def softmax_with_sink(s, sink=None):
    """Softmax over the last axis of float32 scores ``s``; with ``sink``
    (broadcastable to ``s`` less its last axis, kept as 1) a logit that
    joins each row's denominator and carries no value."""
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    b = jnp.broadcast_to(sink.astype(jnp.float32), s.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([s, b], -1), axis=-1)[..., :-1]


def paged_attention_dense(q, pool_k, pool_v, tables, pos,
                          scale_k=None, scale_v=None, *, first=None,
                          sink=None, value_scale=1.0):
    """The PR 12 dense-gather recipe, verbatim: gather the lane's pages
    into a (B, H, W, D) view, fp32 scores / sqrt(D), iota position mask
    at ``finfo(f32).min``, full-width fp32 softmax, fp32 PV — masked
    slots contribute exactly 0.0 and lanes never mix, the two facts
    behind docs/serving.md §"Why eviction is exact".  int8 pools are
    dequantized after the gather (fp32), same score math.  The pool's
    layout only changes how the view is gathered: its values, and every
    operation on them, are those of the ``(num_blocks, H, bs, D)`` pool
    this recipe was written for, bit for bit.  The options of
    `paged_attention` (``first``, ``sink``, ``value_scale``, values of
    another width than the keys) each add their one operation and, left
    out, none."""
    B, nbps = tables.shape
    Hq, D = q.shape[1:]
    H = pool_k.shape[2] // D            # KV heads
    Dv = pool_v.shape[2] // H
    bs = pool_k.shape[1]
    W = nbps * bs

    def view(pool, scale, d):
        g = pool[tables].reshape(B, nbps, bs, H, d)
        if scale is not None:
            g = _dequant(g, scale[tables])
        return g.transpose(0, 3, 1, 2, 4).reshape(B, H, W, d)

    gk, gv = view(pool_k, scale_k, D), view(pool_v, scale_v, Dv)
    if Hq != H:                         # grouped: (B, Hkv, G, D) queries
        q = q.reshape(B, H, Hq // H, D)
        qk, pv, last = "bhgd,bhkd->bhgk", "bhgk,bhkd->bhgd", 3
    else:
        qk, pv, last = "bhd,bhkd->bhk", "bhk,bhkd->bhd", 2
    s = jnp.einsum(qk, q, gk,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, last)
    seen = kpos <= pos.reshape((B,) + (1,) * last)
    if first is not None:
        seen = seen & (kpos >= first.reshape((B,) + (1,) * last))
    s = jnp.where(seen, s, jnp.finfo(jnp.float32).min)
    p = softmax_with_sink(
        s, None if sink is None else sink.reshape(s.shape[1:-1] + (1,)))
    o = jnp.einsum(pv, p, gv, preferred_element_type=jnp.float32)
    if value_scale != 1.0:
        o = o * value_scale
    return o.astype(q.dtype).reshape(B, Hq, Dv)


def _run_copies(pools, bufs, sem, slot, page, first, last, n, bs, start):
    """Start, or wait for, the copies into buffer ``slot`` of the live run
    whose first table entry is ``first``: K's and V's page, as the pool
    holds it, for each of the run's ``n`` entries that holds a position at
    or before ``last``: its first always does, and the others that do are
    the entries behind it up to ``last // bs``; ``page(e)`` reads entry
    ``e`` of the row.  A wait needs a copy's size alone.  The loop over
    those pages is the kernel's own, as many turns as pages are copied: a
    traced kernel holds one page's copies and not ``n`` conditional ones
    (three such loops a kernel; the kernels are traced and lowered at
    every start of a process, whatever the compile cache holds).  The one
    fetching routine of both kernels."""
    from jax.experimental.pallas import tpu as pltpu

    def page_i(i, _=None):
        src = page(first + i) if start else 0
        for pool, buf in zip(pools, bufs):
            copy = pltpu.make_async_copy(
                pool.at[src], buf.at[slot, i], sem.at[slot])
            copy.start() if start else copy.wait()

    page_i(0)
    jax.lax.fori_loop(1, jnp.minimum(n, last // bs - first + 1), page_i, None)


def _softmax_update(s, seen, v, m_ref, l_ref, acc_ref, scale_v=None):
    """One run's online-softmax update, both kernels': ``s`` float32
    scores ``(rows, run)``, ``seen`` their mask, ``v`` the run's values
    ``(run, lanes)`` in float32; the state ``m``, ``l`` ``(rows, 1)`` and
    ``acc`` ``(rows, lanes)`` is float32.  A row's update reads nothing of
    another row, and a run that a row sees nothing of leaves its state bit
    for bit (``alpha`` 1.0, weights 0.0) once an earlier run gave it a
    finite maximum.  ``scale_v``: int8 values' scales, a column a
    position."""
    s = jnp.where(seen, s, jnp.finfo(jnp.float32).min)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)       # masked slots underflow to exactly 0.0
    m_ref[...] = m_new
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    if scale_v is not None:  # (the pipeline brought unseen pages' scales)
        p = jnp.where(seen, p * scale_v, 0.0)
    acc_ref[...] = acc_ref[...] * alpha \
        + jnp.dot(p, v, preferred_element_type=jnp.float32)


def _paged_kernel(tables_ref, pos_ref, *rest,
                  bs, n, heads, kv_heads, kv_quant, windowed=False,
                  sink=False, value_scale=1.0):
    """One grid step = one (lane, run of ``n`` pages), all heads at once.
    The pools stay where they lie (HBM); the body copies a run's pages,
    each as the pool holds it — ``(bs, Hkv*D)``: a position a row, a KV
    head a run of ``D`` lanes — one under the other into one of two VMEM
    buffers, which makes them the run's ``(n*bs, Hkv*D)`` keys or values.
    A live step starts the copies of the NEXT live step (the lane's next
    run, or run 0 of the next lane) into the other buffer before it waits
    for its own, so they land under its math; a run past the lane's
    length bound starts nothing and computes nothing.  Of a live run the
    pages that hold a visible position are copied and no other: what a
    page past the lane's position holds is never read.  Their places in
    the buffers keep what an earlier run left: a score there is replaced
    by the mask whatever the keys are, and the values are an earlier
    page's or the zeros the buffer starts with, finite under a weight of
    exactly 0.0, like a live page's tail.

    Both dots are plain 2-D MXU matmuls over the run as it lies.  The
    query is laid out block-diagonally, ``q_bd[h] = q[h]`` on the lanes
    of head h's KV head and exact zeros elsewhere, so ``q_bd · run^T``
    is each head's own ``(Hq, n*bs)`` scores; ``p · run`` weights every KV
    head's lanes by every head's row, and `_emit` keeps the diagonal
    blocks.  int8 pages enter the dots as they are and their fp32 scales
    multiply the scores and the softmax weights, one per (slot, head);
    a page's ``(bs, Hkv)`` scale block is too narrow to be cut out of HBM
    by a copy of the kernel's own, so the scale pools cross the call
    once a page of the run under a one-page block spec each, entry ``j*n
    + i`` of the row, and the pipeline brings them.

    With as many KV heads as query heads the query and the output cross
    the call flattened, ``(1, H*D)``, as the pages have it; with grouped
    heads they are ``(Hq, D)`` rows (for one KV head the block-diagonal
    layout is the query itself and nothing is masked).

    Keys and values may differ in width (a KV head a run of ``dk`` lanes
    of a key row, of ``dv`` of a value row).  ``windowed``: a third
    prefetched scalar a lane, its first visible position, which lies in
    the row's first entry (`paged_attention`), so a live run still holds
    a visible slot.  ``sink``: an ``(Hq, 1)`` logit joins each row's
    denominator at the end of the walk."""
    from jax.experimental import pallas as pl

    if windowed:
        first_ref, rest = rest[0], rest[1:]
    q_ref, rest = rest[0], rest[1:]
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    pools, rest = rest[:2], rest[2:]                    # K and V, in HBM
    if kv_quant:       # a scale block a page of the run, K's then V's
        scales, rest = (rest[:n], rest[n:2 * n]), rest[2 * n:]
    o_ref, k_buf, v_buf, sem, slot_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    lanes = pl.num_programs(0)
    nb = pl.num_programs(1)
    t = pos_ref[b]
    run = n * bs
    group = heads // kv_heads
    dv = acc_ref.shape[-1] // kv_heads
    d = k_buf.shape[-1] // kv_heads

    def run_copies(lane, first, slot, start):
        _run_copies(pools, (k_buf, v_buf), sem, slot,
                    lambda e: tables_ref[lane, e], first, pos_ref[lane], n,
                    bs, start)

    def own(d=dv):
        """(Hq, Hkv*d): the lanes of row h that are its KV head's.  Built
        where it is used, so a skipped run pays nothing for it."""
        shape = (heads, kv_heads * d)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if group > 1:
            row = row // group
        return jnp.logical_and(col >= row * d, col < (row + 1) * d)

    def q_block_diagonal():
        q = q_ref[0].astype(jnp.float32)
        if group == 1:                      # (1, H*D) over H rows
            return jnp.where(own(d), q, 0.0)
        if kv_heads == 1:                   # (Hq, D): as it is
            return q
        return jnp.where(own(d), jnp.concatenate([q] * kv_heads, axis=1),
                         0.0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, jnp.finfo(jnp.float32).min)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_and(b == 0, j == 0))
    def _first():                           # no step before it to fetch it
        slot_ref[0] = 0
        v_buf[...] = jnp.zeros_like(v_buf)
        run_copies(0, 0, 0, start=True)

    # length bound: runs past the lane's current position hold no
    # visible slot — skip their copies and their math entirely (same
    # trick as _fa_kernel_resident's nk_live).  Run j==0 is live
    # whatever the position says (every lane's first run is fetched by
    # the step before it and must be waited for), and t >= 0 makes m/l
    # finite by emit time.  A live run's first slot is visible, so its
    # row maximum is finite and masked slots weigh exactly 0.0.
    @pl.when(jnp.logical_or(j == 0, j * run <= t))
    def _update():
        slot = slot_ref[0]
        more = jnp.logical_and(j + 1 < nb, (j + 1) * run <= t)
        nxt_lane = jnp.where(more, b, b + 1)

        @pl.when(nxt_lane < lanes)
        def _prefetch():
            run_copies(nxt_lane, jnp.where(more, (j + 1) * n, 0), 1 - slot,
                       start=True)
        slot_ref[0] = 1 - slot
        run_copies(b, j * n, slot, start=False)

        def pages(buf):
            """The run one page under the other, float32: (n*bs, Hkv*D)."""
            return jnp.concatenate(
                [buf[slot, i].astype(jnp.float32) for i in range(n)], axis=0)

        def scale(refs):
            """(Hkv, n*bs): the run's scales, a column a position."""
            return jnp.concatenate([r[0] for r in refs], axis=0).T

        s = jax.lax.dot_general(
            q_block_diagonal(), pages(k_buf),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (Hq, n*bs)
        if kv_quant:
            s = s * scale(scales[0])
        s = s / math.sqrt(d)
        at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = j * run + at <= t
        if windowed:
            seen = jnp.logical_and(seen, j * run + at >= first_ref[b])
        _softmax_update(s, seen, pages(v_buf), m_ref, l_ref, acc_ref,
                        scale(scales[1]) if kv_quant else None)

    @pl.when(j == nb - 1)
    def _emit():
        l = l_ref[...]
        if sink:
            l = l + jnp.exp(sink_ref[...] - m_ref[...])
        if value_scale != 1.0:
            l = l * (1.0 / value_scale)
        if group == 1:
            o = jnp.where(own(), acc_ref[...] / l, 0.0)
            o_ref[0] = jnp.sum(o, axis=0, keepdims=True).astype(o_ref.dtype)
            return
        o = acc_ref[...] / l
        if kv_heads > 1:
            o = jnp.where(own(), o, 0.0)
            o = sum(o[:, k * dv:(k + 1) * dv] for k in range(kv_heads))
        o_ref[0] = o.astype(o_ref.dtype)


# what one grid step of the single-query kernel covers: the pages that
# bring a step's fixed cost under their own (the sweep of
# benchmark/paged_probe.py on a v5e, PERF.md section 6: best at both
# served widths, block 16 x row 1024 and block 64 x row 128), and of VMEM
# no more than this for the runs of K and V, two buffers each
_RUN_PAGES = 16
_RUN_VMEM = 4 * 1024 * 1024


def pages_per_step(block_size, blocks_per_seq, row_bytes) -> int:
    """How many consecutive block-table entries of a lane one grid step
    of the single-query kernel covers, from the shapes alone:
    `_RUN_PAGES`, never more than the sequence has, nor than `_RUN_VMEM`
    holds of the runs of K and V (two buffers each).  1 where nothing
    larger fits: the same kernel body over one page."""
    fit = _RUN_VMEM // (4 * block_size * row_bytes)
    return max(1, min(_RUN_PAGES, blocks_per_seq, fit))


def _paged_call(q, pools, tables, pos, interpret, first=None, sink=None,
                value_scale=1.0):
    """Shared pallas_call: ``pools`` is (pool_k, pool_v) or, for int8
    pages, (pool_k, pool_v, scale_k, scale_v).  With ``Hq == Hkv`` query
    and output cross the call with the head axis flattened, as the pages
    have it; grouped heads cross as ``(Hq, D)`` rows.

    Grid ``(lanes, runs)``, a run `pages_per_step` consecutive entries of
    the lane's table row, aligned to the table's index (so a position is
    the same bits whichever call attends it: a step, or a lane of a
    chunk wherever the chunk began).  The pages of a run are anywhere in
    the pool, so the pools cross the call in HBM as they lie (no block,
    no copy of a pool array) and the kernel fetches a run's pages itself.
    A row that is no whole number of runs is padded with the scratch
    block (block 0, which entries no sequence has reserved already
    name); no position lies there, so the entries are never fetched.

    ``first`` (lanes,), ``sink`` (Hq,) and ``value_scale`` as
    `paged_attention` has them; the value rows may be of another width
    than the key rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    bs, row = pools[0].shape[1:]
    Hkv = row // D
    row_v = pools[1].shape[2]
    Dv = row_v // Hkv
    if row != Hkv * D or H % Hkv or row_v != Hkv * Dv:
        raise ValueError(
            f"paged_attention: {H} query heads of {D} against pool rows "
            f"of {row} and {row_v}: the pools must hold a whole number of "
            "KV heads that divides the query's")
    kv_quant = len(pools) == 4
    grouped = H != Hkv
    if grouped and kv_quant:
        raise ValueError("paged_attention: the kernel has no int8 pages "
                         "for grouped heads (impl='dense' does)")
    nbps = tables.shape[1]
    n = pages_per_step(bs, nbps,
                       (row + row_v) * pools[0].dtype.itemsize // 2)
    if nbps % n:
        tables = jnp.pad(tables, ((0, 0), (0, -nbps % n)))
    windowed, sunk = first is not None, sink is not None
    kernel = functools.partial(_paged_kernel, bs=bs, n=n, heads=H,
                               kv_heads=Hkv, kv_quant=kv_quant,
                               windowed=windowed, sink=sunk,
                               value_scale=float(value_scale))
    # index maps take the grid's indices, then the prefetched scalars
    lane = pl.BlockSpec((1, H, D) if grouped else (1, 1, H * D),
                        lambda b, j, *_: (b, 0, 0))
    out = pl.BlockSpec((1, H, Dv) if grouped else (1, 1, H * Dv),
                       lambda b, j, *_: (b, 0, 0))
    scale_run = [pl.BlockSpec((1, bs, Hkv),
                              lambda b, j, t, *_, i=i: (t[b, j * n + i], 0, 0))
                 for i in range(n)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + windowed,
        grid=(B, tables.shape[1] // n),
        in_specs=[lane]
        + [pl.BlockSpec((H, 1), lambda b, j, *_: (0, 0))] * sunk
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * 2
        + scale_run * (2 * kv_quant),
        out_specs=out,
        scratch_shapes=[pltpu.VMEM((2, n) + pool.shape[1:], pool.dtype)
                        for pool in pools[:2]]
        + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32),
           pltpu.VMEM((H, row_v), jnp.float32),
           pltpu.VMEM((H, 1), jnp.float32),
           pltpu.VMEM((H, 1), jnp.float32)],
    )
    res = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,) + out.block_shape[1:], q.dtype),
        interpret=interpret,
        name="paged_attention_q8" if kv_quant else "paged_attention",
    )(tables, pos, *((first,) if windowed else ()),
      q if grouped else q.reshape(B, 1, H * D),
      *((sink.astype(jnp.float32).reshape(H, 1),) if sunk else ()),
      *pools[:2], *(scale for scale in pools[2:] for _ in range(n)))
    return res.reshape(B, H, Dv)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_core(q, pool_k, pool_v, tables, pos, interpret):
    return _paged_call(q, (pool_k, pool_v), tables, pos, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_core_q8(q, pool_k, pool_v, scale_k, scale_v, tables, pos,
                   interpret):
    return _paged_call(q, (pool_k, pool_v, scale_k, scale_v), tables, pos,
                       interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "value_scale"))
def _paged_core_opts(q, pool_k, pool_v, tables, pos, first, sink, interpret,
                     value_scale):
    """`_paged_core` with a first visible position a lane and, or, a sink
    logit a head (either may be None) and a value scale."""
    return _paged_call(q, (pool_k, pool_v), tables, pos, interpret, first,
                       sink, value_scale)


# --- a window of one sequence's queries: each page once ----------------- #
_WINDOW_VMEM = 48 * 1024 * 1024
# a row tile's float32 scores against one run, in elements: what bounds
# the rows a grid step of the window form takes
_WINDOW_SCORES = 1024 * 1024


def _window_rows(T, group, run):
    """Rows of a KV head (``group`` heads x ``T`` queries, head-major) one
    grid step of the window form takes against a run of ``run`` positions:
    all of them where their scores fit `_WINDOW_SCORES`, else whole heads,
    else a part of one head's queries that the sublane tiling can cut (a
    multiple of 16 that divides ``T``).  A tile changes which rows share a
    matmul and nothing of a row's own arithmetic."""
    cap = max(16, _WINDOW_SCORES // run)
    # tpulint: disable-next=TPU004 -- T, group and run are static shape ints
    if group * T <= cap:
        return group * T
    # tpulint: disable-next=TPU004 -- T and cap are static shape ints
    if T <= cap:
        return T * max(g for g in range(1, group + 1)
                       if group % g == 0 and g * T <= cap)
    parts = [r for r in range(16, cap + 1, 16) if T % r == 0]
    return max(parts) if parts else T


def _window_vmem(rows, run, kv_heads, dk, dv):
    """Bytes of VMEM a grid step of ``rows`` rows a KV head holds beside
    the runs' buffers (`_RUN_VMEM`), everything counted as float32 and a
    last dimension padded to 128 lanes: the query's and the output's block
    (two buffers each), the accumulator, ``m`` and ``l``, and three arrays
    of a tile's scores."""
    def lanes(d):
        return -(-d // 128) * 128
    state = kv_heads * rows * (2 * lanes(dk) + 3 * lanes(dv) + 2 * 128)
    return 4 * (state + 3 * rows * run)


def window_kernel_fits(T, heads, kv_heads, head_dim, v_dim=None) -> bool:
    """Whether `paged_attention_window` has a kernel for these sizes, from
    the shapes alone: ``heads`` a multiple of ``kv_heads``, and a tiling
    of a KV head's ``heads // kv_heads x T`` query rows (`_window_rows`)
    whose blocks, softmax state and scores fit `_WINDOW_VMEM` beside the
    runs' buffers, whatever the pages' size (the longest run `_RUN_VMEM`
    lets pages of 2 bytes an element have).  Any head width: the kernel
    cuts a KV head's lanes out of the run itself."""
    v_dim = head_dim if v_dim is None else v_dim
    if kv_heads < 1 or heads % kv_heads:
        return False
    run = max(16, _RUN_VMEM // (4 * kv_heads * (head_dim + v_dim)))
    rows = _window_rows(T, heads // kv_heads, run)
    return _RUN_VMEM + _window_vmem(rows, run, kv_heads, head_dim, v_dim) \
        <= _WINDOW_VMEM


def _window_kernel(table_ref, start_ref, q_ref, pool_k, pool_v, o_ref,
                   k_buf, v_buf, sem, slot_ref, acc_ref, m_ref, l_ref, *,
                   bs, n, T, value_scale):
    """One grid step = one (tile of query rows, run of ``n`` pages), every
    KV head of the run.  The pools stay in HBM and the run's pages are
    fetched as `_paged_kernel` fetches them (`_run_copies`: whole pages
    into one of two buffers, the next live step's under this step's math;
    the next live step is this tile's next run or the next tile's run 0);
    a run wholly past the window's last position starts nothing and
    computes nothing, and of a live run only the pages that hold a
    position at or before it are copied.

    The heads are separated in VMEM: KV head ``h``'s rows (``q_ref[h]``:
    its ``Hq/Hkv`` heads x the tile's queries) meet the lanes ``h*D ..
    (h+1)*D`` of the run's keys and the lanes ``h*Dv .. (h+1)*Dv`` of its
    values and no other head's, a static slice of the buffer.  Row ``r``
    of tile ``i`` is row ``i*rows + r`` of the KV head's ``G*T``
    head-major rows: the query at position ``start + (i*rows + r) % T``,
    which sees the slots at or before its own.  The query and the keys
    enter ``q . k^T`` as bf16 where both are stored so (each product of
    two bf16 values is exact in float32, so this is the float32 dot's
    products and float32 accumulation); scores, softmax state, weights
    and the accumulator are float32 (`_softmax_update`)."""
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)
    tiles, nb = pl.num_programs(0), pl.num_programs(1)
    kv_heads, rows, dk = q_ref.shape
    dv = o_ref.shape[-1]
    run = n * bs
    start = start_ref[0]
    last = start + (T - 1)

    def run_copies(first, slot, start):
        _run_copies((pool_k, pool_v), (k_buf, v_buf), sem, slot,
                    lambda e: table_ref[e], first, last, n, bs, start)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, jnp.finfo(jnp.float32).min)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _first():                           # no step before it to fetch it
        slot_ref[0] = 0
        v_buf[...] = jnp.zeros_like(v_buf)
        run_copies(0, 0, start=True)

    # run 0 is live whatever the positions say (start >= 0: every row sees
    # its first slot, so every row's maximum is finite from run 0 on)
    @pl.when(jnp.logical_or(j == 0, j * run <= last))
    def _update():
        slot = slot_ref[0]
        more = jnp.logical_and(j + 1 < nb, (j + 1) * run <= last)

        @pl.when(jnp.logical_or(more, i + 1 < tiles))
        def _prefetch():
            run_copies(jnp.where(more, (j + 1) * n, 0), 1 - slot, start=True)
        slot_ref[0] = 1 - slot
        run_copies(j * n, slot, start=False)

        shape = (rows, run)
        # a tile is whole heads, or a part of one head's queries
        at = jax.lax.broadcasted_iota(jnp.int32, shape, 0) % T \
            if rows % T == 0 else \
            (i * rows) % T + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        seen = j * run + jax.lax.broadcasted_iota(jnp.int32, shape, 1) \
            <= start + at

        def head(buf, h, d):
            """KV head h's lanes of the run, one page under the other."""
            return buf[slot, :, :, h * d:(h + 1) * d].reshape(run, d)

        for h in range(kv_heads):
            q, k = q_ref[h], head(k_buf, h, dk)
            if not q.dtype == k.dtype == jnp.bfloat16:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) / math.sqrt(dk)
            _softmax_update(s, seen, head(v_buf, h, dv).astype(jnp.float32),
                            m_ref.at[h], l_ref.at[h], acc_ref.at[h])

    @pl.when(j == nb - 1)
    def _emit():
        l = l_ref[...]
        if value_scale != 1.0:
            l = l * (1.0 / value_scale)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "value_scale"))
def _window_core(q, pool_k, pool_v, table_row, start, interpret,
                 value_scale=1.0):
    """Grid ``(row tiles, runs)``, a run `pages_per_step` consecutive
    entries of the sequence's table row, aligned to the table's index and
    not to the chunk's start, so a position's output is the same bits
    wherever the chunk that computes it began.  A row that is no whole
    number of runs is padded with the scratch block, as `_paged_call`
    pads it: no position lies there."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, D = q.shape
    bs, row = pool_k.shape[1:]
    Hkv = row // D
    row_v = pool_v.shape[2]
    Dv = row_v // Hkv
    G = H // Hkv
    nbps = table_row.shape[0]
    n = pages_per_step(bs, nbps, (row + row_v) * pool_k.dtype.itemsize // 2)
    if nbps % n:
        table_row = jnp.pad(table_row, (0, -nbps % n))
    rows = _window_rows(T, G, n * bs)
    # (T, Hkv, G, D) -> (Hkv, G*T, D): a KV head's rows, head-major
    qh = q.reshape(T, Hkv, G, D).transpose(1, 2, 0, 3).reshape(Hkv, G * T, D)

    def mine(d):
        return pl.BlockSpec((Hkv, rows, d), lambda i, j, *_: (0, i, 0))

    out = pl.pallas_call(
        functools.partial(_window_kernel, bs=bs, n=n, T=T,
                          value_scale=float(value_scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G * T // rows, table_row.shape[0] // n),
            in_specs=[mine(D)] + [pl.BlockSpec(memory_space=pltpu.HBM)] * 2,
            out_specs=mine(Dv),
            scratch_shapes=[pltpu.VMEM((2, n) + pool.shape[1:], pool.dtype)
                            for pool in (pool_k, pool_v)]
            + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32),
               pltpu.VMEM((Hkv, rows, Dv), jnp.float32),
               pltpu.VMEM((Hkv, rows, 1), jnp.float32),
               pltpu.VMEM((Hkv, rows, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Hkv, G * T, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WINDOW_VMEM + 16 * 1024 * 1024),
        interpret=interpret, name="paged_attention_window",
    )(table_row, start.reshape(1), qh, pool_k, pool_v)
    return out.reshape(Hkv, G, T, Dv).transpose(2, 0, 1, 3).reshape(T, H, Dv)


def paged_attention_window(q, pool_k, pool_v, table_row, start, *,
                           value_scale: float = 1.0,
                           interpret: Optional[bool] = None):
    """Attention of a window of ONE sequence's queries ``q`` (T, Hq, D),
    at positions ``start .. start+T-1``, against that sequence's pages
    (``table_row`` (blocks_per_seq,)): query t attends slots ``<= start +
    t``, as `paged_attention` would with every position a lane of the same
    table, but each page is read once for a whole tile of them (a prefill
    chunk's attention).  The value pool's rows may be narrower than the
    key pool's (the result is ``(T, Hq, Dv)``) and ``value_scale``
    multiplies the result, as in `paged_attention`; no first visible
    position and no sink.  Kernel only (`window_kernel_fits` says for
    which sizes; float pages); a caller without one uses
    `paged_attention`."""
    T, H, D = q.shape
    row, row_v = pool_k.shape[2], pool_v.shape[2]
    Hkv = row // D
    if Hkv < 1 or row != Hkv * D or H % Hkv or row_v % Hkv:
        raise ValueError(
            f"paged_attention_window: {H} query heads of {D} against pool "
            f"rows of {row} and {row_v}: the pools must hold a whole number "
            "of KV heads that divides the query's")
    if not window_kernel_fits(T, H, Hkv, D, row_v // Hkv):
        raise ValueError(
            f"paged_attention_window: no kernel for {T} queries of {H} "
            f"heads of {D} over {Hkv} KV heads (window_kernel_fits)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # KV heads are independent: per shard of them under a mesh, a shard's
    # heads a contiguous run of a page's lanes that stays whole 128-lane
    # tiles of both pools' rows (what the kernel's copies can cut out)
    whole = math.lcm(*(128 // math.gcd(128, d) for d in (D, row_v // Hkv)))
    heads, = mosaic.split((Hkv,), (whole,))
    core = functools.partial(_window_core, interpret=interpret,
                             value_scale=float(value_scale))
    pool = P(None, None, heads)
    return mosaic.per_shard(core, (P(None, heads), pool, pool, P(), P()),
                            P(None, heads))(
        q, pool_k, pool_v, table_row, jnp.asarray(start, jnp.int32))


def paged_attention(q, pool_k, pool_v, tables, pos, *,
                    scale_k=None, scale_v=None, first=None, sink=None,
                    value_scale: float = 1.0,
                    impl: Optional[str] = None,
                    interpret: Optional[bool] = None):
    """Single-query attention of ``q`` (B, Hq, D) against the paged KV
    pool (num_blocks, block_size, Hkv*D), ``Hq`` a multiple of ``Hkv``
    (query head h reads KV head ``h // (Hq // Hkv)``), through per-lane
    block tables
    (B, blocks_per_seq) at positions ``pos`` (B,), attending slots
    ``<= pos`` — the serving decode-step attention.

    ``impl``: "pallas" (kernel; interpret-mode on CPU), "dense" (the
    PR 12 gather recipe), or None for `default_impl`.  Pass
    ``scale_k/scale_v`` (num_blocks, block_size, H) fp32 when the pool
    is int8 (per-head symmetric quantization).

    The value pool's rows may be of another width than the key pool's
    (``Hkv * Dv``): the result is ``(B, Hq, Dv)``.  ``first`` (B,): the
    first visible position of each lane (slots ``first .. pos`` are
    attended: a sliding window); it must lie in the row's first entry, so
    a caller with a window hands each lane's table from its first visible
    block on, positions counted from that block's start.  ``sink`` (Hq,):
    a logit a query head that joins the softmax's denominator and carries
    no value.  ``value_scale`` multiplies the result.  None of the three
    with int8 pages.
    """
    impl = impl or default_impl()
    opts = first is not None or sink is not None or value_scale != 1.0
    if opts and scale_k is not None:
        raise ValueError("paged_attention: first / sink / value_scale are "
                         "not built for int8 pages")
    if impl == "dense":
        return paged_attention_dense(q, pool_k, pool_v, tables, pos,
                                     scale_k, scale_v, first=first,
                                     sink=sink, value_scale=value_scale)
    if impl != "pallas":
        raise ValueError(f"paged_attention impl {impl!r} (pallas|dense)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # lanes and heads are independent: per shard of both under a mesh
    # (ops/mosaic.py); every shard walks the whole pool of its heads,
    # which are contiguous runs of the pool's last dimension
    # (with grouped heads the KV heads are what is split: a shard's
    # query heads are those of its KV heads)
    kv_heads = pool_k.shape[2] // q.shape[2]
    lanes, heads = mosaic.split((q.shape[0], kv_heads))
    lane, pool = P(lanes, heads), P(None, None, heads)
    if opts:
        core = functools.partial(_paged_core_opts, interpret=interpret,
                                 value_scale=float(value_scale))
        in_specs = (lane, pool, pool, P(lanes), P(lanes),
                    None if first is None else P(lanes),
                    None if sink is None else P(heads))
        return mosaic.per_shard(core, in_specs, lane)(
            q, pool_k, pool_v, tables, pos, first, sink)
    pools = (pool_k, pool_v) if scale_k is None \
        else (pool_k, pool_v, scale_k, scale_v)
    core = functools.partial(_paged_core if scale_k is None
                             else _paged_core_q8, interpret=interpret)
    in_specs = (lane,) + (pool,) * len(pools) + (P(lanes), P(lanes))
    return mosaic.per_shard(core, in_specs, lane)(q, *pools, tables, pos)
