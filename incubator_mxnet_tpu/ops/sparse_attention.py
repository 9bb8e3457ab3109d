"""Learned sparse attention over the serving pools: an index scores every
earlier position of a query, the ``topk`` best are selected, and the query
attends those alone (the published "indexer" of several long-context
models).  Three routines, each over ``N`` sequences of ``T`` queries: a
decode step is ``N`` lanes of one query, a prefill chunk one sequence of
``T``; query ``t`` of sequence ``n`` stands at position ``last[n] - (T-1)
+ t``.

* `index_scores` — ``I(t, s) = sum_j w[t, j] * relu(qi[t, j] . ki[s])``
  against the index keys of the sequence's pages, a third pool
  ``(num_blocks, block_size, index_row(dim))`` beside K and V that the
  same block tables name.  Float32 out; what lies behind a query's own
  position is unspecified.
* `select_positions` — the ``k`` positions at or before a query's own with
  the largest scores as an int32 mask, ties to the lower position; every
  position while there are no more than ``k``.  No sort: the ``k``-th
  largest score is found bit by bit on the scores' order-preserving
  integer image, 32 counts a row; the kernel copies a tile of rows'
  scores into VMEM up to the tile's highest position alone and counts
  them there.
* `paged_attention_sparse` — grouped-query attention of each query over
  the positions its mask names, through the block table, float32 scores
  and softmax.

``impl="pallas"`` walks the pages as `ops.paged_attention`'s kernels do
(`_run_copies`: the pools in HBM as they lie, runs of whole pages copied by
the kernel itself, the next live run's under this run's math): a grid step
is a (sequence, tile of query rows), and a loop inside it visits the
sequence's live runs, those up to its last position, and no other
(`_walk`; `runs_walked` reckons them on the host), with
the kernels' ``name=`` ``index_scores`` and ``paged_attention_sparse``,
and the selection's kernel ``select_positions`` between them, which
writes the mask as the attention kernel reads it; ``impl="xla"`` is the
plain form the tests compare against and the CPU serves by: the pages
gathered into a dense view, every column counted, full-width softmax.

The attention kernel reads every page up to the sequence's last position
and weighs by the mask, because the selection hands over a mask: a gather
of the selected rows is the cheaper read, but a list made from a mask
costs more than the gather saves under a mean context of some 15k
(measured for the step: docs/serving.md, "An index over the pages"); a
selection that emits the list is ROADMAP's Reach A8(a).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import mosaic
from .paged_attention import (_WINDOW_VMEM, _run_copies, _softmax_update,
                              _window_rows, pages_per_step)

__all__ = ["index_scores", "index_row", "select_positions",
           "paged_attention_sparse", "runs_walked"]

_MIN = float(jnp.finfo(jnp.float32).min)
# query rows of one head a grid step of the index kernel scores at once
_INDEX_ROWS = 256


def index_row(dim: int) -> int:
    """Lanes of a row of the index pool: a position's index key, then
    zeros up to whole tiles of 128 lanes.  The device lays a narrower row
    out at that width anyway, and a page can only be cut out of the pool
    along whole tiles."""
    return -(-dim // 128) * 128


def _walk(tables_ref, last_ref, pools, bufs, sem, slot_ref, n, bs, zero,
          update, slabs=(), outs=()):
    """The fetching schedule of both kernels, grid ``(sequence, row
    tile)``: a loop over the sequence's live runs of ``n`` pages (those
    that hold a position at or before its last; run 0 always) and no
    other.  A turn starts the copies of the next live run (this tile's
    next run, else run 0 of the next tile or sequence) into the other
    buffer, waits for its own and calls ``update(slot)``; the buffer a
    run lands in alternates from run to run across grid steps
    (``slot_ref``).  ``zero``: buffers that must start finite (values: an
    unfetched page's place is weighed by exactly 0.0).  ``slabs``:
    ``(src, buf)``, ``src(seq, tile, run)`` a slice of an HBM array that
    is copied with the run's pages into ``buf[slot]``.  ``outs``: ``(dst,
    buf, sem)``: what ``update`` left in ``buf[slot]`` is copied to
    ``dst(seq, tile, run)`` in HBM; a turn waits for the turn before's
    copy (a wait needs a copy's size alone), the grid step for its
    last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, i = pl.program_id(0), pl.program_id(1)
    seqs, tiles = pl.num_programs(0), pl.num_programs(1)
    runs = tables_ref.shape[1] // n
    live = jax.lax.clamp(1, jax.lax.div(last_ref[b], n * bs) + 1, runs)

    def copies(seq, tile, j, slot, start):
        _run_copies(pools, bufs, sem, slot, lambda e: tables_ref[seq, e],
                    j * n, last_ref[seq], n, bs, start)
        for src, buf in slabs:
            copy = pltpu.make_async_copy(src(seq, tile, j), buf.at[slot],
                                         sem.at[slot])
            copy.start() if start else copy.wait()

    def written(j, slot):
        return [pltpu.make_async_copy(buf.at[slot], dst(b, i, j),
                                      osem.at[slot])
                for dst, buf, osem in outs]

    @pl.when((b == 0) & (i == 0))
    def _first():                       # no step before it to fetch it
        slot_ref[0] = 0
        for buf in zero:
            buf[...] = jnp.zeros_like(buf)
        copies(0, 0, 0, 0, True)

    def turn(j, _):
        slot = slot_ref[0]
        more = j + 1 < live
        nxt = jax.lax.select(more | (i + 1 < tiles), b, b + 1)

        @pl.when(nxt < seqs)
        def _prefetch():
            tile = jax.lax.select(
                more, i, jax.lax.select(i + 1 < tiles, i + 1, 0))
            copies(nxt, tile, jax.lax.select(more, j + 1, 0), 1 - slot, True)
        slot_ref[0] = 1 - slot
        copies(b, i, j, slot, False)
        update(slot)
        for copy in written(j, slot):
            copy.start()
        if outs:                        # the turn before's, out of the other
            @pl.when(j > 0)
            def _out():
                for copy in written(0, 1 - slot):
                    copy.wait()

    jax.lax.fori_loop(0, live, turn, None)
    if outs:                            # the last turn's
        for copy in written(0, 1 - slot_ref[0]):
            copy.wait()


def _pad_runs(tables, n):
    """The table rows padded to whole runs with the scratch block: no
    position lies there, so the entries are never fetched."""
    return jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % n)))


def _index_rows(T):
    """Query rows of a chunk's ``T`` a grid step of the index kernel
    takes: `_INDEX_ROWS` where they divide ``T``, else all of them."""
    return _INDEX_ROWS if T % _INDEX_ROWS == 0 else T


def runs_walked(last, T, bs, nbps, index_bytes, kv_bytes, group):
    """``(walked, table)`` for one call each of `index_scores` and
    `paged_attention_sparse` over sequences of ``T`` queries whose last
    stand at ``last`` (host ints), pages of ``bs`` positions, ``nbps`` a
    table row, rows of ``index_bytes`` and ``kv_bytes`` (K's, V's), a KV
    head ``group`` query heads: the runs the kernels' loops visit (`_walk`:
    a sequence's live runs, once a row tile), and the grid steps a grid
    with a run axis over the whole table would have taken."""
    last = np.minimum(np.asarray(last), nbps * bs - 1)
    walked = table = 0
    for row_bytes, kv in ((index_bytes, False), (kv_bytes, True)):
        n = pages_per_step(bs, nbps, row_bytes)
        runs = -(-nbps // n)
        tiles = group * T // _window_rows(T, group, n * bs) if kv else \
            1 if T == 1 else T // _index_rows(T)
        live = np.clip(last // (n * bs) + 1, 1, runs)
        walked += tiles * int(live.sum())
        table += tiles * runs * last.size
    return walked, table


# --- index scores ----------------------------------------------------------- #
def _index_kernel(tables_ref, last_ref, q_ref, w_ref, pool, o_hbm, buf,
                  sem, slot_ref, o_buf, o_sem, *, bs, n, fold):
    """One grid step = one (sequence, tile of query rows), a turn of its
    loop one live run of ``n`` pages.  ``q_ref`` (P, R, dim) and ``w_ref``
    (P, R, 1): the step's rows against the run's keys ``(n*bs, dim)``,
    ``sum_p relu(q[p] . k^T) * w[p]`` -> (R, n*bs) into ``o_buf[slot]``,
    copied from there to the run's columns of the tile's rows in
    ``o_hbm``.  A chunk has a head a plane and a query a row; a lane of a
    decode step has one plane, its heads the rows, which ``fold`` adds
    up."""
    from jax.experimental import pallas as pl

    run = n * bs
    out_rows = o_buf.shape[1]

    def update(slot):
        k = buf[slot].reshape(run, buf.shape[-1])
        acc = None
        for p in range(q_ref.shape[0]):
            q = q_ref[p]
            if not q.dtype == k.dtype == jnp.bfloat16:
                q, kk = q.astype(jnp.float32), k.astype(jnp.float32)
            else:
                kk = k
            s = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.maximum(s, 0.0) * w_ref[p]
            acc = s if acc is None else acc + s
        if fold:
            acc = jnp.sum(acc, axis=0, keepdims=True)
        o_buf[slot] = acc

    def dst(seq, tile, j):
        return o_hbm.at[seq, pl.ds(tile * out_rows, out_rows),
                        pl.ds(pl.multiple_of(j * run, run), run)]

    _walk(tables_ref, last_ref, (pool,), (buf,), sem, slot_ref, n, bs, (),
          update, outs=((dst, o_buf, o_sem),))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_core(qi, w, pool, tables, last, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, T, Hi = qi.shape[:3]
    bs, Di = pool.shape[1:]
    nbps = tables.shape[1]
    n = pages_per_step(bs, nbps, Di * pool.dtype.itemsize)
    # zeros against the lanes a row has beyond the key's
    qi = jnp.pad(qi, ((0, 0),) * 3 + ((0, Di - qi.shape[3]),))
    tables = _pad_runs(tables, n)
    run = n * bs
    fold = T == 1
    if fold:                # a plane, the heads its rows
        rows, planes, q, wc = Hi, 1, qi.reshape(N, 1, Hi, Di), \
            w.reshape(N, 1, Hi, 1)
    else:                   # a head a plane, the queries its rows
        rows = _index_rows(T)
        planes, q, wc = Hi, qi.transpose(0, 2, 1, 3), \
            w.transpose(0, 2, 1)[..., None]
    out_rows = 1 if fold else rows
    out = pl.pallas_call(
        functools.partial(_index_kernel, bs=bs, n=n, fold=fold),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, 1 if fold else T // rows),
            in_specs=[
                pl.BlockSpec((None, planes, rows, Di),
                             lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec((None, planes, rows, 1),
                             lambda b, i, *_: (b, 0, i, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
            scratch_shapes=[pltpu.VMEM((2, n) + pool.shape[1:], pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((2, out_rows, run), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((N, T, tables.shape[1] * bs),
                                       jnp.float32),
        interpret=interpret, name="index_scores",
    )(tables, jnp.minimum(last, nbps * bs - 1), q, wc.astype(jnp.float32),
      pool)
    return out[..., :nbps * bs]


def _index_xla(qi, w, pool, tables):
    N = qi.shape[0]
    k = pool[tables].reshape(N, -1, pool.shape[-1])[..., :qi.shape[3]]
    s = jnp.einsum("nthd,nwd->nthw", qi, k,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("nthw,nth->ntw", jnp.maximum(s, 0.0),
                      w.astype(jnp.float32))


def index_scores(qi, w, pool_i, tables, last, *, impl: Optional[str] = None,
                 interpret: Optional[bool] = None):
    """Index scores of ``qi`` (N, T, heads, dim) with head weights ``w``
    (N, T, heads) against the index keys ``pool_i`` (num_blocks,
    block_size, `index_row` (dim): a key in a row's first ``dim`` lanes) of
    each sequence's pages (``tables`` (N,
    blocks_per_seq)); ``last`` (N,) is the position of each sequence's
    last query (it may lie past the table's end: no page is read there).
    Returns float32 (N, T, blocks_per_seq * block_size):
    ``sum_j w_j relu(qi_j . ki_s)`` at the positions ``s`` at or before
    the query's own; what lies behind it is unspecified (the kernel writes
    no run behind the sequence's last position): `select_positions` reads
    a row up to its query's position alone."""
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if impl == "xla":
        return _index_xla(qi, w, pool_i, tables)
    if impl != "pallas":
        raise ValueError(f"index_scores impl {impl!r} (pallas|xla)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # sequences are independent: per shard of them under a mesh
    seqs, = mosaic.split((qi.shape[0],))
    return mosaic.per_shard(
        functools.partial(_index_core, interpret=interpret),
        (P(seqs), P(seqs), P(), P(seqs), P(seqs)), P(seqs))(
        qi, w, pool_i, tables, last)


# --- the selection ---------------------------------------------------------- #
_SIGN = -(1 << 31)          # int32's sign bit, the least int32
# VMEM a grid step of the selection kernel holds of its rows: their scores'
# image and the mask's block (two buffers), four bytes a column each
_SELECT_VMEM = 32 * 1024 * 1024
# the widest tile of columns the kernel copies and counts at once
_SELECT_COLS = 2048


def _select_xla(scores, pos, k):
    u32 = jnp.uint32
    valid = jnp.arange(scores.shape[-1], dtype=jnp.int32) <= pos[:, None]
    b = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), u32)
    key = jnp.where(b >> 31 == 1, ~b, b | u32(1 << 31))
    key = jnp.where(valid, key, u32(0))     # below every number's image

    # a loop, not its turns laid out: the compiler keeps the loop's image of
    # a chunk's scores in fast memory, and reads it from HBM for every
    # pass that stands alone (PERF.md section 6, PR 38)
    def turn(i, tau):
        cand = tau | (u32(1 << 31) >> i.astype(u32))
        n = jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, tau)

    tau = jax.lax.fori_loop(0, 32, turn, jnp.zeros(pos.shape, u32))
    above = key > tau[:, None]
    equal = (key == tau[:, None]) & valid
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    crowded = jnp.any(jnp.sum(equal, axis=-1, dtype=jnp.int32) > room)
    return (above | jax.lax.cond(
        crowded,
        lambda: equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                         <= room[:, None]),
        lambda: equal)).astype(jnp.int32)


def _select_tiles(rows, width):
    """(rows a grid step of the selection kernel takes, columns a tile):
    the most rows of 64, 32, 16 and 8 that divide ``rows`` and whose image
    and mask fit `_SELECT_VMEM` (more rows, more work between two turns'
    waits on each other); the widest tile of whole 128 lanes up to
    `_SELECT_COLS` that divides ``width`` (the whole row where none
    does)."""
    tiles = [c for c in range(128, min(width, _SELECT_COLS) + 1, 128)
             if width % c == 0]
    fit = [r for r in (8, 16, 32, 64)
           if rows % r == 0 and 3 * 4 * r * width <= _SELECT_VMEM]
    return max(fit, default=8), max(tiles, default=width)


def _select_kernel(scores, pos_ref, o_ref, img, sem, *, k, bits):
    """One grid step = one tile of ``R`` rows (a decode step's lanes, or a
    chunk's queries of one sequence).  Only the column tiles up to the
    tile's highest position are copied, made the scores' order-preserving
    int32 image in place (a position behind a row's own: the least int32,
    below every number's image) and counted, in each of the 32 turns that
    build a row's threshold bit by bit; the rest of the row's mask is
    written 0.  Ties at the threshold are taken from the lowest position
    up where a row of the tile has more of them than room: the largest
    column bound that leaves no more than the room is built the same way,
    ``bits`` turns."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i32, f32 = jnp.int32, jnp.float32
    n_tiles, R, C = img.shape
    lanes = 128 if C % 128 == 0 else C
    r0 = pl.program_id(0) * R
    ends = pos_ref[...] + 1                             # (R, 1)
    live = jnp.clip(jnp.max(ends - 1) // C + 1, 1, n_tiles)
    col = jax.lax.broadcasted_iota(i32, (R, C), 1)

    def cols(c):                # tile c's columns, whole lane tiles
        return pl.ds(pl.multiple_of(c * C, lanes), C)

    def copy(c):
        return pltpu.make_async_copy(scores.at[pl.ds(r0, R), cols(c)],
                                     img.at[c], sem.at[c])

    def key(c):
        return jax.lax.bitcast_convert_type(img[c], i32)

    def image(c, _):
        copy(c).wait()
        b = key(c)
        b = jnp.where(col + c * C < ends, b ^ ((b >> 31) & ~_SIGN), _SIGN)
        img[c] = jax.lax.bitcast_convert_type(b, f32)

    jax.lax.fori_loop(0, live, lambda c, _: copy(c).start(), None)
    jax.lax.fori_loop(0, live, image, None)

    def count(pred):
        """(R, 1): the columns of the live tiles where ``pred(key, first
        column)`` holds, loaded a slice of ``lanes`` columns at a time and
        added into two accumulators in turn (two chains the VPU
        interleaves)."""
        def tile(c, accs):
            accs = list(accs)
            for n, j in enumerate(range(0, C, lanes)):
                x = jax.lax.bitcast_convert_type(img[c, :, j:j + lanes], i32)
                accs[n % 2] = accs[n % 2] + pred(x, c * C + j).astype(i32)
            return tuple(accs)
        accs = jax.lax.fori_loop(0, live, tile,
                                 (jnp.zeros((R, lanes), i32),) * 2)
        return jnp.sum(accs[0] + accs[1], axis=1, keepdims=True)

    at = jax.lax.broadcasted_iota(i32, (R, lanes), 1)

    def turn(t, tau):
        cand = tau | jax.lax.shift_right_logical(jnp.int32(_SIGN), t)
        thr = jnp.broadcast_to(cand ^ _SIGN, (R, lanes))
        return jnp.where(count(lambda x, _: x >= thr) >= k, cand, tau)

    thr = jax.lax.fori_loop(0, 32, turn, jnp.zeros((R, 1), i32)) ^ _SIGN
    thr_l, ends_l = (jnp.broadcast_to(a, (R, lanes)) for a in (thr, ends))
    room = k - count(lambda x, _: x > thr_l)
    crowded = count(lambda x, c0: (x == thr_l) & (at + c0 < ends_l)) > room

    def place(t, bound):
        cand = bound | jnp.left_shift(jnp.int32(1), bits - 1 - t)
        lim = jnp.broadcast_to(jnp.minimum(cand, ends), (R, lanes))
        n = count(lambda x, c0: (x == thr_l) & (at + c0 < lim))
        return jnp.where(n <= room, cand, bound)

    # a row that is not crowded builds the largest bound: all its equals
    lim = jnp.minimum(ends, jax.lax.cond(
        jnp.max(crowded.astype(i32)) > 0,
        lambda: jax.lax.fori_loop(0, bits, place, jnp.zeros((R, 1), i32)),
        lambda: ends))

    def mask(c, _):
        x = key(c)
        o_ref[:, cols(c)] = (
            (x > thr) | ((x == thr) & (col + c * C < lim))).astype(i32)

    def behind(c, _):
        o_ref[:, cols(c)] = jnp.zeros((R, C), i32)

    jax.lax.fori_loop(0, live, mask, None)
    jax.lax.fori_loop(live, n_tiles, behind, None)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _select_core(scores, pos, k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, W = scores.shape
    pad = -M % 8                # rows of no position: nothing is seen
    scores = jnp.pad(scores, ((0, pad), (0, 0)))
    pos = jnp.pad(pos, (0, pad), constant_values=-1)
    R, C = _select_tiles(M + pad, W)
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k, bits=W.bit_length()),
        grid=((M + pad) // R,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec((R, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((R, W), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((W // C, R, C), jnp.float32),
                        pltpu.SemaphoreType.DMA((W // C,))],
        out_shape=jax.ShapeDtypeStruct((M + pad, W), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SELECT_VMEM + 16 * 1024 * 1024),
        interpret=interpret, name="select_positions",
    )(scores.astype(jnp.float32), pos[:, None])
    return out[:M]


def select_positions(scores, pos, k: int, *, impl: Optional[str] = None,
                     interpret: Optional[bool] = None):
    """int32 0/1, ``scores``' shape: for each row of float32 ``scores``
    (..., W), whose query stands at ``pos`` (...), the ``k`` positions
    ``s <= pos`` with the largest scores, ties to the lower position; all
    of them while ``pos < k``.  The mask is what `paged_attention_sparse`
    reads.

    The ``k``-th largest score is found without a sort: a float's bits,
    with the sign's half of the line turned over, order as the floats do,
    and the largest threshold that still leaves ``k`` scores at or above
    it is built from the most significant bit down, a bit a turn of a
    loop: a turn counts the scores at or above the threshold with the bit
    set, and keeps the bit where ``k`` are left.  Scores that equal the
    threshold are then taken from the lowest position up until ``k`` are
    selected, where some row has more equals than places.
    ``impl="pallas"`` counts a tile of rows in VMEM, over the columns up
    to the tile's highest position alone (`_select_kernel`); ``"xla"``
    over every column, with a running count for the ties."""
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "xla")
    shape = scores.shape
    # a row a query, whatever the leading axes: a decode step's (lanes, 1,
    # W) lays each row out alone in a tile of eight, and the counting then
    # costs five times what it costs on (lanes, W) (PERF.md section 6)
    scores, pos = scores.reshape(-1, shape[-1]), pos.reshape(-1)
    if impl == "xla":
        seen = _select_xla(scores, pos, k)
    elif impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        # rows are independent: per shard of them under a mesh
        rows, = mosaic.split((scores.shape[0],))
        seen = mosaic.per_shard(
            functools.partial(_select_core, k=k, interpret=interpret),
            (P(rows), P(rows)), P(rows))(scores, pos)
    else:
        raise ValueError(f"select_positions impl {impl!r} (pallas|xla)")
    return seen.reshape(shape)


# --- attention over the selected positions --------------------------------- #
def _sparse_kernel(tables_ref, last_ref, q_ref, seen_hbm, pool_k, pool_v,
                   o_ref, k_buf, v_buf, sem, slot_ref, acc_ref, m_ref, l_ref,
                   seen_buf, *, bs, n):
    """One grid step = one (sequence, tile of query rows), every KV head,
    a turn of its loop one live run of ``n`` pages, as
    `ops.paged_attention._window_kernel` with a sequence axis before it
    and the selection in the causal mask's place: ``seen_buf[slot]``
    holds the tile's queries' mask over the run, copied with its pages
    (a row a query; a tile of whole heads repeats it a head), and a slot
    is weighed only where it is set.  A row whose run holds none of its
    positions adds weights that the first run which does wipes out (its
    rescaling factor is exactly 0.0), and every query has selected a
    position."""
    from jax.experimental import pallas as pl

    kv_heads, rows, dk = q_ref.shape
    dv = o_ref.shape[-1]
    T, mask_rows = seen_hbm.shape[1], seen_buf.shape[1]
    run = n * bs
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _MIN)
    l_ref[...] = jnp.zeros_like(l_ref)

    def update(slot):
        seen = seen_buf[slot]                           # (min(rows, T), run)
        if seen.shape[0] == 1:
            seen = jnp.broadcast_to(seen, (rows, run))
        elif rows > seen.shape[0]:
            seen = jnp.concatenate([seen] * (rows // seen.shape[0]), axis=0)
        seen = seen != 0

        def head(buf, h, d):
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

    def src(seq, tile, j):              # the tile's queries' mask rows
        r0 = 0 if rows % T == 0 else jax.lax.rem(tile * rows, T)
        return seen_hbm.at[seq, pl.ds(r0, mask_rows),
                           pl.ds(pl.multiple_of(j * run, run), run)]

    _walk(tables_ref, last_ref, (pool_k, pool_v), (k_buf, v_buf), sem,
          slot_ref, n, bs, (v_buf,), update, slabs=((src, seen_buf),))
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_core(q, pool_k, pool_v, tables, last, seen, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, T, H, D = q.shape
    bs, row = pool_k.shape[1:]
    Hkv = row // D
    G = H // Hkv
    nbps = tables.shape[1]
    n = pages_per_step(bs, nbps, row * pool_k.dtype.itemsize)
    tables = _pad_runs(tables, n)
    run = n * bs
    rows = _window_rows(T, G, run)
    mask_rows = min(rows, T)
    # (N, T, Hkv, G, D) -> (N, Hkv, G*T, D): a KV head's rows, head-major
    qh = q.reshape(N, T, Hkv, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(N, Hkv, G * T, D)
    # the mask as `select_positions` writes it; a row padded to whole runs
    # sees nothing in the padding
    if tables.shape[1] > nbps:
        seen = jnp.pad(seen, ((0, 0), (0, 0),
                              (0, (tables.shape[1] - nbps) * bs)))

    def mine(d):
        return pl.BlockSpec((None, Hkv, rows, d),
                            lambda b, i, *_: (b, 0, i, 0))

    out = pl.pallas_call(
        functools.partial(_sparse_kernel, bs=bs, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, G * T // rows),
            in_specs=[
                mine(D),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=mine(D),
            scratch_shapes=[pltpu.VMEM((2, n) + pool.shape[1:], pool.dtype)
                            for pool in (pool_k, pool_v)]
            + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32),
               pltpu.VMEM((Hkv, rows, D), jnp.float32),
               pltpu.VMEM((Hkv, rows, 1), jnp.float32),
               pltpu.VMEM((Hkv, rows, 1), jnp.float32),
               pltpu.VMEM((2, mask_rows, run), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((N, Hkv, G * T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WINDOW_VMEM + 16 * 1024 * 1024),
        interpret=interpret, name="paged_attention_sparse",
    )(tables, jnp.minimum(last, nbps * bs - 1), qh, seen, pool_k, pool_v)
    return out.reshape(N, Hkv, G, T, D).transpose(0, 3, 1, 2, 4) \
        .reshape(N, T, H, D)


def _sparse_xla(q, pool_k, pool_v, tables, last, seen):
    N, T, H, D = q.shape
    Hkv = pool_k.shape[2] // D
    k = pool_k[tables].reshape(N, -1, Hkv, D)
    v = pool_v[tables].reshape(N, -1, Hkv, D)
    s = jnp.einsum("nthgd,nwhd->nhgtw", q.reshape(N, T, Hkv, H // Hkv, D),
                   k, preferred_element_type=jnp.float32) / math.sqrt(D)
    s = jnp.where(seen[:, None, None] != 0, s, _MIN)
    o = jnp.einsum("nhgtw,nwhd->nthgd", jax.nn.softmax(s, axis=-1), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(N, T, H, D)


def paged_attention_sparse(q, pool_k, pool_v, tables, last, seen, *,
                           impl: Optional[str] = None,
                           interpret: Optional[bool] = None):
    """Attention of ``q`` (N, T, Hq, D) — ``T`` queries of each of ``N``
    sequences — over the positions ``seen`` (N, T, blocks_per_seq *
    block_size; `select_positions`' int32 0/1 mask) names for each
    query, among its sequence's pages
    (``tables`` (N, blocks_per_seq)) of the pools (num_blocks, block_size,
    Hkv*D), ``Hq`` a multiple of ``Hkv``.  ``last`` (N,): the position of
    each sequence's last query; no page behind it is read.  Float32 scores
    / sqrt(D) and softmax over the named positions alone."""
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if impl == "xla":
        return _sparse_xla(q, pool_k, pool_v, tables, last, seen)
    if impl != "pallas":
        raise ValueError(f"paged_attention_sparse impl {impl!r} (pallas|xla)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    D = q.shape[3]
    # sequences and KV heads are independent: per shard of both under a
    # mesh, a shard's heads whole 128-lane tiles of a page's row
    seqs, heads = mosaic.split((q.shape[0], pool_k.shape[2] // D),
                               (1, 128 // math.gcd(128, D)))
    pool = P(None, None, heads)
    return mosaic.per_shard(
        functools.partial(_sparse_core, interpret=interpret),
        (P(seqs, None, heads), pool, pool, P(seqs), P(seqs), P(seqs)),
        P(seqs, None, heads))(q, pool_k, pool_v, tables, last, seen)
