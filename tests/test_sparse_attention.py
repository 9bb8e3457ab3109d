"""`ops/sparse_attention.py`: the index scores and the attention over a
selection, each kernel in interpret mode against its ``impl="xla"`` form,
and the selection itself against a sort.  Tiny sizes, float32; pages
scattered over the pool so that a run of a sequence's pages is no run of
the pool's, contexts that end inside a page and inside a run, and scores
that tie."""
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

_sp = importlib.import_module("incubator_mxnet_tpu.ops.sparse_attention")

BS, NBPS, DI = 4, 20, 8             # 80 positions a sequence; runs of 16 pages


def _pools(rng, n_seq, width, dtype=jnp.float32, nbps=NBPS):
    """(a pool of n_seq x nbps + 1 blocks, tables that name them out of
    order): block 0 is the scratch block."""
    nb = n_seq * nbps + 1
    pool = jnp.asarray(rng.normal(size=(nb, BS, width)), dtype)
    tables = rng.permutation(onp.arange(1, nb)).reshape(n_seq, nbps)
    return pool, jnp.asarray(tables, jnp.int32)


def _positions(last, T):
    """(N, T): the position of each query of each sequence."""
    return onp.asarray(last)[:, None] - (T - 1) + onp.arange(T)[None, :]


def _seen_scores(got, want, last):
    """The index's scores of both forms at or before each query's position
    (what lies behind it is unspecified)."""
    got, want = onp.asarray(got), onp.asarray(want)
    seen = onp.arange(got.shape[-1]) <= _positions(last, got.shape[1])[
        ..., None]
    onp.testing.assert_allclose(got[seen], want[seen], rtol=1e-5, atol=1e-5)


def _index_args(rng, N, T, last, dtype=jnp.float32, nbps=NBPS):
    pool, tables = _pools(rng, N, _sp.index_row(DI), dtype, nbps)
    pool = pool.at[:, :, DI:].set(0)        # a key, then a row's zeros
    qi = jnp.asarray(rng.normal(size=(N, T, 3, DI)), dtype)
    w = jnp.asarray(rng.normal(size=(N, T, 3)), jnp.float32)
    return qi, w, pool, tables, jnp.asarray(last, jnp.int32)


# (N, T, last, nbps): runs of 16 pages, 64 positions; a table of 20 entries
# is two runs, of 50 four, of 16 one
_CASES = [
    (3, 1, [0, 37, 79], NBPS),      # a step's lanes: a first token, a
                                    # context inside a page, a full table
    (1, 16, [41], NBPS),            # a chunk that ends inside a page
    (1, 16, [79], NBPS),            # the table's last chunk
    (2, 8, [7, 70], NBPS),          # a first chunk; one past the first run
    (4, 1, [199, 5, 64, 127], 50),  # lanes on their last run and on their
                                    # first; at a run's first slot and last
    (1, 16, [150], 50),             # a chunk across runs, ending mid-run
    (3, 1, [0, 30, 63], 16),        # a table of one run
    (1, 8, [40], 16)]
_IDS = ["step", "chunk_mid_page", "chunk_last", "two_sequences",
        "lanes_far_apart", "chunk_mid_run", "one_run_step", "one_run_chunk"]


@pytest.mark.parametrize("N, T, last, nbps", _CASES, ids=_IDS)
def test_index_scores_kernel_matches_the_plain_form(N, T, last, nbps):
    args = _index_args(onp.random.default_rng(0), N, T, last, nbps=nbps)
    want = _sp.index_scores(*args, impl="xla")
    got = _sp.index_scores(*args, impl="pallas", interpret=True)
    assert got.shape == want.shape == (N, T, nbps * BS)
    _seen_scores(got, want, last)
    # by hand: sum_j w_j relu(q_j . k_s) at s <= the query's position
    qi, w, pool, tables, last = (onp.asarray(a) for a in args)
    for n in range(N):
        keys = pool[tables[n]].reshape(-1, pool.shape[-1])[:, :DI]
        for t in range(T):
            at = last[n] - (T - 1) + t
            s = onp.maximum(qi[n, t] @ keys.T, 0.0).T @ w[n, t]
            onp.testing.assert_allclose(onp.asarray(want)[n, t, :at + 1],
                                        s[:at + 1], rtol=1e-4, atol=1e-4)


def test_index_scores_in_bfloat16_multiply_what_is_stored():
    args = _index_args(onp.random.default_rng(1), 2, 1, [30, 66],
                       jnp.bfloat16)
    want = _sp.index_scores(*args, impl="xla")
    got = _sp.index_scores(*args, impl="pallas", interpret=True)
    _seen_scores(got, want, [30, 66])


def _by_sort(scores, pos, k):
    """The selection by a stable sort: the k best of s <= pos, ties to the
    lower position."""
    out = onp.zeros(scores.shape, bool)
    for idx in onp.ndindex(scores.shape[:-1]):
        n = int(pos[idx]) + 1
        best = onp.argsort(-scores[idx][:n], kind="stable")[:k]
        out[idx][best] = True
    return out


def test_select_positions_is_the_top_k_without_a_sort():
    rng = onp.random.default_rng(2)
    scores = rng.normal(size=(3, 5, 80)).astype(onp.float32)
    scores[0, 0, :40] *= 1e30               # the sign's half, large and small
    scores[0, 1] = -onp.abs(scores[0, 1])
    scores[0, 2, 3] = onp.inf
    scores[0, 3, 5] = -onp.inf              # a number like any other
    pos = rng.integers(0, 80, size=(3, 5)).astype(onp.int32)
    pos[1, 0], pos[1, 1], pos[1, 2] = 0, 11, 12
    got = onp.asarray(_sp.select_positions(jnp.asarray(scores),
                                           jnp.asarray(pos), 12))
    onp.testing.assert_array_equal(got, _by_sort(scores, pos, 12))
    n = got.sum(-1)
    assert (n == onp.minimum(pos + 1, 12)).all()
    # while there are no more than k, every position
    assert got[1, 1, :12].all() and n[1, 0] == 1 and n[1, 2] == 12


@pytest.mark.parametrize("lead", [(6,), (6, 1), (1, 6), (2, 3)])
def test_the_selection_is_a_rows_own_whatever_the_leading_axes(lead):
    """A step's (lanes, 1, W) and a chunk's (1, T, W) select what their
    rows select one by one: the sort's answer, pairs that tie among them."""
    rng = onp.random.default_rng(5)
    scores = rng.normal(size=(6, 300)).astype(onp.float32)
    scores[1, :100] = scores[1, 100:200]        # pairs that tie
    pos = onp.array([299, 250, 17, 0, 120, 299], onp.int32)
    want = _by_sort(scores, pos, 40)
    got = _sp.select_positions(jnp.asarray(scores.reshape(lead + (300,))),
                               jnp.asarray(pos.reshape(lead)), 40)
    assert got.shape == lead + (300,)
    onp.testing.assert_array_equal(onp.asarray(got).reshape(6, 300), want)


def test_ties_go_to_the_lower_position():
    """Scores that tie at the threshold: ReLU zeros, and whole plateaus."""
    scores = onp.zeros((4, 40), onp.float32)
    scores[0, [3, 9, 20]] = 1.0             # 3 above, 37 zeros for 5 places
    scores[1] = onp.repeat(onp.arange(8, 0, -1), 5)     # plateaus of 5
    scores[2] = 7.0                                     # one plateau
    scores[3] = -onp.arange(40)                         # no tie at all
    pos = onp.array([39, 39, 25, 39], onp.int32)
    got = onp.asarray(_sp.select_positions(jnp.asarray(scores),
                                           jnp.asarray(pos), 8))
    onp.testing.assert_array_equal(got, _by_sort(scores, pos, 8))
    assert sorted(onp.flatnonzero(got[0])) == [0, 1, 2, 3, 4, 5, 9, 20]
    assert sorted(onp.flatnonzero(got[1])) == list(range(8))
    assert sorted(onp.flatnonzero(got[2])) == list(range(8))


# the selection kernel's cases: rows of 4,224 columns (33 x 128) count in
# three tiles of 1,408, so a row's context may end in any of them
SW, SK = 4224, 256


def _step_lanes(rng):
    """A decode step's 16 lanes in one tile, contexts far apart: a first
    token, under k, at k, at a tile's and a page's edges, the full row."""
    pos = onp.array([0, 5, SK - 1, SK, 1407, 1408, 1409, 2047, 2048, 2815,
                     3000, 4095, 4160, 4200, 4222, SW - 1], onp.int32)
    return rng.normal(size=(16, 1, SW)), rng.permutation(pos)[:, None]


def _chunk(rng, last=2900):
    """A chunk's 512 queries of one sequence, its last inside a tile."""
    return rng.normal(size=(1, 512, SW)), \
        (last - 511 + onp.arange(512, dtype=onp.int32))[None]


def _relu_zeros(rng):
    """The index's ReLU: most scores exactly 0, so rows crowd at a
    threshold of 0 and their ties go to the lower positions."""
    s, pos = _chunk(rng, last=1600)
    return onp.maximum(s - 1.5, 0.0), pos


def _signed_zeros_and_min(rng):
    """-0.0 below +0.0, and finfo.min (what the index writes behind a
    query) a number like any other in front of it."""
    s, pos = _step_lanes(rng)
    s[:, :, ::3] = -0.0
    s[:, :, 1::3] = 0.0
    s[::2, :, 2::7] = onp.finfo(onp.float32).min
    return s, pos


def _under_k(rng):
    """Every position selected: no context passes k."""
    s, pos = _chunk(rng, last=SK - 2)
    return s, onp.maximum(pos, -1)


@pytest.mark.parametrize("case", [_step_lanes, _chunk, _relu_zeros,
                                  _signed_zeros_and_min, _under_k],
                         ids=["step_lanes", "chunk_mid_tile", "relu_ties",
                              "signed_zeros_and_min", "under_k"])
def test_selection_kernel_is_the_plain_form_bit_for_bit(case):
    """The kernel (interpret mode) against `impl="xla"` at a step's
    (16, 1, W) and a chunk's (1, 512, W): the same mask, every bit, and
    the sort's."""
    rng = onp.random.default_rng(6)
    scores, pos = case(rng)
    scores = jnp.asarray(scores, jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    want = _sp.select_positions(scores, pos, SK, impl="xla")
    got = _sp.select_positions(scores, pos, SK, impl="pallas",
                               interpret=True)
    assert got.shape == want.shape == scores.shape
    assert got.dtype == want.dtype == jnp.int32
    onp.testing.assert_array_equal(onp.asarray(got), onp.asarray(want))
    seen = onp.asarray(got).reshape(-1, SW)
    p = onp.asarray(pos).reshape(-1)
    assert (seen.sum(-1) == onp.clip(p + 1, 0, SK)).all()
    for r in range(0, len(p), 97):      # a few rows by the sort
        onp.testing.assert_array_equal(
            seen[r], _by_sort(onp.asarray(scores).reshape(-1, SW)[r:r + 1],
                              p[r:r + 1], SK)[0])


def test_selection_tiles_at_the_cells_shapes():
    """The kernel's tiles at the sparse cell's rows of 33,792 columns: 22
    tiles of 1,536 (a context of 4k counts 3 of them), the step's 16 lanes
    one grid step, the chunk's 512 queries 8 steps of 64."""
    assert _sp._select_tiles(16, 33792) == (16, 1536)
    assert _sp._select_tiles(512, 33792) == (64, 1536)
    assert _sp._select_tiles(8, 80) == (8, 80)


def _sparse_args(rng, N, T, last, H=4, Hkv=2, D=16, keep=9, nbps=NBPS):
    pool_k, tables = _pools(rng, N, Hkv * D, nbps=nbps)
    pool_v = jnp.asarray(rng.normal(size=pool_k.shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(N, T, H, D)), jnp.float32)
    last = jnp.asarray(last, jnp.int32)
    scores = jnp.asarray(rng.normal(size=(N, T, nbps * BS)), jnp.float32)
    seen = _sp.select_positions(scores, jnp.asarray(_positions(last, T),
                                                    jnp.int32), keep)
    return q, pool_k, pool_v, tables, last, seen


@pytest.mark.parametrize("N, T, last, nbps", _CASES, ids=_IDS)
def test_sparse_attention_kernel_matches_the_plain_form(N, T, last, nbps):
    """9 positions a query, scattered over the context: a selection
    straddles pages and runs, and the first run of a long context may hold
    none of a query's positions."""
    args = _sparse_args(onp.random.default_rng(3), N, T, last, nbps=nbps)
    want = _sp.paged_attention_sparse(*args, impl="xla")
    got = _sp.paged_attention_sparse(*args, impl="pallas", interpret=True)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-5)
    # by hand, one query: softmax over its positions alone
    q, pk, pv, tables, last, seen = (onp.asarray(a) for a in args)
    n, t, h = N - 1, T - 1, 3
    at = onp.flatnonzero(seen[n, t])
    assert 0 < len(at) <= 9 and at.max() <= last[n]
    k = pk[tables[n]].reshape(-1, 2, 16)[at, h // 2]
    v = pv[tables[n]].reshape(-1, 2, 16)[at, h // 2]
    s = k @ q[n, t, h] / 4.0
    p = onp.exp(s - s.max())
    onp.testing.assert_allclose(onp.asarray(want)[n, t, h],
                                (p / p.sum()) @ v, rtol=1e-4, atol=1e-5)


def test_a_selection_with_nothing_in_the_first_run():
    """Every selected position behind the first run of 16 pages: the rows'
    state starts at the mask's value and the first run that holds a
    position wipes what the empty ones added."""
    rng = onp.random.default_rng(4)
    q, pk, pv, tables, last, _ = _sparse_args(rng, 1, 4, [79])
    seen = onp.zeros((1, 4, 80), onp.int32)
    seen[0, :, [66, 71, 76]] = 1
    args = (q, pk, pv, tables, last, jnp.asarray(seen))
    want = _sp.paged_attention_sparse(*args, impl="xla")
    got = _sp.paged_attention_sparse(*args, impl="pallas", interpret=True)
    assert onp.isfinite(onp.asarray(got)).all()
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-5)


def test_unknown_impls_are_refused():
    args = _index_args(onp.random.default_rng(0), 1, 1, [3])
    with pytest.raises(ValueError, match="pallas|xla"):
        _sp.index_scores(*args, impl="dense")
    with pytest.raises(ValueError, match="pallas|xla"):
        _sp.paged_attention_sparse(
            *_sparse_args(onp.random.default_rng(0), 1, 1, [3]), impl="dense")
    with pytest.raises(ValueError, match="pallas|xla"):
        _sp.select_positions(jnp.zeros((1, 1, 8)), jnp.zeros((1, 1), jnp.int32),
                             2, impl="dense")


# --- the grid-walk form the kernels had before they looped over runs ------- #
# A grid (sequence, row tile, run) over every run of the table, a run behind
# the sequence's last position a step that starts and computes nothing; the
# pipeline brought the mask's and wrote the scores' blocks.  Kept here alone,
# to hold the loop over the live runs to it bit for bit.
def _grid_walk(tables_ref, last_ref, pools, bufs, sem, slot_ref, n, bs, zero,
               update):
    from jax.experimental import pallas as pl

    b, i, j = (pl.program_id(k) for k in range(3))
    seqs, tiles, nb = (pl.num_programs(k) for k in range(3))
    run = n * bs
    last = last_ref[b]

    def copies(seq, first, slot, start):
        _sp._run_copies(pools, bufs, sem, slot, lambda e: tables_ref[seq, e],
                        first, last_ref[seq], n, bs, start)

    @pl.when((b == 0) & (i == 0) & (j == 0))
    def _first():
        slot_ref[0] = 0
        for buf in zero:
            buf[...] = jnp.zeros_like(buf)
        copies(0, 0, 0, True)

    @pl.when((j == 0) | (j * run <= last))
    def _live():
        slot = slot_ref[0]
        more = (j + 1 < nb) & ((j + 1) * run <= last)
        nxt = jnp.where(more | (i + 1 < tiles), b, b + 1)

        @pl.when(nxt < seqs)
        def _prefetch():
            copies(nxt, jnp.where(more, (j + 1) * n, 0), 1 - slot, True)
        slot_ref[0] = 1 - slot
        copies(b, j * n, slot, False)
        update(slot)


def _live_run(j, last, b, run):
    return jnp.minimum(j, last[b] // run)


def _grid_index_kernel(tables_ref, last_ref, q_ref, w_ref, pool, o_ref, buf,
                       sem, slot_ref, *, bs, n, fold):
    run = n * bs

    def update(slot):
        k = buf[slot].reshape(run, buf.shape[-1])
        acc = None
        for p in range(q_ref.shape[0]):
            q, kk = q_ref[p], k
            if not q.dtype == k.dtype == jnp.bfloat16:
                q, kk = q.astype(jnp.float32), k.astype(jnp.float32)
            s = jax.lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.maximum(s, 0.0) * w_ref[p]
            acc = s if acc is None else acc + s
        if fold:
            acc = jnp.sum(acc, axis=0, keepdims=True)
        o_ref[...] = acc

    _grid_walk(tables_ref, last_ref, (pool,), (buf,), sem, slot_ref, n, bs,
               (), update)


def _grid_index(qi, w, pool, tables, last):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, T, Hi = qi.shape[:3]
    bs, Di = pool.shape[1:]
    nbps = tables.shape[1]
    n = _sp.pages_per_step(bs, nbps, Di * pool.dtype.itemsize)
    qi = jnp.pad(qi, ((0, 0),) * 3 + ((0, Di - qi.shape[3]),))
    tables = _sp._pad_runs(tables, n)
    run = n * bs
    fold = T == 1
    if fold:
        rows, planes, q, wc = Hi, 1, qi.reshape(N, 1, Hi, Di), \
            w.reshape(N, 1, Hi, 1)
    else:
        rows = _sp._index_rows(T)
        planes, q, wc = Hi, qi.transpose(0, 2, 1, 3), \
            w.transpose(0, 2, 1)[..., None]
    out_rows = 1 if fold else rows
    out = pl.pallas_call(
        functools.partial(_grid_index_kernel, bs=bs, n=n, fold=fold),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, 1 if fold else T // rows, tables.shape[1] // n),
            in_specs=[
                pl.BlockSpec((None, planes, rows, Di),
                             lambda b, i, j, *_: (b, 0, i, 0)),
                pl.BlockSpec((None, planes, rows, 1),
                             lambda b, i, j, *_: (b, 0, i, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec(
                (None, out_rows, run),
                lambda b, i, j, t, last: (b, i, _live_run(j, last, b, run))),
            scratch_shapes=[pltpu.VMEM((2, n) + pool.shape[1:], pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((N, T, tables.shape[1] * bs),
                                       jnp.float32),
        interpret=True, name="index_scores",
    )(tables, jnp.minimum(last, nbps * bs - 1), q, wc.astype(jnp.float32),
      pool)
    return out[..., :nbps * bs]


def _grid_sparse_kernel(tables_ref, last_ref, q_ref, seen_ref, pool_k, pool_v,
                        o_ref, k_buf, v_buf, sem, slot_ref, acc_ref, m_ref,
                        l_ref, *, bs, n):
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    kv_heads, rows, dk = q_ref.shape
    dv = o_ref.shape[-1]
    run = n * bs

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _sp._MIN)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(slot):
        seen = seen_ref[...]
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
            _sp._softmax_update(s, seen,
                                head(v_buf, h, dv).astype(jnp.float32),
                                m_ref.at[h], l_ref.at[h], acc_ref.at[h])

    _grid_walk(tables_ref, last_ref, (pool_k, pool_v), (k_buf, v_buf), sem,
               slot_ref, n, bs, (v_buf,), update)

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _grid_sparse(q, pool_k, pool_v, tables, last, seen):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, T, H, D = q.shape
    bs, row = pool_k.shape[1:]
    Hkv = row // D
    G = H // Hkv
    nbps = tables.shape[1]
    n = _sp.pages_per_step(bs, nbps, row * pool_k.dtype.itemsize)
    tables = _sp._pad_runs(tables, n)
    run = n * bs
    rows = _sp._window_rows(T, G, run)
    mask_rows = min(rows, T)
    qh = q.reshape(N, T, Hkv, G, D).transpose(0, 2, 3, 1, 4) \
        .reshape(N, Hkv, G * T, D)
    if tables.shape[1] > nbps:
        seen = jnp.pad(seen, ((0, 0), (0, 0),
                              (0, (tables.shape[1] - nbps) * bs)))

    def mine(d):
        return pl.BlockSpec((None, Hkv, rows, d),
                            lambda b, i, j, *_: (b, 0, i, 0))

    out = pl.pallas_call(
        functools.partial(_grid_sparse_kernel, bs=bs, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, G * T // rows, tables.shape[1] // n),
            in_specs=[
                mine(D),
                pl.BlockSpec(
                    (None, mask_rows, run),
                    lambda b, i, j, t, last: (
                        b, (i * rows % T) // mask_rows,
                        _live_run(j, last, b, run))),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=mine(D),
            scratch_shapes=[pltpu.VMEM((2, n) + p.shape[1:], p.dtype)
                            for p in (pool_k, pool_v)]
            + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32),
               pltpu.VMEM((Hkv, rows, D), jnp.float32),
               pltpu.VMEM((Hkv, rows, 1), jnp.float32),
               pltpu.VMEM((Hkv, rows, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, Hkv, G * T, D), q.dtype),
        interpret=True, name="paged_attention_sparse",
    )(tables, jnp.minimum(last, nbps * bs - 1), qh, seen, pool_k, pool_v)
    return out.reshape(N, Hkv, G, T, D).transpose(0, 3, 1, 2, 4) \
        .reshape(N, T, H, D)


@pytest.mark.parametrize("N, T, last, nbps, rows", [
    (4, 1, [199, 5, 64, 127], 50, None),    # a step's lanes far apart
    (1, 16, [150], 50, None),               # a chunk ending mid-run
    (1, 16, [150], 50, 16),                 # row tiles of a whole head
    (2, 16, [150, 40], 50, 8)],             # two sequences in tiles of
                                            # half a head's queries
    ids=["step", "chunk", "tiles_of_heads", "tiles_in_a_head"])
def test_the_loop_over_live_runs_is_the_grid_walk_bit_for_bit(
        monkeypatch, N, T, last, nbps, rows):
    """Both kernels against the form whose grid steps through every run:
    the same runs in the same order, the same arithmetic, so the same
    bits: the index's scores wherever the walk wrote them (every column of
    a sequence's live runs), the attention's whole output.  ``rows``: the
    attention's row tile forced smaller, so that a grid step is a part of
    the sequence's rows and the mask's rows are copied a tile at a time."""
    if rows is not None:
        monkeypatch.setattr(_sp, "_window_rows", lambda *a: rows)
    idx = _index_args(onp.random.default_rng(7), N, T, last, nbps=nbps)
    got = onp.asarray(_sp._index_core(*idx, interpret=True))
    want = onp.asarray(_grid_index(*idx))
    run = 16 * BS
    for n, at in enumerate(last):
        cols = (at // run + 1) * run
        onp.testing.assert_array_equal(got[n, :, :cols], want[n, :, :cols])
    args = _sparse_args(onp.random.default_rng(8), N, T, last, nbps=nbps)
    # traced anew, not from the cache of an earlier call with these shapes
    got = _sp._sparse_core.__wrapped__(*args, interpret=True)
    want = _grid_sparse(*args)
    assert onp.isfinite(onp.asarray(want)).all()
    onp.testing.assert_array_equal(onp.asarray(got), onp.asarray(want))
