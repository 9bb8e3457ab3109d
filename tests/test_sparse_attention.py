"""`ops/sparse_attention.py`: the index scores and the attention over a
selection, each kernel in interpret mode against its ``impl="xla"`` form,
and the selection itself against a sort.  Tiny sizes, float32; pages
scattered over the pool so that a run of a sequence's pages is no run of
the pool's, contexts that end inside a page and inside a run, and scores
that tie."""
import importlib

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

_sp = importlib.import_module("incubator_mxnet_tpu.ops.sparse_attention")

BS, NBPS, DI = 4, 20, 8             # 80 positions a sequence; runs of 16 pages


def _pools(rng, n_seq, width, dtype=jnp.float32):
    """(a pool of n_seq x NBPS + 1 blocks, tables that name them out of
    order): block 0 is the scratch block."""
    nb = n_seq * NBPS + 1
    pool = jnp.asarray(rng.normal(size=(nb, BS, width)), dtype)
    tables = rng.permutation(onp.arange(1, nb)).reshape(n_seq, NBPS)
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


def _index_args(rng, N, T, last, dtype=jnp.float32):
    pool, tables = _pools(rng, N, _sp.index_row(DI), dtype)
    pool = pool.at[:, :, DI:].set(0)        # a key, then a row's zeros
    qi = jnp.asarray(rng.normal(size=(N, T, 3, DI)), dtype)
    w = jnp.asarray(rng.normal(size=(N, T, 3)), jnp.float32)
    return qi, w, pool, tables, jnp.asarray(last, jnp.int32)


@pytest.mark.parametrize("N, T, last", [
    (3, 1, [0, 37, 79]),            # a step's lanes: a first token, a
                                    # context inside a page, a full table
    (1, 16, [41]),                  # a chunk that ends inside a page
    (1, 16, [79]),                  # the table's last chunk
    (2, 8, [7, 70])],               # a first chunk; one past the first run
    ids=["step", "chunk_mid_page", "chunk_last", "two_sequences"])
def test_index_scores_kernel_matches_the_plain_form(N, T, last):
    args = _index_args(onp.random.default_rng(0), N, T, last)
    want = _sp.index_scores(*args, impl="xla")
    got = _sp.index_scores(*args, impl="pallas", interpret=True)
    assert got.shape == want.shape == (N, T, NBPS * BS)
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


def _sparse_args(rng, N, T, last, H=4, Hkv=2, D=16, keep=9):
    pool_k, tables = _pools(rng, N, Hkv * D)
    pool_v = jnp.asarray(rng.normal(size=pool_k.shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(N, T, H, D)), jnp.float32)
    last = jnp.asarray(last, jnp.int32)
    scores = jnp.asarray(rng.normal(size=(N, T, NBPS * BS)), jnp.float32)
    seen = _sp.select_positions(scores, jnp.asarray(_positions(last, T),
                                                    jnp.int32), keep)
    return q, pool_k, pool_v, tables, last, seen


@pytest.mark.parametrize("N, T, last", [
    (3, 1, [0, 37, 79]), (1, 16, [41]), (1, 16, [79]), (2, 8, [7, 70])],
    ids=["step", "chunk_mid_page", "chunk_last", "two_sequences"])
def test_sparse_attention_kernel_matches_the_plain_form(N, T, last):
    """9 positions a query, scattered over the context: a selection
    straddles pages and runs, and the first run of a long context may hold
    none of a query's positions."""
    args = _sparse_args(onp.random.default_rng(3), N, T, last)
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
