"""The routed experts held here (`ops/moe_experts.py`): the grouped matrix
products of the ``moe_experts`` kernel (interpret mode on the CPU) and of
the XLA path against a plain loop over experts, the layout `plan` makes,
and the counts the serving programs report."""
import importlib

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

_moe = importlib.import_module("incubator_mxnet_tpu.ops.moe_experts")


def _case(seed, N=40, K=4, E_all=32, E=8, C=32, F=64, first=8,
          dtype=jnp.float32):
    rng = onp.random.default_rng(seed)
    idx = onp.stack([rng.permutation(E_all)[:K] for _ in range(N)])
    return dict(
        x=jnp.asarray(rng.normal(size=(N, C)), dtype),
        idx=jnp.asarray(idx, jnp.int32),
        wts=jnp.asarray(rng.uniform(0.1, 1.0, size=(N, K)), jnp.float32),
        ok=jnp.asarray(rng.uniform(size=(N,)) > 0.2),
        gate=jnp.asarray(0.2 * rng.normal(size=(E, F, C)), dtype),
        up=jnp.asarray(0.2 * rng.normal(size=(E, F, C)), dtype),
        down=jnp.asarray(0.2 * rng.normal(size=(E, C, F)), dtype),
        first=first, experts=E_all)


def _loop(c):
    """A token at a time, an expert at a time, float64."""
    f = lambda a: onp.asarray(a, onp.float64)
    x, gate, up, down = f(c["x"]), f(c["gate"]), f(c["up"]), f(c["down"])
    idx, wts, ok = onp.asarray(c["idx"]), f(c["wts"]), onp.asarray(c["ok"])
    y = onp.zeros_like(x)
    counts = onp.zeros(gate.shape[0], onp.int64)
    for n in range(x.shape[0]):
        for k in range(idx.shape[1]):
            e = idx[n, k] - c["first"]
            if ok[n] and 0 <= e < gate.shape[0]:
                g, u = gate[e] @ x[n], up[e] @ x[n]
                y[n] += wts[n, k] * (down[e] @ (g / (1 + onp.exp(-g)) * u))
                counts[e] += 1
    return y, counts


def _run(c, impl):
    return _moe.routed_experts(c["x"], c["idx"], c["wts"], c["ok"],
                               c["gate"], c["up"], c["down"],
                               first=c["first"], experts=c["experts"],
                               impl=impl, interpret=True)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("seed", [0, 1])
def test_experts_match_a_loop_over_experts(impl, seed):
    c = _case(seed)
    y, counts = _run(c, impl)
    want, want_counts = _loop(c)
    assert y.shape == c["x"].shape and y.dtype == c["x"].dtype
    onp.testing.assert_allclose(onp.asarray(y), want, atol=2e-5)
    onp.testing.assert_array_equal(onp.asarray(counts), want_counts)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_an_expert_nobody_chose_and_one_that_everyone_chose(impl):
    """Expert 3 of the held ones takes every token (several tiles of its
    own), expert 5 none (no tile: its matrices are never read, here
    NaN)."""
    c = _case(2, N=70)
    first = c["first"]
    idx = c["idx"].at[:, 0].set(first + 3)
    idx = jnp.where(idx == first + 5, 0, idx)
    idx = idx.at[:, 1:].set(jnp.where(idx[:, 1:] == first + 3, 1,
                                      idx[:, 1:]))
    c = dict(c, idx=idx, ok=jnp.ones((70,), bool))
    if impl == "pallas":    # what is never read may hold anything
        c = dict(c, **{n: c[n].at[5].set(jnp.nan)
                       for n in ("gate", "up", "down")})
    y, counts = _run(c, impl)
    want, want_counts = _loop(dict(c, **{n: jnp.nan_to_num(c[n])
                                         for n in ("gate", "up", "down")}))
    assert int(counts[3]) == 70 and int(counts[5]) == 0
    onp.testing.assert_array_equal(onp.asarray(counts), want_counts)
    onp.testing.assert_allclose(onp.asarray(y), want, atol=2e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_no_token_at_all_is_zeros(impl):
    c = dict(_case(3), ok=jnp.zeros((40,), bool))
    y, counts = _run(c, impl)
    assert not onp.asarray(counts).any()
    assert not onp.asarray(y).any()


def test_every_pair_held_here_fits_the_worst_case():
    """All 4 of every token's pairs on the 8 experts held: the layout's
    worst case, nothing dropped."""
    rng = onp.random.default_rng(4)
    c = _case(4, N=48, first=0)
    idx = onp.stack([rng.permutation(8)[:4] for _ in range(48)])
    c = dict(c, idx=jnp.asarray(idx, jnp.int32), ok=jnp.ones((48,), bool))
    y, counts = _run(c, "pallas")
    want, _ = _loop(c)
    assert int(counts.sum()) == 48 * 4
    onp.testing.assert_allclose(onp.asarray(y), want, atol=2e-5)


def test_bfloat16_stays_within_its_rounding():
    c = _case(5, dtype=jnp.bfloat16)
    y, _ = _run(c, "pallas")
    z, _ = _run(c, "xla")
    want, _ = _loop(c)
    assert y.dtype == jnp.bfloat16
    for got in (y, z):
        onp.testing.assert_allclose(onp.asarray(got, onp.float32), want,
                                    atol=0.05)


def test_the_plan_lays_pairs_out_sorted_by_expert():
    c = _case(6)
    tm = 16
    dest, here, row_pair, tile_expert, tile_live, counts = _moe.plan(
        c["idx"], c["ok"], c["first"], 8, tm)
    N, K = c["idx"].shape
    dest, here, row_pair = map(onp.asarray, (dest, here, row_pair))
    tile_expert, tile_live = onp.asarray(tile_expert), onp.asarray(tile_live)
    M = row_pair.shape[0]
    assert M == (-(-N * 4 // tm) + 8) * tm and M % tm == 0
    e = onp.asarray(c["idx"]) - c["first"]
    assert (here == (onp.asarray(c["ok"])[:, None] & (e >= 0) & (e < 8))).all()
    # every pair held here has a row of its own, in a tile of its expert
    rows = dest[here]
    assert len(set(rows.tolist())) == here.sum() == int(counts.sum())
    assert (row_pair[rows] == onp.flatnonzero(here.reshape(-1))).all()
    assert (tile_expert[rows // tm] == e[here]).all()
    assert (tile_live[rows // tm] == 1).all()
    # the other rows are padding, and the live tiles are the first ones
    assert (row_pair == N * K).sum() == M - here.sum()
    n_live = int(tile_live.sum())
    assert (tile_live[:n_live] == 1).all() and not tile_live[n_live:].any()
    assert n_live == sum(-(-int(n) // tm) for n in counts)
    assert (onp.diff(tile_expert[:n_live]) >= 0).all()


def test_rows_a_tile_follow_the_tokens():
    assert _moe.tile_rows(96, 8, 256) == 16         # a step of 96 lanes
    assert _moe.tile_rows(512, 8, 256) == 32        # a chunk of 512
    assert _moe.tile_rows(8192, 8, 256) == 128
    assert _moe.tile_rows(1, 4, 4) == 16


def test_impl_is_validated():
    c = _case(7)
    with pytest.raises(ValueError, match="pallas|xla"):
        _run(c, "dense")
    assert _moe.default_impl("tpu") == "pallas"
    assert _moe.default_impl("cpu") == "xla"
