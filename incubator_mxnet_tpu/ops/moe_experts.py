"""The routed experts a program holds: grouped matrix products over the
token-expert pairs routed to them.

A routed feed-forward sends every token to its ``top_k`` experts.  Of all
the experts a chip holds a few (a share: experts ``first .. first+E-1``);
this module computes, for the pairs whose expert is held here, ``w *
down_e(silu(gate_e x) * up_e x)`` and adds a token's pairs up.  What the
other experts would add is another chip's; nothing here stands in for it.
No pair is dropped and there is no capacity: the buffers are sized for the
worst case (every token's every pair held here).

`routed_experts` is the entry.  ``impl="pallas"``: the pairs are laid out
sorted by expert, each expert's rows padded to whole tiles of ``tm`` rows
(`plan`: a few small integer operations, no sort), and the kernel
``moe_experts`` walks the tiles: grid ``(tile, slice of the expert's
width)``, the tile's expert read from a prefetched table, so an expert's
three matrices stream from HBM once for every tile of its rows (once, where
its rows fit one tile: a decode step's few pairs, a chunk's dozen) and an
expert nobody chose is never read.  Tiles past the last live one map to the
blocks already fetched and compute nothing.  ``impl="xla"``: every held
expert over every token, masked: the CPU's path and the tests' oracle.

Called through `mosaic.per_shard` (tiles are independent: under a mesh
each shard takes a run of them).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mosaic

__all__ = ["routed_experts", "plan", "tile_rows", "default_impl"]

_VMEM = 64 * 1024 * 1024


def default_impl(platform: Optional[str] = None) -> str:
    platform = platform or jax.default_backend()
    return "pallas" if platform == "tpu" else "xla"


def tile_rows(tokens: int, top_k: int, experts: int) -> int:
    """Rows a tile: twice what an expert gets of ``tokens`` under even
    routing over all ``experts``, a power of two in [16, 128] (16 rows are
    a bfloat16 tile; past 128 the MXU gains nothing)."""
    want, tm = 2 * tokens * top_k / experts, 16
    while tm < want and tm < 128:
        tm *= 2
    return tm


def plan(idx, ok, first: int, held: int, tm: int):
    """Where each pair goes.  ``idx`` (N, K) the experts each token chose
    (of all experts), ``ok`` (N,) the tokens that count.  Returns

      dest         (N, K) int32: the pair's row in the sorted layout (any
                   row where not ``here``)
      here         (N, K) bool: the pair's expert is held and its token ok
      row_pair     (M,) int32: the pair (``n * K + k``) in each row, ``N *
                   K`` in a row of padding
      tile_expert  (M // tm,) int32: the held expert (0-based) of each tile
      tile_live    (M // tm,) int32: 1 where the tile holds a pair
      counts       (held,) int32: pairs each held expert took

    ``M = (ceil(N * min(K, held) / tm) + held) * tm``: every pair held
    here, and under a tile of padding an expert."""
    N, K = idx.shape
    e = idx - first
    here = ok[:, None] & (e >= 0) & (e < held)
    hot = (here[..., None] & (e[..., None] == jnp.arange(held))) \
        .reshape(N * K, held).astype(jnp.int32)
    seen = jnp.cumsum(hot, axis=0)                  # pairs of e up to p
    counts = seen[-1]
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_tiles = -(-N * min(K, held) // tm) + held
    M = n_tiles * tm
    rank = jnp.sum(hot * (seen - 1), axis=1)        # within its expert
    start = jnp.sum(hot * ((tile_end - tiles) * tm), axis=1)
    flat_here = here.reshape(-1)
    dest = jnp.where(flat_here, start + rank, M)
    row_pair = jnp.full((M,), N * K, jnp.int32).at[dest].set(
        jnp.arange(N * K, dtype=jnp.int32), mode="drop")
    at = jnp.arange(n_tiles)
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= at[:, None], axis=1), held - 1)
    tile_live = (at < tile_end[-1]).astype(jnp.int32)
    return (jnp.minimum(dest, M - 1).reshape(N, K), here, row_pair,
            tile_expert.astype(jnp.int32), tile_live, counts)


def _experts_kernel(expert_ref, live_ref, src_ref, x_ref, w_ref, gate_ref,
                    up_ref, down_ref, o_ref, acc_ref):
    """One grid step = one (tile of rows, slice of the expert's width):
    the slice's gate and up products, SiLU and product, and its share of
    the down product into the tile's float32 accumulator; the last slice
    weights the rows and writes them.  A tile without a pair does
    nothing."""
    from jax.experimental import pallas as pl

    i, f = pl.program_id(0), pl.program_id(1)
    nf = pl.num_programs(1)
    nt = (((1,), (1,)), ((), ()))       # x (m, k) . w (n, k)^T

    @pl.when(live_ref[i] > 0)
    def _tile():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        g = jax.lax.dot_general(x, gate_ref[0], nt,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, up_ref[0], nt,
                                preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc_ref[...] += jax.lax.dot_general(
            h, down_ref[0], nt, preferred_element_type=jnp.float32)

        @pl.when(f == nf - 1)
        def _write():
            o_ref[...] = (acc_ref[...] * w_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _experts_core(x_rows, w_rows, tile_expert, tile_live, gate, up, down,
                  tm, interpret):
    """The kernel over rows laid out by `plan`: ``x_rows`` (M, C),
    ``w_rows`` (M, 1) float32, a tile every ``tm`` rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, C = x_rows.shape
    F = gate.shape[1]
    ft = 512 if F % 512 == 0 else F
    n_tiles, nf = M // tm, F // ft
    # a tile without a pair points at the blocks of the live tile before
    # it (the first tile's, where none is): nothing new is fetched for it
    at = jnp.arange(n_tiles, dtype=jnp.int32)
    src = jax.lax.cummax(jnp.where(tile_live > 0, at, 0))

    def rows(i, f, e, live, src):
        return (src[i], 0)

    def wide(i, f, e, live, src):       # gate, up: (E, F, C)
        return (e[src[i]], jnp.where(live[i] > 0, f, nf - 1), 0)

    def tall(i, f, e, live, src):       # down: (E, C, F)
        return (e[src[i]], 0, jnp.where(live[i] > 0, f, nf - 1))

    return pl.pallas_call(
        _experts_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_tiles, nf),
            in_specs=[pl.BlockSpec((tm, C), rows),
                      pl.BlockSpec((tm, 1), rows),
                      pl.BlockSpec((1, ft, C), wide),
                      pl.BlockSpec((1, ft, C), wide),
                      pl.BlockSpec((1, C, ft), tall)],
            out_specs=pl.BlockSpec((tm, C), rows),
            scratch_shapes=[pltpu.VMEM((tm, C), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((M, C), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret, name="moe_experts",
    )(tile_expert, tile_live, src, x_rows, w_rows, gate, up, down)


def _experts_xla(x, e, here, wts, gate, up, down):
    """Every held expert over every token, the pairs' weights where the
    token chose it and zero elsewhere."""
    f32 = jnp.float32
    held = gate.shape[0]
    w = jnp.sum(jnp.where(here[..., None]
                          & (e[..., None] == jnp.arange(held)),
                          wts[..., None], 0.0), axis=1)         # (N, held)
    g = jnp.einsum("nc,efc->nef", x, gate, preferred_element_type=f32)
    u = jnp.einsum("nc,efc->nef", x, up, preferred_element_type=f32)
    h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    y = jnp.einsum("nef,ecf->nec", h, down, preferred_element_type=f32)
    return jnp.einsum("nec,ne->nc", y, w)


def routed_experts(x, idx, wts, ok, gate, up, down, *, first: int = 0,
                   experts: Optional[int] = None,
                   impl: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """``x`` (N, C) tokens, ``idx`` (N, K) the experts each chose among
    ``experts`` (all of them: what the tile size is reckoned from; default
    the held ones), ``wts`` (N, K) float32 their weights, ``ok`` (N,) the
    tokens that count; ``gate``, ``up`` (E, F, C) and ``down`` (E, C, F)
    the matrices of experts ``first .. first+E-1``.  Returns ``(y (N, C)
    in x's dtype, counts (E,) int32)``: the weighted sum over each token's
    pairs whose expert is held here, and the pairs each held expert
    took."""
    impl = impl or default_impl()
    N, K = idx.shape
    held = gate.shape[0]
    if impl == "xla":
        e = idx - first
        here = ok[:, None] & (e >= 0) & (e < held)
        counts = jnp.sum(here[..., None] & (e[..., None]
                                            == jnp.arange(held)),
                         axis=(0, 1)).astype(jnp.int32)
        return _experts_xla(x, e, here, wts, gate, up,
                            down).astype(x.dtype), counts
    if impl != "pallas":
        raise ValueError(f"routed_experts impl {impl!r} (pallas|xla)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tm = tile_rows(N, K, experts or held)
    dest, here, row_pair, tile_expert, tile_live, counts = plan(
        idx, ok, first, held, tm)
    token = jnp.minimum(row_pair // K, N - 1)
    real = row_pair < N * K
    x_rows = jnp.where(real[:, None], x[token], jnp.zeros((), x.dtype))
    w_rows = jnp.where(real, wts.reshape(-1)[jnp.minimum(row_pair,
                                                         N * K - 1)], 0.0)
    tiles, = mosaic.split((tile_expert.shape[0],))
    core = functools.partial(_experts_core, tm=tm, interpret=interpret)
    rows = P(tiles, None)
    out = mosaic.per_shard(
        core, (rows, rows, P(tiles), P(tiles), P(), P(), P()), rows)(
        x_rows, w_rows[:, None].astype(jnp.float32), tile_expert, tile_live,
        gate, up, down)
    # a token's pairs, added in float32 (a row no live tile wrote is not
    # `here`, whatever it holds)
    y = jnp.sum(jnp.where(here[..., None], out[dest].astype(jnp.float32),
                          0.0), axis=1)
    return y.astype(x.dtype), counts
