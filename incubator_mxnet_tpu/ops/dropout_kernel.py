"""Fused dropout — mask generated IN-KERNEL by the TPU core PRNG,
drawn per shard over any device mesh.

Kills the "dropout tax" (BASELINE.md: threefry mask generation cost
~16 ms/step ≈ 20 MFU points on BERT-large): instead of materializing a
full-size mask through XLA's counter-based threefry (bandwidth-bound,
and serialized with the step's compute), each Pallas program seeds the
per-core PRNG (`pltpu.prng_seed`) and draws the keep-mask for its tile
on the fly (ref: src/operator/nn/dropout.cc MSHADOW path, SURVEY.md
§2.3 — re-designed for the TPU memory system).

r5 split: the KERNEL emits only the uint8 keep-mask (HBM write at 1
byte/element; it has no array operand at all); the APPLY
(`where(mask, x*scale, 0) [+ residual]`) is ordinary XLA that fuses
into the producer/consumer fusions exactly like the dropout-off graph.
The per-HLO-op A/B profile that motivated this (docs/performance.md)
showed the previous apply-in-kernel design cost ~5 ms/step on the
flagship: +1.9 ms of kernel time (its bandwidth floor) but also +3.7
ms of copy-done stalls and evicted matmul-epilogue fusions from 98
Pallas punctuation points in the schedule.  Backward reuses the SAVED
mask (uint8, ~4 MB per flagship site), so fwd/bwd mask identity holds
by construction and dx fuses into the backward fusions the same way.

Mesh compatibility.  The array is viewed as a canonical 2D grid of
(block_rows x block_cols) tiles whose geometry is fixed by the GLOBAL
shape, and every tile's mask depends only on ``(seed,
global_tile_coordinates)``.  In a program traced over a mesh
(`ops/mosaic.py`) the mask is drawn under a `shard_map` that splits
rows AND columns over the mesh wherever shard boundaries fall on tile
boundaries: each shard computes its global tile offsets from its mesh
coordinates and generates exactly the bits the unpartitioned op would
produce — so any such partitioning yields the identical global mask,
and GSPMD moves the uint8 mask to wherever the activation it is applied
to lives.  (Inside a caller's own manual `shard_map` — the ZeRO-1
explicit tier — the op sees only the local shard: every shard draws the
mask of its LOCAL shape.)

CPU (and any non-TPU backend) takes a block-keyed threefry reference
with the same tile-coordinate keying — same partitioning behavior and
fwd/bwd identity, different bits (documented; tests assert statistics
and consistency properties, not bit equality across backends).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mosaic

__all__ = ["fused_dropout", "fused_dropout_add", "dropout_mask"]

# upper bound on rows per tile; actual tile geometry is shape-derived
_BLOCK_ROWS = 1024
# tile-geometry budget in bytes at the INPUT's itemsize.  Historically a
# VMEM bound for the apply-in-kernel design; today the kernel only
# writes uint8 — but the (shape, dtype)->(br, bc) map is part of the
# MASK-BIT CONTRACT (changing it reshuffles every mask), so the formula
# is frozen, itemsize included
_BLOCK_BUDGET_BYTES = 2 << 20


# shardings up to this many ways (power-of-two meshes) stay sharded;
# the _pick_* ladders are derived from these
_MAX_ROW_SHARDS = 64
_MAX_COL_SHARDS = 8


def _shard_ladder(max_shards):
    s, out = max_shards, []
    while s >= 1:
        out.append(s)
        s //= 2
    return tuple(out)


def _pick_br(R: int, cap: int) -> int:
    """Largest TILE-LEGAL row block: a multiple of 8 (the TPU sublane
    constraint whenever the row grid has >1 step) that keeps row
    sharding alive.  s-way sharding survives iff br divides R/s, so br
    is drawn from the divisors of R // gcd(R, s) for the most ambitious
    power-of-two s first (64-way headroom, then 32, ... 1).  Last
    resorts: br == R when one block fits, else a non-dividing multiple
    of 8 (the kernel runs a ceil grid with a masked tail block — such
    shapes lose row sharding via `_mask2d`'s tile-alignment check,
    never correctness)."""
    def best_mult8_div(n, limit):
        limit = min(limit, n) - min(limit, n) % 8
        for d in range(limit, 7, -8):
            if n % d == 0:
                return d
        return None

    for s_pref in _shard_ladder(_MAX_ROW_SHARDS):
        rs = R // math.gcd(R, s_pref)
        br = best_mult8_div(rs, cap)
        if br:
            return br
    if R <= cap:
        return R  # one grid step: any block height is legal
    return cap - cap % 8 or 8  # ceil grid + masked tail


def _pick_bc(Clp: int, budget: int) -> int:
    """Column block: a multiple of 128 (lane constraint) dividing Clp,
    preferring blocks that divide Clp/s for power-of-two col-shard
    counts s (tensor-parallel activations shard the model dim) so the
    mask can be drawn column-sharded too."""
    def best_mult128_div(n, limit):
        limit = min(limit, n) - min(limit, n) % 128
        for d in range(limit, 127, -128):
            if n % d == 0:
                return d
        return None

    cap = max(128, (budget // 8) - (budget // 8) % 128)
    for s_pref in _shard_ladder(_MAX_COL_SHARDS):
        cs = Clp // math.gcd(Clp, s_pref)
        bc = best_mult128_div(cs, cap)
        if bc:
            return bc
    raise AssertionError(
        f"unreachable: Clp={Clp} is a 128-multiple, so the s=1 rung "
        f"always finds a divisor")


def _row_grid(rows: int, br: int) -> int:
    return -(-rows // br)


def _tile_geometry(R: int, Clp: int, itemsize: int):
    """(block_rows, block_cols) for the GLOBAL (R, Clp) view — static,
    derived only from the global shape so every shard (and fwd/bwd)
    agrees.  Clp is a multiple of 128; bc divides Clp (col-shard
    friendly per _pick_bc); br is tile-legal per _pick_br (multiple of
    8, or the whole R)."""
    budget = max(1024, _BLOCK_BUDGET_BYTES // max(1, itemsize))
    bc = _pick_bc(Clp, budget)
    cap = max(1, min(_BLOCK_ROWS, budget // bc))
    return _pick_br(R, cap), bc


def _dropout_kernel(seed_ref, o_ref, *, rate, ncb, br, bc, kr, kc):
    """One EXECUTION block covers a (kr x kc) window of MASK tiles.

    The mask is a pure function of (seed, global mask-tile id) with
    (br, bc) mask tiles — identical bits to a kr=kc=1 run — while the
    grid moves (kr*br, kc*bc) blocks per step.  Decoupling execution
    blocking from mask geometry is what fixes the 16 KB-per-grid-step
    regime this kernel shipped with (measured 203 GB/s on the BERT
    flagship's (4096,1024) sites; see docs/performance.md).

    r5 redesign: the kernel emits the uint8 KEEP-MASK only; the apply
    (``where(mask, x*scale, 0) [+ res]``) is ordinary XLA so it fuses
    into the producer/consumer fusions exactly like the dropout-off
    graph — the per-op A/B profile showed the old apply-in-kernel
    design cost ~2x its own bandwidth in broken fusions and copy-done
    stalls (docs/performance.md)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # distinct stream per global MASK tile: seed words are (user seed,
    # LINEAR global tile id = (row_block_offset + i) * ncb + j).  Any
    # tile-aligned sharding regenerates the identical bits; TWO words —
    # Mosaic on the v5e rejects 3-word prng_seed — and the second word
    # linearizes (row block, col block) with the STATIC global column
    # block count, so the id is globally unique and shard-invariant.
    thresh = jnp.uint32(min(int(rate * (1 << 32)), (1 << 32) - 1))
    base_i = pl.program_id(0) * kr
    base_j = pl.program_id(1) * kc
    for i in range(kr):  # static unroll over the mask tiles in-block
        for j in range(kc):
            pltpu.prng_seed(seed_ref[0],
                            seed_ref[1] + (base_i + i) * ncb + (base_j + j))
            # raw bits come back int32 — bitcast before unsigned compare
            bits = pltpu.bitcast(pltpu.prng_random_bits((br, bc)),
                                 jnp.uint32)
            # keep iff bits >= rate * 2^32  (P(drop) = rate to 2^-32)
            keep = bits >= thresh
            sl = (slice(i * br, (i + 1) * br), slice(j * bc, (j + 1) * bc))
            o_ref[sl] = keep.astype(jnp.uint8)


# execution-block budget: elements per (in OR out) VMEM block.  With
# double buffering the kernel holds ~4x this in VMEM (2 MB blocks ->
# ~8 MB), well inside the v5e's VMEM while making every DMA >= 2 MB.
_EXEC_BUDGET_BYTES = 2 << 20
# cap on mask tiles per execution block: the kernel body unrolls kr*kc
# PRNG+select sequences statically, so compile time / code size scale
# with it.  128 is the measured flagship configuration (64x128 tiles in
# a (512,1024) block) — bounded, and already DMA-efficient.
_MAX_UNROLL_TILES = 128


def _exec_blocking(rows, cols, br, bc, itemsize):
    """(kr, kc): how many MASK tiles one execution block covers.

    Mask geometry (br, bc) is global-shape-derived and sharding-visible;
    execution blocking is a pure local performance choice, so it adapts
    to the LOCAL (shard) extents.  kr/kc must tile the local mask grid
    exactly; a ragged row tail (ceil grid) keeps kr=1 so the BlockSpec
    masks the tail block the same way the single-tile kernel did."""
    target = max(1, _EXEC_BUDGET_BYTES // max(1, itemsize))
    nbc = cols // bc
    kc = 1
    for k in range(nbc, 0, -1):
        if nbc % k == 0 and k * bc * br <= target and k <= _MAX_UNROLL_TILES:
            kc = k
            break
    if rows % br != 0:
        return 1, kc
    nbr = rows // br
    kr = 1
    for k in range(nbr, 0, -1):
        if (nbr % k == 0 and k * br * kc * bc <= target
                and k * kc <= _MAX_UNROLL_TILES):
            kr = k
            break
    return kr, kc


def _kernel2d(shape, seed, row_blk_off, col_blk_off, rate, br, bc, ncb_g,
              interpret):
    """Run the mask kernel over a (rows_local, cols_local) extent →
    uint8 keep-mask.

    ``row_blk_off``/``col_blk_off``: this shard's global tile offsets
    (0 unpartitioned); ``ncb_g``: GLOBAL column-block count — the
    static stride that linearizes (row block, col block) into the
    shard-invariant tile id."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, cols = shape
    kr, kc = _exec_blocking(rows, cols, br, bc, 1)
    lin_off = (jnp.asarray(row_blk_off, jnp.int32) * ncb_g
               + jnp.asarray(col_blk_off, jnp.int32))
    seeds = jnp.concatenate([seed.astype(jnp.int32), lin_off.reshape(1)])
    return pl.pallas_call(
        functools.partial(_dropout_kernel, rate=rate, ncb=ncb_g,
                          br=br, bc=bc, kr=kr, kc=kc),
        grid=(_row_grid(rows, kr * br), -(-cols // (kc * bc))),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],  # (2,) seed words
        out_specs=pl.BlockSpec((kr * br, kc * bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.uint8),
        interpret=interpret,
        name="dropout_mask",
    )(seeds)


def _ref_blocked(shape, seed, row_blk_off, col_blk_off, rate, br, bc, ncb_g):
    """Threefry reference mask with the SAME global tile keying (CPU /
    oracle): one key per (row block, col block) tile, folded from the
    linear tile id — partition-invariant over rows AND cols."""
    R, Cl = shape
    nbr = _row_grid(R, br)
    nbc = Cl // bc  # bc divides every (global or shard) col extent
    rpad = nbr * br - R  # ceil grid: masked tail rows, like the kernel
    base = jax.random.fold_in(jax.random.PRNGKey(0), seed[0])

    def one(lin_id):
        k = jax.random.fold_in(base, lin_id)
        return jax.random.bernoulli(k, 1.0 - rate, (br, bc))

    ids = ((row_blk_off + jnp.arange(nbr, dtype=jnp.int32))[:, None] * ncb_g
           + (col_blk_off + jnp.arange(nbc, dtype=jnp.int32))[None, :]
           ).reshape(-1)
    out = jax.vmap(one)(ids).astype(jnp.uint8) \
        .reshape(nbr, nbc, br, bc).transpose(0, 2, 1, 3) \
        .reshape(nbr * br, Cl)
    return out[:R] if rpad else out


def _kernel_backend() -> bool:
    # Mosaic-TPU PRNG primitives only exist on the TPU backend; every
    # other backend takes the block-keyed threefry reference.
    return jax.default_backend() == "tpu"


def _blocked(shape, seed, row_blk_off, col_blk_off, rate, br, bc, ncb_g):
    if _kernel_backend():
        return _kernel2d(shape, seed, row_blk_off, col_blk_off, rate, br, bc,
                         ncb_g, interpret=False)
    return _ref_blocked(shape, seed, row_blk_off, col_blk_off, rate, br, bc,
                        ncb_g)


def _mask2d(shape, seed, rate, br, bc, ncb_g):
    """The uint8 keep-mask of the canonical 2D view.  Under a mesh each
    shard draws exactly ITS global tiles (offsets from its mesh
    coordinates).  A mesh axis splits rows or columns only where every
    shard boundary is a tile boundary — `_pick_br`/`_pick_bc` prefer
    blocks that allow power-of-two splits, so an axis is left out (its
    shards repeat the draw) only for shard counts beyond what the
    extent's factorization supports."""
    rows, cols = mosaic.split(shape, (br, bc))
    R, C = shape[0] // mosaic.n_shards(rows), shape[1] // mosaic.n_shards(cols)

    def draw(seed):
        return _blocked((R, C), seed, mosaic.shard_index(rows) * (R // br),
                        mosaic.shard_index(cols) * (C // bc), rate, br, bc,
                        ncb_g)

    return mosaic.per_shard(draw, (P(),), P(rows, cols))(seed)


def _canonical_2d(shape, dtype):
    """((R, C), restore_fn, br, bc, ncb_g) — THE canonical view
    `dropout_mask` and `_run` share (the geometry is part of the mask;
    it is a pure function of the GLOBAL shape+dtype).

    Arrays with a healthy last dim keep it as the column axis (padded to
    a 128 multiple; sharding-friendly: leading dims stay the row axis).
    Small or badly ragged last dims (< 128, or needing > Cl/8 padding)
    FLATTEN first — per-row padding there would inflate the mask (and
    its apply traffic) up to 128x.  ``restore_fn`` cuts the padding off
    the (R, C) mask and gives it the array's shape."""
    shape = tuple(shape)
    n = math.prod(shape)
    itemsize = jnp.dtype(dtype).itemsize
    Cl = shape[-1] if len(shape) >= 2 else n
    pad = (-Cl) % 128
    if len(shape) >= 2 and Cl >= 128 and pad * 8 <= Cl:
        R = n // Cl
        br, bc = _tile_geometry(R, Cl + pad, itemsize)
        return ((R, Cl + pad), (lambda m: m[:, :Cl].reshape(shape)), br, bc,
                (Cl + pad) // bc)
    # flatten path: total tail padding < cols elements
    cols = 512 if n % 512 == 0 else 128
    R = -(-n // cols)
    br, bc = _tile_geometry(R, cols, itemsize)
    return ((R, cols), (lambda m: m.reshape(-1)[:n].reshape(shape)), br, bc,
            cols // bc)


def dropout_mask(x, seed, rate: float):
    """The uint8 keep-mask for ``x``'s canonical 2D view, restored to
    ``x.shape`` — a pure function of (seed, global shape, x.dtype,
    rate); dtype enters through the tile geometry, so a mask drawn for
    a bf16 array does NOT match an fp32 array of the same shape.  Only
    x's shape and dtype are used: the mask is a constant to autodiff."""
    shape2, restore, br, bc, ncb_g = _canonical_2d(x.shape, x.dtype)
    return restore(_mask2d(shape2, seed, float(rate), int(br), int(bc),
                           int(ncb_g)))


def _run(x, seed, rate, interpret):
    """Direct kernel runner (interpret-mode testing): same canonical
    view as `dropout_mask` + the same XLA apply, global tile offset 0,
    no mesh."""
    shape2, restore, br, bc, ncb_g = _canonical_2d(x.shape, x.dtype)
    z = jnp.int32(0)
    m = restore(_kernel2d(shape2, seed, z, z, rate, br, bc, ncb_g, interpret))
    return _apply_mask(x, m, rate)


def _apply_mask(x, mask, rate):
    scale = jnp.asarray(1.0 / (1.0 - rate), x.dtype)
    return jnp.where(mask != 0, x * scale, jnp.zeros_like(x))


def fused_dropout(x, seed, rate: float):
    """Dropout with in-kernel TPU-PRNG mask. ``seed``: (1,) int32 array
    — derive it from the step key via `random.key_to_seed`; same seed →
    same mask.  In a program traced over a mesh (`ops/mosaic.py`) the
    shards draw the global mask bit-for-bit.

    r5 design: the Pallas kernel emits only the uint8 keep-mask (HBM
    write at the mask's byte size, no x read); the apply is ordinary
    XLA (`where(mask, x*scale, 0)`) that fuses into the surrounding
    fusions — the profiled A/B showed apply-in-kernel broke producer/
    consumer fusion and stalled async copies for ~2x the kernel's own
    cost.  Backward is automatic: the saved mask IS the forward mask,
    so fwd/bwd identity holds by construction (and the bwd apply fuses
    the same way)."""
    if rate >= 1.0:  # degenerate: drop everything (threefry-path parity)
        return jnp.zeros_like(x)
    if rate <= 0.0 or x.size == 0:
        return x
    return _apply_mask(x, dropout_mask(x, seed, rate), rate)


def fused_dropout_add(x, res, seed, rate: float):
    """``res + dropout(x)`` — the transformer post-sublayer pattern.
    Literally ``res + fused_dropout(...)`` (one definition, so the mask
    bits and degenerate-rate guards can never fork); the add rides the
    same XLA fusion as the apply, so no extra HBM pass exists between
    the dropout and the residual."""
    return res + fused_dropout(x, seed, rate)
