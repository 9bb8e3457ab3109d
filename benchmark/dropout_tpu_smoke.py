"""Live-TPU smoke for the fused dropout kernel path.

The pytest suite pins jax to CPU (conftest), where fused_dropout takes
the block-keyed threefry reference — so the Mosaic kernel itself (seed
arity, tile legality across geometries, fwd/bwd identity on hardware)
must be validated here, on the real chip.  Run from the repo root:

    python benchmark/dropout_tpu_smoke.py

Exercises every geometry class _pick_br can produce: large aligned
(R>=64*br), mid (8 blocks), single-block fallback (odd R), ragged last
dim (col padding), 3D activations, and bf16.

This script runs on ONE chip.  The sharded case — every shard of a
2x2 mesh drawing its own tiles of the global mask, bit for bit what one
device draws — runs on the four-chip host in `chip_smoke.py --chips 4`
(phase `mesh_dropout_mask`).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as onp

from incubator_mxnet_tpu.ops import dropout_kernel as dk

SEED = jnp.array([7], jnp.int32)

SHAPES = [
    ((4096, 1024), jnp.float32),
    ((64, 256), jnp.float32),
    ((256, 512), jnp.float32),
    ((8, 256), jnp.float32),
    ((5, 77), jnp.float32),      # ragged: col pad + single row block
    ((16, 128), jnp.bfloat16),
    ((32, 512, 1024), jnp.bfloat16),   # (B, T, D) flagship activation
    ((384,), jnp.float32),       # 1D
]


def main():
    assert dk._kernel_backend(), (
        f"not a TPU backend: {jax.default_backend()}")
    rate = 0.3
    for shape, dt in SHAPES:
        # strictly positive so (y != 0) recovers the mask exactly (an x
        # that rounds to 0 in bf16 would fake a dropped element)
        x = (jnp.abs(jax.random.normal(
            jax.random.PRNGKey(1), shape, jnp.float32)) + 1.0).astype(dt)
        y = jax.jit(lambda x: dk.fused_dropout(x, SEED, rate))(x)
        g = jax.jit(jax.grad(
            lambda x: dk.fused_dropout(x, SEED, rate)
            .astype(jnp.float32).sum()))(x.astype(jnp.float32))
        yv = onp.asarray(y.astype(jnp.float32))
        gv = onp.asarray(g)
        keep = (yv != 0).mean()
        assert abs(keep - (1 - rate)) < 0.05, (shape, keep)
        # fwd/bwd identity needs SAME dtype runs (geometry depends on
        # itemsize); re-run fwd in f32 for the comparison
        yf = onp.asarray(jax.jit(
            lambda x: dk.fused_dropout(x, SEED, rate))(
                x.astype(jnp.float32)))
        onp.testing.assert_array_equal(yf != 0, gv != 0)
        # determinism
        y2 = onp.asarray(jax.jit(
            lambda x: dk.fused_dropout(x, SEED, rate))(x)
            .astype(jnp.float32))
        onp.testing.assert_array_equal(yv, y2)
        # execution blocking must NOT change the bits: the mask is a
        # function of the (br, bc) MASK grid only — force kr=kc=1 and
        # compare bitwise
        budget = dk._EXEC_BUDGET_BYTES
        try:
            dk._EXEC_BUDGET_BYTES = 1  # forces kr=kc=1
            y1 = onp.asarray(jax.jit(
                lambda x: dk.fused_dropout(x, SEED, rate))(x)
                .astype(jnp.float32))
        finally:
            dk._EXEC_BUDGET_BYTES = budget
        onp.testing.assert_array_equal(yv, y1)
        print(f"  OK {str(shape):18s} {jnp.dtype(dt).name:9s} keep={keep:.3f}")
    bandwidth()
    print("TPU DROPOUT SMOKE PASS")


def bandwidth():
    """Effective GB/s at the flagship site shape under the r5
    mask-split traffic model (mask write+read at 1 B/elem + apply's
    x read / y write).  History: the r4 apply-in-kernel op measured
    ~200 GB/s before execution blocking and >1100 GB/s after, on a
    2*itemsize model — not directly comparable to this number."""
    import time

    from jax import lax

    x = jnp.abs(jax.random.normal(
        jax.random.PRNGKey(2), (4096, 1024), jnp.float32)).astype(jnp.bfloat16) + 1
    K = 100

    @jax.jit
    def chained(x):
        def body(c, _):
            # pure chain — no extra elementwise pass pollutes the number
            # (kept elements grow 1.111x/iter; 1.111^100 ~ 3.8e4, fine)
            return dk.fused_dropout(c, SEED, 0.1), ()

        out, _ = lax.scan(body, x, None, length=K)
        return out.astype(jnp.float32).sum()

    @jax.jit
    def null(x):
        return (x * jnp.asarray(1.0000001, x.dtype)).astype(jnp.float32).sum()

    def best(f):
        float(f(x))  # compile + warm
        b = float("inf")
        for _ in range(7):
            t0 = time.perf_counter()
            float(f(x))
            b = min(b, time.perf_counter() - t0)
        return b

    per_call = (best(chained) - best(null)) / K
    # r5 mask-split traffic per call: mask write + mask read (1 B/elem
    # each) + the XLA apply's x read and y write.  (Pre-r5
    # apply-in-kernel was 2*itemsize; the old ~200 GB/s r4 gate number
    # is not directly comparable.)
    traffic = x.size * (2 + 2 * x.dtype.itemsize)
    print(f"  flagship-site fused_dropout (mask+apply): "
          f"{per_call*1e6:.1f} us/call, "
          f"{traffic/per_call/1e9:.0f} GB/s effective")


if __name__ == "__main__":
    main()
