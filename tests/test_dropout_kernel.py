"""Fused in-kernel-PRNG dropout (`ops/dropout_kernel.py`).

On the CPU suite `fused_dropout` takes the threefry reference branch —
these tests pin the *contract* both branches share (statistics, scaling,
seed-determinism, fwd/bwd mask identity, ragged shapes) plus the Pallas
kernel body itself in interpret mode where supported.  The TPU branch's
numerics were validated live on the v5e (same assertions).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import random as mxrand
from incubator_mxnet_tpu.ops.dropout_kernel import fused_dropout
from incubator_mxnet_tpu.ndarray.ndarray import NDArray


SEED = jnp.array([7], jnp.int32)


def test_statistics_and_scaling():
    x = jnp.ones((64, 256), jnp.float32)
    y = onp.asarray(jax.device_get(
        jax.jit(lambda x, s: fused_dropout(x, s, 0.25))(x, SEED)))
    keep = (y != 0).mean()
    assert abs(keep - 0.75) < 0.02
    onp.testing.assert_allclose(onp.unique(y[y != 0]), [1.0 / 0.75], rtol=1e-6)
    # E[y] ≈ E[x]
    assert abs(y.mean() - 1.0) < 0.05


def test_seed_determinism():
    x = jnp.ones((32, 128), jnp.float32)
    f = jax.jit(lambda s: fused_dropout(x, s, 0.5))
    a, b = f(SEED), f(SEED)
    onp.testing.assert_array_equal(onp.asarray(a), onp.asarray(b))
    c = f(jnp.array([8], jnp.int32))
    assert (onp.asarray(a) != onp.asarray(c)).any()


def test_fwd_bwd_mask_identity():
    """fwd/bwd mask identity: dx nonzero exactly where y is nonzero,
    with the same scale (r5: guaranteed by the saved uint8 mask)."""
    x = jnp.full((16, 128), 2.0, jnp.float32)
    y = jax.jit(lambda x: fused_dropout(x, SEED, 0.3))(x)
    g = jax.jit(jax.grad(lambda x: fused_dropout(x, SEED, 0.3).sum()))(x)
    y, g = onp.asarray(y), onp.asarray(g)
    onp.testing.assert_array_equal(y != 0, g != 0)
    onp.testing.assert_allclose(g[g != 0], 1.0 / 0.7, rtol=1e-6)


def test_ragged_shape():
    x = jnp.ones((5, 77), jnp.float32)
    y = onp.asarray(jax.jit(lambda x: fused_dropout(x, SEED, 0.5))(x))
    assert y.shape == (5, 77)
    assert 0.3 < (y == 0).mean() < 0.7


def test_key_to_seed_traceable():
    out = jax.jit(lambda k: mxrand.key_to_seed(k))(jax.random.PRNGKey(3))
    assert out.shape == (1,) and out.dtype == jnp.int32


def test_nd_dropout_routes_and_backprops():
    """nd.Dropout trains through the tape regardless of branch."""
    from incubator_mxnet_tpu import autograd

    mx.random.seed(0)
    x = NDArray(jnp.ones((8, 64), jnp.float32))
    x.attach_grad()
    with autograd.record():
        y = mx.nd.Dropout(x, p=0.5)
        L = y.sum()
    L.backward()
    g = onp.asarray(x.grad.asnumpy())
    yv = onp.asarray(y.asnumpy())
    # grad mask mirrors the forward mask (the saved uint8 mask is the
    # single source of truth for fwd and bwd on every backend)
    onp.testing.assert_array_equal(yv != 0, g != 0)


def test_mask_keeps_row_sharding(mesh8):
    """Pin that the mask is NOT drawn replicated for ordinary activation
    shapes on power-of-two row shardings — the r4 review found the first
    tile geometry silently replicated."""
    from incubator_mxnet_tpu.ops import dropout_kernel as dk, mosaic

    # 4800 = 2^5*3*5^2: br must come from divisors of R/8 (600), not R,
    # or the rows silently stay whole (the r4 review's counterexample)
    with mosaic.mesh_context(mesh8):
        for R, Cl in [(4096, 1024), (64, 256), (128, 384), (512, 1024),
                      (4800, 512), (33280, 1024)]:
            Clp = Cl + (-Cl) % 128
            br, bc = dk._tile_geometry(R, Clp, 4)
            assert mosaic.split((R, Clp), (br, bc)) == ("data", None), \
                (R, Cl, br, bc)


def test_mask_keeps_col_sharding(mesh42):
    """Model-dim (tensor-parallel) activations get a column-sharded mask
    too — a row-only mask would be resharded at every dropout call on TP
    meshes (r4 review finding)."""
    from incubator_mxnet_tpu.ops import dropout_kernel as dk, mosaic

    # (128, 384) CANNOT col-shard 2-way (192 per shard has no 128-lane
    # tile) — the second axis must then split the rows further, the
    # columns stay whole
    with mosaic.mesh_context(mesh42):
        for R, Cl, want in [(4096, 1024, ("data", "model")),
                            (256, 512, ("data", "model")),
                            (128, 384, (("data", "model"), None))]:
            br, bc = dk._tile_geometry(R, Cl, 4)
            assert mosaic.split((R, Cl), (br, bc)) == want, (R, Cl, br, bc)


def test_partitioned_matches_unpartitioned_bitexact():
    """With no mesh in context the reference is plain jnp, which GSPMD
    partitions by itself: ANY sharding of x gives the single-device op
    bit-for-bit."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_mxnet_tpu.parallel import create_mesh

    mesh = create_mesh(data=8)
    for shape in [(64, 256), (4096, 1024)]:
        x = jnp.arange(shape[0] * shape[1], dtype=jnp.float32) \
            .reshape(shape) * 1e-3 + 1.0
        ref = onp.asarray(jax.jit(lambda x: fused_dropout(x, SEED, 0.4))(x))
        for spec in [P("data", None), P(None, "data"), P(None, None)]:
            xs = jax.device_put(x, NamedSharding(mesh, spec))
            y = jax.jit(lambda x: fused_dropout(x, SEED, 0.4))(xs)
            onp.testing.assert_array_equal(onp.asarray(y), ref,
                                           err_msg=f"{shape} {spec}")


@pytest.mark.parametrize("axes", [{"data": 8}, {"data": 4, "model": 2},
                                  {"data": 2, "model": 2}])
def test_shards_draw_the_global_mask_bitexact(axes):
    """The mesh property: in a program traced over a mesh every shard
    draws ITS tiles of the global mask (the tile grid is fixed by the
    GLOBAL shape), whatever sharding x arrives with."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_mxnet_tpu.ops import mosaic
    from incubator_mxnet_tpu.parallel import create_mesh

    mesh = create_mesh(**axes)

    def under_mesh(x):
        with mosaic.mesh_context(mesh):
            return fused_dropout(x, SEED, 0.4)

    for shape in [(64, 1024), (4096, 1024), (8, 16, 384), (5, 77)]:
        x = jnp.ones(shape, jnp.float32)
        ref = onp.asarray(jax.jit(lambda x: fused_dropout(x, SEED, 0.4))(x))
        assert "shard_map" in str(jax.make_jaxpr(under_mesh)(x))
        specs = [P()] if shape == (5, 77) else \
            [P("data"), P(*(None,) * (len(shape) - 1), "data"), P()]
        for spec in specs:
            xs = jax.device_put(x, NamedSharding(mesh, spec))
            y = jax.jit(under_mesh)(xs)
            onp.testing.assert_array_equal(onp.asarray(y), ref,
                                           err_msg=f"{shape} {spec}")


def test_partitioned_grad_mask_identity():
    """fwd/bwd mask identity must survive sharding — each shard's mask
    bits come from global tile coords, and the backward reuses them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_mxnet_tpu.parallel import create_mesh

    mesh = create_mesh(data=4, model=2)
    x = jnp.full((32, 256), 2.0, jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(("data", "model"), None)))
    y = jax.jit(lambda x: fused_dropout(x, SEED, 0.3))(xs)
    g = jax.jit(jax.grad(lambda x: fused_dropout(x, SEED, 0.3).sum()))(xs)
    y, g = onp.asarray(y), onp.asarray(g)
    onp.testing.assert_array_equal(y != 0, g != 0)
    onp.testing.assert_allclose(g[g != 0], 1.0 / 0.7, rtol=1e-6)

    # unsharded oracle agrees bit-for-bit
    ref = onp.asarray(jax.jit(lambda x: fused_dropout(x, SEED, 0.3))(x))
    onp.testing.assert_array_equal(y, ref)


def test_partitioned_3d_activation_shape():
    """(B, T, D) transformer activations: batch+seq sharded rows, model
    dim replicated by the rule — the flagship BERT layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_mxnet_tpu.parallel import create_mesh

    mesh = create_mesh(data=4, model=2)
    x = jnp.ones((8, 16, 384), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, "model")))
    y = jax.jit(lambda x: fused_dropout(x, SEED, 0.25))(xs)
    yv = onp.asarray(y)
    assert yv.shape == x.shape
    keep = (yv != 0).mean()
    assert abs(keep - 0.75) < 0.03
    ref = onp.asarray(jax.jit(lambda x: fused_dropout(x, SEED, 0.25))(x))
    onp.testing.assert_array_equal(yv, ref)


def test_pallas_interpret_matches_contract():
    """Run the actual kernel body in interpret mode on CPU (skip cleanly
    if this jax build can't interpret the TPU PRNG primitives)."""
    from incubator_mxnet_tpu.ops import dropout_kernel as dk

    x = jnp.ones((16, 256), jnp.float32)
    try:
        y = dk._run(x, SEED, 0.25, interpret=True)
        y = onp.asarray(jax.device_get(y))
    except Exception as e:  # pragma: no cover - jax-version dependent
        pytest.skip(f"pltpu PRNG not interpretable on this backend: {e}")
    keep = (y != 0).mean()
    assert abs(keep - 0.75) < 0.06
    onp.testing.assert_allclose(onp.unique(y[y != 0]), [1.0 / 0.75], rtol=1e-5)
    y2 = onp.asarray(jax.device_get(dk._run(x, SEED, 0.25, interpret=True)))
    onp.testing.assert_array_equal(y, y2)


class TestDropoutAdd:
    """fused_dropout_add = residual + dropout(x), same mask bits."""

    def test_matches_dropout_plus_add_bitexact(self):
        from incubator_mxnet_tpu.ops.dropout_kernel import (fused_dropout,
                                                            fused_dropout_add)

        x = jax.random.normal(jax.random.PRNGKey(1), (32, 384), jnp.float32)
        r = jax.random.normal(jax.random.PRNGKey(2), (32, 384), jnp.float32)
        fused = onp.asarray(jax.jit(
            lambda a, b: fused_dropout_add(a, b, SEED, 0.3))(x, r))
        split = onp.asarray(jax.jit(
            lambda a, b: b + fused_dropout(a, SEED, 0.3))(x, r))
        onp.testing.assert_array_equal(fused, split)

    def test_gradients(self):
        from incubator_mxnet_tpu.ops.dropout_kernel import (fused_dropout,
                                                            fused_dropout_add)

        x = jax.random.normal(jax.random.PRNGKey(3), (16, 256), jnp.float32)
        r = jax.random.normal(jax.random.PRNGKey(4), (16, 256), jnp.float32)
        dy = jax.random.normal(jax.random.PRNGKey(5), (16, 256), jnp.float32)

        def f(a, b):
            return jnp.sum(fused_dropout_add(a, b, SEED, 0.4) * dy)

        dx, dr = jax.grad(f, argnums=(0, 1))(x, r)
        # residual grad passes through untouched
        onp.testing.assert_array_equal(onp.asarray(dr), onp.asarray(dy))
        # x grad is the regenerated mask applied to dy (same zeros;
        # kept entries differ only by f32 multiply ordering)
        want = onp.asarray(jax.jit(
            lambda d: fused_dropout(d, SEED, 0.4))(dy))
        onp.testing.assert_array_equal(onp.asarray(dx) == 0, want == 0)
        onp.testing.assert_allclose(onp.asarray(dx), want, rtol=1e-6)

    def test_degenerate_rates(self):
        from incubator_mxnet_tpu.ops.dropout_kernel import fused_dropout_add

        x = jnp.ones((8, 128), jnp.float32)
        r = 2 * jnp.ones((8, 128), jnp.float32)
        onp.testing.assert_array_equal(
            onp.asarray(fused_dropout_add(x, r, SEED, 0.0)), 3.0)
        onp.testing.assert_array_equal(
            onp.asarray(fused_dropout_add(x, r, SEED, 1.0)), 2.0)

    def test_partitioned_matches_unsharded(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from incubator_mxnet_tpu.ops.dropout_kernel import fused_dropout_add
        from incubator_mxnet_tpu.parallel import create_mesh

        mesh = create_mesh(data=4, model=2)
        x = jax.random.normal(jax.random.PRNGKey(6), (8, 16, 384), jnp.float32)
        r = jax.random.normal(jax.random.PRNGKey(7), (8, 16, 384), jnp.float32)
        sh = NamedSharding(mesh, P("data", None, "model"))
        y = jax.jit(lambda a, b: fused_dropout_add(a, b, SEED, 0.25))(
            jax.device_put(x, sh), jax.device_put(r, sh))
        ref = jax.jit(lambda a, b: fused_dropout_add(a, b, SEED, 0.25))(x, r)
        onp.testing.assert_array_equal(onp.asarray(y), onp.asarray(ref))

    def test_nd_op_and_gluon_block(self):
        import incubator_mxnet_tpu as mx
        from incubator_mxnet_tpu import _tape, autograd
        from incubator_mxnet_tpu.gluon import nn
        from incubator_mxnet_tpu.ndarray.ndarray import NDArray

        mx.random.seed(0)
        y = NDArray(jnp.ones((4, 256), jnp.float32))
        res = NDArray(2 * jnp.ones((4, 256), jnp.float32))
        blk = nn.DropoutAdd(0.5)
        out_eval = blk(y, res)  # not training: plain sum
        onp.testing.assert_array_equal(out_eval.asnumpy(), 3.0)
        with autograd.record():
            out = blk(y, res)
        v = out.asnumpy()
        kept = v[v != 3.0 - 1.0]  # dropped entries equal the residual (2)
        assert ((v == 2.0) | (v == 4.0)).all()  # 2 + {0, 1/0.5}
        assert 0.2 < (v == 2.0).mean() < 0.8


def test_nested_hybridized_masks_advance_per_step():
    """r5 regression gate: a hybridized child block inside a hybridized
    parent must NOT bake the global (key, counter) into the parent's
    jaxpr as constants — before the step_key provider-awareness fix,
    nested-block dropout masks were identical on every replay of the
    parent program (i.e. every training step)."""
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.gluon.block import HybridBlock

    class P(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.d = nn.Dense(64, flatten=False, in_units=64)
            self.drop = nn.Dropout(0.5)

        def forward(self, x):
            return self.drop(self.d(x))

    mx.random.seed(0)
    p = P()
    p.initialize()
    p.hybridize()
    x = NDArray(jnp.ones((8, 64), jnp.float32))
    with autograd.record():
        a = p(x).asnumpy()
    with autograd.record():
        b = p(x).asnumpy()
    assert (onp.asarray(a) != onp.asarray(b)).any(), \
        "nested hybridized dropout mask is step-constant"
    # seeded replay of the same call sequence reproduces bits exactly
    mx.random.seed(9)
    with autograd.record():
        c = p(x).asnumpy()
    mx.random.seed(9)
    with autograd.record():
        d = p(x).asnumpy()
    onp.testing.assert_array_equal(onp.asarray(c), onp.asarray(d))
