"""Pallas kernels in programs traced over a mesh (`ops/mosaic.py`).

A Mosaic kernel cannot be partitioned automatically, so every kernel of
the package is emitted per shard wherever a mesh is in the trace
context, by one rule on every backend.  On the CPU the kernels run in
interpret mode (or as their jnp references), so these tests pin the
rule itself and that a kernel run per shard gives what one device
gives; `tests/test_chip_compile.py` asks the TPU compiler.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu.ops import mosaic


@pytest.mark.parametrize("ctx,manual,want", [
    (False, None, {}),                              # no mesh: a plain call
    (True, None, {"data": 4, "model": 2}),          # the traced mesh, all of it
    (True, ("data", "model"), {}),                  # fully manual already
    (False, ("data", "model"), {}),                 # a caller's own shard_map
    (True, ("data",), {"model": 2}),                # partly manual: the rest
])
def test_per_shard_reads_the_trace_context(mesh42, ctx, manual, want):
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_mxnet_tpu.ops import mosaic

    seen = []

    def body(x):
        seen.append(mosaic.auto_axes())
        wrapped = mosaic.per_shard(lambda v: v * 2, P(), P())
        return wrapped(x) if want else x * 2

    fn = body if manual is None else shard_map(
        body, mesh=mesh42, in_specs=P("data"), out_specs=P("data"),
        axis_names=set(manual))

    def traced(x):
        with mosaic.mesh_context(mesh42 if ctx else None):
            return fn(x)

    x = jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh42, P("data")))
    out = jax.jit(traced)(x)
    assert seen == [want]
    onp.testing.assert_array_equal(onp.asarray(out), 2.0)


def test_split_deals_axes_over_dims(mesh42):
    from incubator_mxnet_tpu.ops import mosaic

    assert mosaic.split((8, 16)) == (None, None)        # no mesh, no split
    with mosaic.mesh_context(mesh42):
        assert mosaic.split((8, 16)) == ("data", "model")
        assert mosaic.split((4096,), (8,)) == (("data", "model"),)
        assert mosaic.split((8, 3)) == (("data", "model"), None)
        assert mosaic.split((6, 3)) == ("model", None)  # 4 divides neither
        assert mosaic.split((16,), (8,)) == ("model",)  # 16/4 breaks the quantum
        assert mosaic.n_shards(("data", "model")) == 8
        assert mosaic.n_shards(None) == 1


def test_mesh_context_outermost_wins(mesh42, mesh8):
    from incubator_mxnet_tpu.parallel import create_mesh

    with mosaic.mesh_context(None):
        assert mosaic.auto_axes() == {}
    with mosaic.mesh_context(create_mesh(jax.devices()[:1], data=1)):
        assert mosaic.auto_axes() == {}                # one device: no mesh
    with mosaic.mesh_context(mesh42):
        with mosaic.mesh_context(mesh8):               # a block inside a step
            assert mosaic.auto_axes() == {"data": 4, "model": 2}
    assert mosaic.auto_axes() == {}


def _flash(q, pool, tables, pos, labels):
    from incubator_mxnet_tpu.ops.flash_attention import flash_attention

    def loss(q):
        return flash_attention(q, q, q, causal=True).sum()
    return jax.grad(loss)(q)


def _paged(q, pool, tables, pos, labels):
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention

    return paged_attention(q[:, :, 0], pool, pool * 0.5, tables, pos,
                           impl="pallas")


def _paged_int8(q, pool, tables, pos, labels):
    from incubator_mxnet_tpu.contrib.quantization import quantize_kv
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention

    H, D = q.shape[1], q.shape[3]     # a page row is H runs of D
    k8, sk = quantize_kv(pool.reshape(pool.shape[:2] + (H, D)))
    k8 = k8.reshape(pool.shape)
    return paged_attention(q[:, :, 0], k8, k8, tables, pos, scale_k=sk,
                           scale_v=sk, impl="pallas")


def _paged_grouped(q, pool, tables, pos, labels):
    """4 query heads on 2 KV heads: the KV heads are what is split."""
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention

    half = pool[:, :, :pool.shape[2] // 2]
    return paged_attention(q[:, :, 0], half, half * 0.5, tables, pos,
                           impl="pallas")


def _paged_window(q, pool, tables, pos, labels):
    """4 queries of 4 heads on one KV head against lane 1's pages, each
    page once: nothing to split, every shard walks them."""
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention_window

    one = pool[:, :, :q.shape[3]]
    return paged_attention_window(q[:, :, 0], one, one * 0.5, tables[1],
                                  jnp.int32(3))


def _paged_window_heads(q, pool, tables, pos, labels):
    """4 queries of 4 heads of 128 on 4 KV heads, a value scale: the KV
    heads are split, a shard's a run of whole 128-lane tiles of a page's
    row (heads of 16 side by side would stay on one shard: a shard's row
    must be something the kernel's copies can cut out of the pool)."""
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention_window

    wide = jnp.tile(pool, (1, 1, 8))                        # (17, 4, 512)
    return paged_attention_window(q[:, :, :8].reshape(4, 4, 128), wide,
                                  wide * 0.5, tables[1], jnp.int32(3),
                                  value_scale=0.707)


def _scan_step(q, pool, tables, pos, labels):
    """The decode step's form of the selective scan: a lane a sequence of
    one token, split over the lanes."""
    from incubator_mxnet_tpu.ops.selective_scan import selective_scan

    B = q.shape[0]
    x = q.reshape(B, 1, -1)                                 # (4, 1, 4096)
    Di, Ds = 256, 4
    u, z = x[..., :Di], x[..., Di:2 * Di]
    dt = jax.nn.softplus(x[..., 2 * Di:3 * Di])
    Bm, Cm = x[..., 3 * Di:3 * Di + Ds], x[..., 3 * Di + Ds:3 * Di + 2 * Ds]
    A = -jnp.exp(x[0, 0, :Ds * Di].reshape(Ds, Di) * 0.1)
    state = x[:, 0, :Ds * Di].reshape(B, Ds, Di)
    return selective_scan(u, dt, z, Bm, Cm, A, x[0, 0, :Di], state,
                          impl="pallas")


def _paged_options(q, pool, tables, pos, labels):
    """4 query heads on 2 KV heads, keys 32 and values 16 wide, a first
    visible position a lane, a sink logit a head, a value scale: lanes and
    KV heads are split, the sink with the heads."""
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention

    q2 = jnp.concatenate([q[:, :, 0], q[:, :, 1]], -1)      # (4, 4, 32)
    return paged_attention(q2, pool, pool[:, :, :32] * 0.5, tables, pos,
                           first=pos // 2 % 4, sink=q[0, :, 2, 0],
                           value_scale=0.707, impl="pallas")


def _paged_sink_alone(q, pool, tables, pos, labels):
    from incubator_mxnet_tpu.ops.paged_attention import paged_attention

    return paged_attention(q[:, :, 0], pool, pool * 0.5, tables, pos,
                           sink=q[0, :, 2, 0], impl="pallas")


def _moe_experts(q, pool, tables, pos, labels):
    """64 tokens, top-2 of 16 experts of which 4 are held: the tiles of
    rows are what is split."""
    from incubator_mxnet_tpu.ops.moe_experts import routed_experts

    x = q[:, :, :16].reshape(64, 64)
    idx = (labels[:, None] * jnp.array([3, 5]) + jnp.array([0, 1])) % 16
    wts = jax.nn.sigmoid(x[:, :2])
    w = q.reshape(-1)[-3 * 4 * 16 * 64:].reshape(3, 4, 16, 64) * 0.2
    return routed_experts(x, idx.astype(jnp.int32), wts, labels % 7 != 0,
                          w[0], w[1], jnp.swapaxes(w[2], 1, 2), first=2,
                          experts=16, impl="pallas")


def _xent(smoothing):
    def run(q, pool, tables, pos, labels):
        from incubator_mxnet_tpu.ops import xent_kernel as xk

        logits = q.reshape(64, -1)                          # (64, 256)
        loss, lse = xk.run_interpret(logits, labels, smoothing)
        return loss, xk.run_interpret_bwd(logits, labels, lse, loss,
                                          smoothing)
    return run


def _index_scores(q, pool, tables, pos, labels):
    """4 lanes' index queries (4 heads of 16) against an index pool whose
    rows hold a key in their first 16 lanes: the lanes are what is split,
    every shard holds the whole pool."""
    from incubator_mxnet_tpu.ops.sparse_attention import index_scores

    return index_scores(q[:, None, :, 0], q[:, None, :, 1, 0], pool, tables,
                        pos, impl="pallas")


def _paged_sparse(q, pool, tables, pos, labels):
    """4 lanes of 4 query heads on 2 KV heads over 5 selected positions a
    lane: lanes over one axis, the KV heads stay whole (a shard's head
    must be whole 128-lane tiles of a row: heads of 16 are not)."""
    from incubator_mxnet_tpu.ops.sparse_attention import (
        paged_attention_sparse, select_positions)

    half = pool[:, :, :pool.shape[2] // 2]
    seen = select_positions(q[:, :1, 0, :].reshape(4, 1, 16), pos[:, None], 5)
    return paged_attention_sparse(q[:, None, :, 0], half, half * 0.5, tables,
                                  pos, seen, impl="pallas")


@pytest.mark.parametrize("kernel", [_flash, _paged, _paged_int8, _xent(0.0),
                                    _xent(0.1), _paged_grouped, _paged_window,
                                    _paged_window_heads,
                                    _scan_step, _paged_options,
                                    _paged_sink_alone, _moe_experts,
                                    _index_scores, _paged_sparse],
                         ids=["flash_fwd_bwd", "paged", "paged_int8", "xent",
                              "xent_smoothed", "paged_grouped",
                              "paged_window", "paged_window_heads_split",
                              "selective_scan_step",
                              "paged_window_sink_scale", "paged_sink",
                              "moe_experts", "index_scores",
                              "paged_sparse"])
def test_kernel_per_shard_matches_one_device(kernel):
    """The kernel under the 2x2 mesh's `shard_map` (interpret mode here)
    against the same kernel called as it is."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_mxnet_tpu.parallel import create_mesh

    mesh = create_mesh(jax.devices()[:4], data=2, model=2)
    B, H, T, D, bs, nbps = 4, 4, 64, 16, 4, 4
    k = jax.random.PRNGKey(0)
    args = (jax.random.normal(k, (B, H, T, D), jnp.float32),
            jax.random.normal(k, (B * nbps + 1, bs, H * D), jnp.float32),
            jnp.arange(1, B * nbps + 1, dtype=jnp.int32).reshape(B, nbps),
            jnp.array([0, 5, 9, 15], jnp.int32),
            jnp.arange(64, dtype=jnp.int32))

    def under_mesh(*a):
        with mosaic.mesh_context(mesh):
            return kernel(*a)

    assert "shard_map" in str(jax.make_jaxpr(under_mesh)(*args))
    assert "shard_map" not in str(jax.make_jaxpr(kernel)(*args))
    want = jax.jit(kernel)(*args)
    on_mesh = [jax.device_put(a, NamedSharding(mesh, P())) for a in args]
    on_mesh[0] = jax.device_put(args[0], NamedSharding(mesh, P("data", "model")))
    got = jax.jit(under_mesh)(*on_mesh)
    for w, g in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        onp.testing.assert_allclose(onp.asarray(g), onp.asarray(w),
                                    rtol=1e-6, atol=1e-6)


def test_shard_params_block_traces_its_forward_under_the_mesh():
    """A block that `shard_params` placed knows its mesh: its own
    forward (inference, no Trainer) emits the flash kernel per shard and
    answers as the unsharded block does."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.transformer import TransformerLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.parallel import create_mesh
    from incubator_mxnet_tpu.parallel.sharding import shard_params

    mx.random.seed(0)
    net = TransformerLM(vocab=64, units=32, hidden_size=64, num_layers=1,
                        num_heads=4, max_len=64, dropout=0.0)
    net.initialize()
    net.hybridize()
    tokens = NDArray(jnp.arange(4 * 64, dtype=jnp.int32).reshape(4, 64) % 64)
    want = net(tokens).asnumpy()

    split = []
    real = mosaic.per_shard
    mesh = create_mesh(jax.devices()[:4], data=2, model=2)
    shard_params(net, mesh, warn=False)
    assert net._mesh is mesh
    try:
        mosaic.per_shard = lambda *a: split.append(mosaic.auto_axes()) \
            or real(*a)
        got = net(tokens).asnumpy()
    finally:
        mosaic.per_shard = real
    assert split and all(s == {"data": 2, "model": 2} for s in split), split
    onp.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_trainer_mesh_that_nothing_is_placed_on_leaves_a_one_device_step():
    """`Trainer(mesh=)` with unsharded params, no optimizer state and a
    batch the data axis does not divide runs a one-device program: it
    must not be traced under the mesh (a `shard_map` in a one-device
    program cannot be lowered)."""
    import warnings

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import Trainer, nn
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.parallel import create_mesh

    class Net(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.dense = nn.Dense(128, in_units=128, flatten=False)
            self.drop = nn.Dropout(0.5)

        def forward(self, x):
            return self.drop(self.dense(x)).sum()

    mx.random.seed(0)
    net = Net()
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                      mesh=create_mesh(jax.devices()[:4], data=4))
    trainer._capture_hlo = True
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")      # "6 is not divisible by 4"
        with autograd.record():
            loss = net(NDArray(jnp.ones((6, 128))))
        loss.backward()
        trainer.step(1)
    assert trainer._fullstep_ctx is not None
    assert onp.isfinite(loss.asnumpy())
    assert "manual_computation" not in trainer.last_step_stablehlo
