"""LR schedulers, initializers, RNG — unit coverage (SURVEY.md §4)."""
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import initializer as init_mod
from incubator_mxnet_tpu import lr_scheduler as lrs
from incubator_mxnet_tpu.ndarray.ndarray import NDArray


# --------------------------------------------------------------------- #
# schedulers
# --------------------------------------------------------------------- #
def test_factor_scheduler():
    # reference semantics: decay applies strictly AFTER the boundary
    s = lrs.FactorScheduler(step=10, factor=0.5, base_lr=1.0)
    assert s(5) == pytest.approx(1.0)
    assert s(10) == pytest.approx(1.0)
    assert s(11) == pytest.approx(0.5)
    assert s(21) == pytest.approx(0.25)


def test_multifactor_scheduler():
    s = lrs.MultiFactorScheduler(step=[5, 15], factor=0.1, base_lr=1.0)
    assert s(1) == pytest.approx(1.0)
    assert s(6) == pytest.approx(0.1)
    assert s(16) == pytest.approx(0.01, rel=1e-6)


def test_poly_cosine_linear_endpoints():
    p = lrs.PolyScheduler(max_update=100, base_lr=1.0, pwr=2, final_lr=0.0)
    assert p(0) == pytest.approx(1.0)
    assert p(100) == pytest.approx(0.0, abs=1e-6)
    c = lrs.CosineScheduler(max_update=100, base_lr=1.0, final_lr=0.1)
    assert c(0) == pytest.approx(1.0)
    assert c(100) == pytest.approx(0.1, rel=1e-4)
    l = lrs.LinearScheduler(max_update=100, base_lr=1.0, final_lr=0.0)
    assert l(50) == pytest.approx(0.5)


def test_warmup():
    s = lrs.FactorScheduler(step=1000, factor=1.0, base_lr=1.0,
                            warmup_steps=10, warmup_begin_lr=0.0)
    assert s(0) < s(5) < s(10)
    assert s(10) == pytest.approx(1.0)


def test_invsqrt_scheduler():
    s = lrs.InvSqrtScheduler(warmup_steps=16, base_lr=1.0)
    # linearly growing through warmup, peak at warmup, decaying after
    assert s(4) < s(8) < s(16)
    assert s(16) == pytest.approx(16 ** -0.5)
    assert s(64) == pytest.approx(64 ** -0.5)


# --------------------------------------------------------------------- #
# initializers
# --------------------------------------------------------------------- #
def _init_arr(init, shape=(64, 32), name="weight"):
    arr = NDArray(jnp.zeros(shape, jnp.float32))
    init(init_mod.InitDesc(name), arr)
    return arr.asnumpy()


def test_constant_zero_one():
    assert (_init_arr(init_mod.Zero()) == 0).all()
    assert (_init_arr(init_mod.One()) == 1).all()
    assert (_init_arr(init_mod.Constant(2.5)) == 2.5).all()


def test_uniform_normal_stats():
    u = _init_arr(init_mod.Uniform(0.5), (200, 100))
    assert u.min() >= -0.5 and u.max() <= 0.5 and abs(u.mean()) < 0.02
    n = _init_arr(init_mod.Normal(0.1), (200, 100))
    assert abs(n.std() - 0.1) < 0.01


def test_xavier_variants():
    fan_in, fan_out = 32, 64
    x = _init_arr(init_mod.Xavier(factor_type="avg", magnitude=3), (fan_out, fan_in))
    bound = onp.sqrt(3 * 2.0 / (fan_in + fan_out))
    assert onp.abs(x).max() <= bound + 1e-6
    g = _init_arr(init_mod.Xavier(rnd_type="gaussian", factor_type="in",
                                  magnitude=2), (fan_out, fan_in))
    assert abs(g.std() - onp.sqrt(2.0 / fan_in)) < 0.05


def test_orthogonal():
    w = _init_arr(init_mod.Orthogonal(scale=1.0), (32, 32))
    onp.testing.assert_allclose(w @ w.T, onp.eye(32), atol=1e-4)


def test_msra_prelu():
    w = _init_arr(init_mod.MSRAPrelu(), (64, 32))
    assert w.std() > 0


def test_bilinear_upsampling_kernel():
    w = _init_arr(init_mod.Bilinear(), (1, 1, 4, 4))
    assert w.max() <= 1.0 and w.min() >= 0.0
    assert w[0, 0, 1, 1] >= w[0, 0, 0, 0]  # peaked at center


def test_mixed_and_attr_driven():
    mixed = init_mod.Mixed([".*bias", ".*"], [init_mod.Zero(), init_mod.One()]) \
        if hasattr(init_mod, "Mixed") else None
    if mixed is None:
        pytest.skip("no Mixed initializer")
    b = NDArray(jnp.ones(4))
    mixed(init_mod.InitDesc("fc_bias"), b)
    assert (b.asnumpy() == 0).all()


# --------------------------------------------------------------------- #
# RNG
# --------------------------------------------------------------------- #
def test_seed_reproducible():
    mx.random.seed(42)
    a = mx.random.uniform(shape=(8,)).asnumpy()
    mx.random.seed(42)
    b = mx.random.uniform(shape=(8,)).asnumpy()
    onp.testing.assert_array_equal(a, b)


def test_state_capture_includes_step_counter():
    mx.random.seed(0)
    from incubator_mxnet_tpu import random as rnd

    s = rnd.get_state()
    k1, c1 = rnd.step_key()
    rnd.set_state(s)
    k2, c2 = rnd.step_key()
    assert c1 == c2
    onp.testing.assert_array_equal(onp.asarray(k1), onp.asarray(k2))


def test_distribution_ranges():
    mx.random.seed(1)
    u = mx.random.uniform(2.0, 5.0, shape=(1000,)).asnumpy()
    assert u.min() >= 2.0 and u.max() <= 5.0
    n = mx.random.normal(1.0, 2.0, shape=(5000,)).asnumpy()
    assert abs(n.mean() - 1.0) < 0.2 and abs(n.std() - 2.0) < 0.2
    r = mx.random.randint(0, 10, shape=(1000,)).asnumpy()
    assert r.min() >= 0 and r.max() < 10


def test_next_key_unique():
    from incubator_mxnet_tpu import random as rnd

    mx.random.seed(3)
    keys = [tuple(onp.asarray(rnd.next_key()).tolist()) for _ in range(100)]
    assert len(set(keys)) == 100  # block cache must not repeat keys


def test_gpu_memory_info_and_storage_stats():
    free, total = mx.context.gpu_memory_info()
    assert free >= 0 and total >= 0
    stats = mx.context.storage_stats()
    assert isinstance(stats, dict)


def test_naive_engine_nan_guard():
    import jax.numpy as jnp2

    from incubator_mxnet_tpu import runtime

    with runtime.naive_engine(debug_nans=True):
        with pytest.raises(FloatingPointError):
            bad = jnp2.asarray([1.0, float("nan")])
            float(jnp2.sum(bad))


@pytest.mark.parametrize("placed", ["/some/dir", None])
def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch, placed):
    import os

    import jax

    from incubator_mxnet_tpu import runtime

    before = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = runtime.use_compile_cache()
        if placed:  # nothing is set in code
            assert got == placed
            assert jax.config.jax_compilation_cache_dir == before
        else:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert got == os.path.normpath(got) and os.path.isabs(got)
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_inception_v3_in_zoo():
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    mx.random.seed(0)
    net = vision.get_model("inceptionv3", classes=4)
    net.initialize()
    out = net(NDArray(jnp.ones((1, 3, 96, 96))))
    assert out.shape == (1, 4)
