"""AMP: namespace rewrite, dtype policy, LossScaler dynamics
(r1 VERDICT weak item #9: "AMP is a shell")."""
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import amp
from incubator_mxnet_tpu.ndarray.ndarray import NDArray


@pytest.fixture(autouse=True)
def _amp_teardown():
    yield
    amp.reset()


def test_init_rewrites_fp16_ops_to_bf16():
    x = NDArray(jnp.ones((2, 8), jnp.float32))
    w = NDArray(jnp.ones((4, 8), jnp.float32))
    amp.init("bfloat16")
    out = mx.nd.FullyConnected(x, w, num_hidden=4, no_bias=True)
    assert out._data.dtype == jnp.bfloat16  # MXU op ran in bf16
    a = NDArray(jnp.ones((2, 3), jnp.bfloat16))
    s = mx.nd.softmax(a)
    assert s._data.dtype == jnp.float32  # range-sensitive op forced fp32


def test_reset_restores_namespace():
    amp.init("bfloat16")
    assert hasattr(mx.nd.FullyConnected, "__wrapped__")
    amp.reset()
    assert not hasattr(mx.nd.FullyConnected, "__wrapped__")
    x = NDArray(jnp.ones((2, 8), jnp.float32))
    w = NDArray(jnp.ones((4, 8), jnp.float32))
    out = mx.nd.FullyConnected(x, w, num_hidden=4, no_bias=True)
    assert out._data.dtype == jnp.float32


def test_loss_scaler_dynamics():
    s = amp.LossScaler(init_scale=16.0, scale_factor=2.0, scale_window=3)
    # overflow halves
    s.update_scale(True)
    assert s.loss_scale == 8.0
    # window good steps double
    for _ in range(3):
        s.update_scale(False)
    assert s.loss_scale == 16.0
    # floor at 1
    for _ in range(10):
        s.update_scale(True)
    assert s.loss_scale == 1.0


def test_overflow_detection_and_trainer_roundtrip():
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import Trainer, nn

    mx.random.seed(0)
    net = nn.Dense(4, in_units=6)
    net.initialize()
    net(NDArray(jnp.ones((2, 6))))
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    amp.init("float16")
    amp.init_trainer(trainer)
    x = NDArray(jnp.ones((2, 6)))
    with autograd.record():
        # loss math in fp32 (the reference keeps losses fp32; scaling a
        # fp16 loss by 2^16 would overflow by construction)
        loss = amp.scale_loss((net(x).astype("float32") ** 2).mean(), trainer)
    loss.backward()
    amp.unscale(trainer)
    scaler = trainer._amp_loss_scaler
    params = list(net.collect_params().values())
    assert not scaler.has_overflow(params)
    g = net.weight.grad().asnumpy()
    assert onp.isfinite(g).all() and onp.abs(g).max() < 1e3  # unscaled

    # inject an overflow
    net.weight.grad()._data = jnp.full_like(net.weight.grad()._data, jnp.inf)
    assert scaler.has_overflow(params)


def test_convert_model_casts_params():
    from incubator_mxnet_tpu.gluon import nn

    net = nn.Dense(4, in_units=6)
    net.initialize()
    net(NDArray(jnp.ones((2, 6))))
    amp.convert_model(net, "bfloat16")
    assert net.weight.data()._data.dtype == jnp.bfloat16


def test_amp_lists_fully_resolve():
    """Every AMP list entry must resolve to a real exported op — a
    non-resolving entry silently escapes the rewrite (VERDICT r2 #5)."""
    from incubator_mxnet_tpu import amp

    cov = amp.list_coverage()
    assert cov == {"FP16_FUNCS": [], "FP32_FUNCS": [], "FP16_FP32_FUNCS": []}, cov


def test_amp_wraps_contrib_ops():
    """Dotted entries (contrib.interleaved_matmul_*) really get wrapped
    and restored — previously they silently no-opped."""
    from incubator_mxnet_tpu import amp
    from incubator_mxnet_tpu import ndarray as nd

    orig = nd.contrib.interleaved_matmul_selfatt_qk
    amp.init("bfloat16")
    try:
        assert nd.contrib.interleaved_matmul_selfatt_qk is not orig
        assert getattr(nd.contrib.interleaved_matmul_selfatt_qk,
                       "__wrapped__", None) is orig
    finally:
        amp.reset()
    assert nd.contrib.interleaved_matmul_selfatt_qk is orig


def test_device_peak_flops_raises_on_unknown_device():
    from incubator_mxnet_tpu.callback import (device_peak_flops,
                                              device_peak_hbm_bytes_per_s)

    class FakeDev:
        device_kind = "QuantumAccel 9000"
        platform = "quantum"

    class FakeCPU:
        device_kind = "cpu"
        platform = "cpu"

    class FakeV5e:
        device_kind = "TPU v5 lite"
        platform = "tpu"

    for dev in (FakeDev(), FakeCPU()):  # no nominal figure, no warning
        with pytest.raises(ValueError, match="peak known"):
            device_peak_flops(dev)
        with pytest.raises(ValueError, match="peak known"):
            device_peak_hbm_bytes_per_s(dev)
    assert device_peak_flops(FakeV5e()) == 197e12
    assert device_peak_hbm_bytes_per_s(FakeV5e()) == 819e9
