"""Multi-step chaining (`Trainer(chain_steps=K)`) — K canonical steps
buffered into ONE lax.scan program (amortizes the per-dispatch host
gap in the product path).

Parity bar: losses, weights, optimizer behavior, AND BatchNorm running
stats must match the per-step path exactly over full flushes and a
partial (tail) flush; any read mid-chain must flush first and give the
same values.
"""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon
from incubator_mxnet_tpu.gluon import Trainer, nn
from incubator_mxnet_tpu.ndarray.ndarray import NDArray

B, D, NCLS = 8, 12, 4


def _net(seed):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16))
    net.add(nn.BatchNorm())          # aux state must ride the chain carry
    net.add(nn.Activation("relu"))
    net.add(nn.Dense(NCLS))
    net.initialize()
    net(NDArray(mx.nd.ones((B, D))._data))
    net.hybridize()
    return net


def _batch(s):
    r = onp.random.RandomState(100 + s)
    x = r.randn(B, D).astype("float32")
    y = r.randint(0, NCLS, B).astype("int32")
    return x, y


def _run(chain_steps, n_steps, read_every=None, opt="sgd",
         opt_args=None, unroll=False):
    net = _net(seed=7)
    tr = Trainer(net.collect_params(), opt,
                 opt_args or {"learning_rate": 0.05, "momentum": 0.9},
                 keep_grads=False, chain_steps=chain_steps,
                 chain_unroll=unroll)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    read = []
    for s in range(n_steps):
        x, y = _batch(s)
        with autograd.record():
            L = loss_fn(net(NDArray(x)), NDArray(y))
        L.backward()
        tr.step(B)
        if read_every and (s + 1) % read_every == 0:
            read.append(float(L.asnumpy().mean()))
    tr.flush()
    params = [p.data().asnumpy() for p in net.collect_params().values()]
    return params, read, tr


@pytest.mark.parametrize("unroll", [False, True])
def test_chained_matches_per_step_including_bn_stats(unroll):
    p1, _r1, tr1 = _run(1, 7)
    p3, _r3, tr3 = _run(3, 7, unroll=unroll)  # 2 full flushes + 1 tail
    assert tr3._chain_steps == 3
    for i, (a, b) in enumerate(zip(p3, p1)):
        onp.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6,
                                    err_msg=f"param {i}")
    assert tr1._optimizer.num_update == tr3._optimizer.num_update == 7


def test_mid_chain_loss_read_flushes_and_matches():
    _p1, r1, _t1 = _run(1, 6, read_every=1)
    _p3, r3, _t3 = _run(3, 6, read_every=1)  # every read forces a flush
    onp.testing.assert_allclose(r3, r1, rtol=2e-5, atol=2e-6)
    # occasional reads (the Speedometer pattern) must also agree
    _p, r1b, _ = _run(1, 6, read_every=3)
    _p, r3b, _ = _run(3, 6, read_every=3)
    onp.testing.assert_allclose(r3b, r1b, rtol=2e-5, atol=2e-6)


def test_mid_chain_param_read_flushes():
    net = _net(seed=9)
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05},
                 keep_grads=False, chain_steps=4)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _batch(0)
    for _ in range(3):  # step 0 warms the staged cache; 2 enqueue
        with autograd.record():
            L = loss_fn(net(NDArray(x)), NDArray(y))
        L.backward()
        tr.step(B)
    assert len(tr._chain_buf) == 2
    w = net[0].weight.data().asnumpy()  # read must flush
    assert len(tr._chain_buf) == 0
    # and give the post-3-step weights (vs an unchained twin)
    net2 = _net(seed=9)
    tr2 = Trainer(net2.collect_params(), "sgd", {"learning_rate": 0.05},
                  keep_grads=False)
    for _ in range(3):
        with autograd.record():
            L = loss_fn(net2(NDArray(x)), NDArray(y))
        L.backward()
        tr2.step(B)
    onp.testing.assert_allclose(w, net2[0].weight.data().asnumpy(),
                                rtol=2e-5, atol=2e-6)


def test_chained_adam_and_scheduler():
    """Optimizer state + per-step lr (scheduler) ride the chain."""
    from incubator_mxnet_tpu import lr_scheduler

    sched = lambda: lr_scheduler.FactorScheduler(step=2, factor=0.5,
                                                 base_lr=1e-2)
    p1, _r, _t = _run(1, 6, opt="adam",
                      opt_args={"lr_scheduler": sched()})
    p3, _r, _t = _run(3, 6, opt="adam",
                      opt_args={"lr_scheduler": sched()})
    for i, (a, b) in enumerate(zip(p3, p1)):
        onp.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-6,
                                    err_msg=f"param {i}")


def test_chained_save_states_flushes(tmp_path):
    net = _net(seed=11)
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.05, "momentum": 0.9},
                 keep_grads=False, chain_steps=4)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _batch(1)
    for _ in range(3):
        with autograd.record():
            L = loss_fn(net(NDArray(x)), NDArray(y))
        L.backward()
        tr.step(B)
    assert tr._chain_buf
    tr.save_states(str(tmp_path / "t.states"))
    assert not tr._chain_buf  # flushed
    assert tr._optimizer.num_update == 3
    # restored counts round-trip
    tr.load_states(str(tmp_path / "t.states"))
    assert tr._optimizer.num_update == 3


def test_chain_steps_refused_loudly_when_config_unsupported():
    """chain_steps>1 with keep_grads=True must warn once, not silently
    run unchained (review r5 finding)."""
    import warnings as _w

    net = _net(seed=13)
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05},
                 keep_grads=True, chain_steps=4)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _batch(0)
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        for _ in range(2):
            with autograd.record():
                L = loss_fn(net(NDArray(x)), NDArray(y))
            L.backward()
            tr.step(B)
    msgs = [str(w.message) for w in rec if "chain_steps" in str(w.message)]
    assert len(msgs) == 1, msgs  # warned, and only once
    assert "keep_grads" in msgs[0]
    assert not tr._chain_buf


def test_chained_on_mesh_matches_single_device():
    """chain_steps on a TP×DP mesh: the real Gluon BERT through the
    PUBLIC loop, chained, must match the unchained single-device oracle
    (the chained program carries SHARDED weights/states and stacks the
    data-axis-sharded batches in-program)."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.gluon.utils import shard_batch
    from incubator_mxnet_tpu.models import bert
    from incubator_mxnet_tpu.parallel import create_mesh
    from incubator_mxnet_tpu.parallel.sharding import shard_params

    V, D, DFF, L, H, Bb, T = 32, 16, 32, 2, 2, 8, 8

    class WithLoss(HybridBlock):
        def __init__(self, net_, **kw):
            super().__init__(**kw)
            self.net = net_

        def forward(self, tokens, labels):
            mlm_logits, _nsp = self.net(tokens)
            logp = mx.nd.log_softmax(mlm_logits.astype("float32"))
            return -(mx.nd.pick(logp, labels).mean())

    def build():
        mx.random.seed(21)
        net_ = bert.BERTForPretraining(vocab_size=V, units=D,
                                       hidden_size=DFF, num_layers=L,
                                       num_heads=H, dropout=0.0)
        net_.initialize()
        net_(NDArray(jnp.ones((Bb, T), jnp.int32)))
        m = WithLoss(net_)
        m.hybridize()
        return net_, m

    def batch(s):
        k = jax.random.PRNGKey(300 + s)
        kx, ky = jax.random.split(k)
        return (jax.random.randint(kx, (Bb, T), 0, V, dtype=jnp.int32),
                jax.random.randint(ky, (Bb, T), 0, V, dtype=jnp.int32))

    def train(model, tr, mesh, n):
        losses = []
        for s in range(n):
            tok, lab = batch(s)
            if mesh is not None:
                tok, lab = shard_batch(tok, mesh), shard_batch(lab, mesh)
            else:
                tok, lab = NDArray(tok), NDArray(lab)
            with autograd.record():
                L_ = model(tok, lab)
            L_.backward()
            tr.step(1)
        tr.flush()
        losses.append(float(L_.asnumpy()))
        return losses

    net1, m1 = build()
    tr1 = Trainer(m1.collect_params(), "sgd",
                  {"learning_rate": 0.1, "momentum": 0.9},
                  keep_grads=False)
    l1 = train(m1, tr1, None, 6)

    net2, m2 = build()
    mesh = create_mesh(jax.devices()[:8], data=4, model=2)
    shard_params(net2, mesh)
    tr2 = Trainer(m2.collect_params(), "sgd",
                  {"learning_rate": 0.1, "momentum": 0.9},
                  keep_grads=False, mesh=mesh, chain_steps=3)
    l2 = train(m2, tr2, mesh, 6)
    assert tr2._chain_steps == 3 and not tr2._chain_buf
    onp.testing.assert_allclose(l2, l1, rtol=3e-5, atol=3e-6)
    for (pa, pb) in zip(m1.collect_params().values(),
                        m2.collect_params().values()):
        onp.testing.assert_allclose(pb.data().asnumpy(),
                                    pa.data().asnumpy(),
                                    rtol=5e-5, atol=5e-6)
