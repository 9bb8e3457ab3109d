"""Gluon ↔ mesh unification: the real `models/bert.py` (Gluon layers,
flash attention) trains TP×DP through the PUBLIC API —
``autograd.record() → backward() → Trainer.step()`` — on a multi-device
mesh, with loss/param parity against the single-device oracle.

This is the BASELINE.json north-star sentence ("mxnet.gluon.Trainer ...
scales across a TPU pod") made into CI: `shard_params` places the
params by structural-path rules, GSPMD inserts the ICI collectives
inside the Trainer's fused fwd+bwd+update program, and the training
loop itself is unchanged from the single-chip one.
(Ref concept replaced: `group2ctx` + DataParallelExecutorGroup,
SURVEY.md §2.4.)
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd
from incubator_mxnet_tpu.gluon import Trainer
from incubator_mxnet_tpu.gluon.block import HybridBlock
from incubator_mxnet_tpu.gluon.utils import shard_batch, split_and_load
from incubator_mxnet_tpu.models import bert
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.parallel import create_mesh
from incubator_mxnet_tpu.parallel.sharding import shard_params

V, D, DFF, L, H, B, T = 64, 32, 64, 2, 4, 8, 16


class PretrainWithLoss(HybridBlock):
    def __init__(self, net_, **kw):
        super().__init__(**kw)
        self.net = net_

    def forward(self, tokens, labels):
        mlm_logits, nsp_logits = self.net(tokens)
        logp = mx.nd.log_softmax(mlm_logits.astype("float32"))
        mlm = -(mx.nd.pick(logp, labels).mean())
        nsp_logp = mx.nd.log_softmax(nsp_logits.astype("float32"))
        return mlm - (nsp_logp[:, 0].mean())


def _build():
    mx.random.seed(0)
    net = bert.BERTForPretraining(vocab_size=V, units=D, hidden_size=DFF,
                                  num_layers=L, num_heads=H, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((B, T), jnp.int32)))  # materialize deferred shapes
    model = PretrainWithLoss(net)
    model.hybridize()
    return net, model


def _batch(step):
    k = jax.random.PRNGKey(100 + step)
    kx, ky = jax.random.split(k)
    tokens = jax.random.randint(kx, (B, T), 0, V, dtype=jnp.int32)
    labels = jax.random.randint(ky, (B, T), 0, V, dtype=jnp.int32)
    return tokens, labels


def _train(model, trainer, n_steps, mesh=None):
    losses = []
    for s in range(n_steps):
        tokens, labels = _batch(s)
        if mesh is not None:
            tokens = shard_batch(tokens, mesh)
            labels = shard_batch(labels, mesh)
        else:
            tokens, labels = NDArray(tokens), NDArray(labels)
        with autograd.record():
            loss = model(tokens, labels)
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    return losses


def _params_host(net):
    return {n: onp.asarray(jax.device_get(p.data()._data))
            for n, p in net._collect_params_with_prefix().items()}


def test_gluon_bert_tp_dp_parity():
    """TP=2 × DP=2 Gluon BERT == single-device run, through Trainer."""
    # oracle
    net0, model0 = _build()
    tr0 = Trainer(model0.collect_params(), "sgd",
                  {"learning_rate": 0.1, "momentum": 0.9})
    losses0 = _train(model0, tr0, 3)

    # sharded
    net1, model1 = _build()
    mesh = create_mesh(jax.devices()[:4], data=2, model=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no fallback warnings allowed
        report = shard_params(net1, mesh)
    # the rules must actually bite on the real model
    assert report["bert.encoder.layer0.attention.qkv.weight"] == P("model", None)
    assert report["bert.encoder.layer0.attention.proj.weight"] == P(None, "model")
    assert report["bert.encoder.layer0.ffn.ffn_dense1.weight"] == P("model", None)
    assert report["bert.encoder.layer0.ffn.ffn_dense2.weight"] == P(None, "model")
    assert report["bert.word_embed.weight"] == P("model", None)
    assert report["mlm_decoder.weight"] == P("model", None)
    assert report.coverage > 0.5
    qkv = net1.bert.encoder.layer0.attention.qkv.weight
    sh = qkv.data()._data.sharding
    assert isinstance(sh, NamedSharding)
    assert qkv.data()._data.addressable_shards[0].data.shape == (3 * D // 2, D)

    tr1 = Trainer(model1.collect_params(), "sgd",
                  {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
    losses1 = _train(model1, tr1, 3, mesh=mesh)

    onp.testing.assert_allclose(losses0, losses1, rtol=2e-4, atol=2e-5)
    p0, p1 = _params_host(net0), _params_host(net1)
    assert p0.keys() == p1.keys()
    for n in p0:
        onp.testing.assert_allclose(p0[n], p1[n], rtol=2e-3, atol=1e-4,
                                    err_msg=n)
    # params must STILL be sharded after stepping (no silent resharding
    # to replicated through the donated update)
    sh_after = net1.bert.encoder.layer0.attention.qkv.weight.data()._data.sharding
    assert isinstance(sh_after, NamedSharding)
    assert sh_after.spec == P("model", None)
    # optimizer state (momentum + fp32 master) rides the param sharding,
    # plus — ZeRO-1 default-on for a data>1 mesh — a "data" partition on
    # the first spec-free divisible dim (gspmd tier on TP x DP meshes)
    st = tr1._states[tr1._param2idx[qkv.name]]
    st_leaves = [l for l in jax.tree_util.tree_leaves(st)
                 if hasattr(l, "shape") and l.shape == qkv.shape]
    assert st_leaves, "expected same-shape optimizer state leaves"
    for l in st_leaves:
        assert isinstance(l.sharding, NamedSharding)
        assert l.sharding.spec in (P("model", "data"), P("model", None))
        assert l.sharding.spec[0] == "model"


def test_gluon_bert_dp_only_grad_sync():
    """Pure DP on 8 devices: per-device half-batches see different data;
    parity with the single-device full-batch run proves the gradient
    psum happened inside the fused step."""
    net0, model0 = _build()
    tr0 = Trainer(model0.collect_params(), "sgd", {"learning_rate": 0.1})
    losses0 = _train(model0, tr0, 2)

    net1, model1 = _build()
    mesh = create_mesh(data=8)
    shard_params(net1, mesh, warn=False)  # no 'model' axis: all replicated, ok
    tr1 = Trainer(model1.collect_params(), "sgd", {"learning_rate": 0.1},
                  mesh=mesh)
    losses1 = _train(model1, tr1, 2, mesh=mesh)
    onp.testing.assert_allclose(losses0, losses1, rtol=2e-4, atol=2e-5)
    for n, a in _params_host(net0).items():
        onp.testing.assert_allclose(a, _params_host(net1)[n], rtol=2e-3,
                                    atol=1e-4, err_msg=n)


def test_shard_params_report_warns_on_fallback():
    """A matched rule whose dim doesn't divide the mesh must WARN, not
    silently replicate (VERDICT r2 Weak #3)."""
    mx.random.seed(1)
    net = bert.BERTModel(vocab_size=V, units=24, hidden_size=48, num_layers=1,
                         num_heads=3, dropout=0.0)  # 3 heads: 72 % 16 != 0
    net.initialize()
    net(NDArray(jnp.ones((2, 8), jnp.int32)))
    mesh = create_mesh(jax.devices()[:2], model=2)
    import incubator_mxnet_tpu.parallel.sharding as shmod
    rules = [(r"qkv\.weight$", P(None, "nonexistent_axis"))]
    with pytest.warns(UserWarning, match="fell back"):
        rep = shmod.shard_params(net, mesh, rules=rules)
    assert "encoder.layer0.attention.qkv.weight" in rep.fallbacks
    assert rep.coverage == 0.0


def test_trainer_infers_mesh_from_params():
    net, model = _build()
    mesh = create_mesh(jax.devices()[:4], data=2, model=2)
    shard_params(net, mesh)
    tr = Trainer(model.collect_params(), "sgd", {"learning_rate": 0.1})
    assert tr._get_mesh() is mesh


def test_split_and_load_mesh_mode():
    mesh = create_mesh(data=4)
    x = onp.arange(32, dtype=onp.float32).reshape(8, 4)
    out = split_and_load(x, mesh=mesh)
    assert isinstance(out, NDArray)
    assert len(out._data.addressable_shards) >= 4
    onp.testing.assert_array_equal(onp.asarray(jax.device_get(out._data)), x)


def test_gluon_bert_tp_dp_with_dropout_composes():
    """Dropout-enabled BERT must still train sharded: the Trainer traces
    its step with the mesh in context, so every shard draws its own
    tiles of the global mask (ops/mosaic.py).  Same seed → same mask on
    both runs, so full parity holds even with dropout on."""
    def build():
        mx.random.seed(0)
        net = bert.BERTForPretraining(vocab_size=V, units=D, hidden_size=DFF,
                                      num_layers=L, num_heads=H, dropout=0.1)
        net.initialize()
        net(NDArray(jnp.ones((B, T), jnp.int32)))
        model = PretrainWithLoss(net)
        model.hybridize()
        return net, model

    net0, model0 = build()
    tr0 = Trainer(model0.collect_params(), "sgd", {"learning_rate": 0.1})
    losses0 = _train(model0, tr0, 2)

    net1, model1 = build()
    mesh = create_mesh(jax.devices()[:4], data=2, model=2)
    shard_params(net1, mesh)
    tr1 = Trainer(model1.collect_params(), "sgd", {"learning_rate": 0.1},
                  mesh=mesh)
    tr1._capture_hlo = True
    losses1 = _train(model1, tr1, 2, mesh=mesh)
    # the masks are drawn inside a shard_map of the step program
    assert "sdy.manual_computation" in tr1.last_step_stablehlo
    onp.testing.assert_allclose(losses0, losses1, rtol=3e-4, atol=3e-5)
    for n, a in _params_host(net0).items():
        onp.testing.assert_allclose(a, _params_host(net1)[n], rtol=2e-3,
                                    atol=1e-4, err_msg=n)


def test_fsdp_spec_ignores_size_one_axis():
    """dp_axis over a size-1 mesh axis must NOT count as sharded."""
    import warnings as _w

    mx.random.seed(2)
    net = bert.BERTModel(vocab_size=V, units=D, hidden_size=DFF, num_layers=1,
                         num_heads=H, dropout=0.0)
    net.initialize()
    net(NDArray(jnp.ones((2, 8), jnp.int32)))
    mesh = create_mesh(jax.devices()[:2], data=1, model=2)
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        rep = shard_params(net, mesh, dp_axis="data", min_fsdp_elems=1)
    for name, spec in rep.sharded.items():
        assert "data" not in tuple(spec), (name, spec)
