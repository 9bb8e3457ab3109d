"""Generate the golden checkpoint wire-format fixtures (r4 VERDICT #7).

Run ONCE (from the repo root) and COMMIT the outputs; never regenerate
casually — the committed bytes are the backward-compat contract that
future code must keep loading (the reference's
model_backwards_compat_train/inference nightly, SURVEY.md §4,
translated to this framework's formats):

  net.params       — Block.save_parameters `.params` codec
  bundle/ckpt-*    — CheckpointManager full train-state bundle
                     (params + optimizer state + RNG + iterator pos)

    JAX_PLATFORMS=cpu PYTHONPATH=. \
        python tests/fixtures/golden_ckpt/generate.py
"""
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def build_net_and_train():
    import jax
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    mx.random.seed(1234)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize()
    net(NDArray(jnp.ones((4, 8), jnp.float32)))
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    loss_fn = gluon.loss.L2Loss()
    k = jax.random.PRNGKey(0)
    x = NDArray(jax.random.normal(k, (4, 8), jnp.float32))
    y = NDArray(jnp.zeros((4, 4), jnp.float32))
    for _ in range(2):
        with autograd.record():
            L = loss_fn(net(x), y)
        L.backward()
        trainer.step(4)
    return net, trainer, (x, y, loss_fn)


def main():
    from incubator_mxnet_tpu.utils.checkpoint import CheckpointManager

    net, trainer, _ = build_net_and_train()
    net.save_parameters(os.path.join(HERE, "net.params"))
    mgr = CheckpointManager(os.path.join(HERE, "bundle"), keep=0,
                            async_save=False)
    mgr.save(2, net=net, trainer=trainer,
             iterator_state={"epoch": 0, "batch": 2},
             extra={"note": "golden r5 fixture"})
    print("golden fixtures written under", HERE)


if __name__ == "__main__":
    main()
