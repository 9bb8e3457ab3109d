"""ResNet-50 train-step perf probe (VERDICT r3 item: ≥40% of the bf16
compute ceiling).  Measures the canonical Gluon path and a pure-JAX
hand-rolled step to localize where the step time goes: framework
overhead vs XLA conv scheduling.

Run ON THE TPU: python benchmark/resnet_probe.py [gluon|purejax ...]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp


def time_steps(step_once, fetch, n=20, warm=3):
    """Fetch a value ONLY at the timing boundaries (a per-step host
    fetch serializes the queue)."""
    for _ in range(warm):
        out = step_once()
    fetch(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = step_once()
    fetch(out)
    return (time.perf_counter() - t0) / n


def _build_net(B):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    mx.random.seed(0)
    net = resnet50_v1(classes=1000)
    net.initialize()
    # resolve deferred shapes with a TINY batch: the eager forward
    # materializes every intermediate activation
    net(NDArray(jnp.ones((4, 3, 224, 224), jnp.float32)))
    net.cast("bfloat16")
    return net


def gluon_variant(B):
    """The measured-of-record Gluon loop (train.py config)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import Trainer
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    net = _build_net(B)
    net.hybridize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9,
                  "multi_precision": True}, keep_grads=False)
    x = NDArray(jnp.ones((B, 3, 224, 224), jnp.bfloat16))
    y = NDArray(jnp.zeros((B,), jnp.int32))

    def step_once():
        with autograd.record():
            # canonical loop: backward on the per-sample loss (NO .mean()
            # — an eager op on the lazy outputs breaks the one-program
            # chain and forces the residual-materializing staged path,
            # which at BS128 OOMs the chip)
            L = loss_fn(net(x), y)
        L.backward()
        tr.step(B)
        return L

    return B / time_steps(step_once,
                          lambda L: float(L.asnumpy().ravel()[0]))


def purejax_variant(B):
    """Hand-rolled ResNet-50 train step — the XLA ceiling probe."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import functionalize

    net = _build_net(B)
    apply_fn, train_raws, aux_raws = functionalize(net)
    rng = jax.random.PRNGKey(0)
    y = jnp.zeros((B,), jnp.int32)
    x = jnp.ones((B, 3, 224, 224), jnp.bfloat16)

    masters = tuple(w.astype(jnp.float32) for w in train_raws)
    moms = tuple(jnp.zeros_like(m) for m in masters)

    @jax.jit
    def step(masters, moms, aux, xx):
        tr = tuple(m.astype(jnp.bfloat16) for m in masters)

        def loss_of(t):
            out, new_aux = apply_fn(t, aux, rng, xx, training=True)
            logp = jax.nn.log_softmax(out.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1)), new_aux

        (L, new_aux), grads = jax.value_and_grad(loss_of, has_aux=True)(tr)
        new_moms = tuple(0.9 * v + g.astype(jnp.float32)
                         for v, g in zip(moms, grads))
        new_masters = tuple(m - 0.1 * v for m, v in zip(masters, new_moms))
        return new_masters, new_moms, new_aux, L

    state = [masters, moms, aux_raws]

    def step_once():
        m, v, a, L = step(state[0], state[1], state[2], x)
        state[0], state[1], state[2] = m, v, a
        return L

    return B / time_steps(step_once, lambda L: float(jnp.asarray(L)))


def scan_variant(B, K=8, reps=4):
    """K train steps CHAINED inside ONE jit (lax.scan over the full
    train state): pure on-chip step time, no per-dispatch host cost —
    the difference vs `purejax` isolates the dispatch overhead per
    step."""
    from jax import lax

    from incubator_mxnet_tpu.gluon.block import functionalize

    net = _build_net(B)
    apply_fn, train_raws, aux_raws = functionalize(net)
    rng = jax.random.PRNGKey(0)
    y = jnp.zeros((B,), jnp.int32)
    x = jnp.ones((B, 3, 224, 224), jnp.bfloat16)

    masters = tuple(w.astype(jnp.float32) for w in train_raws)
    moms = tuple(jnp.zeros_like(m) for m in masters)

    @jax.jit
    def multi(masters, moms, aux, xx):
        def body(carry, _):
            m, v, a = carry
            tr = tuple(w.astype(jnp.bfloat16) for w in m)

            def loss_of(t):
                out, new_aux = apply_fn(t, a, rng, xx, training=True)
                logp = jax.nn.log_softmax(out.astype(jnp.float32))
                return (-jnp.mean(jnp.take_along_axis(logp, y[:, None], 1)),
                        new_aux)

            (L, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(tr)
            nv = tuple(0.9 * vv + g.astype(jnp.float32)
                       for vv, g in zip(v, grads))
            nm = tuple(mm - 0.1 * vv for mm, vv in zip(m, nv))
            return (nm, nv, new_aux), L

        (m, v, a), Ls = lax.scan(body, (masters, moms, aux), None, length=K)
        return m, v, a, Ls[-1]

    out = multi(masters, moms, aux_raws, x)
    float(jnp.asarray(out[-1]))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = multi(masters, moms, aux_raws, x)
    float(jnp.asarray(out[-1]))
    dt = (time.perf_counter() - t0) / (reps * K)
    return B / dt


def gluon_chain_variant(B, K=8):
    """The PRODUCT path with multi-step chaining: the same public
    record→backward→step loop, Trainer(chain_steps=K) — K steps per
    dispatched program (r4 VERDICT item 1)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.gluon import Trainer
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    net = _build_net(B)
    net.hybridize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9,
                  "multi_precision": True}, keep_grads=False,
                 chain_steps=K)
    x = NDArray(jnp.ones((B, 3, 224, 224), jnp.bfloat16))
    y = NDArray(jnp.zeros((B,), jnp.int32))

    def step_once():
        with autograd.record():
            L = loss_fn(net(x), y)
        L.backward()
        tr.step(B)
        return L

    # time whole chains: n must be a multiple of K so the fetch at the
    # timing boundary lands right after a flush
    return B / time_steps(step_once,
                          lambda L: float(L.asnumpy().ravel()[0]),
                          n=3 * K, warm=2 * K + 1)


def main():
    which = sys.argv[1:] or ["gluon", "purejax"]
    B = int(os.environ.get("RESNET_PROBE_BS", "128"))
    for w in which:
        fn = {"gluon": gluon_variant, "purejax": purejax_variant,
              "scan": scan_variant,
              "gluon_chain": gluon_chain_variant}[w]
        print(f"{w} bf16 BS{B}: {fn(B):.0f} img/s", flush=True)


if __name__ == "__main__":
    main()
