"""Weights from the seed, made by the benchmark and by nobody else.

A configuration's plain reference names its leaves and their shapes
(`param_shapes`), this module draws every leaf in ONE jitted call on the
device, and the driver hands the same arrays to the program (through the
configuration file's `param_map`) and to the reference.  They are
bfloat16 arrays, as the configurations hold their weights, so a program
that keeps them in bf16 and a reference that widens them to fp32 start
from identical numbers.

Matrices and embedding tables are N(0, 0.02) (the published initialiser of
both BERT and GPT-2); biases and LayerNorm shifts are N(0, 0.02) and
LayerNorm gains 1 + N(0, 0.02) instead of the published 0 and 1, so that a
path which drops a bias or a gain changes the result.
"""
from __future__ import annotations

import zlib


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole number up to 2**48 (the driver's seeds
    pass 2**31), split over two fold-ins so nothing overflows int32."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def make(seed: int, shapes: dict, std: float = 0.02) -> dict:
    """{leaf name: bfloat16 array}, one device program.
    A leaf whose name ends in `_g` is a LayerNorm gain (centred on 1)."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)

    def draw(key):
        out = {}
        for name in names:
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            x = std * jax.random.normal(k, tuple(shapes[name]), jnp.float32)
            if name.endswith("_g"):
                x = x + 1.0
            out[name] = x.astype(jnp.bfloat16)
        return out

    return jax.jit(draw)(seed_key(seed, 1))


def leaves(root, param_map: dict, w: dict) -> list:
    """[(reference leaf, layer index or None, program Parameter)]: the
    configuration's `param_map` (attribute paths from `root`, `{i}` for a
    stacked leaf's layer) walked once."""
    def resolve(path):
        obj = root
        for part in path.split("."):
            obj = getattr(obj, part)
        return obj

    out = []
    for name, path in param_map.items():
        if "{i}" in path:
            out += [(name, i, resolve(path.format(i=i)))
                    for i in range(w[name].shape[0])]
        else:
            out.append((name, None, resolve(path)))
    return out


def assign(leaf_list: list, w: dict):
    """Give the program its weights: a copy of its own for every
    parameter, because a trainer donates what the program holds."""
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    for name, i, p in leaf_list:
        p.set_data(NDArray(jnp.copy(w[name] if i is None else w[name][i])))
