"""Pallas kernels in programs that span a device mesh.

A Mosaic (Pallas TPU) kernel cannot be partitioned automatically.  The
TPU compiler refuses a GSPMD program that holds one ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a
shard_map"), and `custom_partitioning` is no way round it on this
installation: jax 0.9.0 never registers its callback with libtpu, so a
multi-device TPU program holding such an op dies with "Custom emitter
for CustomSPMDPartitioning not found" (four v5e chips, PR 24).

So the kernels of this package are emitted per shard, by one rule on
every backend.  Whoever traces a program over a mesh puts that mesh in
the trace context (`mesh_context`: the Trainer around its step program,
a block that `shard_params` placed around its forward), and every kernel
call goes through `per_shard`, which wraps it in a `shard_map` over the
mesh axes a partitioner still owns at that point of the trace; `split`
deals those axes over the dims the kernel is parallel in.  With no mesh
in context — a one-device program, or a caller's own fully manual
`shard_map` — the kernel is called as it is.  A program traced over
sharded arrays with no mesh in context gets the compiler's refusal
above, which says what to do; nothing falls back to a reference.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

__all__ = ["mesh_context", "auto_axes", "split", "n_shards", "shard_index",
           "per_shard"]


def mesh_context(mesh):
    """Context manager for trace time: kernels traced inside see
    ``mesh``.  The outermost context wins (a block's forward traced
    inside the Trainer's step, or inside a manual region, keeps what it
    finds), and a one-device mesh needs none."""
    if mesh is None or mesh.size == 1 \
            or not jax.sharding.get_abstract_mesh().empty:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def auto_axes() -> dict:
    """{axis: size} of the context mesh's axes that are not manual here."""
    mesh = jax.sharding.get_abstract_mesh()
    return {name: size for name, size in mesh.shape.items()
            if name not in mesh.manual_axes}


def split(extents, quanta=None) -> tuple:
    """One `PartitionSpec` entry per dim of ``extents``: mesh axis k goes
    to dim k if that dim's extent per shard stays a multiple of its
    quantum, else to the first dim that can take it, else to none (the
    kernel then repeats its work along that axis)."""
    quanta = quanta or (1,) * len(extents)
    left, took = list(extents), [() for _ in extents]
    axes = [(n, s) for n, s in auto_axes().items() if s > 1]
    for k, (name, size) in enumerate(axes):
        first = min(k, len(extents) - 1)
        for d in [first] + [d for d in range(len(extents)) if d != first]:
            # tpulint: disable-next=TPU004 -- extents are static shapes
            if left[d] % (size * quanta[d]) == 0:
                left[d] //= size
                took[d] += (name,)
                break
    return tuple(t if len(t) > 1 else t[0] if t else None for t in took)


def _axes_of(entry) -> tuple:
    return entry if isinstance(entry, tuple) else (entry,) if entry else ()


def n_shards(entry) -> int:
    """How many shards one entry of `split` makes (ask outside the
    `per_shard` body)."""
    sizes = auto_axes()
    return math.prod(sizes[ax] for ax in _axes_of(entry))


def shard_index(entry):
    """Inside a `per_shard` body: this shard's position along one entry
    of `split` (0 for None)."""
    idx = jnp.int32(0)
    for ax in _axes_of(entry):
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def per_shard(fn, in_specs, out_specs):
    """``fn`` itself when no partitioner owns a mesh axis here, else
    ``fn`` under a `shard_map` that makes every such axis manual."""
    axes = auto_axes()
    if not axes:
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=frozenset(axes), check_vma=False)
