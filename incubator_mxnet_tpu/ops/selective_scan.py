"""The selective scan of a Mamba-1 mixer over the serving engine's
recurrent state.

For ``N`` sequences of ``T`` tokens, channel ``c`` and state element ``n``::

    s_t[n, c] = exp(dt_t[c] * A[n, c]) * s_{t-1}[n, c] + dt_t[c] * u_t[c] * B_t[n]
    y_t[c]    = (sum_n s_t[n, c] * C_t[n] + D[c] * u_t[c]) * silu(z_t[c])

The state is one float32 array ``(S, d_state, d_inner)`` for the engine's
``S`` lanes: channels on the device's lanes, the state's elements on its
sublanes.  The engine donates it; both forms below return it in the same
buffer with only the rows of the sequences at hand rewritten.

* ``rows=None`` — the decode step's form: sequence ``i`` is lane ``i``
  (``N == S``), usually one token each.  A lane whose ``dt`` is 0 keeps its
  state to the bit (``exp(0) * s + 0``): that is how the step leaves
  inactive lanes alone.
* ``rows`` (N,) — the prefill chunk's form: sequence ``i`` continues lane
  ``rows[i]``'s state, from zero where ``reset[i]`` (a prompt's first
  chunk).  The other lanes' rows are not touched at all.

``impl="pallas"``: a kernel named ``selective_scan``, grid (sequence
blocks, channel blocks).  A block's state stays in vector registers while
the tokens are walked, sixteen at a time, so a token costs a handful of
vector operations per state register and nothing crosses HBM but the
streams ``u, dt, z, y`` (and ``B, C``, which enter spread over 128 lanes:
a ``(d_state, 128)`` tile a token is what the vector unit multiplies a
state register by).  ``impl="xla"``: the same recurrence as a `lax.scan`
over tokens; the CPU's path and the kernel's reference.  Both agree to
float32 roundoff.  Under a mesh the step's form runs per shard of the
lanes (`ops/mosaic.py`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mosaic

__all__ = ["selective_scan", "default_impl"]

_LANES = 128
_TOKENS = 16          # tokens a loop iteration walks: one bf16 tile's rows


def default_impl(platform: Optional[str] = None) -> str:
    platform = platform or jax.default_backend()
    return "pallas" if platform == "tpu" else "xla"


# --- XLA -------------------------------------------------------------------- #
def _scan_xla(u, dt, z, Bm, Cm, A, D, state, rows, reset):
    f32 = jnp.float32
    N, T, _ = u.shape
    uf = u.astype(f32)
    s0 = state if rows is None else state[rows]
    if reset is not None:
        s0 = jnp.where(reset[:, None, None] != 0, 0.0, s0)

    def step(s, x):
        u_t, dt_t, b_t, c_t = x                 # (N, Di) (N, Di) (N, Ds) (N, Ds)
        s = jnp.exp(dt_t[:, None, :] * A) * s \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    if T == 1:
        s, y = step(s0, (uf[:, 0], dt[:, 0], Bm[:, 0], Cm[:, 0]))
        y = y[:, None]
    else:
        s, y = jax.lax.scan(step, s0, tuple(
            jnp.swapaxes(a, 0, 1) for a in (uf, dt, Bm, Cm)))
        y = jnp.swapaxes(y, 0, 1)
    y = ((y + D * uf) * jax.nn.silu(z.astype(f32))).astype(u.dtype)
    return y, (s if rows is None else state.at[rows].set(s))


# --- Pallas ----------------------------------------------------------------- #
def _scan_kernel(rows_ref, reset_ref, u_ref, dt_ref, z_ref, b_ref, c_ref,
                 a_ref, d_ref, s_in_ref, y_ref, s_out_ref, *, nb, tg):
    """One grid step: ``nb`` sequences, one block of channels, every
    token.  The state block ``(d_state, bc)`` is the loop's carry."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    T, bc = u_ref.shape[1:]
    reps = bc // b_ref.shape[-1]
    A = a_ref[...]                                  # (Ds, bc)
    Dv = d_ref[...]                                 # (1, bc)
    n0 = pl.program_id(0) * nb

    def spread(tile):
        """(Ds, lanes), every lane the same -> (Ds, bc)."""
        return tile if reps == 1 else jnp.concatenate([tile] * reps, axis=1)

    def walk(i, g, s):
        """Tokens g*tg .. g*tg+tg-1 of sequence i from state s."""
        at = pl.ds(0, tg) if T == tg else pl.ds(pl.multiple_of(g * tg, tg),
                                                tg)
        u = u_ref[i, at, :].astype(f32)             # (tg, bc)
        dt = dt_ref[i, at, :]
        z = z_ref[i, at, :].astype(f32)
        ys = []
        for j in range(tg):
            u_t, dt_t = u[j:j + 1], dt[j:j + 1]     # (1, bc)
            s = jnp.exp(dt_t * A) * s \
                + (dt_t * u_t) * spread(b_ref[i, g * tg + j])
            ys.append(jnp.sum(s * spread(c_ref[i, g * tg + j]), axis=0,
                              keepdims=True) + Dv * u_t)
        y = ys[0] if tg == 1 else jnp.concatenate(ys, axis=0)
        y_ref[i, at, :] = (y * (z * jax.nn.sigmoid(z))).astype(y_ref.dtype)
        return s

    for i in range(nb):
        s0 = jnp.where(reset_ref[n0 + i] != 0, 0.0, s_in_ref[i])
        if T == tg:
            s = walk(i, 0, s0)
        else:
            s = jax.lax.fori_loop(0, T // tg,
                                  lambda g, s, i=i: walk(i, g, s), s0)
        s_out_ref[i] = s


def _seq_block(N, T, by_rows: bool) -> int:
    """Sequences a grid step takes: one-token sequences that are their
    own lanes go eight at a time, so that a decode step's grid is short."""
    # tpulint: disable-next=TPU004 -- N and T are static shape ints, by_rows a Python bool
    if by_rows or T != 1:
        return 1
    return next(n for n in (8, 4, 2, 1) if N % n == 0)


@functools.partial(jax.jit, static_argnames=("nb", "interpret"))
def _scan_core(u, dt, z, Bm, Cm, A, D, state, rows, reset, nb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, T, Di = u.shape
    Ds = A.shape[0]
    # the carry is Ds x bc floats and has to stay in the register file
    bc = next(b for b in (1280, 1024, 512, 256, _LANES, Di) if Di % b == 0)
    tg = _TOKENS if T % _TOKENS == 0 else T
    lanes = _LANES if bc % _LANES == 0 else bc      # (a test's narrow mixer)
    spread = (N, T, Ds, lanes)
    Bb = jnp.broadcast_to(Bm[..., None], spread)
    Cb = jnp.broadcast_to(Cm[..., None], spread)
    seq = pl.BlockSpec((nb, T, bc), lambda n, c, rows, reset: (n, 0, c))
    bcs = pl.BlockSpec((nb, T, Ds, lanes),
                       lambda n, c, rows, reset: (n, 0, 0, 0))
    chan = pl.BlockSpec((Ds, bc), lambda n, c, rows, reset: (0, c))
    dvec = pl.BlockSpec((1, bc), lambda n, c, rows, reset: (0, c))
    st = pl.BlockSpec((nb, Ds, bc), lambda n, c, rows, reset: (rows[n], 0, c))
    y, new_state = pl.pallas_call(
        functools.partial(_scan_kernel, nb=nb, tg=tg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // nb, Di // bc),
            in_specs=[seq, seq, seq, bcs, bcs, chan, dvec, st],
            out_specs=[seq, st]),
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={9: 1},      # the state, in place
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret, name="selective_scan",
    )(rows, reset, u, dt, z, Bb, Cb, A, D.reshape(1, Di), state)
    return y, new_state


def selective_scan(u, dt, z, Bm, Cm, A, D, state, *, rows=None, reset=None,
                   impl: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """``u, z`` (N, T, Di) activations; ``dt`` (N, T, Di), ``Bm, Cm``
    (N, T, Ds), ``A`` (Ds, Di), ``D`` (Di,) float32; ``state`` (S, Ds, Di)
    float32.  Returns ``(y (N, T, Di) in u's dtype, new state)``.

    ``rows`` (N,) int32 names the state row of each sequence (None: row
    ``i``, with ``N == S``); ``reset`` (N,) marks sequences that begin
    from zero state.  ``impl``: "pallas" (the kernel; interpret mode on the
    CPU), "xla", or None for `default_impl`.  The kernel takes ``T == 1``
    or a multiple of 16; other lengths go the XLA way.
    """
    impl = impl or default_impl()
    N, T, _ = u.shape
    if impl == "pallas" and T != 1 and T % _TOKENS:
        impl = "xla"
    if impl == "xla":
        return _scan_xla(u, dt, z, Bm, Cm, A, D, state, rows, reset)
    if impl != "pallas":
        raise ValueError(f"selective_scan impl {impl!r} (pallas|xla)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    nb = _seq_block(N, T, rows is not None)
    # the step's form is parallel in its lanes: per shard of them under a
    # mesh; a chunk continues one lane of a state every shard holds whole
    lanes = mosaic.split((N,), (nb,))[0] if rows is None else None
    if rows is None:
        n_local = N // mosaic.n_shards(lanes)
        rows = jnp.arange(n_local // nb, dtype=jnp.int32)
    if reset is None:
        reset = jnp.zeros((N,), jnp.int32)
    seq = P(lanes)
    core = functools.partial(_scan_core, nb=nb, interpret=interpret)
    in_specs = (seq,) * 5 + (P(), P(), seq, P(), seq)
    return mosaic.per_shard(core, in_specs, (seq, seq))(
        u, dt, z, Bm, Cm, A, D, state, rows, reset.astype(jnp.int32))
