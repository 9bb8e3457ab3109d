"""What every plain reference shares: a matmul whose precision can be
lowered (the control of `correct`), LayerNorm, tanh-GELU.  Plain
`jax.numpy` in float32; imports nothing of the program.

`prec`:
  "fp32"  operands as given, products at matmul precision "highest"
          (on a TPU a float32 matmul is otherwise rounded to bf16 passes);
  "bf16"  both operands rounded to bfloat16 — what the configurations
          state, kept to study the program's own distance;
  "fp8"   both operands rounded to 4 significant bits (e4m3's mantissa;
          range not clipped: nothing here leaves it) — the precision below
          the stated one, the step a later PR would be tempted by.
A lowered precision rounds what every operation stores, not only the matmul
operands: the outputs of matmuls, LayerNorms, GELUs and residual sums too,
as a program does that keeps its activations in that type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _round_sig(x, bits: int):
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * (1 << bits)) / (1 << bits), e)


def lower(x, prec: str):
    if prec == "fp32":
        return x
    if prec == "bf16":
        # by arithmetic, not by a cast there and back: on a TPU XLA removes
        # such a pair of converts and leaves float32 (my chip run, PR 27)
        q = _round_sig(x, 8)
    elif prec == "fp8":
        q = _round_sig(x, 4)
    else:
        raise ValueError(f"unknown precision {prec!r}")
    return q


def einsum(spec: str, a, b, prec: str):
    return jnp.einsum(spec, lower(a, prec), lower(b, prec), precision=HI)


def dense(x, w, b, prec: str):
    """x (..., in) @ w (out, in)^T + b."""
    return lower(einsum("...i,oi->...o", x, w, prec) + b, prec)


def add(a, b, prec: str):
    return lower(a + b, prec)


def layer_norm(x, g, b, prec: str, eps: float = 1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return lower((x - mu) * jax.lax.rsqrt(var + eps) * g + b, prec)


def gelu(x, prec: str):
    return lower(0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3))), prec)


def attention(q, k, v, prec: str):
    """(B, T, H, D) each; causal softmax(q k^T / sqrt(D)) v in float32."""
    D = q.shape[-1]
    s = einsum("bqhd,bkhd->bhqk", q, k, prec) / jnp.sqrt(jnp.float32(D))
    T = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return lower(einsum("bhqk,bkhd->bqhd", p, v, prec), prec)
