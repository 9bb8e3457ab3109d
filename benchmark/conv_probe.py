"""Per-shape conv strategy probe (ResNet-50 shapes, real TPU).

Compares, for each profiled ResNet-50 layer shape, the achieved TF/s of:
  conv_nchw   lax.conv_general_dilated, NCHW (current Convolution path)
  conv_nhwc   lax.conv_general_dilated, NHWC
  tap_nhwc    sum over k*k taps of (N*Ho*Wo, C) @ (C, O) matmuls on a
              padded NHWC input (implicit im2col — no patch matrix ever
              materializes; XLA differentiates each tap matmul into
              matmuls, so fwd AND bwd ride the MXU matmul emitter)
  im2col_nhwc concat the taps into (N,Ho,Wo,k*k*C) then ONE matmul

Methodology: every dispatched program pays a fixed host overhead, so
K iterations are CHAINED inside one jit via lax.scan
(output feeds back as input where shapes allow; otherwise the weight is
perturbed by sum(y)*1e-30 to defeat CSE) and the whole program is timed
once warm.  FLOPs = 2*N*Ho*Wo*O*C*k*k (fwd), 3x for fwd+bwd.
"""
import functools
import time

import jax
import jax.numpy as jnp
from jax import lax

K_FWD = 64   # chained iterations per fwd program
K_GRAD = 16  # grad chains keep K small: each iteration's residuals
             # live until its backward runs (~50 MB x K at C64 H56)

SHAPES = [
    # (name, N, C, H, O, k, stride)  square-channel shapes chain y->x
    ("3x3_C64_H56", 128, 64, 56, 64, 3, 1),
    ("3x3_C128_H28", 128, 128, 28, 128, 3, 1),
    ("3x3_C256_H14", 128, 256, 14, 256, 3, 1),
    ("3x3_C512_H7", 128, 512, 7, 512, 3, 1),
    ("1x1_C64_O256_H56", 128, 64, 56, 256, 1, 1),
    ("1x1_C1024_O256_H14", 128, 1024, 14, 256, 1, 1),
    ("7x7_C3_H224_s2", 128, 3, 224, 64, 7, 2),
]


def conv_xla(x, w, stride, pad, spec):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=[(pad, pad)] * 2,
        dimension_numbers=spec)


def tap_conv_nhwc(x, w, stride, pad):
    """x (N,H,W,C); w (k,k,C,O). Implicit-im2col tap matmuls."""
    k = w.shape[0]
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    H = x.shape[1]
    Ho = (H - k) // stride + 1
    acc = None
    for dy in range(k):
        for dx in range(k):
            xs = x[:, dy:dy + stride * (Ho - 1) + 1:stride,
                   dx:dx + stride * (Ho - 1) + 1:stride, :]
            t = jnp.dot(xs, w[dy, dx])
            acc = t if acc is None else acc + t
    return acc


def im2col_conv_nhwc(x, w, stride, pad):
    k = w.shape[0]
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    H = x.shape[1]
    Ho = (H - k) // stride + 1
    cols = [x[:, dy:dy + stride * (Ho - 1) + 1:stride,
              dx:dx + stride * (Ho - 1) + 1:stride, :]
            for dy in range(k) for dx in range(k)]
    patches = jnp.concatenate(cols, axis=-1)
    return jnp.dot(patches, w.reshape(-1, w.shape[-1]))


def chain_fwd(f, same_shape, k):
    """k conv calls in ONE program."""
    if same_shape:
        def run(x, w):
            def body(c, _):
                return f(c, w), ()
            y, _ = lax.scan(body, x, None, length=k)
            return y
    else:
        def run(x, w):
            def body(w, _):
                y = f(x, w)
                # defeat CSE/DCE: fold a negligible function of y into w
                return w + (jnp.sum(y) * 1e-30).astype(w.dtype), ()
            w, _ = lax.scan(body, w, None, length=k)
            return w
    return run


def chain_grad(f, same_shape, k):
    def loss(x, w):
        if same_shape:
            def body(c, _):
                return f(c, w), ()
            y, _ = lax.scan(body, x, None, length=k)
            return jnp.sum(y.astype(jnp.float32))
        else:
            def body(c, _):
                y = f(x, w + c)
                return (jnp.sum(y) * 1e-30).astype(w.dtype), ()
            c, _ = lax.scan(body, jnp.zeros((), w.dtype), None, length=k)
            return jnp.sum(c.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1))


def scalarized(fn):
    """Reduce the chain output to ONE scalar INSIDE the jit, so timing
    needs exactly one cheap host fetch (a fresh jnp.sum on the host
    side would compile a new program inside the timed region)."""
    def g(*args):
        out = fn(*args)
        return functools.reduce(
            jnp.add, [jnp.sum(l.astype(jnp.float32))
                      for l in jax.tree_util.tree_leaves(out)])
    return jax.jit(g)


def timeone(jfn, args, k, reps):
    """reps dispatches of a k-iteration chained program, ONE fetch at
    the end: the fetch amortizes over reps*k iterations (aim >= several
    hundred ms of real work)."""
    float(jfn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        y = jfn(*args)
    float(y)
    return (time.perf_counter() - t0) / (reps * k)


def main():
    key = jax.random.PRNGKey(0)
    print(f"devices: {jax.devices()}")
    for name, N, C, H, O, k, s in SHAPES:
        pad = (k - 1) // 2
        Ho = (H + 2 * pad - k) // s + 1
        flops_fwd = 2 * N * Ho * Ho * O * C * k * k
        same = (C == O and s == 1)
        x_nchw = jax.random.normal(key, (N, C, H, H), jnp.bfloat16) * 0.1
        x_nhwc = jnp.transpose(x_nchw, (0, 2, 3, 1))
        w_oikk = jax.random.normal(key, (O, C, k, k), jnp.bfloat16) * 0.05
        w_kkco = jnp.transpose(w_oikk, (2, 3, 1, 0))

        cands = {
            "conv_nchw": (lambda x, w: conv_xla(
                x, w, s, pad, ("NCHW", "OIHW", "NCHW")), x_nchw, w_oikk),
            "conv_nhwc": (lambda x, w: conv_xla(
                x, w, s, pad, ("NHWC", "HWIO", "NHWC")), x_nhwc, w_kkco),
            "tap_nhwc": (lambda x, w: tap_conv_nhwc(x, w, s, pad),
                         x_nhwc, w_kkco),
            "im2col_nhwc": (lambda x, w: im2col_conv_nhwc(x, w, s, pad),
                            x_nhwc, w_kkco),
        }
        print(f"\n== {name} (fwd {flops_fwd/1e9:.1f} GFLOP, "
              f"chain={'y->x' if same else 'w-perturb'}) ==", flush=True)
        for cname, (f, xx, ww) in cands.items():
            try:
                t = timeone(scalarized(chain_fwd(f, same, K_FWD)), (xx, ww), K_FWD, 12)
                tg = timeone(scalarized(chain_grad(f, same, K_GRAD)), (xx, ww), K_GRAD, 24)
                print(f"  {cname:12s} fwd {flops_fwd/t/1e12:7.1f} TF/s"
                      f"   fwd+bwd {3*flops_fwd/tg/1e12:7.1f} TF/s",
                      flush=True)
            except Exception as e:
                print(f"  {cname:12s} FAILED: {type(e).__name__}: {e}",
                      flush=True)


if __name__ == "__main__":
    main()
