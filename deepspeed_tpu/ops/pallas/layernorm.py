"""Fused LayerNorm Pallas kernel (fwd + custom-vjp bwd).

Reference: csrc/transformer/normalize_kernels.cu (fused layer_norm fwd/bwd
with saved mean/rstd). XLA fuses LN chains well on its own; this kernel
exists for the very-wide-row regime (d_model ≥ 4096) where a single-pass
Welford + on-chip residency beats XLA's default fusion, and for parity with
the reference op surface.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256


from ._common import interpret_mode as _interpret


def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)                     # [R, D]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _ln_fwd(x2d, gamma, beta, eps):
    n, d = x2d.shape
    rows = min(BLOCK_ROWS, n)
    grid = (pl.cdiv(n, rows),)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        # per-row stats are [n, 1] columns: a 1-D f32[n] output tiled at
        # ``rows`` does not match XLA's 1-D layout (Mosaic refuses it)
        out_specs=(pl.BlockSpec((rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)),
        interpret=_interpret(),
    )(x2d, gamma, beta)
    return y, mean[:, 0], rstd[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, gamma, beta, eps=1e-5):
    """LayerNorm over the last dim. x: [..., D]."""
    shape = x.shape
    y, _, _ = _ln_fwd(x.reshape(-1, shape[-1]), gamma, beta, eps)
    return y.reshape(shape)


def _fused_ln_fwd(x, gamma, beta, eps):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    y, mean, rstd = _ln_fwd(x2d, gamma, beta, eps)
    return y.reshape(shape), (x2d, gamma, mean, rstd, shape)


def _fused_ln_bwd(eps, res, g):
    x2d, gamma, mean, rstd, shape = res
    d = shape[-1]
    g2d = g.reshape(-1, d).astype(jnp.float32)
    x32 = x2d.astype(jnp.float32)
    xhat = (x32 - mean[:, None]) * rstd[:, None]
    gg = g2d * gamma.astype(jnp.float32)[None, :]
    # standard LN backward (matches the reference's
    # cuApplyLayerNormGradient math)
    mean_gg = jnp.mean(gg, axis=-1, keepdims=True)
    mean_gg_xhat = jnp.mean(gg * xhat, axis=-1, keepdims=True)
    dx = (gg - mean_gg - xhat * mean_gg_xhat) * rstd[:, None]
    dgamma = jnp.sum(g2d * xhat, axis=0)
    dbeta = jnp.sum(g2d, axis=0)
    return (dx.astype(x2d.dtype).reshape(shape),
            dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype))


fused_layer_norm.defvjp(_fused_ln_fwd, _fused_ln_bwd)
