"""Fused layer norm: mean, variance, normalise and affine in one VMEM pass
(parity: operators/layer_norm_op.cc; the jit/ layernorm kernel), with the
stock-jnp layer norm it is held against. Which body a call runs is the
registry's choice (``ops/pallas/registry.py``).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["fused_layer_norm"]


def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y = xc * rstd * g_ref[:].astype(jnp.float32) + b_ref[:].astype(
        jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:, 0] = mu[:, 0]
    rstd_ref[:, 0] = rstd[:, 0]


def _ln_fwd(x2, g, b, eps, block_n, interpret):
    n, hdim = x2.shape
    block_n = min(block_n, n)
    grid = (pl.cdiv(n, block_n),)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            _vmem_spec((block_n, hdim), lambda i: (i, 0)),
            _vmem_spec((hdim,), lambda i: (0,)),
            _vmem_spec((hdim,), lambda i: (0,)),
        ],
        out_specs=[
            _vmem_spec((block_n, hdim), lambda i: (i, 0)),
            # stats ride as [n, 1] (bn, 1) blocks: Mosaic's layout for a
            # bare f32[n] is lane-tiled T(1024) and rejects (bn,) blocks
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm_fwd",
    )(x2, g, b)
    return y, mu[:, 0], rstd[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_layer_norm(x2, g, b, eps, block_n, interpret):
    y, _, _ = _ln_fwd(x2, g, b, eps, block_n, interpret)
    return y


def _fused_ln_fwd(x2, g, b, eps, block_n, interpret):
    y, mu, rstd = _ln_fwd(x2, g, b, eps, block_n, interpret)
    return y, (x2, g, mu, rstd)


def _fused_ln_bwd(eps, block_n, interpret, res, dy):
    x2, g, mu, rstd = res
    x32 = x2.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    xhat = (x32 - mu[:, None]) * rstd[:, None]
    gf = g.astype(jnp.float32)
    dg = jnp.sum(dy32 * xhat, axis=0)
    db = jnp.sum(dy32, axis=0)
    wdy = dy32 * gf
    c1 = jnp.mean(wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx = (wdy - c1 - xhat * c2) * rstd[:, None]
    return dx.astype(x2.dtype), dg.astype(g.dtype), db.astype(g.dtype)


_fused_layer_norm.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def _layer_norm_reference(x, gamma, beta, eps=1e-12, block_n=256):
    """Stock-jnp layer norm, bit-identical to models/bert._layer_norm's
    historical inline math (fp32 stats, x.dtype out)."""
    x = jnp.asarray(x)
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps) \
        * jnp.asarray(gamma).astype(jnp.float32) \
        + jnp.asarray(beta).astype(jnp.float32)
    return y.astype(x.dtype)


def _fused_layer_norm_pallas(x, gamma, beta, eps=1e-12, block_n=256,
                             interpret=False):
    x = jnp.asarray(x)
    shape = x.shape
    hdim = shape[-1]
    x2 = x.reshape(-1, hdim)
    n = x2.shape[0]
    block_n = min(block_n, n)
    pad = (-n) % block_n
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = _fused_layer_norm(x2, jnp.asarray(gamma), jnp.asarray(beta),
                          float(eps), int(block_n), bool(interpret))
    if pad:
        y = y[:n]
    return y.reshape(shape)


def fused_layer_norm(x, gamma, beta, eps=1e-12, block_n=256):
    """LayerNorm over the last axis in a single VMEM pass.

    x: [..., H]; gamma/beta: [H]. Stats in fp32, output in x.dtype
    (parity: operators/layer_norm_op.cc; jit/ layernorm kernel).
    """
    return _registry.dispatch("fused_layer_norm", x, gamma, beta, eps=eps,
                              block_n=block_n)


_registry.register_kernel(
    "fused_layer_norm", _layer_norm_reference, _fused_layer_norm_pallas,
    doc="one-VMEM-pass layer norm (fp32 stats)")
