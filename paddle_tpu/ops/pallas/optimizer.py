"""Fused optimizer-update kernels (SGD / momentum / Adam).

The stock apply path lowers each update rule as a chain of elementwise
jnp ops — every intermediate (momentum*v, (1-b1)*g, sqrt(m2)+eps, ...)
is a separate HBM round trip. The Pallas bodies stream param + grad +
slots through VMEM once per 256x128 block and write param + slots back
in the same pass.

Reference bodies mirror the exact ``Optimizer._update`` math in
optimizer.py (sgd_op.cc / momentum_op.cc / adam_op.cc rules); the
wrappers in optimizer.py pin output dtypes to the stock ones via
``jax.eval_shape`` over the reference, so mixed-precision params (bf16
p, f32 lr) keep their historical promotion behavior bit-for-bit."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import registry as _registry

__all__ = []

_LANES = 128


def _vmem_spec(*args, **kwargs):
    kwargs.setdefault("memory_space", pltpu.VMEM)
    return pl.BlockSpec(*args, **kwargs)


def _round_up(v, m):
    return -(-v // m) * m


def _ew_call(name, kernel, arrays, scalars, n_out, interpret):
    """Run an elementwise kernel over same-size tensors: flatten to
    [rows, 128] f32 blocks, ride the scalars in as one (1, ns) block,
    return n_out f32 arrays of the original flat size. ``name`` is the
    kernel's name in the compiled program and in a profile."""
    size = arrays[0].size
    rows = -(-size // _LANES)
    br = min(256, _round_up(rows, 8))
    rows_p = _round_up(rows, br)
    pad = rows_p * _LANES - size
    padded = [
        jnp.pad(jnp.asarray(a).reshape(-1).astype(jnp.float32), (0, pad))
        .reshape(rows_p, _LANES) for a in arrays
    ]
    sc = jnp.stack([jnp.asarray(s, jnp.float32).reshape(()) for s in
                    scalars]).reshape(1, -1)
    ns = sc.shape[1]
    outs = pl.pallas_call(
        kernel,
        grid=(rows_p // br,),
        in_specs=[_vmem_spec((br, _LANES), lambda i: (i, 0))
                  for _ in padded]
        + [_vmem_spec((1, ns), lambda i: (0, 0))],
        out_specs=[_vmem_spec((br, _LANES), lambda i: (i, 0))] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows_p, _LANES), jnp.float32)]
        * n_out,
        interpret=interpret,
        name=name,
    )(*padded, sc)
    if n_out == 1:
        outs = [outs] if not isinstance(outs, (list, tuple)) else outs
    return [o.reshape(-1)[:size] for o in outs]


# -- SGD -------------------------------------------------------------------

def fused_sgd_reference(p, g, lr, interpret=None):
    return p - lr * g


def _sgd_kernel(p_ref, g_ref, sc_ref, o_ref):
    o_ref[...] = p_ref[...] - sc_ref[0, 0] * g_ref[...]


def fused_sgd_pallas(p, g, lr, interpret=False):
    shape = jnp.shape(p)
    (out,) = _ew_call("fused_sgd", _sgd_kernel, [p, g], [lr], 1,
                      bool(interpret))
    return out.reshape(shape)


# -- momentum --------------------------------------------------------------

def fused_momentum_reference(p, g, v, lr, momentum=0.9,
                             use_nesterov=False, interpret=None):
    v_new = momentum * v + g
    if use_nesterov:
        new_p = p - lr * (g + momentum * v_new)
    else:
        new_p = p - lr * v_new
    return new_p, v_new


def _momentum_kernel(p_ref, g_ref, v_ref, sc_ref, po_ref, vo_ref, *,
                     momentum, nesterov):
    lr = sc_ref[0, 0]
    g = g_ref[...]
    v = momentum * v_ref[...] + g
    if nesterov:
        po_ref[...] = p_ref[...] - lr * (g + momentum * v)
    else:
        po_ref[...] = p_ref[...] - lr * v
    vo_ref[...] = v


def fused_momentum_pallas(p, g, v, lr, momentum=0.9, use_nesterov=False,
                          interpret=False):
    shape = jnp.shape(p)
    kernel = functools.partial(_momentum_kernel, momentum=float(momentum),
                               nesterov=bool(use_nesterov))
    new_p, new_v = _ew_call("fused_momentum", kernel, [p, g, v], [lr], 2,
                             bool(interpret))
    return new_p.reshape(shape), new_v.reshape(shape)


# -- Adam ------------------------------------------------------------------

def fused_adam_reference(p, g, m1, m2, lr, t, beta1=0.9, beta2=0.999,
                         epsilon=1e-8, interpret=None):
    t = jnp.asarray(t).astype(jnp.float32)
    m1n = beta1 * m1 + (1 - beta1) * g
    m2n = beta2 * m2 + (1 - beta2) * jnp.square(g)
    bc = jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    new_p = p - lr * bc * m1n / (jnp.sqrt(m2n) + epsilon)
    return new_p, m1n, m2n


def _adam_kernel(p_ref, g_ref, m1_ref, m2_ref, sc_ref, po_ref, m1o_ref,
                 m2o_ref, *, beta1, beta2, epsilon):
    lr_bc = sc_ref[0, 0]  # lr * bias-correction, folded outside (scalars)
    g = g_ref[...]
    m1 = beta1 * m1_ref[...] + (1 - beta1) * g
    m2 = beta2 * m2_ref[...] + (1 - beta2) * g * g
    po_ref[...] = p_ref[...] - lr_bc * m1 / (jnp.sqrt(m2) + epsilon)
    m1o_ref[...] = m1
    m2o_ref[...] = m2


def fused_adam_pallas(p, g, m1, m2, lr, t, beta1=0.9, beta2=0.999,
                      epsilon=1e-8, interpret=False):
    shape = jnp.shape(p)
    t32 = jnp.asarray(t).astype(jnp.float32)
    # bias correction is pure scalar work — fold into lr on the host side
    bc = jnp.sqrt(1 - beta2 ** t32) / (1 - beta1 ** t32)
    kernel = functools.partial(_adam_kernel, beta1=float(beta1),
                               beta2=float(beta2), epsilon=float(epsilon))
    new_p, m1n, m2n = _ew_call("fused_adam", kernel, [p, g, m1, m2],
                               [lr * bc], 3, bool(interpret))
    return new_p.reshape(shape), m1n.reshape(shape), m2n.reshape(shape)


_registry.register_kernel(
    "fused_sgd", fused_sgd_reference, fused_sgd_pallas,
    doc="p - lr*g, one VMEM pass")
_registry.register_kernel(
    "fused_momentum", fused_momentum_reference, fused_momentum_pallas,
    doc="momentum/nesterov update + velocity slot, one VMEM pass")
_registry.register_kernel(
    "fused_adam", fused_adam_reference, fused_adam_pallas,
    doc="bias-corrected Adam update + both moment slots, one VMEM pass")
