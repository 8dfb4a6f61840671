"""Fused softmax cross-entropy: max, logsumexp and the label pick in one
pass over the vocab axis (the reference's softmax_with_cross_entropy fused
op, operators/softmax_with_cross_entropy_op.cc), with the stock-jnp loss it
is held against. Which body a call runs is the registry's choice
(``ops/pallas/registry.py``).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["softmax_cross_entropy"]


def _xent_kernel(logits_ref, labels_ref, loss_ref, lse_ref):
    x = logits_ref[:].astype(jnp.float32)                  # [bn, V]
    lab = labels_ref[:, 0]                                 # [bn]
    m = jnp.max(x, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m[:, None]), axis=-1))
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    picked = jnp.sum(jnp.where(cols == lab[:, None], x, 0.0), axis=-1)
    loss_ref[:, 0] = lse - picked
    lse_ref[:, 0] = lse


def _xent_fwd_call(logits2, labels1, block_n, interpret):
    n, v = logits2.shape
    block_n = min(block_n, n)
    grid = (pl.cdiv(n, block_n),)
    # 1-D vectors ride as [n, 1] blocks (bn, 1): Mosaic's layout for a
    # bare s32/f32[n] is lane-tiled T(1024) and rejects (bn,) blocks
    loss, lse = pl.pallas_call(
        _xent_kernel,
        grid=grid,
        in_specs=[
            _vmem_spec((block_n, v), lambda i: (i, 0)),
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="softmax_xent_fwd",
    )(logits2, labels1[:, None])
    return loss[:, 0], lse[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _softmax_xent(logits2, labels1, block_n, interpret):
    loss, _ = _xent_fwd_call(logits2, labels1, block_n, interpret)
    return loss


def _softmax_xent_fwd(logits2, labels1, block_n, interpret):
    loss, lse = _xent_fwd_call(logits2, labels1, block_n, interpret)
    return loss, (logits2, labels1, lse)


def _softmax_xent_bwd(block_n, interpret, res, dloss):
    logits2, labels1, lse = res
    x = logits2.astype(jnp.float32)
    p = jnp.exp(x - lse[:, None])
    onehot = jax.nn.one_hot(labels1, x.shape[-1], dtype=jnp.float32)
    dx = (p - onehot) * dloss[:, None]
    return dx.astype(logits2.dtype), None


_softmax_xent.defvjp(_softmax_xent_fwd, _softmax_xent_bwd)


def _xent_reference(logits, labels, block_n=128):
    """Stock-jnp softmax cross-entropy (fp32 max/logsumexp/pick)."""
    logits = jnp.asarray(logits)
    labels = jnp.asarray(labels, jnp.int32)
    x = logits.astype(jnp.float32)
    m = jnp.max(x, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m[..., None]), axis=-1))
    picked = jnp.take_along_axis(x, labels[..., None],
                                 axis=-1)[..., 0]
    return lse - picked


def _softmax_xent_pallas(logits, labels, block_n=128, interpret=False):
    logits = jnp.asarray(logits)
    labels = jnp.asarray(labels, jnp.int32)
    v = logits.shape[-1]
    lead = logits.shape[:-1]
    logits2 = logits.reshape(-1, v)
    labels1 = labels.reshape(-1)
    n = logits2.shape[0]
    # cap the row block so one (block_n, V) fp32 tile (double-buffered)
    # stays well under the ~16MB VMEM budget even at LM vocab sizes
    vmem_rows = max(8, (4 << 20) // max(4 * v, 1) // 8 * 8)
    block_n = min(block_n, vmem_rows, n)
    pad = (-n) % block_n
    if pad:
        logits2 = jnp.pad(logits2, ((0, pad), (0, 0)))
        labels1 = jnp.pad(labels1, (0, pad))
    loss = _softmax_xent(logits2, labels1, int(block_n), bool(interpret))
    if pad:
        loss = loss[:n]
    return loss.reshape(lead)


def softmax_cross_entropy(logits, labels, block_n=128):
    """Fused per-example softmax cross-entropy.

    logits: [..., V]; labels: [...] int. Returns [...] fp32 losses.
    One pass computes max, logsumexp, and the label pick (parity:
    operators/softmax_with_cross_entropy_op.cc fused op).
    """
    return _registry.dispatch("softmax_cross_entropy", logits, labels,
                              block_n=block_n)


_registry.register_kernel(
    "softmax_cross_entropy", _xent_reference, _softmax_xent_pallas,
    doc="fused max/logsumexp/pick over the vocab axis")
