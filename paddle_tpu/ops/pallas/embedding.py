"""Pallas body for the sparse embedding scatter-add.

``embedding_scatter_add`` — dst[ids] += updates, the segment-sum /
``.at[].add`` pattern behind merge_selected_rows, sparse SGD and the
NativeSparseTable apply path. The Pallas body reduces each
destination-row block with a one-hot [rows_block, n] @ [n, d] matmul —
duplicate indices are summed by the dot itself, so the result is
deterministic by construction (same property the stock segment_sum
gives, unlike loop-carried float adds). Differentiable via custom_vjp
(the backward is a stock-jnp gather, not a nested kernel).

The lookup itself (rows = table[ids]) has no Pallas body: a one-row
block breaks Mosaic's (8, 128) block rule, a one-row DMA its tile
alignment, and an 8-row-group body moves 8x the bytes in one grid step
per id. ``ops/nn.embedding`` takes ``jnp.take``."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import registry as _registry

__all__ = []


def _round_up(v, m):
    return -(-v // m) * m


# -- scatter-add -----------------------------------------------------------

def embedding_scatter_add_reference(dst, ids, updates):
    """Stock body: .at[].add — drops out-of-range ids (JAX default)."""
    return jnp.asarray(dst).at[jnp.asarray(ids)].add(jnp.asarray(updates))


def _scatter_kernel(dst_ref, ids_ref, upd_ref, o_ref, *, bh):
    i = pl.program_id(0)
    rows = i * bh + jax.lax.broadcasted_iota(jnp.int32, (bh, 1), 0)
    # [bh, n_pad] one-hot; padded ids are -1 so their column stays zero,
    # and (matching .at[].add semantics) out-of-range ids contribute nowhere
    onehot = (rows == ids_ref[...]).astype(jnp.float32)
    acc = dst_ref[...].astype(jnp.float32) + jax.lax.dot_general(
        onehot, upd_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _scatter_call(dst, ids, updates, interpret):
    h, d = dst.shape
    n = ids.shape[0]
    dp = _round_up(d, 128)
    n_pad = _round_up(max(n, 1), 128)
    bh = min(256, _round_up(h, 8))
    hp = _round_up(h, bh)
    if hp != h or dp != d:
        dst = jnp.pad(dst, ((0, hp - h), (0, dp - d)))
    ids32 = ids.astype(jnp.int32)
    if n_pad != n:
        ids32 = jnp.pad(ids32, (0, n_pad - n), constant_values=-1)
        updates = jnp.pad(updates, ((0, n_pad - n), (0, 0)))
    if dp != d:
        updates = jnp.pad(updates, ((0, 0), (0, dp - d)))
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, bh=bh),
        grid=(hp // bh,),
        in_specs=[
            pl.BlockSpec((bh, dp), lambda i: (i, 0)),
            pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
            pl.BlockSpec((n_pad, dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bh, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, dp), dst.dtype),
        interpret=interpret,
        name="embedding_scatter_add",
    )(dst, ids32.reshape(1, -1), updates)
    if hp != h or dp != d:
        out = out[:h, :d]
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _scatter_add(dst, ids, updates, interpret):
    return _scatter_call(dst, ids, updates, interpret)


def _scatter_fwd(dst, ids, updates, interpret):
    return _scatter_call(dst, ids, updates, interpret), ids


def _scatter_bwd(interpret, ids, dy):
    return dy, None, jnp.take(dy, jnp.asarray(ids), axis=0)


_scatter_add.defvjp(_scatter_fwd, _scatter_bwd)


def embedding_scatter_add_pallas(dst, ids, updates, interpret=False):
    """dst[ids] += updates via per-row-block one-hot matmul reduction."""
    dst = jnp.asarray(dst)
    ids = jnp.asarray(ids).reshape(-1)
    updates = jnp.asarray(updates)
    if ids.shape[0] == 0 or dst.ndim != 2 or updates.ndim != 2:
        return embedding_scatter_add_reference(dst, ids, updates)
    # the one-hot body holds the padded updates block whole in VMEM —
    # the shared registry budget guard decides (and counts) fallback
    n_pad = _round_up(ids.shape[0], 128)
    dp = _round_up(dst.shape[1], 128)
    if not _registry.within_vmem_budget("embedding_scatter_add",
                                        n_pad * dp):
        return embedding_scatter_add_reference(dst, ids, updates)
    return _scatter_add(dst, ids, updates, bool(interpret))


_registry.register_kernel(
    "embedding_scatter_add", embedding_scatter_add_reference,
    embedding_scatter_add_pallas,
    doc="dst[ids] += updates (one-hot matmul; duplicate-safe)")
