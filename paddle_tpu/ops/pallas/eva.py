"""The EVA aggregation (``paddle_tpu/ops/eva.py``) as Mosaic kernels, a
forward and a backward one tied by a ``jax.custom_vjp``: ``flash_fwd_eva``
and ``flash_bwd_eva``. A sibling of ``flash_attention.py``, whose pieces it
imports (the lane-dense statistics' rows, the compiler parameters, the name
its outputs are kept under); the four calls of that module keep their bodies.

**Two sets of keys, one online softmax.** A program of the grid is a batch
row, a head and a block of ``block`` queries, which lies inside one window
(``block`` divides ``window``). Its key tiles are of two kinds:

- the *token* tiles of its own window up to the diagonal: the window's keys
  and values are the program's K/V block (the ``BlockSpec`` index is the
  window, so a window is fetched once for its query blocks), block r of the
  window visits the r tiles under the diagonal unmasked and the diagonal's
  tile masked;
- the *summary* tiles of earlier windows only: a tile is one window's ``window
  / chunk`` summaries, window w visits the first w of them, so nothing is
  masked at the window's grain either. A query of window 0 sees no summary.

One running maximum and sum over both kinds, float32 scores, statistics and
accumulators, the operands read as they are (bfloat16 in a model). No ``[S,
S]`` bias or mask is formed, nor one over the summaries.

**The backward is query-major too**: the same grid and the same two loops.
It rebuilds each score tile transposed, ``[keys, queries] = K Q^T``, from the
saved logsumexp (``flash_bwd``'s form: no product contracts over a tile's
rows), adds ``dV += p^T dO`` and ``dK += dz^T Q`` into float32 scratches that
live across the query blocks (the window's, written out when its last query
block is done; the summaries', ``[S / chunk, D]``, written out with the
head's last block) and keeps the block's ``dQ^T`` as the loops' carry. It
returns ``dq``, ``dk``, ``dv``, ``dksum`` and ``dvsum``.

**Kept for the backward** under ``flash_attention.KEPT``: the context and
the logsumexp, so ``models/blocks.recomputed`` keeps them and the forward
kernel runs once a layer.

A shape the blocks cannot tile (a window that is no whole blocks, a sequence
that is no whole windows, heads of different sizes for scores and values)
takes the reference body.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import eva as _reference
from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.flash_attention import (
    KEPT, _FLASH_BWD_COMPILER_PARAMS, _FLASH_COMPILER_PARAMS, _NEG_INF,
    _as_row)
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["eva_tiles_visited_pct", "KEPT"]

_F32 = jnp.float32
#: queries a program, and token keys a tile: the flash kernels' 512
_BLOCK = 512


def _nt(a, b):
    """``a b^T`` of two [rows, d] tiles in float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _nn(a, b):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _visits(iq, blocks_w):
    """(token tiles under the diagonal, summary tiles) query block ``iq``
    visits where a window is ``blocks_w`` blocks: the bounds both kernels'
    loops run over, and the ones ``eva_tiles_visited_pct`` counts. The
    diagonal's own tile comes on top of the first."""
    return iq % blocks_w, iq // blocks_w


def _rows(ref, j, size):
    """Tile ``j`` of ``size`` rows of a [1, 1, rows, D] block, in float32."""
    return ref[0, 0, pl.ds(pl.multiple_of(j * size, size), size), :] \
        .astype(_F32)


def _fwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref, *,
                sm_scale, block, per_window):
    """One (batch, head, query block) cell: the window's token tiles up to
    the diagonal, then the earlier windows' summary tiles, one online
    softmax over both. The logsumexp goes out as row ``iq`` of the head's
    [nq, block] block, which stays in VMEM across the query blocks."""
    iq = pl.program_id(2)
    under, earlier = _visits(iq, k_ref.shape[2] // block)
    q = q_ref[0, 0].astype(_F32) * sm_scale                    # [bq, d]

    def update(s, values, carry):
        m_prev, l_prev, acc = carry
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        return (m_new, l_prev * alpha + jnp.sum(p, axis=-1),
                acc * alpha[:, None] + _nn(p, values))

    def token(jk, carry, diagonal=False):
        s = _nt(q, _rows(k_ref, jk, block))                    # [bq, bk]
        if diagonal:    # the tile starts where the block does
            keep = lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                <= lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(keep, s, _NEG_INF)
        return update(s, _rows(v_ref, jk, block), carry)

    def summary(js, carry):
        return update(_nt(q, _rows(ks_ref, js, per_window)),
                      _rows(vs_ref, js, per_window), carry)

    stats = (jnp.full((block,), _NEG_INF, _F32), jnp.zeros((block,), _F32),
             jnp.zeros((block, v_ref.shape[-1]), _F32))
    stats = lax.fori_loop(0, under, token, stats)
    stats = token(under, stats, diagonal=True)
    m, l, acc = lax.fori_loop(0, earlier, summary, stats)
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, pl.ds(iq, 1), :] = _as_row(m + jnp.log(l))


def _bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, ks_ref,
                vs_ref, dq_ref, dk_ref, dv_ref, dks_ref, dvs_ref, dk_acc,
                dv_acc, dks_acc, dvs_acc, *, sm_scale, block, per_window):
    """One (batch, head, query block) cell, the forward's loops again: each
    tile's probabilities once from the saved logsumexp, built transposed,
    [keys, queries]; the keys' and the summaries' gradients summed into the
    float32 scratches, the block's ``dQ^T`` the loops' carry."""
    iq = pl.program_id(2)
    blocks_w = k_ref.shape[2] // block
    under, earlier = _visits(iq, blocks_w)

    @pl.when(iq == 0)
    def _():
        dks_acc[...] = jnp.zeros_like(dks_acc)
        dvs_acc[...] = jnp.zeros_like(dvs_acc)

    @pl.when(under == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qs = q_ref[0, 0].astype(_F32) * sm_scale                   # [bq, d]
    do = do_ref[0, 0].astype(_F32)
    lse_row = lse_ref[0, 0, pl.ds(iq, 1), :]                   # [1, bq]
    d_row = delta_ref[0, 0, pl.ds(iq, 1), :]

    def tile(keys, values, j, size, dk_to, dv_to, dqt, diagonal=False):
        rows = pl.ds(pl.multiple_of(j * size, size), size)
        k_blk, v_blk = _rows(keys, j, size), _rows(values, j, size)
        st = _nt(k_blk, qs)                                    # [bk, bq]
        if diagonal:
            keep = lax.broadcasted_iota(jnp.int32, st.shape, 0) \
                <= lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(keep, st, _NEG_INF)
        pt = jnp.exp(st - lse_row)
        dv_to[rows, :] += _nn(pt, do)
        dzt = pt * (_nt(v_blk, do) - d_row)
        dk_to[rows, :] += _nn(dzt, qs)
        return dqt + _nn(k_blk.T, dzt)                         # [d, bq]

    def token(jk, dqt, diagonal=False):
        return tile(k_ref, v_ref, jk, block, dk_acc, dv_acc, dqt, diagonal)

    def summary(js, dqt):
        return tile(ks_ref, vs_ref, js, per_window, dks_acc, dvs_acc, dqt)

    dqt = jnp.zeros((q_ref.shape[-1], block), _F32)
    dqt = lax.fori_loop(0, under, token, dqt)
    dqt = token(under, dqt, diagonal=True)
    dqt = lax.fori_loop(0, earlier, summary, dqt)
    dq_ref[0, 0] = (dqt.T * sm_scale).astype(dq_ref.dtype)

    @pl.when(under == blocks_w - 1)
    def _():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(iq == pl.num_programs(2) - 1)
    def _():
        dks_ref[0, 0] = dks_acc[...].astype(dks_ref.dtype)
        dvs_ref[0, 0] = dvs_acc[...].astype(dvs_ref.dtype)


def _specs(s, d, block, window, summaries):
    """The ``BlockSpec``s of a call (a query block, the window it lies in,
    the head's summaries whole, the head's statistics whole) and the
    summaries a window: a summary tile's rows."""
    blocks_w = window // block

    def at(rows, index):
        return _vmem_spec((1, 1, rows, d), index)

    return (at(block, lambda ib, ih, iq: (ib, ih, iq, 0)),
            at(window, lambda ib, ih, iq: (ib, ih, iq // blocks_w, 0)),
            at(summaries, lambda ib, ih, iq: (ib, ih, 0, 0)),
            _vmem_spec((1, 1, s // block, block),
                       lambda ib, ih, iq: (ib, ih, 0, 0)),
            summaries * window // s)


# The two calls are jitted functions of their own, entered through
# ``registry.lowered_once`` and as the ``custom_vjp``'s backward rule, as
# ``ssd.py``'s are: a model's layers share one trace of each kernel and one
# lowering to Mosaic.
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _eva_fwd(q, k, v, ksum, vsum, sm_scale, block, window, interpret):
    """(o, lse): lse is [B, H, nq, block] float32, a lane-dense row a query
    block, as the backward reads it."""
    b, h, s, d = q.shape
    block_q, of_window, of_head, stats, per_window = _specs(
        s, d, block, window, ksum.shape[2])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, block=block,
                          per_window=per_window),
        grid=(b, h, s // block),
        in_specs=[block_q, of_window, of_window, of_head, of_head],
        out_specs=[block_q, stats],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, s // block, block), _F32)],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd_eva",
    )(q, k, v, ksum, vsum)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _eva_bwd(sm_scale, block, window, interpret, res, do):
    q, k, v, ksum, vsum, o, lse = res
    b, h, s, d = q.shape
    summaries = ksum.shape[2]
    block_q, of_window, of_head, stats, per_window = _specs(
        s, d, block, window, summaries)
    delta = jnp.sum(do.astype(_F32) * o.astype(_F32), -1)
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, block=block,
                          per_window=per_window),
        grid=(b, h, s // block),
        in_specs=[block_q, block_q, stats, stats, of_window, of_window,
                  of_head, of_head],
        out_specs=[block_q, of_window, of_window, of_head, of_head],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (q, k, v, ksum, vsum)],
        scratch_shapes=[pltpu.VMEM((window, d), _F32)] * 2
        + [pltpu.VMEM((summaries, d), _F32)] * 2,
        compiler_params=_FLASH_BWD_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_eva",
    )(q, do, lse, delta.reshape(b, h, s // block, block), k, v, ksum, vsum))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _eva(q, k, v, ksum, vsum, sm_scale, block, window, interpret):
    return _eva_vjp_fwd(q, k, v, ksum, vsum, sm_scale, block, window,
                        interpret)[0]


def _eva_vjp_fwd(q, k, v, ksum, vsum, sm_scale, block, window, interpret):
    # o and lse carry the name KEPT, here on the residuals themselves: a
    # caller's ``jax.checkpoint`` keeps the pair by a policy over that name
    # and its recomputation has no use for the forward call
    o, lse = (checkpoint_name(t, KEPT) for t in _registry.lowered_once(
        _eva_fwd, (q, k, v, ksum, vsum),
        (sm_scale, block, window, interpret)))
    return o, (q, k, v, ksum, vsum, o, lse)


_eva.defvjp(_eva_vjp_fwd, _eva_bwd)


def _blocks(s, window, chunk):
    """(block, window, tiles?) a call of ``s`` positions runs at: one window
    where ``window`` reaches the whole sequence; ``tiles`` is whether the
    kernels' blocks tile it (module docstring)."""
    window = min(window, s)
    block = min(_BLOCK, window)
    return block, window, (window % block == 0 and s % window == 0
                           and window % chunk == 0 and s % chunk == 0)


def _eva_attention_pallas(q, k, v, ksum, vsum, window, chunk,
                          interpret=False):
    """Pallas body: the shape rule and the kernel call."""
    s, d = q.shape[2:]
    block, window, tiles = _blocks(s, window, chunk)
    if not tiles or v.shape[-1] != d or k.shape[1] != q.shape[1]:
        return _reference._eva_attention_reference(q, k, v, ksum, vsum,
                                                   window, chunk)
    return _eva(q, k, v, ksum, vsum, 1.0 / math.sqrt(d), block, window,
                bool(interpret))


def eva_tiles_visited_pct(seq_len, window, chunk):
    """The share, in percent, of a causal flash call's score tiles (by their
    area: a summary tile is ``window / chunk`` keys wide, a token tile
    ``block``) that the EVA call visits at ``seq_len`` positions, forward
    and backward alike: counted with the bounds the kernels' loops run over
    (``_visits``), so a kernel that masked the other windows' tokens and did
    not skip them would read 100 and more. A program counter, computed on
    the host; nothing runs on a device. None where the blocks do not tile
    the call (the reference body runs)."""
    block, window, tiles = _blocks(seq_len, window, chunk)
    if not tiles:
        return None
    nq = seq_len // block
    under, earlier = _visits(np.arange(nq), window // block)
    visited = np.sum((under + 1) * block + earlier * (window // chunk))
    return 100.0 * float(visited) / float(block * nq * (nq + 1) // 2)


_registry.register_kernel(
    "eva_attention", _reference._eva_attention_reference,
    _eva_attention_pallas,
    doc="EVA aggregation: a window's tokens and the earlier windows' chunk "
        "summaries under one online softmax; no [S,S] mask in HBM",
    batch_leading=("q", "k", "v", "ksum", "vsum"))
