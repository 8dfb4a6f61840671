"""Flash attention: blockwise online-softmax attention, forward and
backward, with the dense attention it is held against.

The [S, S] score matrix never exists in HBM (the reference materialises
scores in operators/math/ softmax + matmul calls). Forward is one Pallas
kernel and backward is one: it rebuilds each score tile once, from the
saved logsumexp, and takes dQ, dK, dV and the key-bias gradient from it.
Which body a call runs is the registry's choice (``ops/pallas/registry.py``).

Two things a call may say beside ``causal``, both static Python values of
the call and no option of the program:

- ``window``: a query sees the ``window`` keys that end at its own (causal
  only). The kernels skip what lies outside the band and do not mask it: the
  forward's key loop starts at the block that holds ``q0 - window + 1``, the
  backward's query loop for a key block ends at the block that holds ``k0 +
  block_k - 1 + window - 1`` (``_key_blocks``, ``_query_blocks``: the bounds
  the loops run over, and the ones ``tiles_visited_pct`` counts).
- fewer key/value heads than query heads: k and v are [B, Hkv, S, D] with H a
  multiple of Hkv, and query head i reads key/value head ``i // (H / Hkv)``.
  The ``BlockSpec`` index maps send it there, so no repeated K or V exists in
  HBM; the backward kernel writes a dK and a dV part a query head and one XLA
  sum adds a group's parts (the group's dQ accumulators, [group, d, S] float32
  beside its Q and dO, do not fit VMEM at 16 384 positions, so the sum is not
  inside the kernel).

With ``window=None`` and one key/value head a query head the calls are the
kernels ``flash_fwd`` and ``flash_bwd``, traced and lowered as before either
existed; a windowed call is ``flash_fwd_window`` and ``flash_bwd_window`` in a
trace, whatever its heads.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["flash_attention", "tiles_visited_pct"]

_NEG_INF = -1e30

#: scoped-VMEM limit for the flash kernels. Each holds one head's
#: whole-sequence operands resident (K/V forward; Q, dO, dQ and its
#: float32 accumulator backward), which passes Mosaic's 16 MiB default at
#: S=4096. 64 MiB is half of a v5e core's 128 MiB of VMEM.
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 << 20)
#: the backward accumulates dQ across its key-block axis, so that axis
#: runs in order
_FLASH_BWD_COMPILER_PARAMS = dataclasses.replace(
    _FLASH_COMPILER_PARAMS,
    dimension_semantics=("parallel", "parallel", "arbitrary"))
#: the backward lays up to this many query tiles of a key block out as
#: straight-line code, so that one tile's matmuls run under the next one's
#: elementwise work (v5e, S=4096: 8 tiles 12.0 ms a call, 4 12.6, 1 13.8)
_FLASH_BWD_UNROLL = 8
#: where a head is one tile (S <= 512: one query block, one key block) a
#: program takes up to this many heads, their tiles as straight-line code
#: like the backward's groups: a one-tile program has nothing of its own to
#: run under its matmuls, and pays a grid step for every tile (v5e, B=64
#: H=12 S=512 d=64, forward + backward a layer: 1 head 2.29 ms, 2 2.09,
#: 4 2.03, 6 2.04, 12 1.99; my chip run, PR 29)
_FLASH_HEADS_PER_PROGRAM = 4


def _as_row(col):
    """A per-row statistic ([n] or [n, 1], one value a sublane) as one
    lane-dense [1, n] row: what an HBM array can hold without padding every
    value to 128 lanes. Broadcast over the lanes and transposed, which is
    exact (the MXU would round)."""
    n = col.shape[0]
    return jnp.broadcast_to(col.reshape(n, 1), (n, 128)).T[:1]


def _masked_scores(qs, k_blk, b_blk, q0, k0, causal, transposed=False,
                   window=None):
    """Scaled scores for one (q-block, k-block) tile: qs is pre-scaled
    [bq, d], k_blk [bk, d], b_blk [bk] additive key bias; q0/k0 are the
    tile's absolute row/col offsets for the causal mask. Shared by the
    forward and the backward kernel so masking/bias can never drift
    between them. ``transposed`` gives the tile as [bk, bq] (K Q^T, what
    the backward wants); b_blk is then a [bk, 1] column. With ``window`` a
    query sees only the ``window`` keys that end at its own."""
    rows, cols = (k_blk, qs) if transposed else (qs, k_blk)
    s = jax.lax.dot_general(
        rows, cols, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [bq, bk] | [bk, bq]
    s = s + (b_blk if transposed else b_blk[None, :])
    if causal:
        q_axis = 1 if transposed else 0
        qi = q0 + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        ki = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
        keep = ki <= qi
        if window is not None:
            keep &= ki > qi - window
        s = jnp.where(keep, s, _NEG_INF)
    return s


def _key_blocks(iq, nk, block_q, block_k, causal, window, xp=jnp):
    """[first, end) of the key blocks that hold a key some query of query
    block ``iq`` sees: up to the diagonal where causal, from the block that
    holds ``q0 - window + 1`` where windowed. The forward kernel's loop runs
    over exactly these, so what ``tiles_visited_pct`` counts with them
    (``xp=numpy``) is what the kernel visits."""
    first, end = 0, nk
    if causal:
        end = xp.minimum(nk, ((iq + 1) * block_q + block_k - 1) // block_k)
    if window is not None:
        first = xp.maximum(iq * block_q - window + 1, 0) // block_k
    return first, end


def _query_blocks(ik, nq, block_q, block_k, causal, window, xp=jnp):
    """[first, end) of the query blocks that hold a query which sees some
    key of key block ``ik``: from the diagonal where causal, to the block
    that holds ``k0 + block_k - 1 + window - 1`` where windowed. The
    backward kernel's loop."""
    first, end = 0, nq
    if causal:
        first = (ik * block_k) // block_q
    if window is not None:
        end = xp.minimum(nq, ((ik + 1) * block_k + window - 2) // block_q + 1)
    return first, end


def _flash_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                      sm_scale, block_k, causal, seq_len, block_q,
                      window=None):
    """One (batch, heads, q-block) cell: stream K/V blocks, keep running
    (max, sum, acc) — the online-softmax recurrence. The logsumexp goes
    out as row ``iq`` of the heads' [nq, bq] block, which stays in VMEM
    across the q-blocks."""
    heads, bq = q_ref.shape[1:3]
    dv = v_ref.shape[-1]
    nk = seq_len // block_k
    iq = pl.program_id(2)

    def head(ih):
        q = q_ref[0, ih].astype(jnp.float32) * sm_scale    # [bq, d]

        def body(jk, carry):
            m_prev, l_prev, acc = carry
            k_blk = k_ref[0, ih, pl.ds(jk * block_k, block_k), :] \
                .astype(jnp.float32)                       # [bk, d]
            v_blk = v_ref[0, ih, pl.ds(jk * block_k, block_k), :] \
                .astype(jnp.float32)
            b_blk = bias_ref[0, 0, pl.ds(jk * block_k, block_k)] \
                .astype(jnp.float32)                       # [bk]
            s = _masked_scores(q, k_blk, b_blk, iq * block_q, jk * block_k,
                               causal, window=window)
            m_cur = jnp.max(s, axis=-1)                    # [bq]
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])                # [bq, bk]
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        init = (jnp.full((bq,), _NEG_INF, jnp.float32),
                jnp.zeros((bq,), jnp.float32),
                jnp.zeros((bq, dv), jnp.float32))
        if nk == 1:
            m, l, acc = body(0, init)
        else:
            # causal: stop at the diagonal. K blocks entirely above it are
            # fully masked — skipping them halves causal attention FLOPs;
            # windowed: start where the band does
            m, l, acc = lax.fori_loop(
                *_key_blocks(iq, nk, block_q, block_k, causal, window),
                body, init)
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, ih] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, ih, pl.ds(iq, 1), :] = _as_row(m + jnp.log(l_safe))

    for ih in range(heads):
        head(ih)


def _heads_per_program(h, nq, nk, group=1):
    """How many heads one program takes: several where a head is one tile,
    the largest divisor of ``h`` up to ``_FLASH_HEADS_PER_PROGRAM``; one
    where the heads of a program would not read the same key/value head."""
    if nq > 1 or nk > 1 or group > 1:
        return 1
    return max(n for n in range(1, min(h, _FLASH_HEADS_PER_PROGRAM) + 1)
               if h % n == 0)


# The two calls are jitted functions of their own so that a model's layers,
# which call them with the same shapes, share one trace of the kernel and
# one lowering to Mosaic: a program holds one function a kernel and calls
# it a layer (XLA inlines the calls). Traced anew a layer, the 24 calls of
# BERT-base were 3 s of every start, from the compile cache or not
# (PERF.md section 6, PR 29).
def _call_name(stem, window):
    """A windowed call has a name of its own in a trace."""
    return stem if window is None else stem + "_window"


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _flash_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k,
               interpret, window=None):
    """(o, lse): lse is [B, H, nq, bq] float32, a lane-dense row a query
    block, as the backward reads it."""
    b, h, s, d = q.shape
    dv = v.shape[-1]
    group = h // k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = s // block_q
    hb = _heads_per_program(h, nq, s // block_k, group)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, block_k=block_k,
        causal=causal, seq_len=s, block_q=block_q, window=window)

    def q_block(ib, ih, iq):
        return (ib, ih, iq, 0)

    def whole(ib, ih, iq):
        return (ib, ih, 0, 0)

    def whole_kv(ib, ih, iq):
        return (ib, ih // group, 0, 0)

    kv = whole if group == 1 else whole_kv

    # Mosaic tiling constraint: a block's last two dims must be
    # (8k, 128k)-divisible or equal to the array's — so the per-batch
    # bias rides as [B, 1, S] (block (1, 1, S)), and the heads' lse block
    # is the whole [nq, bq], written back when the heads change.
    return pl.pallas_call(
        kernel,
        grid=(b, h // hb, nq),
        in_specs=[
            _vmem_spec((1, hb, block_q, d), q_block),
            _vmem_spec((1, hb, s, d), kv),
            _vmem_spec((1, hb, s, dv), kv),
            _vmem_spec((1, 1, s), lambda ib, ih, iq: (ib, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((1, hb, block_q, dv), q_block),
            _vmem_spec((1, hb, nq, block_q), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, nq, block_q), jnp.float32),
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name=_call_name("flash_fwd", window),
    )(q, k, v, bias[:, None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention(q, k, v, bias, sm_scale, causal, block_q, block_k,
                     interpret, window=None):
    o, _ = _flash_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k,
                      interpret, window)
    return o


def _flash_attention_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k,
                         interpret, window=None):
    o, lse = _flash_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k,
                        interpret, window)
    return o, (q, k, v, bias, o, lse)


def _flash_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                      bias_ref, dq_ref, dk_ref, dv_ref, db_ref, dqt_acc, *,
                      sm_scale, block_q, block_k, causal, seq_len,
                      window=None):
    """One (batch, heads, k-block) cell: stream Q/dO blocks, rebuild each
    tile's probabilities once from the saved logsumexp, and take all four
    gradients from it. The tile is built transposed, [bk, bq] = K Q^T, so
    that no product contracts over a tile's rows: dV += p^T dO and
    dK += dz^T Q are plain, and dQ is accumulated transposed,
    dQ^T += K^T dz^T, in a float32 [d, S] scratch a head that lives across
    the key blocks (lse/delta ride as lane-dense [1, bq] rows, and the
    key-bias gradient goes out as one: row ``ik`` of the heads' [nk, bk]
    block). Scores never touch HBM, nor do partial dQs."""
    ik = pl.program_id(2)
    heads = q_ref.shape[1]
    nq = seq_len // block_q

    @pl.when(ik == 0)
    def _():
        dqt_acc[...] = jnp.zeros_like(dqt_acc)

    b_col = bias_ref[0].astype(jnp.float32)                # [bk, 1]
    # causal: q-blocks strictly above the diagonal see only masked scores,
    # so the loop starts at the diagonal and its length varies; otherwise
    # the tiles go in groups of straight-line code
    unroll = 1 if causal else max(
        u for u in range(1, min(nq, _FLASH_BWD_UNROLL) + 1) if nq % u == 0)

    def head(ih):
        k_blk = k_ref[0, ih].astype(jnp.float32)           # [bk, d]
        v_blk = v_ref[0, ih].astype(jnp.float32)
        kt_blk = k_blk.T                                   # [d, bk]
        bk, d = k_blk.shape
        dv = v_blk.shape[-1]

        def tile(jq, carry):
            dk_acc, dv_acc, db_acc = carry
            q0 = pl.multiple_of(jq * block_q, block_q)
            qs = q_ref[0, ih, pl.ds(q0, block_q), :] \
                .astype(jnp.float32) * sm_scale            # [bq, d]
            do_blk = do_ref[0, ih, pl.ds(q0, block_q), :] \
                .astype(jnp.float32)
            lse_row = lse_ref[0, ih, pl.ds(jq, 1), :]      # [1, bq]
            d_row = delta_ref[0, ih, pl.ds(jq, 1), :]
            st = _masked_scores(qs, k_blk, b_col, q0, ik * block_k, causal,
                                transposed=True, window=window)
            pt = jnp.exp(st - lse_row)                     # [bk, bq]
            dv_acc = dv_acc + jax.lax.dot_general(
                pt, do_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bk, d]
            dpt = jax.lax.dot_general(
                v_blk, do_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bk, bq]
            dzt = pt * (dpt - d_row)
            dk_acc = dk_acc + jax.lax.dot_general(
                dzt, qs, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bk, d]
            db_acc = db_acc + jnp.sum(dzt, axis=1, keepdims=True)
            dqt_acc[ih, :, pl.ds(q0, block_q)] += jax.lax.dot_general(
                kt_blk, dzt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [d, bq]
            return dk_acc, dv_acc, db_acc

        def group(g, carry):
            for i in range(unroll):
                carry = tile(g * unroll + i, carry)
            return carry

        init = (jnp.zeros((bk, d), jnp.float32),
                jnp.zeros((bk, dv), jnp.float32),
                jnp.zeros((bk, 1), jnp.float32))
        if unroll == nq:
            dk, dv, db = group(0, init)
        else:
            # causal: unroll is 1, the groups are the query blocks
            dk, dv, db = lax.fori_loop(
                *_query_blocks(ik, nq // unroll, block_q, block_k, causal,
                               window), group, init)
        dk_ref[0, ih] = dk.astype(dk_ref.dtype)
        dv_ref[0, ih] = dv.astype(dv_ref.dtype)
        db_ref[0, ih, pl.ds(ik, 1), :] = _as_row(db)

    for ih in range(heads):
        head(ih)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _():
        for ih in range(heads):
            dq_ref[0, ih] = (dqt_acc[ih].T * sm_scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _flash_attention_bwd(sm_scale, causal, block_q, block_k, interpret,
                         window, res, do):
    """Blockwise recompute backward as one Pallas kernel, ``flash_bwd``,
    gridded over key blocks: it yields dK and dV, and dQ with them. Live
    memory stays O(block · S); the [S, S] score matrix never exists. Where
    a group of query heads shares a key/value head the kernel writes each
    query head's dK and dV and the group's are summed after it."""
    q, k, v, bias, o, lse = res
    b, h, s, d = q.shape
    dv = v.shape[-1]
    group = h // k.shape[1]
    nq, nk = s // block_q, s // block_k
    hb = _heads_per_program(h, nq, nk, group)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    kernel = functools.partial(
        _flash_bwd_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, causal=causal, seq_len=s, window=window)

    def whole(ib, ih, ik):
        return (ib, ih, 0, 0)

    def k_block(ib, ih, ik):
        return (ib, ih, ik, 0)

    def kv_block(ib, ih, ik):
        return (ib, ih // group, ik, 0)

    kv = k_block if group == 1 else kv_block

    # lse/delta as one lane-dense row a query block ([B,H,nq,bq]), the
    # key-bias gradient as one a key block ([B,H,nk,bk]); the bias as a
    # column ([B,S,1]), since it runs down the transposed tile's rows
    dq, dk, dv, dbh = pl.pallas_call(
        kernel,
        grid=(b, h // hb, nk),
        in_specs=[
            _vmem_spec((1, hb, s, d), whole),
            _vmem_spec((1, hb, s, dv), whole),
            _vmem_spec((1, hb, nq, block_q), whole),
            _vmem_spec((1, hb, nq, block_q), whole),
            _vmem_spec((1, hb, block_k, d), kv),
            _vmem_spec((1, hb, block_k, dv), kv),
            _vmem_spec((1, block_k, 1), lambda ib, ih, ik: (ib, ik, 0)),
        ],
        out_specs=[
            _vmem_spec((1, hb, s, d), whole),
            _vmem_spec((1, hb, block_k, d), k_block),
            _vmem_spec((1, hb, block_k, dv), k_block),
            _vmem_spec((1, hb, nk, block_k), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s, dv), v.dtype),
            jax.ShapeDtypeStruct((b, h, nk, block_k), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, d, s), jnp.float32)],
        compiler_params=_FLASH_BWD_COMPILER_PARAMS,
        interpret=interpret,
        name=_call_name("flash_bwd", window),
    )(q, do, lse, delta.reshape(b, h, nq, block_q), k, v, bias[:, :, None])
    if group > 1:
        dk, dv = (jnp.sum(t.reshape(b, h // group, group, s, -1)
                          .astype(jnp.float32), axis=2).astype(t.dtype)
                  for t in (dk, dv))
    dbias = jnp.sum(dbh.reshape(b, h, s), axis=1)          # [B,S]
    return dq, dk, dv, dbias.astype(bias.dtype)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _dense_attention_reference(q, k, v, bias=None, causal=False,
                               sm_scale=None, block_q=512, block_k=512,
                               window=None):
    """Stock-jnp attention (scores materialized): the semantic reference
    the flash kernel is pinned against. block_q/block_k are accepted (and
    ignored) so both bodies share one signature. Fewer key/value heads than
    query heads are repeated to the query heads here."""
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    b, h, s, d = q.shape
    if k.shape[1] != h:
        k, v = (jnp.repeat(t, h // t.shape[1], axis=1) for t in (k, v))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    qs = q.astype(jnp.float32) * sm_scale
    scores = jnp.einsum("bhqd,bhkd->bhqk", qs, k.astype(jnp.float32))
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32).reshape(b, s)
        scores = scores + bias[:, None, None, :]
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, (s, s), 0)
        ki = lax.broadcasted_iota(jnp.int32, (s, s), 1)
        keep = ki <= qi
        if window is not None:
            keep &= ki > qi - window
        scores = jnp.where(keep, scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _blocks(s, block_q, block_k):
    """(block_q, block_k, pad) a call of ``s`` positions runs at."""
    if s <= max(block_q, block_k):
        # short sequences: one block each way — but still pad to the
        # 128-lane grain so Mosaic never gets an unaligned whole-array
        # block (e.g. S=300 bf16 must not reach the kernel unpadded)
        pad = (-s) % 128
        block_q = block_k = s + pad
    else:
        # pad only to the 128-lane grain, then shrink each block to the
        # largest power-of-two (>=128) dividing the padded length — a
        # S=640 input runs at block 128 with zero pad instead of paying
        # ~60% masked pad work at block 512
        pad = (-s) % 128
        sp = s + pad
        while block_q > 128 and sp % block_q:
            block_q //= 2
        while block_k > 128 and sp % block_k:
            block_k //= 2
        if sp % block_q or sp % block_k:
            # non-power-of-two caller blocks: fall back to lcm padding
            # (the grid floors by block_q and the kv loops by block_k —
            # S must be a multiple of BOTH or trailing keys are dropped)
            pad = (-s) % math.lcm(block_q, block_k)
    return block_q, block_k, pad


def _flash_attention_pallas(q, k, v, bias=None, causal=False,
                            sm_scale=None, block_q=512, block_k=512,
                            window=None, interpret=False):
    """Pallas body: block-size resolution, 128-lane padding, kernel call."""
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    b, h, s, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if bias is None:
        bias = jnp.zeros((b, s), jnp.float32)
    bias = jnp.asarray(bias, jnp.float32).reshape(b, s)
    block_q, block_k, pad = _blocks(s, block_q, block_k)
    if pad:
        zf = ((0, 0), (0, 0), (0, pad), (0, 0))
        q = jnp.pad(q, zf)
        k = jnp.pad(k, zf)
        v = jnp.pad(v, zf)
        bias = jnp.pad(bias, ((0, 0), (0, pad)),
                       constant_values=_NEG_INF)
    out = _flash_attention(q, k, v, bias, float(sm_scale), bool(causal),
                           int(block_q), int(block_k), bool(interpret),
                           window)
    if pad:
        out = out[:, :, :s, :]
    return out


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    block_q=512, block_k=512, window=None):
    """Blockwise (flash) attention.

    q: [B, H, S, D]; k: [B, Hkv, S, D]; v: [B, Hkv, S, Dv]. Dv may differ
    from D (latent attention scores on 192 channels and carries 128) and D
    need be no multiple of 128: a block takes the whole head, whatever its
    size, and Mosaic lays 192 out on two lane tiles. H is a multiple of Hkv
    and query head i reads key/value head ``i // (H / Hkv)``. bias: optional
    [B, S] additive key bias (e.g. key-padding mask as 0 / -inf). ``window``
    (with ``causal``): a query sees the ``window`` keys that end at its own;
    one that reaches the whole sequence is the causal call. Returns [B, H,
    S, Dv] in q.dtype. The default ``sm_scale`` is 1 / sqrt(D). Sequence is
    padded to the block size internally (padded keys masked).
    """
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} key "
                         f"and {v.shape[1]} value heads")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a positive count of keys behind "
                             "a causal query")
        if window >= q.shape[2]:
            window = None
    return _registry.dispatch(
        "flash_attention", q, k, v, bias=bias, causal=causal,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k, window=window)


def tiles_visited_pct(seq_len, window, block_q=512, block_k=512):
    """The share, in percent, of a causal call's score tiles that the same
    call with ``window`` visits, forward and backward together, at the
    blocks a call of ``seq_len`` positions runs at: counted with the bounds
    the kernels' loops run over (``_key_blocks``, ``_query_blocks``), so a
    kernel that masks the band's outside and does not skip it reads 100. A
    program counter, computed on the host; nothing runs on a device."""
    block_q, block_k, pad = _blocks(seq_len, block_q, block_k)
    nq, nk = (seq_len + pad) // block_q, (seq_len + pad) // block_k
    if window is not None and window >= seq_len:
        window = None

    def tiles(window):
        first, end = _key_blocks(np.arange(nq), nk, block_q, block_k, True,
                                 window, xp=np)
        fwd = np.sum(end - first)
        first, end = _query_blocks(np.arange(nk), nq, block_q, block_k,
                                   True, window, xp=np)
        return fwd + np.sum(end - first)

    return 100.0 * float(tiles(window)) / float(tiles(None))


_registry.register_kernel(
    "flash_attention", _dense_attention_reference, _flash_attention_pallas,
    doc="blockwise online-softmax attention; [S,S] scores never in HBM",
    batch_leading=("q", "k", "v", "bias"))
