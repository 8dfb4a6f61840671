"""The chunked gated delta rule (``paddle_tpu/ops/kda.py``) as Mosaic kernels,
a forward and a backward one tied by a ``jax.custom_vjp``: ``kda_fwd`` and
``kda_bwd`` for a decay a channel, which the first paragraphs describe, and
``gdn_fwd`` and ``gdn_bwd`` for a decay a head (below). The rank of ``g``
chooses.

**Grid and layout.** A grid step is a batch row, ``_HEADS`` heads and a
*unit* of ``_UNIT`` = 128 positions; the units of a head are a sequential
axis and its state (kept transposed, ``[dv, dk]``, float32) lives in a VMEM
scratch across them. ``[B, S, H, d]`` is read as ``[B, S, H * d]`` with a
block ``(1, 128, heads * d)``, a head a lane tile, so the kernels read q, k,
v, g once and nothing is transposed around them. (The reshape is a bitcast for
float32 and, alone, a relayout for bfloat16, whose tiles pair rows: 0.3 ms an
array at [1, 8192, 32, 128]. Since PR 39 the mixers hand over what
``delta_glue.py``'s kernels wrote, ``[B, S, H * d]`` viewed as ``[B, S, H,
d]``, and take the result the same way: the compiler folds each pair of
reshapes to nothing, and the compiled steps hold no copy or reshape of q,
k, v, o or their gradients; ``tests/test_tpu_aot_compile.py`` holds that.)
The ``128 / chunk`` chunks of a
unit share every matmul, their score matrices and inverses being the
diagonal blocks of one ``[128, 128]`` tile.

**Scores without a [s, s, d] tensor.** ``P_ij = sum_c a_ic b_jc exp(G_ic -
G_jc)`` (``j < i``, same chunk) is the sum over the levels ``s = chunk / 2,
..., 1`` of one matmul each: at level ``s`` a row in the lower half of its
block of ``2 s`` rows is a query, a row in the upper half a key, and the
reference row ``R`` is the first row of the lower half, which lies between
every such pair: ``(a exp(G - R)) (b exp(R - G))^T``. A row is one or the
other, so ONE exponential a row and level, ``exp(+-(G - R))``, serves q and k
as queries and k as a key; it is clamped at 0, so **no exponent is ever
positive**, and the level's mask drops the products of rows on the wrong
sides. The levels' masks tile the strict lower triangle of every chunk. The
reference body does the same at one level, 16, and sums the pairs closer
than that channel by channel; here every level is a matmul, with operands in
the inputs' dtype from ``_SUB`` rows up and in float32 (``HIGHEST``: six
passes) below, which is the reference's arithmetic at a sub-block of
``_SUB``.

**The inverse is explicit.** ``(I + A)^{-1}`` by the block formula, level by
level: with ``X_s`` the inverse of the diagonal blocks of ``s`` rows (``X_1
= I``), ``X_2s = X_s - X_s (A * mask_s) X_s``: float32 matmuls of the unit's
tile, no custom call, no division. The running sum ``G`` is a product with a
triangle of ones, exact in three passes (``_sum_over``).

**Where it rounds:** where the reference body does. ``G``, every
exponential, the inverse, ``u0`` and the state are float32; ``w``, ``a_qk``,
``q_in``, ``k_out``, ``u``, the state as a matmul operand and the levels'
operands from ``_SUB`` up are rounded to the inputs' dtype, with float32
accumulation; the backward rounds the cotangents it multiplies the same way
(on the chip the reference's default-precision products do).

**The heads are a batch axis.** A unit's work before it meets the state is a
chain of dependent matmuls, the chunks' walk over the state another, and
Mosaic issues matmuls in the order the kernel names them: one head a grid
step leaves the MXUs waiting on every link (PERF.md section 6, PR 31: 13.2 ms
a layer forward, the reference body's time). So a grid step takes ``_HEADS``
heads and every array of the kernel has them as its leading axis, ``[H, 128,
.]``: each product is one ``dot_general`` batched over the heads, which
Mosaic unrolls head after head, so the heads' chains interleave link by
link. (Written as eight copies of a head's code in lock step the kernels
run as fast, 6.9 | 16.7 ms a layer for 7.0 | 16.8, and cost every set-up 6 s
of trace and lowering: the ledger's line for PR 31; PERF.md section 6,
PR 32.) Only the running sum, whose left operand is the same triangle of ones
for every head, is one product over the heads side by side along the lanes.

**A decay a head** (``g`` of rank 3; Gated DeltaNet) has kernels of its own,
``gdn_fwd`` and ``gdn_bwd``, on the same grid and the same walk over the
state (``_replay``, ``_walk_back``), because its scores are other work: with
one decay a row, ``P_ij = (a_i . b_j) exp(G_i - G_j)`` is ONE product a key
head, ``[q; k] k^T`` with operands in the inputs' dtype, times a ``[128, 128]``
mask of exponentials a value head. The exponent is the difference itself, so
it is never positive and needs no levels and no reference rows; the running
sum is a masked lane reduction of the head's lane-dense row of ``g`` (as
beta's), exact in float32. Where ``n`` value heads share a key head, q and k
come ``heads / n`` key heads wide (the index maps put block ``ih`` of both
widths side by side: nothing is repeated in HBM), a value head takes its key
head's tile in VMEM, and the backward adds a group's parts before it writes
them. It keeps the state a unit starts from and the inverse, 537 MB a layer at
``[1, 16384, 32, 128]``, and forms the scores again. A chunk is ``CHUNK_HEAD``
positions: the levels of the inverse are what a larger chunk costs.

**Kept for the backward**, a unit and head: the state the unit starts from
(float32), and three ``[128, 128]`` tiles: ``a_qk`` (the inputs' dtype),
``P_kk`` and the inverse (float32). 470 MB a layer at [1, 8192, 32, 128] (a
state a chunk alone would be 537). ``kda_fwd``'s forward rule names them
and the output (``KEPT``), so a caller's ``jax.checkpoint`` can keep them
by a policy and leave the forward kernel out of its recomputation, as Kimi
Linear's mixers do (``models/blocks.recomputed``); what ``gdn_fwd`` keeps
has no name and is transient under Qwen3-Next's plain ``jax.checkpoint``.
The backward kernel runs the same
grid backwards with the state's gradient in the scratch: it forms ``G``, the
levels' operands and ``[u0 | w]`` again, replays the unit's chunks for the
states inside it, walks them backwards, and takes all five gradients.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import kda as _reference
from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["KEPT"]

#: the ``jax.ad_checkpoint.checkpoint_name`` of what ``kda_fwd`` hands
#: ``kda_bwd`` that only the kernel makes: the output and the four arrays
#: kept a unit and head. As ``flash_attention.KEPT``: a ``jax.checkpoint``
#: whose policy saves the name (``models/blocks.recomputed``) does not run
#: the forward kernel again. ``gdn_fwd``'s are not named: what it keeps does
#: not fit beside the step that calls it (``models/qwen3_next.py``).
KEPT = "kda_kept"

#: positions a grid step, a *unit*: their chunks share one [128, 128] tile of
#: scores and inverse
_UNIT = 128
#: heads a grid step: their work on a unit is so many independent chains of
#: matmuls (v5e, [1, 8192, 32, 128], a layer forward | forward + backward:
#: 4 heads 7.5 | 18.0 ms, 8 7.0 | 16.8; PERF.md section 6, PR 32; in PR 31's
#: lock step 1 head 10.4 | 25.6, 2 8.6 | 21.6, 16 no faster than 8 and past
#: the VMEM the calls ask; two or four units a step instead of more heads
#: were no faster)
_HEADS = 8
#: levels of the scores from this many rows up take their operands in the
#: inputs' dtype, the levels below in float32: the reference body's
#: sub-block
_SUB = 8

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
# batched over the heads (axis 0): the axes contracted of [H, m, k] operands
_NN, _NT, _TN = (2, 1), (2, 2), (1, 1)

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20)


def _dot(a, b, dims, precision=None):
    """One product a head: a, b [H, ., .], contracted over ``dims``."""
    return lax.dot_general(a, b, (tuple((c,) for c in dims), ((0,), (0,))),
                           precision=precision, preferred_element_type=_F32)


def _sum_over(ones, x, transposed):
    """``ones`` [128, 128] (0 / 1, bfloat16; transposed or not) times float32
    ``x`` [128, n] at float32's precision in three passes, not ``HIGHEST``'s
    six: the ones are exact in bfloat16 and x is split into three bfloat16
    pieces that sum to it exactly. The heads side by side along ``n``: one
    product for all of them."""
    n = x.shape[-1]
    high = x.astype(jnp.bfloat16)
    rest = x - high.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
    out = lax.dot_general(
        ones, jnp.concatenate([high, mid, low], axis=1),
        (((0 if transposed else 1,), (0,)), ((), ())),
        preferred_element_type=_F32)
    return out[:, :n] + out[:, n:2 * n] + out[:, 2 * n:]


def _heads(wide, heads):
    """[128, H * d], a head a lane tile, as [H, 128, d]."""
    d = wide.shape[-1] // heads
    return jnp.stack([wide[:, h * d:(h + 1) * d] for h in range(heads)])


def _wide(x):
    """``_heads`` undone."""
    return jnp.concatenate([x[h] for h in range(x.shape[0])], axis=1)


def _levels(chunk):
    s = chunk // 2
    while s:
        yield s
        s //= 2


class _Masks:
    """The unit's constant [128, 128] masks, from two iotas; they broadcast
    over the heads."""

    def __init__(self, chunk, dk=None):
        i = lax.broadcasted_iota(jnp.int32, (_UNIT, _UNIT), 0)
        j = lax.broadcasted_iota(jnp.int32, (_UNIT, _UNIT), 1)
        self.eye = i == j
        #: level s: row in the lower, column in the upper half of one block
        #: of 2 s rows
        self.level = {
            s: ((i ^ j) < 2 * s) & ((i & s) != 0) & ((j & s) == 0)
            for s in _levels(chunk)}
        if dk is not None:
            #: +1 on a level's queries, -1 on its keys, for every channel (a
            #: decay a channel's scores alone have levels)
            row = lax.broadcasted_iota(jnp.int32, (_UNIT, dk), 0)
            self.side = {
                s: jnp.where((row & s) != 0, 1.0, -1.0).astype(_F32)
                for s in _levels(chunk)}
        chunk_mate = self.mate = (i ^ j) < chunk
        self.strict = chunk_mate & (j < i)
        self.lower = chunk_mate & (j <= i)
        #: the running sum inside a chunk as a product
        self.tril = jnp.where(self.lower, 1.0, 0.0).astype(jnp.bfloat16)


def _rows_of(x, first, group):
    """Row ``first`` of every ``group`` rows of x [H, n, d], on all the rows
    of its group."""
    h, n, d = x.shape
    if group >= 8:
        picked = x.reshape(h, n // group, group, d)[:, :, first:first + 1]
        return jnp.broadcast_to(picked, (h, n // group, group, d)) \
            .reshape(h, n, d)
    tiles = x.reshape(h, n // 8, 8, d)
    row = lax.broadcasted_iota(jnp.int32, tiles.shape, 2)
    out = None
    for lo in range(0, 8, group):
        picked = jnp.broadcast_to(tiles[:, :, lo + first:lo + first + 1],
                                  tiles.shape)
        out = picked if out is None else jnp.where(row >= lo, picked, out)
    return out.reshape(h, n, d)


def _col(rows, eye):
    """Lane-dense rows [H, 1, n] as columns [H, n, 1]: exact, a sum of one
    value and zeros."""
    return jnp.sum(jnp.where(eye, rows, 0.0), axis=2, keepdims=True)


def _row(cols, eye):
    """``_col`` undone."""
    return jnp.sum(jnp.where(eye, cols, 0.0), axis=1, keepdims=True)


def _level_operands(q32, k32, G, masks, chunk, dt):
    """What every level of the scores multiplies, and the backward reads:
    [(s, e, q e, k e, [q e; k e] as the level's operand, precision)]. At a
    level a row is a query (lower half of its block, decayed from the
    reference row down to itself) or a key (upper half, decayed from itself
    down to the reference row), never both: one exponential a row serves q,
    k as a query and k as a key."""
    kept = []
    for s in _levels(chunk):
        e = jnp.exp(jnp.minimum(
            (G - _rows_of(G, s, 2 * s)) * masks.side[s], 0.0))
        qe, ke = q32 * e, k32 * e
        od, precision = (dt, None) if s >= _SUB else (_F32, _HI)
        kept.append((s, e, qe, ke,
                     jnp.concatenate([qe, ke], axis=1).astype(od), precision))
    return kept


def _scores(kept, q32, k32, masks):
    """(P_qk with its diagonal, P_kk strictly below it): [H, 128, 128]
    float32 tiles, zero outside the chunks; a level's mask keeps its
    products of queries with keys."""
    p_qk = p_kk = jnp.zeros((q32.shape[0], _UNIT, _UNIT), _F32)
    for s, _, _, _, operand, precision in kept:
        p = _dot(operand, operand[:, _UNIT:], _NT, precision)  # [H, 256, 128]
        p_qk = jnp.where(masks.level[s], p[:, :_UNIT], p_qk)
        p_kk = jnp.where(masks.level[s], p[:, _UNIT:], p_kk)
    diag = jnp.sum(q32 * k32, axis=2, keepdims=True)
    return jnp.where(masks.eye, diag, p_qk), p_kk


def _lower_halves(x, s):
    """The rows of x [H, 128, n] in the lower half of their block of 2 s
    rows (s a multiple of 8: whole sublane tiles), [H, 64, n]."""
    h, _, n = x.shape
    return x.reshape(h, _UNIT // (2 * s), 2, s, n)[:, :, 1] \
        .reshape(h, _UNIT // 2, n)


def _as_lower_halves(x, s):
    """``_lower_halves`` undone, zeros in the upper halves."""
    h, _, n = x.shape
    x = x.reshape(h, _UNIT // (2 * s), 1, s, n)
    return jnp.concatenate([jnp.zeros_like(x), x], axis=2) \
        .reshape(h, _UNIT, n)


def _inverse(a, masks, chunk):
    """(I + a)^{-1} for a strictly lower triangular inside every chunk. A
    level changes only the rows in the lower halves of its blocks; from 8
    rows up those are whole tiles and the products take them alone."""
    levels = sorted(_levels(chunk))
    x = jnp.where(masks.eye, 1.0, 0.0) - jnp.where(masks.level[1], a, 0.0)
    for s in levels[1:]:
        below = jnp.where(masks.level[s], a, 0.0)
        if s % 8:
            x = x - _dot(x, _dot(below, x, _NN, _HI), _NN, _HI)
        else:
            below = _dot(_lower_halves(below, s), x, _NN, _HI)
            x = x - _as_lower_halves(
                _dot(_lower_halves(x, s), _as_lower_halves(below, s), _NN,
                     _HI), s)
    return x


def _unit_operands(refs, found_refs, masks, chunk):
    """What a unit's chunks need beside the states they start from, for all
    the heads of the grid step: the reference body's ``_prepare`` on one
    tile a head. ``found_refs`` are (a_qk, P_kk, the inverse) where the
    forward kernel kept them, else None."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    heads = beta_ref.shape[1]
    dt = q_ref.dtype
    q, k, v = (_heads(ref[0], heads) for ref in (q_ref, k_ref, v_ref))
    dv = v.shape[-1]
    q32, k32, v32 = (t.astype(_F32) for t in (q, k, v))
    beta_col = _col(beta_ref[0, :, 0], masks.eye)
    G = _heads(_sum_over(masks.tril, g_ref[0], False), heads)
    e_in = jnp.exp(G)
    g_end = _rows_of(G, chunk - 1, chunk)
    e_out = jnp.exp(g_end - G)
    kept = _level_operands(q32, k32, G, masks, chunk, dt)
    kg = k32 * e_in
    rhs = jnp.concatenate([v32, kg], axis=2)
    if found_refs is None:
        p_qk, p_kk = _scores(kept, q32, k32, masks)
        a_qk = p_qk.astype(dt)
        inv = _inverse(beta_col * p_kk, masks, chunk)
    else:
        a_qk, p_kk, inv = (ref[0, :, 0] for ref in found_refs)
    solved = _dot(inv, beta_col * rhs, _NN, _HI)             # [u0 | w]
    return dict(
        q32=q32, k32=k32, e_in=e_in, e_out=e_out, kg=kg, kept=kept, p_kk=p_kk,
        a_qk=a_qk, rhs=rhs, beta_col=beta_col,
        inv=inv, solved=solved, u0=solved[:, :, :dv],
        w=solved[:, :, dv:].astype(dt),
        q_in=(q32 * e_in).astype(dt), k_out=(k32 * e_out).astype(dt),
        decay=[jnp.exp(G[:, c + chunk - 1:c + chunk])
               for c in range(0, _UNIT, chunk)])


def _replay(x, state_t, chunk):
    """The unit's chunks in order from the states ``state_t`` ([H, dv, dk],
    transposed): (the states after the unit, the states every chunk starts
    from, u [H, 128, dv], q_in S [H, 128, dv])."""
    dt = x["w"].dtype
    states, us, reads = [], [], []
    for n, c in enumerate(range(0, _UNIT, chunk)):
        piece = slice(c, c + chunk)
        states.append(state_t)
        both = _dot(jnp.concatenate([x["w"][:, piece], x["q_in"][:, piece]],
                                    axis=1),
                    state_t.astype(dt), _NT)                 # [H, 2 C, dv]
        u = x["u0"][:, piece] - both[:, :chunk]
        us.append(u)
        reads.append(both[:, chunk:])
        state_t = state_t * x["decay"][n] \
            + _dot(u.astype(dt), x["k_out"][:, piece], _TN)
    return state_t, states, jnp.concatenate(us, axis=1), \
        jnp.concatenate(reads, axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, st_ref,
                a_qk_ref, p_kk_ref, inv_ref, state, *, chunk):
    """One (batch, heads, unit) grid step: ``state`` [heads, dv, dk] lives
    across a head's units."""
    heads = beta_ref.shape[1]
    dt = q_ref.dtype
    masks = _Masks(chunk, q_ref.shape[-1] // heads)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    x = _unit_operands((q_ref, k_ref, v_ref, g_ref, beta_ref), None, masks,
                       chunk)
    st_ref[0, :, 0] = state[...]
    a_qk_ref[0, :, 0] = x["a_qk"]
    p_kk_ref[0, :, 0] = x["p_kk"]
    inv_ref[0, :, 0] = x["inv"]
    state[...], _, u, read = _replay(x, state[...], chunk)
    o_ref[0] = _wide(read + _dot(x["a_qk"], u.astype(dt), _NN)) \
        .astype(o_ref.dtype)


def _walk_back(x, st_ref, do_ref, dstate, chunk):
    """The backward kernels' walk: the states inside the unit, replayed from
    the one it was found in (``st_ref``); then the chunks backwards: the
    state's gradient (read from the scratch ``dstate`` and left there for
    the unit before), and those of u, w and the three operands that meet the
    state. Returns (the output's gradient a head, u, the gradient of ``[u0 |
    w]``, of ``q_in``, of ``k_out``, and a chunk an entry what the chunk's
    whole decay gets through the state, [H, 1, dk])."""
    dt = x["w"].dtype
    starts = list(enumerate(range(0, _UNIT, chunk)))
    _, states, u, _ = _replay(x, st_ref[0, :, 0], chunk)
    do = _heads(do_ref[0], x["w"].shape[0]).astype(dt)
    du_scores = _dot(x["a_qk"], do, _TN)                     # a_qk^T do
    d_state = dstate[...]
    du, d_read, dk_out, dg_end = [], [], [], []
    for i, c in reversed(starts):
        piece = slice(c, c + chunk)
        d_state_op = d_state.astype(dt)
        du_c = du_scores[:, piece] + _dot(x["k_out"][:, piece], d_state_op,
                                          _NT)
        dk_out.append(_dot(u[:, piece].astype(dt), d_state_op, _NN))
        cot = jnp.concatenate([do[:, piece], (-du_c).astype(dt)], axis=1)
        d_read.append(_dot(cot, states[i].astype(dt), _NN))  # dq_in; dw
        dg_end.append(x["decay"][i] * jnp.sum(
            states[i] * d_state, axis=1, keepdims=True))
        d_state = d_state * x["decay"][i] + _dot(
            cot, jnp.concatenate([x["q_in"][:, piece], x["w"][:, piece]],
                                 axis=1), _TN)
        du.append(du_c)
    dstate[...] = d_state
    du, d_read, dk_out, dg_end = (
        t[::-1] for t in (du, d_read, dk_out, dg_end))
    d_solved = jnp.concatenate(
        [jnp.concatenate(du, axis=1),
         jnp.concatenate([t[:, chunk:] for t in d_read], axis=1)], axis=2)
    dq_in = jnp.concatenate([t[:, :chunk] for t in d_read], axis=1)
    return do, u, d_solved, dq_in, jnp.concatenate(dk_out, axis=1), dg_end


def _solve_back(x, do, u, d_solved, masks, dv):
    """Back through ``[u0 | w] = inv (beta * [V | K exp(G)])`` with ``inv =
    (I + beta * P_kk)^-1`` and through the output's ``a_qk u``: the gradients
    of P_qk, P_kk and beta, of ``[V | K exp(G)]`` and its ``K exp(G)`` half."""
    dt = do.dtype
    beta_col = x["beta_col"]
    d_rhs = _dot(x["inv"], d_solved, _TN, _HI)
    dp_qk = jnp.where(masks.lower, _dot(do, u.astype(dt), _NT), 0.0)
    d_a = jnp.where(masks.strict, -_dot(d_rhs, x["solved"], _NT, _HI), 0.0)
    d_beta = jnp.sum(d_a * x["p_kk"], axis=2, keepdims=True) \
        + jnp.sum(d_rhs * x["rhs"], axis=2, keepdims=True)
    d_rhs = beta_col * d_rhs
    dkg = d_rhs[:, :, dv:]
    return dp_qk, beta_col * d_a, d_beta, d_rhs, dkg


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, a_qk_ref,
                p_kk_ref, inv_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, dstate, *, chunk):
    """The same grid, a head's units last first: ``dstate`` is the gradient
    of the state the unit hands on."""
    heads = beta_ref.shape[1]
    dv = v_ref.shape[-1] // heads
    masks = _Masks(chunk, q_ref.shape[-1] // heads)
    last_row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    starts = list(enumerate(range(0, _UNIT, chunk)))

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    x = _unit_operands((q_ref, k_ref, v_ref, g_ref, beta_ref),
                       (a_qk_ref, p_kk_ref, inv_ref), masks, chunk)

    do, u, d_solved, dq_in, dk_out, dg_end = _walk_back(
        x, st_ref, do_ref, dstate, chunk)

    dp_qk, dp_kk, d_beta, d_rhs, dkg = _solve_back(x, do, u, d_solved, masks,
                                                   dv)

    q32, k32 = x["q32"], x["k32"]
    out_term = dk_out * (k32 * x["e_out"])
    dq = dq_in * x["e_in"]
    dk = dkg * x["e_in"] + dk_out * x["e_out"]
    dG = dkg * x["kg"] + dq_in * (q32 * x["e_in"]) - out_term
    # a chunk's last row of G is also its whole decay and the origin of
    # k_out's exponents
    dG = dG + jnp.concatenate(
        [jnp.where(last_row, dg_end[i] + jnp.sum(
            out_term[:, c:c + chunk], axis=1, keepdims=True), 0.0)
         for i, c in starts], axis=1)
    d_diag = jnp.sum(jnp.where(masks.eye, dp_qk, 0.0), axis=2, keepdims=True)
    dq = dq + d_diag * k32
    dk = dk + d_diag * q32
    for s, e, qe, ke, operand, precision in x["kept"]:
        dp = jnp.concatenate(
            [jnp.where(masks.level[s], dp_qk, 0.0),
             jnp.where(masks.level[s], dp_kk, 0.0)],
            axis=1).astype(operand.dtype)                    # [H, 256, 128]
        d_query = _dot(dp, operand[:, _UNIT:], _NN, precision)  # [H, 256, d]
        d_key = _dot(dp, operand, _TN, precision)            # [H, 128, d]
        dq = dq + d_query[:, :_UNIT] * e
        dk = dk + (d_query[:, _UNIT:] + d_key) * e
        # a query's exponent rises with its G, a key's falls
        dG = dG + d_query[:, :_UNIT] * qe + (d_query[:, _UNIT:] - d_key) * ke

    dq_ref[0] = _wide(dq).astype(dq_ref.dtype)
    dk_ref[0] = _wide(dk).astype(dk_ref.dtype)
    dv_ref[0] = _wide(d_rhs[:, :, :dv]).astype(dv_ref.dtype)
    dg_ref[0] = _sum_over(masks.tril, _wide(dG), True).astype(dg_ref.dtype)
    dbeta_ref[0, :, 0] = _row(d_beta, masks.eye)


# ---------------------------------------------------------------------------
# a decay a head (g of rank 3) and grouped key heads
# ---------------------------------------------------------------------------
def _head_decay_operands(refs, inv_ref, masks, chunk, n):
    """``_unit_operands`` where the decay is one number a head and position
    (``g_ref`` a lane-dense row a head, as beta's) and ``n`` value heads
    share a key head: q and k come as the grid step's ``heads / n`` key
    heads and a value head takes its key head's tile in VMEM. A unit's
    scores are ONE product a key head, ``[q; k] k^T`` with operands in the
    inputs' dtype, times ``exp(G_i - G_j)`` a value head, the exponent a
    difference of running sums and so never positive: no levels, no
    reference rows. ``inv_ref`` is the inverse where the forward kernel kept
    it, else None."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    heads = beta_ref.shape[1]
    dt = q_ref.dtype
    q, k = (_heads(ref[0], heads // n) for ref in (q_ref, k_ref))
    v32 = _heads(v_ref[0], heads).astype(_F32)
    dv = v32.shape[-1]

    def per_value_head(t):
        return t if n == 1 else jnp.stack([t[h // n] for h in range(heads)])

    q32, k32 = (per_value_head(t.astype(_F32)) for t in (q, k))
    beta_col = _col(beta_ref[0, :, 0], masks.eye)
    g_row = g_ref[0, :, 0]                                   # [H, 1, 128]
    # the running sum inside a chunk and the chunk's whole sum, on every row
    G = jnp.sum(jnp.where(masks.lower, g_row, 0.0), axis=2, keepdims=True)
    g_end = jnp.sum(jnp.where(masks.mate, g_row, 0.0), axis=2, keepdims=True)
    decay = jnp.where(masks.lower, jnp.exp(jnp.minimum(
        G - _row(G, masks.eye), 0.0)), 0.0)                  # [H, 128, 128]
    operand = jnp.concatenate([q, k], axis=1)                # [H / n, 256, d]
    raw = per_value_head(_dot(operand, k, _NT))              # [H, 256, 128]
    p_qk = raw[:, :_UNIT] * decay
    p_kk = jnp.where(masks.eye, 0.0, raw[:, _UNIT:] * decay)
    inv = _inverse(beta_col * p_kk, masks, chunk) if inv_ref is None \
        else inv_ref[0, :, 0]
    e_in = jnp.exp(G)
    e_out = jnp.exp(g_end - G)
    kg = k32 * e_in
    rhs = jnp.concatenate([v32, kg], axis=2)
    solved = _dot(inv, beta_col * rhs, _NN, _HI)             # [u0 | w]
    return dict(
        k=k, operand=operand, q32=q32, k32=k32, e_in=e_in, e_out=e_out, kg=kg,
        decay_mask=decay, p_qk=p_qk, p_kk=p_kk, a_qk=p_qk.astype(dt), rhs=rhs,
        beta_col=beta_col, inv=inv, solved=solved, u0=solved[:, :, :dv],
        w=solved[:, :, dv:].astype(dt), q_in=(q32 * e_in).astype(dt),
        k_out=(k32 * e_out).astype(dt),
        decay=[jnp.exp(G[:, c + chunk - 1:c + chunk])
               for c in range(0, _UNIT, chunk)])


def _head_decay_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref,
                           st_ref, inv_ref, state, *, chunk, n):
    """``_fwd_kernel`` for a decay a head: it keeps the state a unit starts
    from and the inverse; the scores are one product to form again."""
    dt = q_ref.dtype
    masks = _Masks(chunk)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    x = _head_decay_operands((q_ref, k_ref, v_ref, g_ref, beta_ref), None,
                             masks, chunk, n)
    st_ref[0, :, 0] = state[...]
    inv_ref[0, :, 0] = x["inv"]
    state[...], _, u, read = _replay(x, state[...], chunk)
    o_ref[0] = _wide(read + _dot(x["a_qk"], u.astype(dt), _NN)) \
        .astype(o_ref.dtype)


def _head_decay_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref,
                           inv_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                           dbeta_ref, dstate, *, chunk, n):
    """``_bwd_kernel`` for a decay a head: the decay's gradient is one number
    a row (the channels' sum), the scores' gradient one product a key head on
    the sum of its value heads' parts."""
    heads = beta_ref.shape[1]
    dv = v_ref.shape[-1] // heads
    dt = q_ref.dtype
    masks = _Masks(chunk)
    last_row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    starts = list(enumerate(range(0, _UNIT, chunk)))

    def per_key_head(t):
        """The sum of a key head's value heads' parts: [H / n, ., .]."""
        return t if n == 1 else jnp.stack(
            [sum((t[h + i] for i in range(1, n)), t[h])
             for h in range(0, heads, n)])

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    x = _head_decay_operands((q_ref, k_ref, v_ref, g_ref, beta_ref), inv_ref,
                             masks, chunk, n)
    do, u, d_solved, dq_in, dk_out, dg_end = _walk_back(
        x, st_ref, do_ref, dstate, chunk)

    dp_qk, dp_kk, d_beta, d_rhs, dkg = _solve_back(x, do, u, d_solved, masks,
                                                   dv)

    q32, k32 = x["q32"], x["k32"]
    out_term = dk_out * (k32 * x["e_out"])
    dq = dq_in * x["e_in"]
    dk = dkg * x["e_in"] + dk_out * x["e_out"]
    dG = jnp.sum(dkg * x["kg"] + dq_in * (q32 * x["e_in"]) - out_term,
                 axis=2, keepdims=True)                      # [H, 128, 1]
    # a chunk's last row of G is also its whole decay and the origin of
    # k_out's exponents
    dG = dG + jnp.concatenate(
        [jnp.where(last_row, jnp.sum(
            dg_end[i] + jnp.sum(out_term[:, c:c + chunk], axis=1,
                                keepdims=True), axis=2, keepdims=True), 0.0)
         for i, c in starts], axis=1)
    # P = raw * exp(G_i - G_j): a row's G gets its row of dP * P, a column's
    # loses its column
    moved = dp_qk * x["p_qk"] + dp_kk * x["p_kk"]
    dG = dG + jnp.sum(moved, axis=2, keepdims=True) \
        - _col(jnp.sum(moved, axis=1, keepdims=True), masks.eye)
    d_raw = per_key_head(jnp.concatenate(
        [dp_qk * x["decay_mask"], dp_kk * x["decay_mask"]], axis=1)) \
        .astype(dt)                                          # [H / n, 256, 128]
    d_query = _dot(d_raw, x["k"], _NN)                       # [H / n, 256, d]
    d_key = _dot(d_raw, x["operand"], _TN)                   # [H / n, 128, d]

    dq_ref[0] = _wide(per_key_head(dq) + d_query[:, :_UNIT]) \
        .astype(dq_ref.dtype)
    dk_ref[0] = _wide(per_key_head(dk) + d_query[:, _UNIT:] + d_key) \
        .astype(dk_ref.dtype)
    dv_ref[0] = _wide(d_rhs[:, :, :dv]).astype(dv_ref.dtype)
    # g_j reaches every later G of its chunk
    dg_ref[0, :, 0] = jnp.sum(jnp.where(masks.lower, dG, 0.0), axis=1,
                              keepdims=True)
    dbeta_ref[0, :, 0] = _row(d_beta, masks.eye)


def _specs(s, heads, dk, dv, backward, n=1):
    """Block specs of a grid step of ``heads`` heads and one unit: (q, k, g
    and their gradients, ``heads / n`` key heads wide; v, o and theirs; then
    a unit and head: a lane-dense row (beta's; a decay a head's), the state,
    a [128, 128] tile). Backward the units come last first."""
    units = s // _UNIT

    def at(t):
        return units - 1 - t if backward else t

    def stream(d, heads=heads):
        return _vmem_spec((1, _UNIT, heads * d),
                          lambda ib, ih, t: (ib, at(t), ih))

    def per_unit(*tile):
        return _vmem_spec((1, heads, 1, *tile),
                          lambda ib, ih, t: (ib, ih, at(t)) + (0,) * len(tile))

    return stream(dk, heads // n), stream(dv), per_unit(1, _UNIT), \
        per_unit(dv, dk), per_unit(_UNIT, _UNIT)


def _heads_per_step(h, n=1):
    """Heads a grid step of ``h``: the most up to ``_HEADS`` that divide
    them, in whole groups of the ``n`` value heads that share a key head;
    None where no such number is."""
    return max((m for m in range(1, min(h, _HEADS) + 1)
                if h % m == 0 and m % n == 0), default=None)


def _flat(t):
    return t.reshape(*t.shape[:2], -1)


# Jitted functions of their own, as the flash calls are: a model's layers
# share one trace of each kernel and one lowering to Mosaic.
@functools.partial(jax.jit, static_argnums=(5, 6))
def _kda_fwd(q, k, v, g, beta_rows, chunk, interpret):
    """(o [B, S, H, dv], then a unit and head: the state it starts from [B,
    H, S / 128, dv, dk], and a_qk, P_kk and the inverse [B, H, S / 128, 128,
    128]): S a multiple of 128, beta_rows [B, H, S / 128, 1, 128] float32."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    heads = _heads_per_step(h)
    wide, wide_v, rows, states, tiles = _specs(s, heads, dk, dv, False)
    tile = (b, h, s // _UNIT, _UNIT, _UNIT)
    o, *kept = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(b, h // heads, s // _UNIT),
        in_specs=[wide, wide, wide_v, wide, rows],
        out_specs=[wide_v, states, tiles, tiles, tiles],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
            jax.ShapeDtypeStruct((b, h, s // _UNIT, dv, dk), _F32),
            jax.ShapeDtypeStruct(tile, q.dtype),
            jax.ShapeDtypeStruct(tile, _F32),
            jax.ShapeDtypeStruct(tile, _F32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="kda_fwd",
    )(_flat(q), _flat(k), _flat(v), _flat(g), beta_rows)
    return o.reshape(b, s, h, dv), *kept


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kda_bwd(chunk, interpret, res, do):
    q, k, v, g, beta_rows, *kept = res
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    heads = _heads_per_step(h)
    wide, wide_v, rows, states, tiles = _specs(s, heads, dk, dv, True)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(b, h // heads, s // _UNIT),
        in_specs=[wide, wide, wide_v, wide, rows, states, tiles, tiles, tiles,
                  wide_v],
        out_specs=[wide, wide, wide_v, wide, rows],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * dk), q.dtype),
            jax.ShapeDtypeStruct((b, s, h * dk), k.dtype),
            jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
            jax.ShapeDtypeStruct((b, s, h * dk), g.dtype),
            jax.ShapeDtypeStruct(beta_rows.shape, _F32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="kda_bwd",
    )(_flat(q), _flat(k), _flat(v), _flat(g), beta_rows, *kept, _flat(do))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta_rows, chunk, interpret):
    return _kda_vjp_fwd(q, k, v, g, beta_rows, chunk, interpret)[0]


def _kda_vjp_fwd(q, k, v, g, beta_rows, chunk, interpret):
    # one trace for the pass and for the recomputed mixer's JVP. What only
    # the kernel makes carries the name KEPT; the operands do not.
    o, *kept = (checkpoint_name(t, KEPT) for t in _registry.traced_once(
        _kda_fwd, q, k, v, g, beta_rows, chunk, interpret))
    return o, (q, k, v, g, beta_rows, *kept)


_kda.defvjp(_kda_vjp_fwd, _kda_bwd)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _gdn_fwd(q, k, v, g_rows, beta_rows, chunk, interpret):
    """``_kda_fwd`` for a decay a head: q, k [B, S, H / n, dk], v [B, S, H,
    dv], g_rows and beta_rows [B, H, S / 128, 1, 128] float32. Returns (o,
    then a unit and head the state it starts from and the inverse)."""
    b, s, hk, dk = q.shape
    h, dv = v.shape[2:]
    n = h // hk
    heads = _heads_per_step(h, n)
    wide, wide_v, rows, states, tiles = _specs(s, heads, dk, dv, False, n)
    o, *kept = pl.pallas_call(
        functools.partial(_head_decay_fwd_kernel, chunk=chunk, n=n),
        grid=(b, h // heads, s // _UNIT),
        in_specs=[wide, wide, wide_v, rows, rows],
        out_specs=[wide_v, states, tiles],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
            jax.ShapeDtypeStruct((b, h, s // _UNIT, dv, dk), _F32),
            jax.ShapeDtypeStruct((b, h, s // _UNIT, _UNIT, _UNIT), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="gdn_fwd",
    )(_flat(q), _flat(k), _flat(v), g_rows, beta_rows)
    return o.reshape(b, s, h, dv), *kept


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gdn_bwd(chunk, interpret, res, do):
    q, k, v, g_rows, beta_rows, *kept = res
    b, s, hk, dk = q.shape
    h, dv = v.shape[2:]
    n = h // hk
    heads = _heads_per_step(h, n)
    wide, wide_v, rows, states, tiles = _specs(s, heads, dk, dv, True, n)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_head_decay_bwd_kernel, chunk=chunk, n=n),
        grid=(b, h // heads, s // _UNIT),
        in_specs=[wide, wide, wide_v, rows, rows, states, tiles, wide_v],
        out_specs=[wide, wide, wide_v, rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, hk * dk), q.dtype),
            jax.ShapeDtypeStruct((b, s, hk * dk), k.dtype),
            jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
            jax.ShapeDtypeStruct(g_rows.shape, _F32),
            jax.ShapeDtypeStruct(beta_rows.shape, _F32),
        ],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="gdn_bwd",
    )(_flat(q), _flat(k), _flat(v), g_rows, beta_rows, *kept, _flat(do))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg, dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn(q, k, v, g_rows, beta_rows, chunk, interpret):
    return _gdn_vjp_fwd(q, k, v, g_rows, beta_rows, chunk, interpret)[0]


def _gdn_vjp_fwd(q, k, v, g_rows, beta_rows, chunk, interpret):
    o, *kept = _registry.traced_once(_gdn_fwd, q, k, v, g_rows, beta_rows,
                                     chunk, interpret)
    return o, (q, k, v, g_rows, beta_rows, *kept)


_gdn.defvjp(_gdn_vjp_fwd, _gdn_bwd)


def _kda_chunked_pallas(q, k, v, g, beta, chunk, interpret=False):
    """Pallas body: the shape rule, the padding to whole units, beta (and a
    decay a head) as a lane-dense row a unit and head. ``g``'s rank says
    which pair of kernels: ``kda_fwd`` / ``kda_bwd`` for a decay a channel,
    ``gdn_fwd`` / ``gdn_bwd`` for a decay a head, which read a group's key
    head in place; a decay a channel under grouped key heads (no model has
    that pair) takes a value head's own copy of q and k."""
    h = v.shape[2]
    head_decay = g.ndim == 3
    if not head_decay and q.shape[2] != h:
        q, k = (jnp.repeat(t, h // t.shape[2], axis=2) for t in (q, k))
    b, s, hk, dk = q.shape
    if dk % 128 or v.shape[-1] % 128 or _UNIT % chunk or chunk < 2 \
            or q.dtype != k.dtype or _heads_per_step(h, h // hk) is None:
        # a head that is no whole lane tile (kimi_linear_tiny: 16)
        return _reference._kda_chunked(q, k, v, g, beta, chunk)
    pad = (-s) % _UNIT
    g = g.astype(_F32)
    beta = beta.astype(_F32)
    if pad:
        # a padded position neither decays (g = 0) nor writes (beta = 0)
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))

    def rows(t):
        return t.transpose(0, 2, 1).reshape(b, h, -1, 1, _UNIT)

    if head_decay:
        return _gdn(q, k, v, rows(g), rows(beta), chunk, interpret)[:, :s]
    return _kda(q, k, v, g, rows(beta), chunk, interpret)[:, :s]


_registry.register_kernel(
    "kda_chunked", _reference._kda_chunked, _kda_chunked_pallas,
    doc="chunked gated delta rule; a chunk's scores, inverse and state "
        "never in HBM")
