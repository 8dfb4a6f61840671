"""The chunked state-space scan (``paddle_tpu/ops/ssd.py``: Mamba-2's SSD) as
Mosaic kernels, a forward and a backward one tied by a ``jax.custom_vjp``:
``ssd_fwd`` and ``ssd_bwd``.

**Grid and layout.** A grid step is a batch row, a *unit* of ``_STEP``
chunks of ``chunk`` positions and a ``B``/``C`` group; the units of a row are
a sequential axis (backward, last first) and every head's state ``[P, N]``
(float32; backward its gradient) lives in a VMEM scratch across them, ``[G,
heads a group x P, N]``. The operands are read **as the mixer has them**:
``x | B | C`` as the ONE rows-major array its convolution writes, ``[b, S, H
P + 2 G N]``, in blocks of a unit's whole rows (the groups are the innermost
grid axis, so a block is fetched once a unit; a group takes its heads' ``H P
/ G`` lanes of ``x`` and its ``N`` lanes of ``B`` and of ``C`` out of it),
``dt`` and the log-decay ``[b, S, H]`` float32 a unit by all heads (a
chunk's running sum is formed once, for all heads, as one product with a
triangle of ones); ``y`` ``[b, S, H P]`` leaves a group's lanes a step, and
the gradient of ``x | B | C`` as one array again, which the convolution's
backward reads as it is. The entry takes ``x`` ``[b, S, H, P]``, ``B`` and
``C`` ``[b, S, G, N]`` apart, as ``ops/ssd.py`` does, and lays them side by
side itself: where they are the column ranges of one array
(``models/nemotron_h._mamba``), the compiler folds that to the array, and
nothing is copied or transposed around the calls.

**A chunk's work stays in VMEM.** Inside a grid step two loops
(``lax.fori_loop``: one copy of the code in the MLIR, however many chunks
and heads): over the unit's chunks, each with the group's scores ``C_i .
B_j`` once, and inside that over the group's *lane tiles*, 128 lanes of
``x`` that hold ``128 / P`` heads (two at P = 64; a head of a multiple of
128 is its own tile). A head's decay ``exp(G_i - G_j)`` is formed on the
lower triangle from the difference, so **no exponent is ever positive**
(``ops/ssd.py``'s rule); the decayed scores times ``dt x`` is one product a
head over the whole tile, of which the head's lanes are taken (the MXU is
128 wide: a product 64 wide costs the same); the starting state's part
``exp(G_i) h_0 C_i``, the skip ``D x`` and the state's update are one
product each a tile. **A number a position and head meets its head's
channels on the MXU**: ``dt`` and ``G`` spread over a head's lanes, and a
head's column of ``G`` over a chunk's lanes for its decays, are products of
the ``[chunk, H]`` block, as three bfloat16 pieces that sum to it exactly
(``_pieces``), with zeros and ones; on the vector units each is a
permutation a vreg, which the first form of these kernels spent more slots
on than on anything else (a layer 0.55 | 1.30 ms that way, 0.51 | 1.03 this).
No ``[chunk, chunk]`` array reaches HBM. Precision is the reference body's: the
running log-decay, the exponentials and the state float32, the matmul
operands in ``x.dtype`` (bfloat16 in a model) with float32 accumulation,
``y`` in ``x.dtype``.

**Kept for the backward** (``KEPT``): ``y`` and the state every unit starts
from, float32 ``[b, units, H P, N]`` (32 MiB each a layer at ``[1, 8192, 32,
64]`` with a state of 128): what only the kernel makes. A caller's
``jax.checkpoint`` keeps them by name (``models/blocks.recomputed``) and
leaves ``ssd_fwd`` out of its recomputation: the forward runs once a layer.
``ssd_bwd`` forms the states inside a unit again (one state update a chunk
but the last), then walks the chunks backwards with the states' gradient in
the scratch, forms the scores and decays again and takes all six gradients.
``G``'s gradient has no ``[chunk, chunk]`` term of its own: through a head's
decays a row gains ``dy . (W u)`` and a column loses ``u . (W^T dy)``, sums
over the head's channels of products the kernel forms anyway, both of the
same rounded ``W``, so that they cancel over a chunk to float32's rounding
before the running sum back to ``a``; ``D``'s leaves as a partial sum a unit and lane, summed
outside.

A shape the blocks cannot tile (a chunk that is no multiple of 8 rows, a
head that neither divides nor is a multiple of 128 lanes, groups whose
``N`` is no multiple of 128, states beyond the VMEM budget) takes the
reference body.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import ssd as _reference
from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["KEPT"]

#: the ``jax.ad_checkpoint.checkpoint_name`` of what ``ssd_fwd`` hands
#: ``ssd_bwd`` that only the kernel makes: ``y`` and the units' starting
#: states. As ``flash_attention.KEPT`` and ``kda.KEPT``: a ``jax.checkpoint``
#: whose policy saves the name (``models/blocks.recomputed``) does not run
#: the forward kernel again.
KEPT = "ssd_kept"

_LANES = 128
#: chunks a grid step, a *unit*: ``ssd_fwd`` keeps the state a unit starts
#: from, not a chunk's, and ``ssd_bwd`` forms the states inside a unit again
#: (one state update a chunk but the last). Two halve what a layer keeps (32
#: MiB at the cell's size for 64) and the grid's steps; with one, the five
#: layers' states and outputs did not fit beside the step (PERF.md section
#: 6, PR 50).
_STEP = 2
#: lane tiles an iteration of a group's loop (``_over_tiles``). On a v5e at
#: the cell's size, a layer forward | backward: one 0.63 | 1.50 ms, two 0.51
#: | 1.03 (PERF.md section 6, PR 50); four would be a twentieth and a tenth
#: less by the compiler's schedule and cost every set-up another second of
#: lowering: not taken
_PAIR = 2
_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
# the axes contracted of two 2-D operands
_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 << 20)


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, (((dims[0],), (dims[1],)), ((), ())),
                           precision=precision, preferred_element_type=_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _chunk_rows(c, chunk):
    return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)


def _over_tiles(tiles, tile, carry):
    """``tile(k, carry)`` for every lane tile of a group, ``_PAIR`` tiles an
    iteration of the loop where they come in such numbers: an iteration is
    one chain of products and exponentials, and the scheduler fills the
    slots a chain leaves empty only with another chain of the same
    iteration."""
    pair = _PAIR if tiles % _PAIR == 0 else 1

    def some(i, carry):
        for k in range(pair):
            carry = tile(i * pair + k, carry)
        return carry

    return lax.fori_loop(0, tiles // pair, some, carry)


def _pieces_lanes(h):
    """Lanes of the ``_pieces`` of a [., h] block: three times h, in whole
    lane tiles."""
    return -(-3 * h // _LANES) * _LANES


def _pieces(t):
    """float32 ``t`` [m, n] as three bfloat16 arrays that sum to it exactly,
    side by side along the lanes and padded with zeros to whole lane tiles:
    what a product with zeros and ones takes to be exact in one pass of the
    MXU (the three pieces are summed by the contraction)."""
    high = t.astype(jnp.bfloat16)
    rest = t - high.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
    parts = [high, mid, low]
    pad = _pieces_lanes(t.shape[1]) - 3 * t.shape[1]
    if pad:
        parts.append(jnp.zeros((t.shape[0], pad), jnp.bfloat16))
    return jnp.concatenate(parts, axis=1)


def _sums_scratch(h, chunk):
    """What ``_running_sums`` fills a grid step: (gt_scr, g_pieces,
    dt_pieces)."""
    pieces = pltpu.VMEM((_STEP, chunk, _pieces_lanes(h)), jnp.bfloat16)
    return pltpu.VMEM((_STEP, h, chunk), _F32), pieces, pieces


def _running_sums(a_ref, dt_ref, gt_scr, g_pieces, dt_pieces):
    """The heads' running log-decay inside each chunk of the step: head by
    position (a head's row), and position by head as ``_pieces`` (what a
    tile spreads over its lanes), the step beside it: two products of a
    chunk's ``a`` [chunk, H] with a triangle of ones at float32's
    precision."""
    steps, _, chunk = gt_scr.shape
    i, j = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)

    def sums(c, carry):
        positions = _chunk_rows(c, chunk)
        a = a_ref[0, positions, :]
        g_pieces[c] = _pieces(_dot((j <= i).astype(_F32), a, _NN, _HI))
        gt_scr[c] = _dot(a, (i <= j).astype(_F32), _TN, _HI)
        dt_pieces[c] = _pieces(dt_ref[0, positions, :])
        return carry

    lax.fori_loop(0, steps, sums, 0)


class _Tile:
    """What both kernels read of one lane tile of a group in chunk ``c`` of
    the step: its lanes, the heads on them, and each head's step and
    running log-decay spread over the head's lanes (module docstring: on
    the MXU, from the block's ``_pieces``)."""

    def __init__(self, c, k, ig, p, gt_scr, g_pieces, dt_pieces,
                 lanes_a_group):
        _, h, chunk = gt_scr.shape
        self.h, self.c = h, c
        self.width = max(p, _LANES)
        self.per_tile = self.width // p
        off = pl.multiple_of(k * self.width, self.width)
        self.lanes = pl.ds(off, self.width)       # of the group's block
        self.packed = pl.ds(pl.multiple_of(
            ig * lanes_a_group + k * self.width, self.width), self.width)
        first = (ig * lanes_a_group) // p + k * self.per_tile
        self.heads = [first + e for e in range(self.per_tile)]
        self.on_lanes = _iota((1, self.width), 1) // p
        self.on_rows = _iota((self.width, 1), 0) // p
        self.g_pieces = g_pieces
        on_heads_lanes = self.ones(first + self.on_lanes, self.width)
        self.g = _dot(g_pieces[c], on_heads_lanes, _NN)    # [chunk, width]
        self.dt = _dot(dt_pieces[c], on_heads_lanes, _NN)
        self.to_end = jnp.exp(self.g[chunk - 1:chunk] - self.g)
        self.g_rows = [gt_scr[c, pl.ds(hd, 1), :] for hd in self.heads]
        self.ends = [row[:, chunk - 1:chunk] for row in self.g_rows]  # [1, 1]
        self.lower = _iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)

    def ones(self, head_of_lane, lanes):
        """[pieces' lanes, lanes] of 0 / 1: a piece's column of head ``h``
        on the lanes whose ``head_of_lane`` is ``h``."""
        k = self.g_pieces.shape[2]
        row = _iota((k, lanes), 0)
        hit = (row == head_of_lane) | (row == head_of_lane + self.h) \
            | (row == head_of_lane + 2 * self.h)
        return hit.astype(jnp.bfloat16)

    def spread(self, per_head, over=None):
        """A [1, 1] a head on the head's lanes, or with
        ``over=self.on_rows`` on the head's rows of the state."""
        over = self.on_lanes if over is None else over
        out = per_head[-1]
        for e in range(self.per_tile - 2, -1, -1):
            out = jnp.where(over == e, per_head[e], out)
        return out

    def of_head(self, e, t, over=None):
        """``t`` with zeros off head ``e``'s lanes (rows)."""
        if self.per_tile == 1:
            return t
        over = self.on_lanes if over is None else over
        return jnp.where(over == e, t, jnp.zeros_like(t))

    def decay(self, e):
        """exp(G_i - G_j) on the lower triangle, 0 above it."""
        chunk = self.lower.shape[0]
        g_i = _dot(self.g_pieces[self.c], self.ones(self.heads[e], chunk),
                   _NN)                                    # [chunk, chunk]
        return jnp.exp(jnp.where(self.lower, g_i - self.g_rows[e], -jnp.inf))

    def kept(self):
        """What the chunk leaves of a state, on the heads' rows of it."""
        return self.spread([jnp.exp(end) for end in self.ends], self.on_rows)

    def after(self, h0, u32, Bm, dtype, hi):
        """The state [width, N] behind the chunk that starts from ``h0``."""
        return self.kept() * h0 + _dot((self.to_end * u32).astype(dtype), Bm,
                                       _TN, hi)


def _group_lanes(ig, xbc_ref, channels, n):
    """(the ``n`` lanes of group ``ig``'s ``B``, those of its ``C``) in the
    packed array, whose ``B`` begin behind ``x``'s ``channels`` and whose
    ``C`` half way from there to its end."""
    def lanes(first):
        start = first + ig * n
        return pl.ds(pl.multiple_of(start, _LANES) if n % _LANES == 0
                     else start, n)

    return lanes(channels), lanes((channels + xbc_ref.shape[2]) // 2)


def _fwd_kernel(xbc_ref, dt_ref, a_ref, d_ref, y_ref, h0_ref,
                state, gt_scr, g_pieces, dt_pieces, *, p, n):
    t, ig = pl.program_id(1), pl.program_id(2)
    dtype = xbc_ref.dtype
    hi = _HI if dtype == _F32 else None
    lanes_a_group = y_ref.shape[2]
    b_lanes, c_lanes = _group_lanes(ig, xbc_ref, gt_scr.shape[1] * p, n)
    steps, _, chunk = gt_scr.shape

    @pl.when(t == 0)
    def _():
        state[ig] = jnp.zeros(state.shape[1:], _F32)

    @pl.when(ig == 0)
    def _():
        _running_sums(a_ref, dt_ref, gt_scr, g_pieces, dt_pieces)

    h0_ref[0, 0] = state[ig]

    def a_chunk(c, carry):
        positions = _chunk_rows(c, chunk)
        Bm = xbc_ref[0, positions, b_lanes]
        Cm = xbc_ref[0, positions, c_lanes]
        scores = _dot(Cm, Bm, _NT, hi)                     # [chunk, chunk]

        def tile(k, carry):
            tl = _Tile(c, k, ig, p, gt_scr, g_pieces, dt_pieces,
                       lanes_a_group)
            rows = tl.lanes
            x32 = xbc_ref[0, positions, tl.packed].astype(_F32)
            u32 = tl.dt * x32
            u = u32.astype(dtype)
            y = None
            for e in range(tl.per_tile - 1, -1, -1):
                part = _dot((scores * tl.decay(e)).astype(dtype), u, _NN, hi)
                y = part if y is None else jnp.where(tl.on_lanes == e, part,
                                                     y)
            h0 = state[ig, rows, :]                        # [width, N]
            y = y + jnp.exp(tl.g) * _dot(Cm, h0.astype(dtype), _NT, hi) \
                + d_ref[:, tl.lanes] * x32
            y_ref[0, positions, tl.lanes] = y.astype(y_ref.dtype)
            state[ig, rows, :] = tl.after(h0, u32, Bm, dtype, hi)
            return carry

        return _over_tiles(lanes_a_group // max(p, _LANES), tile, carry)

    lax.fori_loop(0, steps, a_chunk, 0)


def _bwd_kernel(xbc_ref, dt_ref, a_ref, d_ref, h0_ref, dy_ref,
                dxbc_ref, ddt_ref, da_ref, dd_ref,
                dstate, starts, gt_scr, g_pieces, dt_pieces, dg_scr, ddt_scr,
                ds_scr, dbs_scr, dcs_scr, *, p, n):
    t, ig = pl.program_id(1), pl.program_id(2)
    dtype = xbc_ref.dtype
    hi = _HI if dtype == _F32 else None
    lanes_a_group = dy_ref.shape[2]
    b_lanes, c_lanes = _group_lanes(ig, xbc_ref, gt_scr.shape[1] * p, n)
    steps, h, chunk = gt_scr.shape
    tiles = lanes_a_group // max(p, _LANES)

    @pl.when(t == 0)
    def _():
        dstate[ig] = jnp.zeros(dstate.shape[1:], _F32)

    @pl.when(ig == 0)
    def _():
        _running_sums(a_ref, dt_ref, gt_scr, g_pieces, dt_pieces)

    # the states the step's later chunks start from, formed again
    starts[0] = h0_ref[0, 0]

    def replay(c, carry):
        positions = _chunk_rows(c, chunk)
        Bm = xbc_ref[0, positions, b_lanes]

        def tile(k, carry):
            tl = _Tile(c, k, ig, p, gt_scr, g_pieces, dt_pieces,
                       lanes_a_group)
            u32 = tl.dt * xbc_ref[0, positions, tl.packed].astype(_F32)
            starts[c + 1, tl.lanes, :] = tl.after(starts[c, tl.lanes, :], u32,
                                                  Bm, dtype, hi)
            return carry

        return _over_tiles(tiles, tile, carry)

    lax.fori_loop(0, steps - 1, replay, 0)
    dd_ref[...] = jnp.zeros(dd_ref.shape, _F32)
    last_row = _iota((chunk, 1), 0) == chunk - 1
    head_of_lane = _iota((chunk, h), 1)

    def a_chunk(back, carry):
        c = steps - 1 - back
        positions = _chunk_rows(c, chunk)
        Bm = xbc_ref[0, positions, b_lanes]
        Cm = xbc_ref[0, positions, c_lanes]
        scores = _dot(Cm, Bm, _NT, hi)
        ds_scr[...] = jnp.zeros(ds_scr.shape, _F32)
        dbs_scr[...] = jnp.zeros(dbs_scr.shape, _F32)
        dcs_scr[...] = jnp.zeros(dcs_scr.shape, _F32)

        def tile(k, carry):
            tl = _Tile(c, k, ig, p, gt_scr, g_pieces, dt_pieces,
                       lanes_a_group)
            rows = tl.lanes
            x32 = xbc_ref[0, positions, tl.packed].astype(_F32)
            dy = dy_ref[0, positions, tl.lanes]
            dy32 = dy.astype(_F32)
            u32 = tl.dt * x32
            u = u32.astype(dtype)
            h0, dh = starts[c, rows, :], dstate[ig, rows, :]
            h0c, dhc = h0.astype(dtype), dh.astype(dtype)
            from_start = jnp.exp(tl.g)
            dyc = (from_start * dy32).astype(dtype)
            dcs_scr[...] += _dot(dyc, h0c, _NN, hi)
            dbs_scr[...] += _dot((tl.to_end * u32).astype(dtype), dhc, _NN,
                                 hi)
            dstate[ig, rows, :] = _dot(dyc, Cm, _TN, hi) + tl.kept() * dh
            # what y reads of the state, and what the state's end reads of u
            y = from_start * _dot(Cm, h0c, _NT, hi)        # [chunk, width]
            du_state = tl.to_end * _dot(Bm, dhc, _NT, hi)
            du = du_state
            for e in range(tl.per_tile):
                decay = tl.decay(e)
                weights = (scores * decay).astype(dtype)
                dy_e = tl.of_head(e, dy)
                ds_scr[...] += _dot(dy_e, u, _NT, hi) * decay
                du = du + _dot(weights, dy_e, _TN, hi)
                y = y + tl.of_head(e, _dot(weights, u, _NN, hi))
            dxbc_ref[0, positions, tl.packed] = (
                tl.dt * du + d_ref[:, tl.lanes] * dy32).astype(dtype)
            dd_ref[0, 0, :, tl.lanes] += jnp.sum(dy32 * x32, axis=0,
                                                 keepdims=True)
            # G's gradient a position and lane. Through a head's decays a
            # row of them gains what its position reads, dy . (W u), and a
            # column loses what its position writes, u . (W^T dy): both of
            # the same rounded W, so that they cancel over a chunk before
            # the running sum back to ``a``; the same through the two decays
            # of the state, from the chunk's start and to its end
            dg_lanes = dy32 * y - du * u32
            ddt_lanes = du * x32
            to_end = jnp.sum(du_state * u32, axis=0, keepdims=True)
            state_rows = dh * h0                           # [width, N]
            dg, ddt = dg_scr[c], ddt_scr[c]
            for e, hd in enumerate(tl.heads):
                def lane_sum(t, e=e):
                    return jnp.sum(tl.of_head(e, t), axis=1, keepdims=True)

                # the chunk's last G is also the origin of the decays to its
                # end
                at_end = lane_sum(to_end) + jnp.exp(tl.ends[e]) * jnp.sum(
                    jnp.sum(tl.of_head(e, state_rows, tl.on_rows), axis=0,
                            keepdims=True), axis=1, keepdims=True)
                dg_e = lane_sum(dg_lanes) + jnp.where(last_row, at_end, 0.0)
                dg = jnp.where(head_of_lane == hd, dg_e, dg)
                ddt = jnp.where(head_of_lane == hd, lane_sum(ddt_lanes), ddt)
            dg_scr[c] = dg
            ddt_scr[c] = ddt
            return carry

        _over_tiles(tiles, tile, 0)
        ds = ds_scr[...].astype(dtype)
        dxbc_ref[0, positions, c_lanes] = (
            _dot(ds, Bm, _NN, hi) + dcs_scr[...]).astype(dtype)
        dxbc_ref[0, positions, b_lanes] = (
            _dot(ds, Cm, _TN, hi) + dbs_scr[...]).astype(dtype)

        @pl.when(ig == pl.num_programs(2) - 1)
        def _():
            # a_j reaches every later G of its chunk
            i, j = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
            da_ref[0, positions, :] = _dot((j >= i).astype(_F32), dg_scr[c],
                                           _NN, _HI)
            ddt_ref[0, positions, :] = ddt_scr[c]

        return carry

    lax.fori_loop(0, steps, a_chunk, 0)


def _specs(units, h, p, g, n, unit, backward):
    """Block specs of a grid step (batch row, unit of ``_STEP`` chunks,
    group): (the packed ``x | B | C`` and its gradient, whole rows; y and
    its gradient, a group's lanes; dt, the log-decay and theirs; D over the
    lanes; the unit's starting states; D's partial sums). Backward the units
    come last first."""
    lanes = h // g * p

    def at(t):
        return units - 1 - t if backward else t

    return (_vmem_spec((1, unit, h * p + 2 * g * n),
                       lambda ib, t, ig: (ib, at(t), 0)),
            _vmem_spec((1, unit, lanes), lambda ib, t, ig: (ib, at(t), ig)),
            _vmem_spec((1, unit, h), lambda ib, t, ig: (ib, at(t), 0)),
            _vmem_spec((1, lanes), lambda ib, t, ig: (0, ig)),
            _vmem_spec((1, 1, lanes, n),
                       lambda ib, t, ig: (ib, at(t), ig, 0)),
            _vmem_spec((1, 1, 1, lanes),
                       lambda ib, t, ig: (ib, at(t), 0, ig)))


def _sizes(xbc, dt, n, g):
    """(b, S, H, P) of a packed call."""
    b, s, width = xbc.shape
    h = dt.shape[2]
    return b, s, h, (width - 2 * g * n) // h


# Jitted functions of their own, as the flash calls and the delta rule's
# are: a model's layers share one trace of each kernel and one lowering to
# Mosaic. They take ``x | B | C`` as ONE rows-major array, ``[b, S, H P + 2 G
# N]``, and give ``y`` ``[b, S, H P]`` and the packed array's gradient: what
# crosses the ``custom_vjp`` then has one layout, the kernels', and nothing
# stands between the mixer's convolution, which makes the three side by
# side, and the kernels (``_ssd_pallas``). (With ``[b, S, H, P]`` views
# inside, the kept ``y`` and its float32 copy lived as ``[1, 8192, 32,
# 64]``, a head's 64 channels on 128 lanes: 14 ms a step of copies under
# ``ssd_gate`` and ``short_conv``, PERF.md section 6, PR 50.)
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _ssd_fwd(xbc, dt, a, d_lanes, n, g, chunk, interpret):
    """(y [b, S, H P], the units' starting states [b, S / unit, H P, N]
    float32): S a multiple of ``_STEP * chunk``, ``d_lanes`` [1, H P]
    float32."""
    b, s, h, p = _sizes(xbc, dt, n, g)
    unit = _STEP * chunk
    units = s // unit
    packed, wide, heads, skip, states, _ = _specs(units, h, p, g, n, unit,
                                                  False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, n=n),
        grid=(b, units, g),
        in_specs=[packed, heads, heads, skip],
        out_specs=[wide, states],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * p), xbc.dtype),
                   jax.ShapeDtypeStruct((b, units, h * p, n), _F32)],
        scratch_shapes=[pltpu.VMEM((g, h // g * p, n), _F32),
                        *_sums_scratch(h, chunk)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="ssd_fwd",
    )(xbc, dt, a, d_lanes)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _ssd_bwd(n, g, chunk, interpret, res, dy):
    xbc, dt, a, d_lanes, h0 = res
    b, s, h, p = _sizes(xbc, dt, n, g)
    unit = _STEP * chunk
    units = s // unit
    packed, wide, heads, skip, states, partial = _specs(
        units, h, p, g, n, unit, True)
    lanes = h // g * p
    dxbc, ddt, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, n=n),
        grid=(b, units, g),
        in_specs=[packed, heads, heads, skip, states, wide],
        out_specs=[packed, heads, heads, partial],
        out_shape=[jax.ShapeDtypeStruct(xbc.shape, xbc.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(a.shape, _F32),
                   jax.ShapeDtypeStruct((b, units, 1, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((g, lanes, n), _F32),
                        pltpu.VMEM((_STEP, lanes, n), _F32),
                        *_sums_scratch(h, chunk),
                        pltpu.VMEM((_STEP, chunk, h), _F32),
                        pltpu.VMEM((_STEP, chunk, h), _F32),
                        pltpu.VMEM((chunk, chunk), _F32),
                        pltpu.VMEM((chunk, n), _F32),
                        pltpu.VMEM((chunk, n), _F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="ssd_bwd",
    )(xbc, dt, a, d_lanes, h0, dy)
    return dxbc, ddt, da, jnp.sum(dd, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ssd(xbc, dt, a, d_lanes, n, g, chunk, interpret):
    return _ssd_vjp_fwd(xbc, dt, a, d_lanes, n, g, chunk, interpret)[0]


def _ssd_vjp_fwd(xbc, dt, a, d_lanes, n, g, chunk, interpret):
    # one trace for the pass and for the recomputed mixer's JVP, and one
    # lowering for every layer's. What only the kernel makes carries the
    # name KEPT; the operands do not.
    y, h0 = (checkpoint_name(t, KEPT) for t in _registry.lowered_once(
        _ssd_fwd, (xbc, dt, a, d_lanes), (n, g, chunk, interpret)))
    return y, (xbc, dt, a, d_lanes, h0)


_ssd.defvjp(_ssd_vjp_fwd, _ssd_bwd)


def _tiles(x, B, C, chunk):
    """True where the kernels' blocks tile the operands (module docstring)."""
    _, _, h, p = x.shape
    g, n = B.shape[2:]
    lanes = h // g * p
    return chunk % 8 == 0 and (p % _LANES == 0 or _LANES % p == 0) \
        and lanes % _LANES == 0 and (g == 1 or n % _LANES == 0) \
        and x.dtype == B.dtype == C.dtype and _registry.within_vmem_budget(
            "ssd", (h + (2 + _STEP) * h // g) * p * n
        + 4 * _STEP * chunk * lanes)


def _ssd_pallas(x, dt, a, B, C, D, chunk, interpret=False):
    """Pallas body: the shape rule, the padding to whole chunks with
    positions that change nothing, ``D`` a lane (zeros for None)."""
    if not _tiles(x, B, C, chunk):
        return _reference._ssd_chunked(x, dt, a, B, C, D, chunk)
    s, h, p = x.shape[1:]
    pad = -s % (_STEP * chunk)
    dt, a = dt.astype(_F32), a.astype(_F32)
    if pad:
        x, dt, a, B, C = (jnp.pad(t, ((0, 0), (0, pad))
                                  + ((0, 0),) * (t.ndim - 2))
                          for t in (x, dt, a, B, C))
    d_lanes = jnp.zeros((1, h * p), _F32) if D is None \
        else jnp.repeat(D.astype(_F32), p)[None]
    b, padded = x.shape[:2]
    # side by side as the mixer's convolution made them: where x, B and C
    # are the column ranges of one array (``nemotron_h._mamba``), the
    # compiler folds this to that array, and the gradient's ranges back to
    # the one array the convolution's backward reads
    xbc = jnp.concatenate([t.reshape(b, padded, -1) for t in (x, B, C)],
                          axis=-1)
    y = _ssd(xbc, dt, a, d_lanes, B.shape[3], B.shape[2], chunk, interpret)
    return y.reshape(x.shape)[:, :s]


_registry.register_kernel(
    "ssd", _reference._ssd_chunked, _ssd_pallas,
    doc="chunked state-space scan (Mamba-2's SSD); a chunk's scores and "
        "decays never in HBM, the heads' states in VMEM across the chunks")
