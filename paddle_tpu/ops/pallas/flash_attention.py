"""Flash attention: blockwise online-softmax attention, forward and
backward, with the dense attention it is held against.

The [S, S] score matrix never exists in HBM (the reference materialises
scores in operators/math/ softmax + matmul calls). Forward is one Pallas
kernel and backward is one: it rebuilds each score tile once, from the
saved logsumexp, and takes dQ, dK, dV and the key-bias gradient from it
(no bias operand and no such gradient where the call gave no bias).
Which body a call runs is the registry's choice (``ops/pallas/registry.py``).

**Two operand layouts, one kernel body a direction.** What a program of the
grid works on is a *lane tile* of heads: ``rows`` positions of the heads
that share 128 lanes (or one head of any size). The operands' rank says
where the tiles lie:

- heads-major, rank 4: q [B, H, S, D], k [B, Hkv, S, D], v [B, Hkv, S, Dv].
  A tile is one head, of any size (192 lies on two lane tiles), and Dv may
  differ from D. What ``blocks.causal_attention`` calls.
- rows-major, rank 3: q [B, S, H D], k and v [B, S, Hkv D], or one [B, S,
  3 H D] array that holds the three side by side: **the layout the
  projection matmuls produce and consume**, so nothing is split, reshaped or
  transposed in HBM around the call, and the context goes out as [B, S,
  H D]. The ``BlockSpec`` index maps address the heads along the lanes
  (q's tile j of the packed array is lane block j, k's ``H D / 128 + j``,
  v's ``2 H D / 128 + j``). At D = 128 a tile is a head. At D = 64 it is
  **two heads side by side**, and the kernels never slice it at lane 64:
  they take the two apart by what the MXU contracts over. With ``m_h`` the
  0/1 mask of head h's lanes, ``(Q m_h) K^T`` contracts over all 128 lanes
  and the other head's add exact zeros; ``P_h V`` fills both heads' lanes
  and a select keeps head h's; the backward likewise (``K m_h`` and ``V m_h``
  for the scores and dP, selects on dK and dV, and ``(K m_h)^T dZ_h^T``
  summed over the pair into one [128, S] float32 dQ^T). The sums over zeros
  are exact, so the arithmetic is the heads-major call's. What BERT calls.
  The blocks take D of 64 or 128 with ``H D`` a multiple of 128, Dv = D and,
  at 64, as many key/value heads as query heads; a rank-3 call of any other
  shape (an odd count of 64-wide heads, D = 192) is transposed to
  heads-major here and runs those blocks (``_takes_rows_major``).

Nothing but the operands' shape chooses; the registry's gauge names the
layout a call took (``operand_layout``).

Two things a call may say beside ``causal``, both static Python values of
the call and no option of the program:

- ``window``: a query sees the ``window`` keys that end at its own (causal
  only). The kernels skip what lies outside the band and do not mask it: the
  forward's key loop starts at the block that holds ``q0 - window + 1``, the
  backward's query loop for a key block ends at the block that holds ``k0 +
  block_k - 1 + window - 1`` (``_key_blocks``, ``_query_blocks``: the bounds
  the loops run over, and the ones ``tiles_visited_pct`` counts). Every
  tile a loop visits is masked, also the ones no edge of the visible region
  crosses: the mask is scheduled into vector slots the tile leaves empty,
  and a loop of their own for the crossed tiles costs more than it saves
  (``_FLASH_FWD_GROUP``'s table). What a causal tile cost was the chain from
  product to softmax to product, one tile a trip: a block's tiles run in
  groups of straight-line code (``_in_groups``: whole groups, then the
  fewer than a group left, one a trip), but for a call none of whose blocks
  has more tiles than a group (a window of one block), which keeps its one
  loop (``_causal_group``).
- fewer key/value heads than query heads: H a multiple of Hkv, and query
  head i reads key/value head ``i // (H / Hkv)``. The ``BlockSpec`` index
  maps send it there, so no repeated K or V exists in HBM; the backward
  kernel writes a dK and a dV part a query head and one XLA sum adds a
  group's parts (the group's dQ accumulators, [group, d, S] float32 beside
  its Q and dO, do not fit VMEM at 16 384 positions, so the sum is not
  inside the kernel).

With ``window=None`` the calls are the kernels ``flash_fwd`` and
``flash_bwd`` in either layout; a windowed call is ``flash_fwd_window`` and
``flash_bwd_window`` in a trace, whatever its heads.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["flash_attention", "tiles_visited_pct", "KEPT"]

#: the ``jax.ad_checkpoint.checkpoint_name`` of what a forward call hands its
#: backward that only the kernel makes: the context and the logsumexp. A
#: ``jax.checkpoint`` whose policy saves this name keeps the two and does not
#: run the forward kernel again (``models/blocks.recomputed``); q, k, v and
#: the bias are not named, so they are formed again from the checkpoint's
#: inputs. Under any other checkpoint, or none, the name does nothing.
KEPT = "flash_attention_kept"

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
#: what the backward needs beside its whole-sequence operands (the key
#: block's tiles, the statistics' rows, Mosaic's own stack: 1.1 MiB counted
#: by the compiler at S = 16 384, d = 256), generously
_FLASH_BWD_HEADROOM = 16 << 20
#: where the count of a key block's query tiles is the call's own (not
#: causal), the backward lays up to this many of them out as straight-line
#: code, so that one tile's matmuls run under the next one's elementwise
#: work (v5e, S=4096: 8 tiles 12.0 ms a call, 4 12.6, 1 13.8)
_FLASH_BWD_UNROLL = 8
#: the tiles of a causal call run in groups of this many, straight-line code
#: likewise, a direction. Their count follows the program id, so a block's
#: loop is whole groups and then the fewer than a group that are left, one
#: an iteration; the forward's last tile stands behind the loops. Kernels
#: alone, v5e, ms a call, forward | backward (my chip runs, PR 46). Groups
#: of 1, 2, 4, 8, measured with the tiles under the diagonal unmasked and in
#: loops of their own (the parent's one loop first): Kanana's [1, 32, 16384,
#: 192 / 128] 25.9 | 53.9, then 25.3 | 54.6, 24.2 | 52.5, 23.1 | 51.6, 22.9 |
#: 51.7; Laguna's [1, 48 over 8, 16384, 128] 27.2 | 54.0, then 27.4 | 53.4,
#: 26.0 | 50.7, 24.0 | 49.7, 23.8 | 49.9; Qwen3-Next's [1, 16 over 2, 16384,
#: 256] 15.9 | 34.2, then 16.3 | 35.0, 15.1 | 33.7, 14.4 | 33.1, 14.4 | 39.4
#: (eight tiles of a head of 256 no longer sit in the registers' shadow).
#: Groups of 4 with that split (the last tile straight-line in the forward,
#: the diagonal's in a loop of one trip in the backward) against groups of 4
#: over every tile, all masked, as it is now: Kanana 22.29 | 51.58 and 22.61
#: | 50.94, Laguna 22.85 | 49.75 and 23.09 | 49.13, Qwen3-Next 13.80 | 33.09
#: and 13.77 | 32.65, OLMoE's [2, 16, 4096, 128] 1.323 | 2.692 and 1.342 |
#: 2.595: the mask is 0 to 1.5% of a forward, the loop it saves 1.2 to 3.7%
#: of a backward. The forward's also where the count is the call's own:
#: BERT's [8, 4096, 3 x 768], two heads a lane tile, 6.82 ms at 1, 5.84 at
#: 2, 5.45 at 4
_FLASH_FWD_GROUP = 4
_FLASH_BWD_GROUP = 4
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
    [bq, d], k_blk [bk, d], b_blk [bk] additive key bias, or None where the
    call gave none; q0/k0 are the tile's absolute row/col offsets for the
    causal mask. Shared by the forward and the backward kernel so masking/
    bias can never drift between them. ``transposed`` gives the tile as
    [bk, bq] (K Q^T, what the backward wants); b_blk is then a [bk, 1]
    column. With ``window`` a query sees only the ``window`` keys that end
    at its own."""
    rows, cols = (k_blk, qs) if transposed else (qs, k_blk)
    s = jax.lax.dot_general(
        rows, cols, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [bq, bk] | [bk, bq]
    if b_blk is not None:
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


def _causal_group(blocks, programs, sizes, group):
    """``group`` for a causal call, whose programs' counts of tiles follow
    their ids, or 1 where none of its ``programs`` visits more tiles than
    ``group`` (static, from the sizes alone): such a call (a window of one
    block: two tiles a block) keeps its one loop (v5e, Laguna's [1, 64 over
    8, 16384, 128] behind 512: 5.69 | 11.91 ms a call, in groups 5.57 |
    12.28)."""
    first, end = blocks(np.arange(programs), *sizes, xp=np)
    return group if np.max(end - first) > group else 1


def _in_groups(lo, hi, tile, carry, group):
    """``carry = tile(j, carry)`` for j in [lo, hi) in order, ``group``
    tiles an iteration as straight-line code, so that one tile's products
    run under the next one's elementwise work. Static bounds: the largest
    divisor of the count up to ``group``, and no loop where that is the
    count. Traced bounds (a causal block's count follows its program id):
    whole groups, then the fewer than ``group`` that are left singly;
    nothing is padded with masked tiles to make the count even."""
    count = None
    if isinstance(lo, int) and isinstance(hi, int):
        count = hi - lo
        group = max(u for u in range(1, max(1, min(count, group)) + 1)
                    if count % u == 0)
    start = lo

    def many(g, carry):
        for i in range(group):
            carry = tile(start + g * group + i, carry)
        return carry

    if group == count:
        return many(0, carry)
    if group > 1:
        whole = (hi - lo) // group
        carry = lax.fori_loop(0, whole, many, carry)
        if count is not None:       # a divisor of it: nothing is left
            return carry
        lo = lo + whole * group
    return lax.fori_loop(lo, hi, tile, carry)


#: lanes of a vector register, and of a rows-major block's lane tile
_LANES = 128


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """What a call's operands say of themselves (``_geometry``)."""
    b: int
    h: int
    hkv: int
    s: int
    d: int
    dv: int
    #: rank 3: the heads lie along the lanes of [B, S, heads D] arrays
    rows_major: bool
    #: heads a lane tile: 2 at D = 64 rows-major, else 1
    per_tile: int
    #: the first lane tile of q, k and v in the arrays that hold them: not
    #: all 0 where one [B, S, 3 H D] array holds the three
    first: tuple

    @property
    def group(self):
        return self.h // self.hkv


def _geometry(operands, heads):
    """The sizes of a call from its operands: (q, k, v) heads-major at rank
    4, rows-major at rank 3, where ``heads`` tells the query heads and a
    tuple of one array is q, k and v side by side."""
    q = operands[0]
    if q.ndim == 4:
        _, k, v = operands
        b, h, s, d = q.shape
        return _Geometry(b, h, k.shape[1], s, d, v.shape[-1], False, 1,
                         (0, 0, 0))
    b, s, width = q.shape
    if len(operands) == 1:
        d = width // (3 * heads)
        hkv, dv = heads, d
        first = (0, heads * d // _LANES, 2 * heads * d // _LANES)
    else:
        _, k, v = operands
        d = width // heads
        hkv = k.shape[-1] // d
        dv = v.shape[-1] // hkv
        first = (0, 0, 0)
    return _Geometry(b, heads, hkv, s, d, dv, True, max(1, _LANES // d),
                     first)


def _takes_rows_major(geo):
    """True where the rows-major blocks take a rank-3 call: every lane tile
    is whole heads (D of 64 or 128, ``H D`` a multiple of 128), the values
    are as wide as the scores, and at two heads a tile a key/value pair lies
    under every query pair."""
    return (geo.d in (64, _LANES) and geo.dv == geo.d
            and (geo.h * geo.d) % _LANES == 0
            and (geo.per_tile == 1 or geo.hkv == geo.h))


def _tile(ref, j, rows=slice(None)):
    """The index of ``rows`` positions of lane tile ``j`` of a program's
    block: head j of a heads-major [1, heads, rows, D] block, lanes [128 j,
    128 j + 128) of a rows-major [1, rows, 128 tiles] one (a static, aligned
    slice)."""
    if len(ref.shape) == 4:
        return (0, j, rows, slice(None))
    return (0, rows, slice(_LANES * j, _LANES * (j + 1)))


def _block_tiles(ref):
    """(lane tiles, positions) of a program's block of either layout."""
    if len(ref.shape) == 4:
        return ref.shape[1], ref.shape[2]
    return ref.shape[2] // _LANES, ref.shape[1]


def _head_of_lane(shape, per_tile):
    """Which of a lane tile's ``per_tile`` heads each lane of a [rows, 128]
    array belongs to."""
    return lax.broadcasted_iota(jnp.int32, shape, 1) // (_LANES // per_tile)


def _head_lanes(x, per_tile):
    """``x`` [rows, 128] once a head of its lane tile, the other heads'
    lanes zero; ``x`` itself where a tile is one head. A product that
    contracts over the lanes then takes one head's part, exactly."""
    if per_tile == 1:
        return [x]
    head = _head_of_lane(x.shape, per_tile)
    return [jnp.where(head == h, x, 0.0) for h in range(per_tile)]


def _merge_heads(parts):
    """One [rows, 128] tile from a [rows, 128] array a head of it: head h's
    lanes from ``parts[h]``."""
    out = parts[0]
    if len(parts) > 1:
        head = _head_of_lane(out.shape, len(parts))
        for h in range(1, len(parts)):
            out = jnp.where(head == h, parts[h], out)
    return out


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, block_k, causal,
                      seq_len, block_q, window=None, per_tile=1):
    """One (batch, lane tiles, q-block) cell: stream K/V blocks, keep running
    (max, sum, acc) a head — the online-softmax recurrence. The logsumexp
    goes out as row ``iq`` of the heads' [nq, bq] block, which stays in VMEM
    across the q-blocks. Where a tile is two heads (``per_tile``) a K/V block
    is read once for both. ``rest`` is (bias_ref, o_ref, lse_ref), or the
    last two alone where the call gave no bias."""
    *bias_ref, o_ref, lse_ref = rest
    tiles, bq = _block_tiles(q_ref)
    dv = v_ref.shape[-1] if len(v_ref.shape) == 4 else _LANES
    nk = seq_len // block_k
    iq = pl.program_id(2)
    sizes = (nk, block_q, block_k, causal, window)

    def tile(j):
        q = q_ref[_tile(q_ref, j)].astype(jnp.float32) * sm_scale  # [bq, d]
        qs = _head_lanes(q, per_tile)

        def body(jk, carry):
            k_blk = k_ref[_tile(k_ref, j, pl.ds(jk * block_k, block_k))] \
                .astype(jnp.float32)                       # [bk, d]
            v_blk = v_ref[_tile(v_ref, j, pl.ds(jk * block_k, block_k))] \
                .astype(jnp.float32)
            b_blk = bias_ref[0][0, 0, pl.ds(jk * block_k, block_k)] \
                .astype(jnp.float32) if bias_ref else None  # [bk]
            out = []
            for q_h, (m_prev, l_prev, acc) in zip(qs, carry):
                s = _masked_scores(q_h, k_blk, b_blk, iq * block_q,
                                   jk * block_k, causal, window=window)
                m_cur = jnp.max(s, axis=-1)                # [bq]
                m_new = jnp.maximum(m_prev, m_cur)
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new[:, None])            # [bq, bk]
                l_new = l_prev * alpha + jnp.sum(p, axis=-1)
                acc = acc * alpha[:, None] + jax.lax.dot_general(
                    p, v_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                out.append((m_new, l_new, acc))
            return tuple(out)

        stats = tuple((jnp.full((bq,), _NEG_INF, jnp.float32),
                       jnp.zeros((bq,), jnp.float32),
                       jnp.zeros((bq, dv), jnp.float32))
                      for _ in range(per_tile))
        if nk == 1:
            stats = body(0, stats)
        else:
            # causal: stop at the diagonal. K blocks entirely above it are
            # fully masked — skipping them halves causal attention FLOPs;
            # windowed: start where the band does
            first, end = _key_blocks(iq, *sizes)
            group = _FLASH_FWD_GROUP if not causal else _causal_group(
                _key_blocks, seq_len // block_q, sizes, _FLASH_FWD_GROUP)
            # a block whose count follows its program id closes with one
            # tile as straight-line code beside the block's end, not one
            # more trip of a loop
            last = int(causal and group > 1)
            stats = _in_groups(first, end - last, body, stats, group)
            if last:
                stats = body(end - 1, stats)
        l_safe = [jnp.maximum(l, 1e-30) for _, l, _ in stats]
        o_ref[_tile(o_ref, j)] = _merge_heads(
            [acc / l[:, None] for (_, _, acc), l in zip(stats, l_safe)]
        ).astype(o_ref.dtype)
        for h, ((m, _, _), l) in enumerate(zip(stats, l_safe)):
            lse_ref[0, j * per_tile + h, pl.ds(iq, 1), :] = \
                _as_row(m + jnp.log(l))

    for j in range(tiles):
        tile(j)


def _heads_per_program(h, nq, nk, group=1, per_tile=1):
    """How many heads one program takes: several where a head is one tile,
    the largest divisor of ``h`` up to ``_FLASH_HEADS_PER_PROGRAM`` that is
    whole lane tiles of ``per_tile`` heads; one tile where the heads of a
    program would not read the same key/value head."""
    if nq > 1 or nk > 1 or group > 1:
        return per_tile
    tiles = h // per_tile
    return per_tile * max(
        n for n in range(1, max(1, min(tiles, _FLASH_HEADS_PER_PROGRAM
                                       // per_tile)) + 1)
        if tiles % n == 0)


def _operand_spec(geo, hb, rows, width, which=None, blocked=True):
    """The ``BlockSpec`` of ``rows`` positions of a program's ``hb`` heads,
    ``width`` a head: row block ``ir`` (the grid's third index) where
    ``blocked``, else the whole sequence. ``which`` is 0, 1 or 2 for q, k or
    v as the forward's operands hold them (k and v a key/value head a group
    of query heads; in a packed array each from its first lane tile on),
    None for an array of the call's own (dO, the results)."""
    group = geo.group if which else 1

    def head(ih):
        return ih // group if group > 1 else ih

    def row(ir):
        return ir if blocked else 0

    if not geo.rows_major:
        return _vmem_spec((1, hb, rows, width),
                          lambda ib, ih, ir: (ib, head(ih), row(ir), 0))
    tiles = hb // geo.per_tile
    first = geo.first[which] // tiles if which is not None else 0
    return _vmem_spec((1, rows, _LANES * tiles),
                      lambda ib, it, ir: (ib, row(ir), first + head(it)))


def _heads_shape(geo, heads, width):
    """The shape of an array of ``heads`` heads of ``width`` in the call's
    layout."""
    if geo.rows_major:
        return (geo.b, geo.s, heads * width)
    return (geo.b, heads, geo.s, width)


# The two calls are jitted functions of their own so that a model's layers,
# which call them with the same shapes, share one trace of the kernel and
# one lowering to Mosaic: a program holds one function a kernel and calls
# it a layer (XLA inlines the calls). Traced anew a layer, the 24 calls of
# BERT-base were 3 s of every start, from the compile cache or not
# (PERF.md section 6, PR 29).
def _call_name(stem, window):
    """A windowed call has a name of its own in a trace."""
    return stem if window is None else stem + "_window"


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8))
def _flash_fwd(operands, bias, sm_scale, causal, block_q, block_k,
               interpret, window=None, heads=None):
    """(o, lse): lse is [B, H, nq, bq] float32, a lane-dense row a query
    block, as the backward reads it, in either layout."""
    geo = _geometry(operands, heads)
    q, k, v = operands if len(operands) == 3 else operands * 3
    b, h, s = geo.b, geo.h, geo.s
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = s // block_q
    hb = _heads_per_program(h, nq, s // block_k, geo.group, geo.per_tile)
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, block_k=block_k,
        causal=causal, seq_len=s, block_q=block_q, window=window,
        per_tile=geo.per_tile)

    def whole(ib, ih, iq):
        return (ib, ih, 0, 0)

    # Mosaic tiling constraint: a block's last two dims must be
    # (8k, 128k)-divisible or equal to the array's — so the per-batch
    # bias rides as [B, 1, S] (block (1, 1, S)), and the heads' lse block
    # is the whole [nq, bq], written back when the heads change.
    # A call without a bias has no such operand, and its tiles add none.
    biased = bias is not None
    return pl.pallas_call(
        kernel,
        grid=(b, h // hb, nq),
        in_specs=[
            _operand_spec(geo, hb, block_q, geo.d, 0),
            _operand_spec(geo, hb, s, geo.d, 1, blocked=False),
            _operand_spec(geo, hb, s, geo.dv, 2, blocked=False),
        ] + [_vmem_spec((1, 1, s), lambda ib, ih, iq: (ib, 0, 0))] * biased,
        out_specs=[
            _operand_spec(geo, hb, block_q, geo.dv),
            _vmem_spec((1, hb, nq, block_q), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(_heads_shape(geo, h, geo.dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, nq, block_q), jnp.float32),
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name=_call_name("flash_fwd", window),
    )(q, k, v, *([bias[:, None, :]] if biased else []))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _flash_attention(operands, bias, sm_scale, causal, block_q, block_k,
                     interpret, window=None, heads=None):
    """``operands`` is (q, k, v), or the one array that holds the three."""
    o, _ = _flash_fwd(operands, bias, sm_scale, causal, block_q, block_k,
                      interpret, window, heads)
    return o


def _flash_attention_fwd(operands, bias, sm_scale, causal, block_q, block_k,
                         interpret, window=None, heads=None):
    """The forward rule: (o, what the backward reads). o and lse carry the
    name ``KEPT``, here on the residuals themselves, because lse never
    leaves the rule: a caller's ``jax.checkpoint`` can keep the pair by a
    policy over that name, and its recomputation then has no use for the
    forward call (the operands and the bias it forms again)."""
    o, lse = (checkpoint_name(t, KEPT) for t in _flash_fwd(
        operands, bias, sm_scale, causal, block_q, block_k, interpret,
        window, heads))
    return o, (operands, bias, o, lse)


def _flash_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, *rest,
                      sm_scale, block_q, block_k, causal, seq_len,
                      window=None, per_tile=1):
    """One (batch, lane tiles, k-block) cell: stream Q/dO blocks, rebuild
    each tile's probabilities once from the saved logsumexp, and take all
    four gradients from it. The tile is built transposed, [bk, bq] = K Q^T,
    so that no product contracts over a tile's rows: dV += p^T dO and
    dK += dz^T Q are plain, and dQ is accumulated transposed,
    dQ^T += K^T dz^T, in a float32 [d, S] scratch a lane tile that lives
    across the key blocks (lse/delta ride as lane-dense [1, bq] rows, and the
    key-bias gradient goes out as one: row ``ik`` of the heads' [nk, bk]
    block). Scores never touch HBM, nor do partial dQs. Where a tile is two
    heads (``per_tile``) a Q/dO block is read once for both, and each adds
    its 64 rows of the tile's dQ^T. ``rest`` is (bias_ref, dq_ref, dk_ref,
    dv_ref, db_ref, dqt_acc); a call without a bias has neither bias_ref nor
    db_ref, and sums no bias gradient."""
    biased = len(rest) == 6
    bias_ref, db_ref = (rest[0], rest[4]) if biased else (None, None)
    dq_ref, dk_ref, dv_ref = rest[1:4] if biased else rest[:3]
    dqt_acc = rest[-1]
    ik = pl.program_id(2)
    tiles, _ = _block_tiles(q_ref)
    nq = seq_len // block_q
    sizes = (nq, block_q, block_k, causal, window)

    @pl.when(ik == 0)
    def _():
        dqt_acc[...] = jnp.zeros_like(dqt_acc)

    b_col = bias_ref[0].astype(jnp.float32) if biased else None  # [bk, 1]

    def lane_tile(j):
        k_blk = k_ref[_tile(k_ref, j)].astype(jnp.float32)     # [bk, d]
        v_blk = v_ref[_tile(v_ref, j)].astype(jnp.float32)
        kt_blk = k_blk.T                                   # [d, bk]
        ks = _head_lanes(k_blk, per_tile)
        vs = _head_lanes(v_blk, per_tile)
        bk, d = k_blk.shape
        # transposed, a tile's heads lie along the sublanes, where a slice
        # at 64 is aligned: a head's rows of K^T and of the tile's dQ^T
        head_rows = [slice(None)] if per_tile == 1 else [
            slice(h * (d // per_tile), (h + 1) * (d // per_tile))
            for h in range(per_tile)]
        kts = [kt_blk] if per_tile == 1 else [kt_blk[r] for r in head_rows]
        dv = v_blk.shape[-1]

        def tile(jq, carry):
            q0 = pl.multiple_of(jq * block_q, block_q)
            qs = q_ref[_tile(q_ref, j, pl.ds(q0, block_q))] \
                .astype(jnp.float32) * sm_scale            # [bq, d]
            do_blk = do_ref[_tile(do_ref, j, pl.ds(q0, block_q))] \
                .astype(jnp.float32)
            out = []
            for h, (dk_acc, dv_acc, *db_acc) in enumerate(carry):
                head = j * per_tile + h
                lse_row = lse_ref[0, head, pl.ds(jq, 1), :]    # [1, bq]
                d_row = delta_ref[0, head, pl.ds(jq, 1), :]
                st = _masked_scores(qs, ks[h], b_col, q0, ik * block_k,
                                    causal, transposed=True, window=window)
                pt = jnp.exp(st - lse_row)                 # [bk, bq]
                dv_acc = dv_acc + jax.lax.dot_general(
                    pt, do_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [bk, d]
                dpt = jax.lax.dot_general(
                    vs[h], do_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [bk, bq]
                dzt = pt * (dpt - d_row)
                dk_acc = dk_acc + jax.lax.dot_general(
                    dzt, qs, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [bk, d]
                db_acc = [acc + jnp.sum(dzt, axis=1, keepdims=True)
                          for acc in db_acc]
                dqt_acc[j, head_rows[h], pl.ds(q0, block_q)] += \
                    jax.lax.dot_general(
                        kts[h], dzt, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)    # [d, bq]
                out.append((dk_acc, dv_acc, *db_acc))
            return tuple(out)

        grads = tuple((jnp.zeros((bk, d), jnp.float32),
                       jnp.zeros((bk, dv), jnp.float32),
                       *([jnp.zeros((bk, 1), jnp.float32)] if biased else []))
                      for _ in range(per_tile))
        if nq == 1:
            grads = tile(0, grads)
        else:
            # causal: q-blocks strictly above the diagonal see only masked
            # scores, so the loop starts at the diagonal; windowed: ends
            # where the band does. Not causal: the count is the call's own
            group = _FLASH_BWD_UNROLL if not causal else _causal_group(
                _query_blocks, seq_len // block_k, sizes, _FLASH_BWD_GROUP)
            grads = _in_groups(*_query_blocks(ik, *sizes), tile, grads, group)
        dk_ref[_tile(dk_ref, j)] = _merge_heads(
            [g[0] for g in grads]).astype(dk_ref.dtype)
        dv_ref[_tile(dv_ref, j)] = _merge_heads(
            [g[1] for g in grads]).astype(dv_ref.dtype)
        if biased:
            for h, (_, _, db) in enumerate(grads):
                db_ref[0, j * per_tile + h, pl.ds(ik, 1), :] = _as_row(db)

    for j in range(tiles):
        lane_tile(j)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _():
        for j in range(tiles):
            dq_ref[_tile(dq_ref, j)] = (dqt_acc[j].T * sm_scale) \
                .astype(dq_ref.dtype)


def _head_sums(x, heads, factors):
    """[B, S, heads] float32: the sums of ``x`` [B, S, heads D] float32
    over each head's D lanes, as a product with the heads' 0/1 indicator
    matrix. A reduce over a part of the lanes would first relayout ``x``
    (XLA copies the float32 array with the positions minor: 100 MB a BERT
    layer; compiler, PR 36). The product is held to the reduce's arithmetic
    by its precision: where ``x`` is a product of two ``factors`` of 8
    significant bits (bfloat16) it has 16, two bfloat16 pieces hold it
    exactly and three passes (HIGH) add them in float32; otherwise six."""
    member = jnp.repeat(jnp.eye(heads, dtype=jnp.float32),
                        x.shape[-1] // heads, axis=0)      # [heads D, heads]
    return jax.lax.dot_general(
        x, member, (((2,), (0,)), ((), ())),
        precision=lax.Precision.HIGH if factors == jnp.bfloat16
        else lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _bwd_compiler_params(resident_bytes):
    """The backward call's compiler parameters where its whole-sequence
    operands (Q, dO and dQ in the operands' two-byte dtype, each in the
    pipeline's two buffers and on whole rows of 128 lanes, and dQ's float32
    accumulator) take ``resident_bytes`` of VMEM: the 64 MiB every call has
    had, and where they and ``_FLASH_BWD_HEADROOM`` pass it (16 384
    positions of a head of 256: 64 MiB resident, 65.12 counted by the
    compiler, 80 MiB asked; of a head of 192 scored and 128 carried: 52
    resident, since 192 lies on 256 lanes, 64.54 counted where the call has
    a bias, 68 asked) that much, of the 128 MiB a v5e core has."""
    need = resident_bytes + _FLASH_BWD_HEADROOM
    if need <= _FLASH_BWD_COMPILER_PARAMS.vmem_limit_bytes:
        return _FLASH_BWD_COMPILER_PARAMS
    return dataclasses.replace(_FLASH_BWD_COMPILER_PARAMS,
                               vmem_limit_bytes=need)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _flash_attention_bwd(sm_scale, causal, block_q, block_k, interpret,
                         window, heads, res, do):
    """Blockwise recompute backward as one Pallas kernel, ``flash_bwd``,
    gridded over key blocks: it yields dK and dV, and dQ with them. Live
    memory stays O(block · S); the [S, S] score matrix never exists. Where
    a group of query heads shares a key/value head the kernel writes each
    query head's dK and dV and the group's are summed after it. The
    gradients go back in the operands' layout; where one array held q, k
    and v, as one: [dQ | dK | dV]."""
    operands, bias, o, lse = res
    geo = _geometry(operands, heads)
    q, k, v = operands if len(operands) == 3 else operands * 3
    b, h, s, group = geo.b, geo.h, geo.s, geo.group
    nq, nk = s // block_q, s // block_k
    hb = _heads_per_program(h, nq, nk, group, geo.per_tile)
    delta = do.astype(jnp.float32) * o.astype(jnp.float32)
    if geo.rows_major:
        delta = _head_sums(delta, h, do.dtype).transpose(0, 2, 1)
    else:
        delta = jnp.sum(delta, -1)
    kernel = functools.partial(
        _flash_bwd_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, causal=causal, seq_len=s, window=window,
        per_tile=geo.per_tile)

    def whole(ib, ih, ik):
        return (ib, ih, 0, 0)

    def lanes(width):
        """What a head of ``width`` takes of VMEM's rows of 128 lanes."""
        return width if geo.rows_major else -(-width // _LANES) * _LANES

    # lse/delta as one lane-dense row a query block ([B,H,nq,bq]), the
    # key-bias gradient as one a key block ([B,H,nk,bk]); the bias as a
    # column ([B,S,1]), since it runs down the transposed tile's rows. A
    # call without a bias has neither, and its kernel sums no gradient of one
    biased = bias is not None
    dq, dk, dv, *dbh = pl.pallas_call(
        kernel,
        grid=(b, h // hb, nk),
        in_specs=[
            _operand_spec(geo, hb, s, geo.d, 0, blocked=False),
            _operand_spec(geo, hb, s, geo.dv, blocked=False),
            _vmem_spec((1, hb, nq, block_q), whole),
            _vmem_spec((1, hb, nq, block_q), whole),
            _operand_spec(geo, hb, block_k, geo.d, 1),
            _operand_spec(geo, hb, block_k, geo.dv, 2),
        ] + [_vmem_spec((1, block_k, 1), lambda ib, ih, ik: (ib, ik, 0))
             ] * biased,
        out_specs=[
            _operand_spec(geo, hb, s, geo.d, blocked=False),
            _operand_spec(geo, hb, block_k, geo.d),
            _operand_spec(geo, hb, block_k, geo.dv),
        ] + [_vmem_spec((1, hb, nk, block_k), whole)] * biased,
        out_shape=[
            jax.ShapeDtypeStruct(_heads_shape(geo, h, geo.d), q.dtype),
            jax.ShapeDtypeStruct(_heads_shape(geo, h, geo.d), k.dtype),
            jax.ShapeDtypeStruct(_heads_shape(geo, h, geo.dv), v.dtype),
        ] + [jax.ShapeDtypeStruct((b, h, nk, block_k), jnp.float32)] * biased,
        scratch_shapes=[pltpu.VMEM(
            (hb // geo.per_tile, _LANES if geo.rows_major else geo.d, s),
            jnp.float32)],
        compiler_params=_bwd_compiler_params(
            hb * s * (2 * 2 * (2 * lanes(geo.d) + lanes(geo.dv))
                      + 4 * geo.d)),
        interpret=interpret,
        name=_call_name("flash_bwd", window),
    )(q, do, lse, delta.reshape(b, h, nq, block_q), k, v,
      *([bias[:, :, None]] if biased else []))
    if group > 1:
        split, axis = ((b, s, geo.hkv, group, -1), 3) if geo.rows_major \
            else ((b, geo.hkv, group, s, -1), 2)
        dk, dv = (jnp.sum(t.reshape(split).astype(jnp.float32), axis=axis)
                  .astype(t.dtype) for t in (dk, dv))
        if geo.rows_major:
            dk, dv = dk.reshape(b, s, -1), dv.reshape(b, s, -1)
    dbias = jnp.sum(dbh[0].reshape(b, h, s), axis=1).astype(bias.dtype) \
        if biased else None                                # [B,S]
    grads = (dq, dk, dv) if len(operands) == 3 \
        else (jnp.concatenate([dq, dk, dv], axis=-1),)
    return grads, dbias


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _heads_major(q, k, v, num_heads):
    """Rank-3 operands ([B, S, heads D] each, or q alone holding the three
    side by side) as [B, heads, S, D]: what the dense reference computes
    on, and what the Pallas body hands the heads-major blocks where the
    rows-major ones do not take a shape."""
    if k is None:
        q, k, v = jnp.split(q, 3, axis=-1)
    b, s, _ = q.shape
    d = q.shape[-1] // num_heads

    def heads(t, width):
        return t.reshape(b, s, -1, width).transpose(0, 2, 1, 3)

    k = heads(k, d)
    return heads(q, d), k, heads(v, v.shape[-1] // k.shape[1])


def _rows_major(ctx):
    """[B, H, S, Dv] as [B, S, H Dv]."""
    b, _, s, _ = ctx.shape
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)


def _dense_attention_reference(q, k=None, v=None, bias=None, causal=False,
                               sm_scale=None, block_q=512, block_k=512,
                               window=None, num_heads=None):
    """Stock-jnp attention (scores materialized): the semantic reference
    the flash kernel is pinned against. block_q/block_k are accepted (and
    ignored) so both bodies share one signature. Fewer key/value heads than
    query heads are repeated to the query heads here; rank-3 operands are
    taken to heads and the result back."""
    q = jnp.asarray(q)
    if q.ndim == 3:
        return _rows_major(_dense_attention_reference(
            *_heads_major(q, k, v, num_heads), bias=bias, causal=causal,
            sm_scale=sm_scale, window=window))
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


def operand_layout(q, k=None, v=None, num_heads=None, **_):
    """"rows_major" where a call of these operands runs the rows-major
    blocks, "" where the heads-major ones: the operands' rank and sizes
    decide and nothing else. The registry's gauge carries it
    (``pallas_kernels_selected{kernel, body}``: ``pallas_rows_major``)."""
    if np.ndim(q) != 3:
        return ""
    operands = (q,) if k is None else (q, k, v)
    return "rows_major" if _takes_rows_major(
        _geometry(operands, num_heads)) else ""


def _flash_attention_pallas(q, k=None, v=None, bias=None, causal=False,
                            sm_scale=None, block_q=512, block_k=512,
                            window=None, num_heads=None, interpret=False):
    """Pallas body: the operands' layout, block-size resolution, 128-lane
    padding, kernel call."""
    q = jnp.asarray(q)
    if q.ndim == 3 and not operand_layout(q, k, v, num_heads):
        return _rows_major(_flash_attention_pallas(
            *_heads_major(q, k, v, num_heads), bias=bias, causal=causal,
            sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            window=window, interpret=interpret))
    operands = (q,) if k is None else (q, jnp.asarray(k), jnp.asarray(v))
    geo = _geometry(operands, num_heads)
    b, s = geo.b, geo.s
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(geo.d)
    block_q, block_k, pad = _blocks(s, block_q, block_k)
    # no bias is a fact of the call: its kernels add none and sum no
    # gradient of one. A padded call has one by construction
    if bias is not None or pad:
        bias = jnp.zeros((b, s), jnp.float32) if bias is None \
            else jnp.asarray(bias, jnp.float32).reshape(b, s)
    positions = 1 if geo.rows_major else 2
    if pad:
        zf = [(0, 0)] * q.ndim
        zf[positions] = (0, pad)
        operands = tuple(jnp.pad(t, zf) for t in operands)
        bias = jnp.pad(bias, ((0, 0), (0, pad)),
                       constant_values=_NEG_INF)
    out = _flash_attention(operands, bias, float(sm_scale), bool(causal),
                           int(block_q), int(block_k), bool(interpret),
                           window, num_heads)
    if pad:
        out = lax.slice_in_dim(out, 0, s, axis=positions)
    return out


def flash_attention(q, k=None, v=None, bias=None, causal=False,
                    sm_scale=None, block_q=512, block_k=512, window=None,
                    num_heads=None):
    """Blockwise (flash) attention.

    Heads-major: q [B, H, S, D], k [B, Hkv, S, D], v [B, Hkv, S, Dv], and
    the result [B, H, S, Dv]. Dv may differ from D (latent attention scores
    on 192 channels and carries 128) and D need be no multiple of 128: a
    block takes the whole head, whatever its size, and Mosaic lays 192 out
    on two lane tiles.

    Rows-major, the layout of a projection's product: q [B, S, H D], k and v
    [B, S, Hkv D] with ``num_heads`` = H; or q alone, [B, S, 3 H D], holding
    q, k and v side by side (``x @ qkv_w`` as it is). The result is [B, S,
    H D] and goes into the output projection as it is. No transpose, split
    or reshape in HBM where D is 64 or 128 and ``H D`` a multiple of 128
    (module docstring); any other shape is transposed to heads-major here.

    H is a multiple of Hkv and query head i reads key/value head ``i // (H /
    Hkv)``. bias: optional [B, S] additive key bias (e.g. key-padding mask as
    0 / -inf). ``window`` (with ``causal``): a query sees the ``window`` keys
    that end at its own; one that reaches the whole sequence is the causal
    call. The result has q's dtype. The default ``sm_scale`` is 1 / sqrt(D).
    Sequence is padded to the block size internally (padded keys masked).
    """
    if np.ndim(q) == 3:
        if num_heads is None or (k is None) != (v is None):
            raise ValueError("rank-3 operands: num_heads, and k and v both "
                             "or neither")
        geo = _geometry((q,) if k is None else (q, k, v), num_heads)
        # a value head's size is what v's width leaves a key head
        positions, heads = geo.s, (geo.h, geo.hkv, geo.hkv)
    else:
        positions = q.shape[2]
        heads = (q.shape[1], k.shape[1], v.shape[1])
    if heads[0] % heads[1] or heads[1] != heads[2]:
        raise ValueError(f"{heads[0]} query heads over {heads[1]} key "
                         f"and {heads[2]} value heads")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a positive count of keys behind "
                             "a causal query")
        if window >= positions:
            window = None
    return _registry.dispatch(
        "flash_attention", q, k, v, bias=bias, causal=causal,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k, window=window,
        num_heads=num_heads)


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
    batch_leading=("q", "k", "v", "bias"), layout=operand_layout)
