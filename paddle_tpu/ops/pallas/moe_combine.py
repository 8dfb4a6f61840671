"""The way back of a dropless expert layer that holds a share of the experts.

``moe_combine(y [T, D] float32, rows [R, D], token [R] int, weight [R]
float32) -> [T, D] float32``: ``y`` with ``weight[r] * rows[r]`` added into
row ``token[r]``, for every r. Each product is formed and summed in float32
and a token takes as many rows as the router sent it (``top_k`` at most in
``parallel/moe._held_experts``, the one caller: a pass's experts' outputs
into ``y`` forward, the rows' gradients into ``dx`` backward). A row of
weight 0 adds nothing, and neither does one whose token is ``T`` or more.

Two bodies behind the kernel registry (``ops/pallas/registry.py``):

- reference: ``y.at[token].add(rows * weight)``, XLA's scatter-add, which
  GSPMD partitions. What the CPU tests run and what ``auto`` takes under a
  mesh of more than one device. On a TPU it sorts the row numbers and
  walks the rows one after another: 4.8 ms for 32 768 rows of 2048, half of
  them of weight 0 (PERF.md section 6, PR 41, has the body below beside it).
- Pallas: the rows are put in **token order** first (one sort of R keys with
  the row number as payload, the rows of weight 0 keyed last; one gather of
  ``rows`` in its own dtype, as far as the rows that count go:
  ``rows_held``), so the rows of a tile of tokens are
  consecutive, and runs of equal tokens are summed on the MXU, megablox
  style as ``grouped_matmul_dw`` sums a group's rows. The work list is the
  (row tile, token tile) pairs that intersect, in order, made outside the
  kernel from the sorted tokens and handed in by scalar prefetch. An entry
  builds the ``[token tile, row tile]`` block that holds ``weight[r]`` where
  row r belongs to token t and 0 elsewhere, and adds its product with the
  row tile into the token tile's block of the result, which stays in VMEM
  over the entries of one token tile. ``y`` is updated in place
  (``input_output_aliases``): a token tile no row touches is neither read
  nor written, and the row tiles wholly of weight 0 are not visited.
  **The arithmetic is the reference's**: the float32 block is split into
  three bfloat16 pieces that sum to it exactly (``kda._sum_over``'s
  split), so with bfloat16 rows every product is exact and the sum is
  float32's, in another order; rows of another dtype are multiplied at
  ``HIGHEST``.

The Pallas body takes ``D`` in whole lane tiles and ``T`` in whole sublane
tiles; any other shape runs the reference body inside it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.grouped_matmul import _tile

__all__ = ["moe_combine", "rows_held"]

_F32 = jnp.float32
#: tokens and rows of a work entry, and the widest block of ``D``
_TILE_T, _TILE_R, _TILE_D = 256, 128, 2304
#: rows one step of ``rows_held`` gathers
_GATHER = 4096
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=64 << 20,
    dimension_semantics=("parallel", "arbitrary"))


def rows_held(x, index, held):
    """``x[index]`` [R, D] for the first ``held`` indices, zeros past them:
    gathered ``_GATHER`` rows at a time as far as ``held`` goes. XLA's gather
    on a TPU walks its rows, 1.5 ms for 32 768 rows of 2048 (PERF.md section
    6, PR 41), and a pass of ``parallel/moe._held_experts`` at par holds
    half a tile: the rows past the rows held are read by nobody."""
    r = index.shape[0]
    if r % _GATHER or r == _GATHER:
        return jnp.take(x, index, axis=0, mode="clip")

    def some(i, rows):
        at = lax.dynamic_slice(index, (i * _GATHER,), (_GATHER,))
        # "clip": no pass over the rows to fill in what an index out of
        # range would get
        return lax.dynamic_update_slice(
            rows, jnp.take(x, at, axis=0, mode="clip"), (i * _GATHER, 0))

    return lax.fori_loop(0, -(-held // _GATHER), some,
                         jnp.zeros((r,) + x.shape[1:], x.dtype))


def _work_list(tokens, held, tiles_t, tt, tr):
    """The (row tile, token tile) pairs to visit, in order, as fixed-size
    arrays: (row_ids [W], token_ids [W], n_work [1]), W = R // tr + tiles_t.

    ``tokens`` [R] ascending, the first ``held`` of them rows that count. A
    row tile visits the token tiles from its first row's to that of its last
    row that counts, so consecutive entries advance the row tile or the
    token tile and never go back: the entries of one token tile are
    consecutive. The entries from ``n_work`` on repeat the last one and are
    skipped."""
    tiles_r = tokens.shape[0] // tr
    starts = jnp.arange(tiles_r, dtype=jnp.int32) * tr
    ends = jnp.minimum(starts + tr, held)
    first = tokens[starts] // tt
    last = tokens[jnp.maximum(ends - 1, 0)] // tt
    visits = jnp.where(starts < held, last - first + 1, 0)
    visit_ends = jnp.cumsum(visits)
    n_work = visit_ends[-1]
    at = jnp.minimum(jnp.arange(tiles_r + tiles_t, dtype=jnp.int32),
                     jnp.maximum(n_work - 1, 0))
    # entry -> its row tile: the number of tiles whose visits end at or
    # before it
    row_ids = jnp.searchsorted(visit_ends, at, side="right")
    row_ids = jnp.minimum(row_ids, tiles_r - 1).astype(jnp.int32)
    token_ids = jnp.clip(
        first[row_ids] + at - (visit_ends - visits)[row_ids], 0, tiles_t - 1)
    return row_ids, token_ids.astype(jnp.int32), n_work.reshape(1)


def _product(block, rows):
    """float32 ``block`` [tt, tr] times ``rows`` [tr, D] at float32's
    precision. bfloat16 rows: the block as three bfloat16 pieces that sum to
    it exactly, stacked so that a tile of the rows is loaded into the MXU
    once for the three, each product exact and the sums float32's."""
    contract = (((1,), (0,)), ((), ()))
    if rows.dtype != jnp.bfloat16:
        return lax.dot_general(block, rows.astype(_F32), contract,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=_F32)
    tt = block.shape[0]
    high = block.astype(jnp.bfloat16)
    rest = block - high.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
    out = lax.dot_general(jnp.concatenate([high, mid, low], axis=0), rows,
                          contract, preferred_element_type=_F32)
    return out[:tt] + out[tt:2 * tt] + out[2 * tt:]


def _combine_kernel(rows_of_ref, tokens_of_ref, n_ref, token_ref, weight_ref,
                    rows_ref, y_ref, out_ref, *, tt):
    """One (block of D, work entry) cell."""
    i = pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _():
        tile = tokens_of_ref[i]
        tr = token_ref.shape[1]
        tokens = tile * tt + lax.broadcasted_iota(jnp.int32, (tt, tr), 0)
        block = jnp.where(tokens == token_ref[...], weight_ref[...], 0.0)
        part = _product(block, rows_ref[...])
        fresh = (i == 0) | (tokens_of_ref[jnp.maximum(i - 1, 0)] != tile)

        @pl.when(fresh)
        def _():
            out_ref[...] = y_ref[...] + part

        @pl.when(jnp.logical_not(fresh))
        def _():
            out_ref[...] += part

    # no row counts: the one block the grid holds goes back as it came
    @pl.when((i == 0) & (n_ref[0] == 0))
    def _():
        out_ref[...] = y_ref[...]


# A jitted function of its own, as the grouped matmul's calls are: a model's
# expert layers, forward and backward, share one trace of it and one
# lowering to Mosaic (PERF.md section 6, PR 32).
@functools.partial(jax.jit, static_argnums=(4,))
def _combine(y, rows, token, weight, interpret):
    t, d = y.shape
    r = rows.shape[0]
    tt = next(s for s in (_TILE_T, 128, 64, 32, 16, 8) if t % s == 0)
    tr, td = _TILE_R, _tile(d, _TILE_D)
    rp = -(-r // tr) * tr
    key = jnp.where(weight != 0, token.astype(jnp.int32), t)
    key = jnp.pad(jnp.minimum(key, t), (0, rp - r), constant_values=t)
    key, place = lax.sort((key, jnp.arange(rp, dtype=jnp.int32)), num_keys=1,
                          is_stable=False)
    held = jnp.sum(key < t, dtype=jnp.int32)
    weight = jnp.where(jnp.arange(rp) < held,
                       jnp.take(weight, place, mode="clip"), 0.0)
    rows = rows_held(rows, place, held)
    rows_of, tokens_of, n_work = _work_list(key, held, t // tt, tt, tr)

    def entry(i, n):
        return jnp.minimum(i, jnp.maximum(n[0] - 1, 0))

    def row_map(j, i, rows_of, tokens_of, n):
        return 0, rows_of[entry(i, n)]

    return pl.pallas_call(
        functools.partial(_combine_kernel, tt=tt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // td, rows_of.shape[0]),
            in_specs=[
                pl.BlockSpec((1, tr), row_map),
                pl.BlockSpec((1, tr), row_map),
                pl.BlockSpec((tr, td), lambda j, i, ro, to, n:
                             (ro[entry(i, n)], j)),
                pl.BlockSpec((tt, td), lambda j, i, ro, to, n:
                             (to[entry(i, n)], j)),
            ],
            out_specs=pl.BlockSpec((tt, td), lambda j, i, ro, to, n:
                                   (to[entry(i, n)], j))),
        out_shape=jax.ShapeDtypeStruct(y.shape, _F32),
        input_output_aliases={6: 0},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="moe_combine",
    )(rows_of, tokens_of, n_work, key.reshape(1, rp),
      weight.astype(_F32).reshape(1, rp), rows, y)


def moe_combine_reference(y, rows, token, weight):
    return y.at[token].add(rows.astype(_F32) * weight[:, None])


def moe_combine_pallas(y, rows, token, weight, interpret=False):
    if y.shape[1] % 128 or y.shape[0] % 8 or y.dtype != _F32:
        return moe_combine_reference(y, rows, token, weight)
    return _registry.traced_once(_combine, y, rows, token, weight,
                                 bool(interpret))


def moe_combine(y, rows, token, weight):
    """``y`` [T, D] float32 with ``weight[r] * rows[r]`` (float32 products)
    added into row ``token[r]`` for every row r of ``rows`` [R, D]. A row of
    weight 0, or with a token of T or more, adds nothing."""
    return _registry.dispatch("moe_combine", y, rows, token, weight)


_registry.register_kernel(
    "moe_combine", moe_combine_reference, moe_combine_pallas,
    doc="y[token] += weight * rows, rows in token order summed on the MXU")
