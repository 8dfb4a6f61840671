"""Grouped matmul: the product a dropless mixture-of-experts layer is made of.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]``:
the rows of ``lhs`` are sorted by group, group ``g`` owns the
``group_sizes[g]`` rows after those of the groups before it, and each row
is multiplied by its own group's matrix. Rows past ``sum(group_sizes)``
come out zero. The work is ``2 sum(group_sizes) K N``, to the row tile: no
capacity, no padding to the fullest group, no product over every group, and
no product for the rows past the last group, which a layer that holds a
share of a router's experts has thirty times as many of as rows of its own
(``parallel/moe.dropless_moe_ffn``, ``held``). Those rows' tiles are written
as zeros, at the speed of memory, and fetch nothing.

Two bodies behind the kernel registry (``ops/pallas/registry.py``):

- reference: ``jax.lax.ragged_dot``, jax's own primitive, with jax's own
  gradient. What the CPU tests run, and what ``auto`` takes under a mesh of
  more than one device.
- Pallas: the megablox scheme (Gale et al. 2022; jax's
  ``pallas.ops.tpu.megablox``). The work list is the (group, row tile)
  pairs that intersect, in order, made outside the kernel from
  ``group_sizes`` and handed in by scalar prefetch; the index maps pick the
  row tile and the group's matrix for each entry, so a matrix is fetched
  once for all the consecutive tiles of its group. A tile that straddles two
  groups is visited once for each and the store is masked to the group's
  rows. The tiles wholly past the groups' rows come last on the list, to be
  zeroed; the weights' gradient does not visit them. Its gradient is the
  same call on the transposed matrices (``grouped_matmul``) and the
  per-group ``lhs^T dout`` (``grouped_matmul_dw``), which sums the tiles of
  one group in a float32 scratch. Every group is on the work list, empty
  ones too, so an empty group's gradient is written, as zeros.

XLA:TPU lowers ``ragged_dot`` to a Mosaic kernel of its own with the same
scheme, but that call carries no jax name stack (its ``op_name`` is
``ragged-dot-none``), so a profile cannot put its time under the scope that
issued it, forward or backward; that, and the tile shapes, are why one chip
takes the Pallas body (PERF.md, PR 26).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import registry as _registry

__all__ = ["grouped_matmul"]

#: row tile, and the widest contraction and output blocks. At the widths of
#: a 2048 x 1024 and of a 2304 x 1024 expert the contraction is one block,
#: so a group's matrix stays in VMEM while its row tiles stream past it.
_TILE_M, _TILE_K, _TILE_N = 256, 2304, 1024
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=64 << 20,
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _tile(size, most):
    """The largest block not over ``most`` that divides ``size`` into whole
    lane-aligned blocks; the whole of ``size`` where there is none."""
    if size <= most:
        return size
    for t in range(most, 127, -128):
        if size % t == 0:
            return t
    return size


def _work_list(group_sizes, m, tm):
    """The (group, row tile) pairs to visit, in order, as fixed-size arrays.

    Returns (group_offsets [G+1], group_ids [W], tile_ids [W], counts [2])
    with W = m // tm + G and counts = (n_real, n_work). The first ``n_real``
    entries are the products: a group visits the tiles its rows touch, an
    empty group one tile, to which it writes nothing. The entries from
    ``n_real`` to ``n_work`` are the tiles wholly past ``sum(group_sizes)``,
    one entry each, under the last group: they hold rows of no group, so
    they are zeroed with no product and nothing fetched. Entries from
    ``n_work`` on repeat the last one and are skipped."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = jnp.minimum(starts // tm, tiles_m - 1)
    last = jnp.maximum((ends - 1) // tm, first)
    visits = last - first + 1
    w = tiles_m + g
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), visits,
                           total_repeat_length=w)
    visit_starts = jnp.cumsum(visits) - visits
    at = jnp.arange(w, dtype=jnp.int32)
    n_real = jnp.sum(visits)
    first_tail = (ends[g - 1] + tm - 1) // tm
    n_work = n_real + tiles_m - first_tail
    tile_ids = jnp.where(
        at < n_real, first[group_ids] + at - visit_starts[group_ids],
        jnp.minimum(first_tail + at - n_real, tiles_m - 1))
    return offsets, group_ids, tile_ids, jnp.stack([n_real, n_work])


def _row_mask(offsets_ref, group, tile, tm, shape):
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])


def _gmm_kernel(offsets_ref, groups_ref, tiles_ref, counts_ref,
                lhs_ref, rhs_ref, out_ref, acc_ref, *, tm, transpose_rhs):
    """One (output column block, work entry, contraction block) cell."""
    i, k = pl.program_id(1), pl.program_id(2)

    @pl.when(i < counts_ref[0])
    def _():
        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        acc_ref[...] += lax.dot_general(
            lhs_ref[...], rhs_ref[0], contract,
            preferred_element_type=jnp.float32)

        @pl.when(k == pl.num_programs(2) - 1)
        def _():
            group, tile = groups_ref[i], tiles_ref[i]
            mask = _row_mask(offsets_ref, group, tile, tm, acc_ref.shape)
            # the first entry of a tile finds nothing of its own in the
            # output block: rows of no group are written as zeros
            fresh = (i == 0) | (tiles_ref[jnp.maximum(i - 1, 0)] != tile)
            kept = jnp.where(fresh, jnp.zeros_like(out_ref), out_ref[...])
            out_ref[...] = jnp.where(mask, acc_ref[...].astype(out_ref.dtype),
                                     kept)

    # a tile wholly past the groups' rows: zeros, and no product
    @pl.when((i >= counts_ref[0]) & (i < counts_ref[1])
             & (k == pl.num_programs(2) - 1))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _real(i, counts):
    """The work entry whose operands entry ``i`` has resident: its own for
    a product, the last product's for the entries after them, so that a
    tile that is only zeroed, and a skipped entry, fetch nothing."""
    return jnp.minimum(i, counts[0] - 1)


# Jitted functions of their own, as the flash and the delta-rule calls are: an
# expert layer calls each three times, and a model's layers share one trace
# of a call and one lowering to Mosaic for every shape (PERF.md section 6,
# PR 32).
@functools.partial(jax.jit, static_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    """[M, K] x [G, K, N] (or [G, N, K] transposed) -> [M, N]."""
    m, kdim = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = min(_TILE_M, m)
    pad = (-m) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    mp = m + pad
    tk, tn = _tile(kdim, _TILE_K), _tile(n, _TILE_N)
    offsets, group_ids, tile_ids, counts = _work_list(group_sizes, mp, tm)
    last_k = kdim // tk - 1

    def lhs_map(j, i, k, offsets, groups, tiles, counts):
        return tiles[_real(i, counts)], jnp.where(i < counts[0], k, last_k)

    def rhs_map(j, i, k, offsets, groups, tiles, counts):
        k = jnp.where(i < counts[0], k, last_k)
        return (groups[i], j, k) if transpose_rhs else (groups[i], k, j)

    def out_map(j, i, k, offsets, groups, tiles, counts):
        return tiles[i], j

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, group_ids.shape[0], kdim // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_map),
                pl.BlockSpec((1, tn, tk) if transpose_rhs else (1, tk, tn),
                             rhs_map),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="grouped_matmul",
    )(offsets, group_ids, tile_ids, counts, lhs, rhs)
    return out[:m] if pad else out


def _tgmm_kernel(offsets_ref, groups_ref, tiles_ref, counts_ref,
                 lhs_ref, dout_ref, dw_ref, acc_ref, *, tm):
    """One (column block, contraction block, work entry) cell of the
    weights' gradient: the tiles of one group are consecutive entries. The
    tiles past the groups' rows add nothing and are not visited."""
    i = pl.program_id(2)
    last = counts_ref[0] - 1

    @pl.when(i <= last)
    def _():
        group, tile = groups_ref[i], tiles_ref[i]

        @pl.when((i == 0) | (groups_ref[jnp.maximum(i - 1, 0)] != group))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        mask = _row_mask(offsets_ref, group, tile, tm, lhs_ref.shape)
        rows = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref))
        acc_ref[...] += lax.dot_general(
            rows, dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when((i == last) | (groups_ref[jnp.minimum(i + 1, last)] != group))
        def _():
            dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3,))
def _tgmm(lhs, dout, group_sizes, interpret):
    """Per group, ``lhs[rows]^T dout[rows]``: [M, K], [M, N] -> [G, K, N]."""
    m, kdim = lhs.shape
    n = dout.shape[1]
    tm = min(_TILE_M, m)
    pad = (-m) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        dout = jnp.pad(dout, ((0, pad), (0, 0)))
    tk, tn = _tile(kdim, _TILE_K), _tile(n, _TILE_N // 2)
    offsets, group_ids, tile_ids, counts = _work_list(group_sizes, m + pad,
                                                      tm)

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, kdim // tk, group_ids.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, k, i, o, g, t, c: (t[_real(i, c)], k)),
                pl.BlockSpec((tm, tn),
                             lambda j, k, i, o, g, t, c: (t[_real(i, c)], j)),
            ],
            out_specs=pl.BlockSpec((1, tk, tn),
                                   lambda j, k, i, o, g, t, c: (g[i], k, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((group_sizes.shape[0], kdim, n),
                                       lhs.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="grouped_matmul_dw",
    )(offsets, group_ids, tile_ids, counts, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, False, interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, interpret):
    return (_gmm(lhs, rhs, group_sizes, False, interpret),
            (lhs, rhs, group_sizes))


def _grouped_matmul_bwd(interpret, res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    return (_gmm(dout, rhs, group_sizes, True, interpret),
            _tgmm(lhs, dout, group_sizes, interpret).astype(rhs.dtype), None)


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul_reference(lhs, rhs, group_sizes):
    return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                          preferred_element_type=lhs.dtype)


def grouped_matmul_pallas(lhs, rhs, group_sizes, interpret=False):
    return _grouped_matmul(lhs, rhs.astype(lhs.dtype), group_sizes,
                           bool(interpret))


def grouped_matmul(lhs, rhs, group_sizes):
    """Each row of ``lhs`` [M, K] times the matrix of its group in ``rhs``
    [G, K, N]; rows sorted by group, ``group_sizes`` [G] int. Returns
    [M, N] in ``lhs.dtype`` (float32 accumulation). Differentiable in
    ``lhs`` and ``rhs``."""
    return _registry.dispatch("grouped_matmul", lhs, rhs, group_sizes)


_registry.register_kernel(
    "grouped_matmul", grouped_matmul_reference, grouped_matmul_pallas,
    doc="[M,K] x [G,K,N] by sorted row groups: dropless expert matmul")
