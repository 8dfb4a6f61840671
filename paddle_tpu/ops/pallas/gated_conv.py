"""The double-gated short convolution of a convolution / attention hybrid (the
LFM2 family's operator) as one op on the projection's own array: ``bcu`` is
``x @ W_in``, ``[B, S, 3 C]`` rows-major, its three column ranges a gate
``B``, a gate ``C`` and the signal ``u``, and

    z = B * u;  c_t = sum_j taps_j z_{t - K + 1 + j};  y = C * c

(causal, depthwise, zeros before the sequence, no bias, no activation). Left
to XLA the three ranges are sliced out of the product and their gradients
concatenated back into it, a copy of the widest activation of the mixer each
way (what the delta-rule mixers paid before ``delta_glue.py``: PERF.md section
6, PR 39). Here an activation is read once and written once a direction:

- ``gated_conv_fwd`` reads ``B``, ``C`` and ``u`` in place through three index
  maps on the one array (as the flash kernels read a packed ``qkv``) and
  writes ``y`` ``[B, S, C]``;
- ``gated_conv_bwd`` keeps the operand alone, forms ``z`` and ``c`` again in
  VMEM and writes ``d[B | C | u]`` as ONE array, the gradient of the
  projection's product as the weight-gradient matmul wants it, and the taps'
  gradient. Its blocks are whole rows of the array (all ``3 C`` columns of
  ``_ROWS`` positions) so that one call writes all three ranges; a step
  walks its columns ``_lanes`` at a time.

Built from ``delta_glue.py``'s pieces: the walk of a block ``_CHUNK`` rows at
a time, the taps' reach carried from chunk to chunk in registers and from
block to block through a second ``BlockSpec`` on the same array (forward) or
a VMEM scratch (backward, which walks the sequence from its end), the shifted
copies as sublane rotations, the taps' gradient as eight partial rows a tap
resident across the walk. Float32 inside, the operand's dtype at the results.

The reference body runs on the CPU, where ``C`` is no whole lane tile
(``lfm2_tiny``), and under a mesh that splits more than the batch; under a
mesh that splits the batch alone the kernels run a shard at a time
(``batch_leading``).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.delta_glue import (
    _CHUNK, _COMPILER_PARAMS, _F32, _HALO, _REACH, _chunks, _convolved,
    _fold, _lanes, _pad_rows, _rows_at, _shifted, _taps_of, _tile_above)
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["gated_short_conv"]

#: positions a grid step, both directions. The backward's blocks are all 3 C
#: columns wide: at C = 2048 a block of the operand is 3 MiB, and the step's
#: blocks (operand, its gradient, dy, each double-buffered) 14 MiB of VMEM
_ROWS = 256

_BWD_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20)


def _gated_short_conv_reference(bcu, taps):
    k, c = taps.shape
    s = bcu.shape[1]
    gate_b, gate_c, u = (bcu[..., i * c:(i + 1) * c].astype(_F32)
                         for i in range(3))
    z = jnp.pad(gate_b * u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[j].astype(_F32) * z[:, j:j + s] for j in range(k))
    return (gate_c * conv).astype(bcu.dtype)


# ---------------------------------------------------------------------------
# forward: a (batch, columns, positions) grid, three ranges of one array
# ---------------------------------------------------------------------------
def _fwd_kernel(b_ref, c_ref, u_ref, halo_b_ref, halo_u_ref, taps_ref,
                y_ref):
    """b_ref, c_ref, u_ref, y_ref [1, rows, lanes]: a range's block each;
    halo_*_ref [1, 16, lanes] the rows before the block; taps_ref [K,
    lanes]."""
    w = _taps_of(taps_ref)
    before = jnp.where(
        pl.program_id(2) == 0, 0.0,
        (halo_b_ref[0].astype(_F32)
         * halo_u_ref[0].astype(_F32))[_HALO - _REACH:])

    def chunk(i, before):
        at = _rows_at(i)
        z = b_ref[0, at, :].astype(_F32) * u_ref[0, at, :].astype(_F32)
        conv = _convolved(_shifted(z, before, len(w)), w)
        y_ref[0, at, :] = (c_ref[0, at, :].astype(_F32) * conv) \
            .astype(y_ref.dtype)
        return z[_CHUNK - _REACH:]

    lax.fori_loop(0, _chunks(y_ref), chunk, before)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _fwd(bcu, taps, lanes, interpret):
    """bcu [B, S, 3 C] with S whole blocks, taps [K, C] float32: y [B, S,
    C]."""
    b, s, c3 = bcu.shape
    c = c3 // 3
    rows = min(_ROWS, s)
    n = c // lanes                    # column blocks a range

    def block(first):
        return _vmem_spec((1, rows, lanes),
                          lambda ib, ic, t: (ib, t, first + ic))

    def halo(first):
        return _vmem_spec(
            (1, _HALO, lanes),
            lambda ib, ic, t: (ib, jnp.maximum(t * (rows // _HALO) - 1, 0),
                               first + ic))

    return pl.pallas_call(
        _fwd_kernel,
        grid=(b, n, s // rows),
        in_specs=[block(0), block(n), block(2 * n), halo(0), halo(2 * n),
                  _vmem_spec((taps.shape[0], lanes),
                             lambda ib, ic, t: (0, ic))],
        out_specs=_vmem_spec((1, rows, lanes),
                             lambda ib, ic, t: (ib, t, ic)),
        out_shape=jax.ShapeDtypeStruct((b, s, c), bcu.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="gated_conv_fwd",
    )(bcu, bcu, bcu, bcu, bcu, taps)


# ---------------------------------------------------------------------------
# backward: a (batch, positions) grid, the positions last block first
# ---------------------------------------------------------------------------
def _bwd_kernel(x_ref, halo_ref, taps_ref, dy_ref, dx_ref, dw_ref, after, *,
                lanes):
    """x_ref, dx_ref [1, rows, 3 C]; halo_ref [1, 16, 3 C] the rows before
    the block; taps_ref [K, C]; dy_ref [1, rows, C]; dw_ref [1, 8 K, C] eight
    partial rows a tap, resident across the walk; ``after`` [8, C] holds dp
    of the 8 rows after the block."""
    c = dy_ref.shape[2]
    k = taps_ref.shape[0]
    n = _chunks(x_ref)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        after[...] = jnp.zeros_like(after)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    first = t == pl.num_programs(1) - 1          # the sequence's first block
    for lo in range(0, c, lanes):
        cols_b, cols_c, cols_u = (pl.ds(r * c + lo, lanes) for r in range(3))
        cols = pl.ds(lo, lanes)
        w = [taps_ref[j:j + 1, cols] for j in range(k)]

        def z_of(rows, ref=x_ref):
            return ref[0, rows, cols_b].astype(_F32) \
                * ref[0, rows, cols_u].astype(_F32)

        halo = jnp.where(first, 0.0,
                         z_of(slice(None), halo_ref)[_HALO - _REACH:])

        def chunk(i, carried):
            dp_after, dws = carried
            ci = n - 1 - i
            at = _rows_at(ci)
            gate_b = x_ref[0, at, cols_b].astype(_F32)
            u = x_ref[0, at, cols_u].astype(_F32)
            z = gate_b * u
            before = jnp.where(ci == 0, halo,
                               z_of(_tile_above(ci))[_HALO - _REACH:])
            zs = _shifted(z, before, k)
            dy = dy_ref[0, at, cols].astype(_F32)
            dx_ref[0, at, cols_c] = (dy * _convolved(zs, w)) \
                .astype(dx_ref.dtype)
            dp = dy * x_ref[0, at, cols_c].astype(_F32)
            ext = jnp.concatenate([dp, dp_after], axis=0)
            ups = [dp] + [pltpu.roll(ext, _CHUNK + _REACH - m, 0)[:_CHUNK]
                          for m in range(1, k)]
            dz = sum(w[k - 1 - m] * ups[m] for m in range(k))
            dx_ref[0, at, cols_b] = (dz * u).astype(dx_ref.dtype)
            dx_ref[0, at, cols_u] = (dz * gate_b).astype(dx_ref.dtype)
            return dp[:_REACH], [dws[j] + _fold(dp * zs[k - 1 - j])
                                 for j in range(k)]

        zero = jnp.zeros((8, lanes), _F32)
        after[:, cols], dws = lax.fori_loop(
            0, n, chunk, (after[:, cols], [zero] * k))
        for j in range(k):
            dw_ref[0, 8 * j:8 * j + 8, cols] += dws[j]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _bwd(lanes, interpret, bcu, taps, dy):
    """(d[B | C | u] [B, S, 3 C], the taps' gradient as eight partial rows a
    tap and batch row [B, 8 K, C])."""
    b, s, c3 = bcu.shape
    k, c = taps.shape
    rows = min(_ROWS, s)
    last = s // rows - 1

    def at(t):
        return last - t

    return pl.pallas_call(
        functools.partial(_bwd_kernel, lanes=lanes),
        grid=(b, s // rows),
        in_specs=[
            _vmem_spec((1, rows, c3), lambda ib, t: (ib, at(t), 0)),
            _vmem_spec((1, _HALO, c3), lambda ib, t: (
                ib, jnp.maximum(at(t) * (rows // _HALO) - 1, 0), 0)),
            _vmem_spec((k, c), lambda ib, t: (0, 0)),
            _vmem_spec((1, rows, c), lambda ib, t: (ib, at(t), 0))],
        out_specs=[_vmem_spec((1, rows, c3), lambda ib, t: (ib, at(t), 0)),
                   _vmem_spec((1, 8 * k, c), lambda ib, t: (ib, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((b, 8 * k, c), _F32)],
        scratch_shapes=[pltpu.VMEM((_REACH, c), _F32)],
        compiler_params=_BWD_COMPILER_PARAMS,
        interpret=interpret,
        name="gated_conv_bwd",
    )(bcu, bcu, taps, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gated_conv(bcu, taps, lanes, interpret):
    return _registry.traced_once(_fwd, bcu, taps, lanes, interpret)


def _gated_conv_vjp_fwd(bcu, taps, lanes, interpret):
    return _gated_conv(bcu, taps, lanes, interpret), (bcu, taps)


def _gated_conv_vjp_bwd(lanes, interpret, res, dy):
    bcu, taps = res
    k, c = taps.shape
    dx, dw = _registry.traced_once(_bwd, lanes, interpret, bcu, taps, dy)
    return dx, dw.reshape(-1, k, 8, c).sum((0, 2))


_gated_conv.defvjp(_gated_conv_vjp_fwd, _gated_conv_vjp_bwd)


def _gated_short_conv_pallas(bcu, taps, interpret=False):
    """Pallas body: the shape rule and the padding to whole blocks (a padded
    position is after every real one: nothing reads it, and its gradient is
    cut off)."""
    s = bcu.shape[1]
    k, c = taps.shape
    if c % 128 or k - 1 > _REACH:
        return _gated_short_conv_reference(bcu, taps)
    rows = min(_ROWS, -(-s // _CHUNK) * _CHUNK)
    return _gated_conv(_pad_rows(bcu, rows), taps.astype(_F32),
                       _lanes(128, c), interpret)[:, :s]


def gated_short_conv(bcu, taps):
    """The double-gated causal depthwise convolution over positions: bcu [B,
    S, 3 C] = ``[B | C | u]`` (a projection's product as it is), taps [K, C];
    ``y_t = C_t * sum_j taps_j (B u)_{t - K + 1 + j}``, zeros before the
    sequence, no bias, no activation. Returns y [B, S, C] in ``bcu.dtype``;
    float32 inside. Differentiable in bcu and taps. Under the caller's scope
    (``gated_conv`` in ``models/lfm2.py``)."""
    if bcu.shape[-1] != 3 * taps.shape[-1]:
        raise ValueError(f"bcu {bcu.shape} is not three ranges of the taps' "
                         f"{taps.shape} columns")
    return _registry.dispatch("gated_short_conv", bcu, taps)


_registry.register_kernel(
    "gated_short_conv", _gated_short_conv_reference,
    _gated_short_conv_pallas,
    doc="y = C * conv(B * u) on the three column ranges of one projection, "
        "one pass each way",
    batch_leading=("bcu",), whole=("taps",))
