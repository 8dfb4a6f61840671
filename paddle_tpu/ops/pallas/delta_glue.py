"""The memory-bound passes around a delta-rule mixer's core (``ops/kda.py``)
as two ops on rows-major ``[B, S, C]`` arrays, the projections' own layout, a
head a lane tile: from ``x @ w`` to ``kda_chunked`` and from ``kda_chunked``
to ``@ out_w`` an activation is read once and written once a direction and is
never viewed as ``[B, S, H, d]``.

- ``short_conv_norm``: the causal depthwise convolution over positions, SiLU
  and, on the column ranges that ask for it, the L2 norm a head, in one pass
  (``conv_norm_fwd``), and their backward in another (``conv_norm_bwd``), which
  keeps the operands alone and forms the pre-activation again in VMEM.
- ``gated_head_norm``: the output's RMS norm a head times its gain times
  ``silu`` or ``sigmoid`` of the gate (``gated_norm_fwd``, ``gated_norm_bwd``,
  which writes ``do`` over ``o`` and ``dz`` over ``z``: a layer's ``dz`` waits
  for the weight-gradient products XLA schedules last, and as a buffer of
  its own it cost the Kimi Linear step 1.5 GiB of heap: PERF.md section 6,
  PR 39).

**Blocks.** A grid step is a batch row, ``_lanes`` columns (whole heads, up to
``_WIDE``) and ``_ROWS`` positions, the positions the grid's last axis. A step
walks its rows ``_CHUNK`` at a time, a loop and not one expression on the
whole block, so that what is live at once is a chunk's dozen float32
intermediates and not the block's. A head's sum of squares is a lane
reduction of a tile-aligned slice: no reshape exists.

**The taps' reach.** A chunk needs the ``K - 1`` rows before it: inside a
block the loop carries the chunk before's last rows, and a block reads the 16
rows before it through a second ``BlockSpec`` on the same array (zeros at the
sequence's start). A row's shifted copies are sublane rotations of the chunk
with those rows on top. The backward walks the sequence from its end, as
``kda._walk_back`` does: ``dx_t`` needs ``dp`` of the ``K - 1`` rows after
``t``, carried from chunk to chunk in registers and from block to block in a
VMEM scratch. The taps' gradient is a float32 block resident across the walk,
eight partial rows a tap that are summed (with the batch) outside.

**Column ranges of one array.** Gated DeltaNet convolves ``[q | k | v]`` as
one array and norms q and k alone: ``parts`` names the ranges, a call a range
reads its columns of ``x`` and of the taps through its index maps, and the
backward's calls write their columns of ONE ``dx`` (each aliases the one
before's), so neither a split nor its concatenate exists. Kimi Linear's three
projections are three calls of one range.

**Where it rounds.** Float32 inside, the operands' dtype at the results. The
reference bodies are the models' former code (``short_conv`` then
``l2_normalize``; ``rms_normalize`` times the gate's activation); against
them the kernels drop roundings and add none: the convolution's result is not
rounded before its norm, and Kimi Linear's normed output not before its gate.

The reference bodies run where a head is no whole lane tile
(``kimi_linear_tiny``: 16), on the CPU, and under a mesh that splits more than
the batch; under a mesh that splits the batch alone the kernels run a shard at
a time (``batch_leading``).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["short_conv", "l2_normalize", "short_conv_norm",
           "gated_head_norm"]

#: positions a grid step
_ROWS = 512
#: columns a grid step, at most
_WIDE = 512
#: rows a pass of a step's inner loop (whole bfloat16 tiles of 16). Chosen on
#: the chip (PERF.md section 6, PR 39; v5e, [1, 16384, 8192] in three ranges,
#: ms forward | backward on the host's clock, which holds about 1 ms of
#: dispatch): 16 rows 2.87 | 3.60, 32 2.45 | 3.12, 64 2.19 | 2.94; at 1024
#: columns 2.35 | 3.39, 2.03 | 3.04, 2.24 | 3.12; 1024 rows a step no faster.
#: A chunk's chain of exponentials, lane reductions and rotations is long, and
#: more rows a pass give the scheduler more of it to overlap.
_CHUNK = 64
#: rows of the block before that a step reads for the taps' reach (a
#: bfloat16 tile; its last ``_REACH`` are used)
_HALO = 16
#: the most rows a tap may reach back: a float32 tile's
_REACH = 8

_F32 = jnp.float32
_ACTIVATIONS = ("silu", "sigmoid")

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 << 20)


# ---------------------------------------------------------------------------
# reference bodies: the models' former code
# ---------------------------------------------------------------------------
def l2_normalize(x, scale=1.0):
    """``x / sqrt(sum(x^2) + 1e-6) * scale`` over the last axis (a head of
    a delta-rule mixer's queries or keys): float32 inside, ``x.dtype`` out,
    under the caller's scope."""
    x32 = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + 1e-6)
    return (x32 * (inv * scale)).astype(x.dtype)


def short_conv(x, taps):
    """Causal depthwise convolution over positions, then SiLU: x [B, S, C],
    taps [K, C]; ``y_t = sum_j taps_j x_{t - K + 1 + j}``, no bias."""
    k = taps.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(taps[j] * padded[:, j:j + s].astype(jnp.float32)
            for j in range(k))
    return jax.nn.silu(y).astype(x.dtype)


def _ranges(parts):
    """[(first column, width, scale)] of ``parts`` laid side by side."""
    start, out = 0, []
    for width, scale in parts:
        out.append((start, width, scale))
        start += width
    return out


def _short_conv_norm_reference(x, taps, head_dim, parts):
    b, s, _ = x.shape
    y = short_conv(x, taps)
    outs = []
    for start, width, scale in _ranges(parts):
        part = y[..., start:start + width]
        if scale is not None:
            part = l2_normalize(part.reshape(b, s, -1, head_dim), scale) \
                .reshape(b, s, width)
        outs.append(part)
    return tuple(outs)


def _act(name, z32):
    return jax.nn.silu(z32) if name == "silu" else jax.nn.sigmoid(z32)


def _gated_head_norm_reference(o, z, gain, eps, act):
    b, s, c = o.shape
    d = gain.shape[0]
    o32 = o.astype(_F32).reshape(b, s, c // d, d)
    inv = lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + eps)
    normed = (o32 * inv * gain.astype(_F32)).reshape(b, s, c)
    return (normed * _act(act, z.astype(_F32))).astype(o.dtype)


# ---------------------------------------------------------------------------
# the convolution, SiLU and the norm a head
# ---------------------------------------------------------------------------
def _chunks(ref):
    return ref.shape[1] // _CHUNK


def _rows_at(c):
    return pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)


def _tile_above(c):
    """The ``_HALO`` rows that end where chunk ``c`` starts (chunk 0 has
    none in its block: its own first, which the caller does not use)."""
    return pl.ds(pl.multiple_of(jnp.maximum(c * _CHUNK - _HALO, 0), _HALO),
                 _HALO)


def _heads_of(x, head):
    """x [rows, lanes] a head at a time: tile-aligned lane slices."""
    return [x[:, h:h + head] for h in range(0, x.shape[1], head)]


def _shifted(x32, before, k):
    """The chunk's rows shifted down by 0 .. k - 1 positions, ``before``
    [8, lanes] the rows above it: sublane rotations of the two together."""
    ext = jnp.concatenate([before, x32], axis=0)
    return [x32] + [pltpu.roll(ext, m, 0)[_REACH:] for m in range(1, k)]


def _taps_of(taps_ref):
    return [taps_ref[j:j + 1, :] for j in range(taps_ref.shape[0])]


def _convolved(xs, w):
    """``sum_j taps_j x_{t - K + 1 + j}`` in the reference body's order."""
    k = len(w)
    return sum(w[j] * xs[k - 1 - j] for j in range(k))


def _conv_fwd_kernel(at_ref, scale_ref, x_ref, halo_ref, taps_ref, o_ref, *,
                     head, normed):
    """One (batch, columns, positions) grid step: x_ref, o_ref [1, rows,
    lanes], halo_ref [1, 16, lanes] the rows before, taps_ref [K, lanes];
    ``at_ref`` (the range's first column block: the index maps read it) and
    ``scale_ref`` (what a normed head is scaled by) are scalars in SMEM, so
    that ranges of one width are one program."""
    del at_ref
    w = _taps_of(taps_ref)
    scale = scale_ref[0]
    before = jnp.where(pl.program_id(2) == 0, 0.0,
                       halo_ref[0].astype(_F32)[_HALO - _REACH:])

    def chunk(c, before):
        at = _rows_at(c)
        x32 = x_ref[0, at, :].astype(_F32)
        p = _convolved(_shifted(x32, before, len(w)), w)
        a = p * jax.nn.sigmoid(p)
        if normed:
            a = jnp.concatenate(
                [ah * (lax.rsqrt(jnp.sum(ah * ah, axis=1, keepdims=True)
                                 + 1e-6) * scale)
                 for ah in _heads_of(a, head)], axis=1)
        o_ref[0, at, :] = a.astype(o_ref.dtype)
        return x32[_CHUNK - _REACH:]

    lax.fori_loop(0, _chunks(x_ref), chunk, before)


def _fold(x):
    """[rows, lanes] as the sum of its float32 tiles, [8, lanes]."""
    return jnp.sum(x.reshape(x.shape[0] // 8, 8, x.shape[1]), axis=0)


def _conv_bwd_kernel(at_ref, scale_ref, x_ref, halo_ref, taps_ref, dy_ref,
                     *rest, head, normed):
    """The same grid, the positions last block first: ``after`` holds dp of
    the 8 rows after the block, ``dw_ref`` [1, 8 K, lanes] eight partial rows
    a tap, resident across the walk. ``rest`` may start with the array whose
    columns the call leaves as they are (it aliases ``dx_ref``'s)."""
    del at_ref
    dx_ref, dw_ref, after = rest[-3:]
    w = _taps_of(taps_ref)
    scale = scale_ref[0]
    k = len(w)
    n = _chunks(x_ref)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        after[...] = jnp.zeros_like(after)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    halo = jnp.where(t == pl.num_programs(2) - 1, 0.0,
                     halo_ref[0].astype(_F32)[_HALO - _REACH:])

    def chunk(i, carried):
        dp_after, dws = carried
        c = n - 1 - i
        at = _rows_at(c)
        x32 = x_ref[0, at, :].astype(_F32)
        above = x_ref[0, _tile_above(c), :].astype(_F32)[_HALO - _REACH:]
        before = jnp.where(c == 0, halo, above)
        xs = _shifted(x32, before, k)
        p = _convolved(xs, w)
        sg = jax.nn.sigmoid(p)
        da = dy_ref[0, at, :].astype(_F32)
        if normed:
            a = p * sg
            parts = []
            for ah, dn in zip(_heads_of(a, head), _heads_of(da, head)):
                r = lax.rsqrt(jnp.sum(ah * ah, axis=1, keepdims=True) + 1e-6)
                moved = jnp.sum(dn * ah, axis=1, keepdims=True)
                parts.append((r * scale) * (dn - ah * (r * r * moved)))
            da = jnp.concatenate(parts, axis=1)
        dp = da * (sg * (1.0 + p * (1.0 - sg)))
        ext = jnp.concatenate([dp, dp_after], axis=0)
        ups = [dp] + [pltpu.roll(ext, _CHUNK + _REACH - m, 0)[:_CHUNK]
                      for m in range(1, k)]
        dx_ref[0, at, :] = sum(w[k - 1 - m] * ups[m] for m in range(k)) \
            .astype(dx_ref.dtype)
        return dp[:_REACH], [dws[j] + _fold(dp * xs[k - 1 - j])
                             for j in range(k)]

    zero = jnp.zeros((8, x_ref.shape[2]), _F32)
    after[...], dws = lax.fori_loop(0, n, chunk, (after[...], [zero] * k))
    for j in range(k):
        dw_ref[0, 8 * j:8 * j + 8, :] += dws[j]


def _lanes(head, *widths):
    """Columns a grid step: the most whole heads up to ``_WIDE`` lanes that
    divide every one of ``widths``."""
    whole = math.gcd(*widths)
    return head * max(m for m in range(1, max(_WIDE // head, 1) + 1)
                      if whole % (head * m) == 0)


def _conv_specs(s, rows, lanes, taps, backward):
    """(x's block, the 16 rows before it, the taps' columns, a block of an
    array of the range's own width); the range's first column block is the
    first prefetched scalar. Backward the positions come last block first."""
    def at(t):
        return s // rows - 1 - t if backward else t

    def halo(t):
        return jnp.maximum(at(t) * (rows // _HALO) - 1, 0)

    return (_vmem_spec((1, rows, lanes),
                       lambda ib, ic, t, first, _: (ib, at(t), first[0] + ic)),
            _vmem_spec((1, _HALO, lanes),
                       lambda ib, ic, t, first, _: (ib, halo(t),
                                                    first[0] + ic)),
            _vmem_spec((taps, lanes),
                       lambda ib, ic, t, first, _: (0, first[0] + ic)),
            _vmem_spec((1, rows, lanes),
                       lambda ib, ic, t, first, _: (ib, at(t), ic)))


def _block_rows(s):
    """Positions a grid step of a sequence of ``s`` (whole chunks)."""
    return min(_ROWS, -(-s // _CHUNK) * _CHUNK)


def _part_scalars(start, lanes, scale):
    """A range's prefetched scalars: its first column block, its scale."""
    return (jnp.full((1,), start // lanes, jnp.int32),
            jnp.full((1,), 1.0 if scale is None else scale, _F32))


# Jitted functions of their own, as the flash and delta-rule calls are: a
# model's layers share one trace of each kernel and one lowering to Mosaic.
# A call a column range, and where the range starts and what it is scaled by
# are operands: ranges of one width (q and k) are one program.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _conv_part_fwd(first, scale, x, taps, width, lanes, head, normed,
                   interpret):
    """One range of ``width`` columns of x [B, S, C] (S whole blocks) and of
    taps [K, C] float32, from column block ``first`` on: [B, S, width]."""
    b, s, _ = x.shape
    rows = _block_rows(s)
    block, halo, tap, own = _conv_specs(s, rows, lanes, taps.shape[0], False)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, head=head, normed=normed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, width // lanes, s // rows),
            in_specs=[block, halo, tap], out_specs=own),
        out_shape=jax.ShapeDtypeStruct((b, s, width), x.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="conv_norm_fwd",
    )(first, scale, x, x, taps)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _conv_part_bwd(lanes, head, normed, interpret, first, scale, x, taps, dy,
                   dx):
    """(dx, the taps' gradient as eight partial rows a tap) of one range:
    its columns of ``dx`` [B, S, C] are written, the others are the ``dx``
    handed in (None: left unwritten)."""
    b, s, _ = x.shape
    k, width = taps.shape[0], dy.shape[-1]
    rows = _block_rows(s)
    block, halo, tap, own = _conv_specs(s, rows, lanes, k, True)
    handed = [] if dx is None else [dx]
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, head=head, normed=normed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, width // lanes, s // rows),
            in_specs=[block, halo, tap, own]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(handed),
            out_specs=[block,
                       _vmem_spec((1, 8 * k, lanes),
                                  lambda ib, ic, t, *_: (ib, 0, ic))],
            scratch_shapes=[pltpu.VMEM((_REACH, lanes), _F32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, 8 * k, width), _F32)],
        input_output_aliases={6: 0} if handed else {},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="conv_norm_bwd",
    )(first, scale, x, x, taps, dy, *handed)


def _part_lanes(head, parts):
    return _lanes(head, *(n for start, width, _ in _ranges(parts)
                          for n in (width, start or width)))


def _conv_fwd(x, taps, head, parts, interpret):
    lanes = _part_lanes(head, parts)
    return tuple(
        _registry.traced_once(
            _conv_part_fwd, *_part_scalars(start, lanes, scale), x, taps,
            width, lanes, head, scale is not None, interpret)
        for start, width, scale in _ranges(parts))


def _conv_bwd(head, parts, interpret, res, dys):
    """(dx, the taps' gradient). The ranges' calls write their columns of
    the one ``dx``, each into what the call before handed on; the widest
    range goes first, so that the others, which alias, are one program where
    they are one width."""
    x, taps = res
    b = x.shape[0]
    k = taps.shape[0]
    lanes = _part_lanes(head, parts)
    ranges = _ranges(parts)
    dx, dws = None, [None] * len(parts)
    for i in sorted(range(len(parts)), key=lambda i: -parts[i][0]):
        start, width, scale = ranges[i]
        dx, dw = _registry.traced_once(
            _conv_part_bwd, lanes, head, scale is not None, interpret,
            *_part_scalars(start, lanes, scale), x, taps, dys[i], dx)
        dws[i] = dw.reshape(b, k, 8, width).sum((0, 2))
    return dx, jnp.concatenate(dws, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_norm(x, taps, head, parts, interpret):
    return _conv_fwd(x, taps, head, parts, interpret)


def _conv_norm_vjp_fwd(x, taps, head, parts, interpret):
    return _conv_fwd(x, taps, head, parts, interpret), (x, taps)


_conv_norm.defvjp(_conv_norm_vjp_fwd, _conv_bwd)


def _pad_rows(t, rows):
    pad = (-t.shape[1]) % rows
    return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t


def _short_conv_norm_pallas(x, taps, head_dim, parts, interpret=False):
    """Pallas body: the shape rule, the padding to whole blocks (a padded
    position is after every real one: nothing reads it)."""
    s = x.shape[1]
    if head_dim % 128 or any(width % head_dim for width, _ in parts) \
            or taps.shape[0] - 1 > _REACH:
        # a head that is no whole lane tile (kimi_linear_tiny: 16)
        return _short_conv_norm_reference(x, taps, head_dim, parts)
    outs = _conv_norm(_pad_rows(x, _block_rows(s)), taps.astype(_F32),
                      head_dim, parts, interpret)
    return tuple(o[:, :s] for o in outs)


def short_conv_norm(x, taps, head_dim, parts):
    """A delta-rule mixer's causal depthwise convolution over positions,
    SiLU and L2 norm a head: x [B, S, C] (a projection's product, or several
    side by side), taps [K, C], ``y_t = silu(sum_j taps_j x_{t - K + 1 +
    j})``. ``parts`` cuts the columns into ranges, ``((width, scale), ...)``
    from column 0 on: a range with a scale is normed a head of ``head_dim``
    channels, ``y / sqrt(sum(y^2) + 1e-6) * scale``; one with ``None`` is
    not. Returns a range an array, ``[B, S, width]`` in ``x.dtype``.
    Differentiable in x and taps. Under the caller's scope (``short_conv``
    in both delta-rule models)."""
    parts = tuple((int(width), None if scale is None else float(scale))
                  for width, scale in parts)
    if sum(width for width, _ in parts) != x.shape[-1] \
            or taps.shape[-1] != x.shape[-1]:
        raise ValueError(f"parts {parts} and taps {taps.shape} do not cover "
                         f"the columns of x {x.shape}")
    return _registry.dispatch("short_conv_norm", x, taps, int(head_dim),
                              parts)


# ---------------------------------------------------------------------------
# the output's norm a head times its gate
# ---------------------------------------------------------------------------
def _act_and_slope(act, z):
    """(act(z), act'(z)) of float32 z."""
    sg = jax.nn.sigmoid(z)
    if act == "sigmoid":
        return sg, sg * (1.0 - sg)
    return z * sg, sg * (1.0 + z * (1.0 - sg))


def _gate_fwd_kernel(o_ref, z_ref, gain_ref, y_ref, *, eps, act):
    """One (batch, columns, positions) grid step: o_ref, z_ref, y_ref [1,
    rows, lanes], gain_ref [1, d]."""
    d = gain_ref.shape[1]
    gain = gain_ref[...]

    def chunk(c, _):
        at = _rows_at(c)
        gate, _ = _act_and_slope(act, z_ref[0, at, :].astype(_F32))
        normed = jnp.concatenate(
            [oh * lax.rsqrt(jnp.mean(oh * oh, axis=1, keepdims=True) + eps)
             * gain for oh in _heads_of(o_ref[0, at, :].astype(_F32), d)],
            axis=1)
        y_ref[0, at, :] = (normed * gate).astype(y_ref.dtype)
        return 0

    lax.fori_loop(0, _chunks(o_ref), chunk, 0)


def _gate_bwd_kernel(o_ref, z_ref, gain_ref, dy_ref, do_ref, dz_ref,
                     dgain_ref, *, eps, act):
    """The same grid: ``dgain_ref`` [1, 8, d], eight partial rows, is
    resident across a batch row's steps."""
    d = gain_ref.shape[1]
    gain = gain_ref[...]

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dgain_ref[...] = jnp.zeros_like(dgain_ref)

    def chunk(c, dgain):
        at = _rows_at(c)
        gate, slope = _act_and_slope(act, z_ref[0, at, :].astype(_F32))
        dy = dy_ref[0, at, :].astype(_F32)
        dnormed = _heads_of(dy * gate, d)
        dos, normed = [], []
        for oh, dn in zip(_heads_of(o_ref[0, at, :].astype(_F32), d),
                          dnormed):
            r = lax.rsqrt(jnp.mean(oh * oh, axis=1, keepdims=True) + eps)
            unit = oh * r
            dgain = dgain + _fold(dn * unit)
            dn = dn * gain
            moved = jnp.mean(dn * unit, axis=1, keepdims=True)
            dos.append(r * (dn - unit * moved))
            normed.append(unit * gain)
        do_ref[0, at, :] = jnp.concatenate(dos, axis=1).astype(do_ref.dtype)
        dz_ref[0, at, :] = (dy * jnp.concatenate(normed, axis=1) * slope) \
            .astype(dz_ref.dtype)
        return dgain

    dgain_ref[0] += lax.fori_loop(0, _chunks(o_ref), chunk,
                                  jnp.zeros((8, d), _F32))


def _gate_specs(rows, lanes, d):
    return (_vmem_spec((1, rows, lanes), lambda ib, ic, t: (ib, t, ic)),
            _vmem_spec((1, d), lambda ib, ic, t: (0, 0)))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gate_fwd(o, z, gain, eps, act, interpret):
    """o, z [B, S, C] with S whole blocks, gain [1, d] float32."""
    b, s, c = o.shape
    d = gain.shape[1]
    rows, lanes = _block_rows(s), _lanes(d, c)
    block, gains = _gate_specs(rows, lanes, d)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, eps=eps, act=act),
        grid=(b, c // lanes, s // rows),
        in_specs=[block, block, gains],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="gated_norm_fwd",
    )(o, z, gain)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _gate_bwd(eps, act, interpret, res, dy):
    o, z, gain = res
    b, s, c = o.shape
    d = gain.shape[1]
    rows, lanes = _block_rows(s), _lanes(d, c)
    block, gains = _gate_specs(rows, lanes, d)
    do, dz, dgain = pl.pallas_call(
        functools.partial(_gate_bwd_kernel, eps=eps, act=act),
        grid=(b, c // lanes, s // rows),
        in_specs=[block, block, gains, block],
        out_specs=[block, block,
                   _vmem_spec((1, 8, d), lambda ib, ic, t: (ib, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((b, 8, d), _F32)],
        # each gradient over its primal: a step reads its blocks of o and z
        # before it writes them, and a recomputed mixer's o and z end here
        input_output_aliases={0: 0, 1: 1},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="gated_norm_bwd",
    )(o, z, gain, dy)
    return do, dz, dgain.sum((0, 1))[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gated_norm(o, z, gain, eps, act, interpret):
    return _registry.traced_once(_gate_fwd, o, z, gain, eps, act, interpret)


def _gated_norm_vjp_fwd(o, z, gain, eps, act, interpret):
    return _gated_norm(o, z, gain, eps, act, interpret), (o, z, gain)


def _gated_norm_vjp_bwd(eps, act, interpret, res, dy):
    return _registry.traced_once(_gate_bwd, eps, act, interpret, res, dy)


_gated_norm.defvjp(_gated_norm_vjp_fwd, _gated_norm_vjp_bwd)


def _gated_head_norm_pallas(o, z, gain, eps, act, interpret=False):
    """Pallas body: the shape rule and the padding to whole blocks (a padded
    row is zeros in, zeros out)."""
    s = o.shape[1]
    if gain.shape[0] % 128 or o.dtype != z.dtype:
        return _gated_head_norm_reference(o, z, gain, eps, act)
    rows = _block_rows(s)
    return _gated_norm(_pad_rows(o, rows), _pad_rows(z, rows),
                       gain.astype(_F32)[None], eps, act, interpret)[:, :s]


def gated_head_norm(o, z, gain, eps, act):
    """A delta-rule mixer's output norm and gate: ``o / sqrt(mean(o^2) +
    eps) * gain * act(z)`` with the mean over a head of ``d = len(gain)``
    channels, o and z [B, S, H d], ``act`` "silu" or "sigmoid". Float32
    inside, ``o.dtype`` out, under the caller's scope. Differentiable in o,
    z and gain."""
    if act not in _ACTIVATIONS or o.shape != z.shape \
            or o.shape[-1] % gain.shape[0]:
        raise ValueError(f"act {act!r} of {_ACTIVATIONS}; o {o.shape}, z "
                         f"{z.shape}, heads of {gain.shape}")
    return _registry.dispatch("gated_head_norm", o, z, gain, float(eps), act)


_registry.register_kernel(
    "short_conv_norm", _short_conv_norm_reference, _short_conv_norm_pallas,
    doc="causal depthwise convolution, SiLU and the L2 norm a head in one "
        "pass each way",
    batch_leading=("x",), whole=("taps",))
_registry.register_kernel(
    "gated_head_norm", _gated_head_norm_reference, _gated_head_norm_pallas,
    doc="RMS norm a head times its gain times the gate's activation",
    batch_leading=("o", "z"), whole=("gain",))
