"""Neural-net structural ops: conv / pool / norm / embedding / dropout.

Parity targets: operators/conv_op.cc(+cudnn), conv_transpose_op.cc,
pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, group_norm_op.cc,
data_norm_op.cc, dropout_op.cc, lookup_table_op.cc, one_hot_op.cc,
label_smooth_op.cc, lrn_op.cc, pad_op.cc, pad2d_op.cc, interpolate_op.cc,
pixel_shuffle_op.cc, affine_channel_op.cc, unfold_op.cc,
space_to_depth_op.cc, shuffle_channel_op.cc, grid_sampler_op.cc.

Convs/matmuls are the MXU ops; layouts default to the reference's NCHW but
everything is expressed through lax.conv_general_dilated dimension numbers
so XLA picks TPU-optimal internal layouts.
"""

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core import random as ptrandom

__all__ = [
    "conv2d", "conv2d_transpose", "conv3d", "conv3d_transpose",
    "depthwise_conv2d", "pool2d",
    "pool3d", "adaptive_pool2d", "adaptive_pool3d",
    "batch_norm", "layer_norm", "group_norm",
    "instance_norm", "data_norm", "sync_batch_norm", "dropout",
    "embedding", "one_hot",
    "label_smooth", "lrn", "pad", "pad2d", "pad_constant_like",
    "interpolate", "resize_nearest", "resize_bilinear", "image_resize",
    "image_resize_short", "pixel_shuffle",
    "affine_channel", "unfold", "space_to_depth", "shuffle_channel",
    "fc_act",
]


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


def _conv_padding(padding, spatial):
    if isinstance(padding, str):
        return padding.upper()
    p = _pair(padding, spatial)
    return [(int(x), int(x)) for x in p]


def conv2d(x, weight, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    """conv_op.cc parity. weight layout OIHW (out, in/groups, kh, kw)."""
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape,
        (data_format, "OIHW", data_format))
    return lax.conv_general_dilated(
        x, weight,
        window_strides=_pair(stride),
        padding=_conv_padding(padding, 2),
        rhs_dilation=_pair(dilation),
        dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=jnp.float32 if x.dtype == jnp.bfloat16 else None)


def depthwise_conv2d(x, weight, stride=1, padding=0, dilation=1,
                     data_format="NCHW", name=None):
    c = x.shape[1] if data_format == "NCHW" else x.shape[-1]
    return conv2d(x, weight, stride, padding, dilation, groups=c,
                  data_format=data_format)


def conv3d(x, weight, stride=1, padding=0, dilation=1, groups=1, name=None):
    dn = lax.conv_dimension_numbers(x.shape, weight.shape,
                                    ("NCDHW", "OIDHW", "NCDHW"))
    return lax.conv_general_dilated(
        x, weight, window_strides=_pair(stride, 3),
        padding=_conv_padding(padding, 3), rhs_dilation=_pair(dilation, 3),
        dimension_numbers=dn, feature_group_count=groups)


def conv3d_transpose(x, weight, stride=1, padding=0, dilation=1, groups=1,
                     name=None):
    """conv_transpose_op.cc 3-D parity. Weight layout IODHW
    (in, out/groups, kd, kh, kw), same filter convention as
    conv2d_transpose; lowered as the gradient-of-conv formulation
    (lhs-dilation) so XLA maps it onto the MXU like a forward conv."""
    stride, dilation = _pair(stride, 3), _pair(dilation, 3)
    pads = _pair(padding, 3)
    kd, kh, kw = weight.shape[2], weight.shape[3], weight.shape[4]
    dn = lax.conv_dimension_numbers(
        x.shape,
        (weight.shape[1] * groups, weight.shape[0] // groups, kd, kh, kw),
        ("NCDHW", "OIDHW", "NCDHW"))
    w = jnp.flip(weight, axis=(2, 3, 4))
    cin, cog = weight.shape[0], weight.shape[1]
    if groups == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        w = w.reshape(groups, cin // groups, cog, kd, kh, kw)
        w = jnp.swapaxes(w, 1, 2).reshape(
            groups * cog, cin // groups, kd, kh, kw)
    pad = [(dilation[i] * (k - 1) - pads[i],) * 2
           for i, k in enumerate((kd, kh, kw))]
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1, 1), padding=pad,
        lhs_dilation=stride, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups)


def conv2d_transpose(x, weight, stride=1, padding=0, dilation=1, groups=1,
                     data_format="NCHW", name=None):
    """conv_transpose_op.cc parity. weight layout IOHW (in, out/groups, kh, kw),
    matching the reference's transpose-conv filter layout."""
    stride, dilation = _pair(stride), _pair(dilation)
    pads = _pair(padding)
    kh, kw = weight.shape[2], weight.shape[3]
    # gradient-of-conv formulation: lhs-dilate input by stride
    dn = lax.conv_dimension_numbers(x.shape,
                                    (weight.shape[1] * groups, weight.shape[0] // groups, kh, kw),
                                    (data_format, "OIHW", data_format))
    # flip spatial dims and swap I/O to turn conv_transpose into conv;
    # grouped case: IOHW rows are group-major, so regroup to
    # (out, in/groups, kh, kw) for feature_group_count semantics
    w = jnp.flip(weight, axis=(2, 3))
    cin, cog = weight.shape[0], weight.shape[1]  # in, out/groups
    if groups == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        w = w.reshape(groups, cin // groups, cog, kh, kw)
        w = jnp.swapaxes(w, 1, 2).reshape(groups * cog, cin // groups, kh, kw)
    pad_h = dilation[0] * (kh - 1) - pads[0]
    pad_w = dilation[1] * (kw - 1) - pads[1]
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1),
        padding=[(pad_h, pad_h), (pad_w, pad_w)],
        lhs_dilation=stride, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups)


def pool2d(x, pool_size=2, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, ceil_mode=False, exclusive=True,
           data_format="NCHW", name=None):
    """pool_op.cc parity (max/avg, exclusive avg-padding semantics,
    NCHW or NHWC layout — pool_op.cc handles both via data_format)."""
    if data_format not in ("NCHW", "NHWC"):
        raise ValueError(f"pool2d: data_format must be NCHW|NHWC, "
                         f"got {data_format!r}")
    sp = (2, 3) if data_format == "NCHW" else (1, 2)
    if global_pooling:
        if pool_type == "max":
            return jnp.max(x, axis=sp, keepdims=True)
        return jnp.mean(x, axis=sp, keepdims=True)
    ks = _pair(pool_size)
    st = _pair(pool_stride)
    pd = _pair(pool_padding)

    def lay(h, w, one=1):
        # place the spatial entries at the layout's H/W positions
        out = [one, one, one, one]
        out[sp[0]], out[sp[1]] = h, w
        return tuple(out)

    window = lay(ks[0], ks[1])
    strides = lay(st[0], st[1])
    ph = (pd[0], pd[0] + (st[0] - 1 if ceil_mode else 0))
    pw = (pd[1], pd[1] + (st[1] - 1 if ceil_mode else 0))
    pads = lay(ph, pw, one=(0, 0))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, pads)
    s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
    if exclusive:
        # ones only over the spatial plane (singleton batch/channel):
        # the count is layout-independent and broadcasts in the divide
        ones = jnp.ones(lay(x.shape[sp[0]], x.shape[sp[1]]), x.dtype)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / cnt
    return s / (ks[0] * ks[1])


def pool3d(x, pool_size=2, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, name=None):
    if global_pooling:
        axis = (2, 3, 4)
        return (jnp.max if pool_type == "max" else jnp.mean)(x, axis=axis, keepdims=True)
    ks, st, pd = _pair(pool_size, 3), _pair(pool_stride, 3), _pair(pool_padding, 3)
    window, strides = (1, 1) + ks, (1, 1) + st
    pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pd)
    if pool_type == "max":
        return lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
    s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
    return s / (ks[0] * ks[1] * ks[2])


def _adaptive_masks(size, out):
    """[out, size] 0/1 membership mask of pool_op.h's adaptive windows:
    cell i covers [floor(i*size/out), ceil((i+1)*size/out))
    (AdaptiveStartIndex/AdaptiveEndIndex). Shapes are static, so the
    mask is a compile-time-constant matrix — the avg reduction becomes
    a (normalized) matmul the MXU tiles, the max a masked reduce."""
    import numpy as _np
    idx = _np.arange(size)
    starts = _np.floor(_np.arange(out) * size / out).astype(int)
    ends = _np.ceil((_np.arange(out) + 1) * size / out).astype(int)
    return jnp.asarray(
        (idx[None, :] >= starts[:, None]) & (idx[None, :] < ends[:, None]),
        jnp.float32)


def _adaptive_reduce(x, axes, outs, pool_type):
    """Adaptive pooling over the given axes to the given output sizes
    via per-axis membership masks (axes reduced one at a time)."""
    for ax, out in zip(axes, outs):
        size = x.shape[ax]
        m = _adaptive_masks(size, out)                   # [out, size]
        xm = jnp.moveaxis(x, ax, -1)                     # [..., size]
        if pool_type == "max":
            big = jnp.finfo(x.dtype).min if jnp.issubdtype(
                x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            # [..., out, size] masked -> max over size
            r = jnp.max(jnp.where(m.astype(bool), xm[..., None, :], big),
                        axis=-1)
        else:
            # highest precision: the mask matmul must reproduce the
            # exact per-cell mean (the divisible reshape path is exact,
            # and pool parity tests compare at tight tolerances); the
            # f32 mask promotes the accumulation — cast back so bf16
            # inputs keep bf16 outputs like the sibling paths
            r = (jnp.einsum("...s,os->...o", xm, m,
                            precision=jax.lax.Precision.HIGHEST)
                 / m.sum(-1)).astype(x.dtype)
        x = jnp.moveaxis(r, -1, ax)
    return x


def adaptive_pool2d(x, pool_size, pool_type="avg", name=None):
    """Adaptive pooling (pool_op.cc adaptive=True): arbitrary output
    sizes via the reference's per-cell start/end windows
    (pool_op.h AdaptiveStartIndex/AdaptiveEndIndex); the divisible case
    keeps the cheap reshape-reduce."""
    n, c, h, w = x.shape
    oh, ow = _pair(pool_size)
    if h % oh == 0 and w % ow == 0:
        x = x.reshape(n, c, oh, h // oh, ow, w // ow)
        return (jnp.max if pool_type == "max" else jnp.mean)(x, axis=(3, 5))
    return _adaptive_reduce(x, (2, 3), (oh, ow), pool_type)


def adaptive_pool3d(x, pool_size, pool_type="avg", name=None):
    """Adaptive 3-D pooling (pool_op.cc adaptive=True over NCDHW; ref
    python/paddle/fluid/layers/nn.py adaptive_pool3d). Arbitrary output
    sizes; divisible sizes keep the reshape-reduce fast path."""
    n, c, d, h, w = x.shape
    od, oh, ow = _pair(pool_size, 3)
    if d % od == 0 and h % oh == 0 and w % ow == 0:
        x = x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow)
        return (jnp.max if pool_type == "max" else jnp.mean)(
            x, axis=(3, 5, 7))
    return _adaptive_reduce(x, (2, 3, 4), (od, oh, ow), pool_type)


def batch_norm(x, scale, bias, mean, variance, epsilon=1e-5, momentum=0.9,
               is_test=False, data_layout="NCHW", use_global_stats=False,
               name=None):
    """batch_norm_op.cc parity.

    Returns (out, mean_out, variance_out, saved_mean, saved_variance) in
    training mode to mirror the reference's outputs; running stats use
    ``new = m*old + (1-m)*batch`` (batch_norm_op.cc momentum semantics).
    """
    axis = 1 if data_layout == "NCHW" else x.ndim - 1
    red = tuple(i for i in range(x.ndim) if i != axis)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]

    if is_test or use_global_stats:
        m, v = mean, variance
        out = (x - m.reshape(bshape)) * (
            scale.reshape(bshape) * lax.rsqrt(v.reshape(bshape) + epsilon)
        ) + bias.reshape(bshape)
        return out, mean, variance, m, v

    m = jnp.mean(x, axis=red)
    v = jnp.var(x, axis=red)
    out = (x - m.reshape(bshape)) * (
        scale.reshape(bshape) * lax.rsqrt(v.reshape(bshape) + epsilon)
    ) + bias.reshape(bshape)
    mean_out = momentum * mean + (1 - momentum) * m
    var_out = momentum * variance + (1 - momentum) * v
    return out, mean_out, var_out, m, v


def sync_batch_norm(x, scale, bias, mean, variance, epsilon=1e-5,
                    momentum=0.9, is_test=False, data_layout="NCHW",
                    axis_name=None, name=None):
    """Cross-replica batch norm (sync_batch_norm_op.cu parity).

    Batch statistics are averaged across the ``axis_name`` mesh axis via
    XLA collectives (replacing the reference's hand-rolled two-pass NCCL
    allreduce of sum/sum-of-squares). Call inside shard_map/pmap with the
    data axis name; with axis_name=None it degrades to plain batch_norm
    (single-replica semantics).
    """
    if is_test or axis_name is None:
        return batch_norm(x, scale, bias, mean, variance, epsilon,
                          momentum, is_test=is_test,
                          data_layout=data_layout)
    axis = 1 if data_layout == "NCHW" else x.ndim - 1
    red = tuple(i for i in range(x.ndim) if i != axis)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    # two-moment form so one pmean pair gives exact global stats
    m_local = jnp.mean(x, axis=red)
    sq_local = jnp.mean(jnp.square(x), axis=red)
    m = lax.pmean(m_local, axis_name)
    sq = lax.pmean(sq_local, axis_name)
    v = sq - jnp.square(m)
    out = (x - m.reshape(bshape)) * (
        scale.reshape(bshape) * lax.rsqrt(v.reshape(bshape) + epsilon)
    ) + bias.reshape(bshape)
    mean_out = momentum * mean + (1 - momentum) * m
    var_out = momentum * variance + (1 - momentum) * v
    return out, mean_out, var_out, m, v


def layer_norm(x, scale=None, bias=None, begin_norm_axis=1, epsilon=1e-5,
               name=None):
    """layer_norm_op.cc parity: normalize over dims [begin_norm_axis:)."""
    red = tuple(range(begin_norm_axis, x.ndim))
    m = jnp.mean(x, axis=red, keepdims=True)
    v = jnp.var(x, axis=red, keepdims=True)
    out = (x - m) * lax.rsqrt(v + epsilon)
    norm_shape = x.shape[begin_norm_axis:]
    if scale is not None:
        out = out * scale.reshape(norm_shape)
    if bias is not None:
        out = out + bias.reshape(norm_shape)
    return out


def group_norm(x, scale=None, bias=None, groups=32, epsilon=1e-5,
               data_layout="NCHW", name=None):
    """group_norm_op.cc parity (NCHW)."""
    n, c = x.shape[0], x.shape[1]
    g = groups
    xs = x.reshape((n, g, c // g) + x.shape[2:])
    red = tuple(range(2, xs.ndim))
    m = jnp.mean(xs, axis=red, keepdims=True)
    v = jnp.var(xs, axis=red, keepdims=True)
    xs = (xs - m) * lax.rsqrt(v + epsilon)
    out = xs.reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        out = out * scale.reshape(bshape)
    if bias is not None:
        out = out + bias.reshape(bshape)
    return out


def instance_norm(x, scale=None, bias=None, epsilon=1e-5, name=None):
    return group_norm(x, scale, bias, groups=x.shape[1], epsilon=epsilon)


def data_norm(x, batch_size, batch_sum, batch_square_sum, epsilon=1e-4,
              name=None):
    """data_norm_op.cc parity: normalize by accumulated batch statistics."""
    means = batch_sum / batch_size
    scales = jnp.sqrt(batch_size / (batch_square_sum - batch_size * jnp.square(means) + epsilon))
    return (x - means) * scales


def dropout(x, dropout_prob=0.5, is_test=False, seed=None,
            dropout_implementation="downgrade_in_infer", rng=None, name=None):
    """dropout_op.cc parity, both implementations:
    downgrade_in_infer (scale at inference) and upscale_in_train."""
    if dropout_prob == 0.0:
        return x
    if is_test:
        if dropout_implementation == "downgrade_in_infer":
            return x * (1.0 - dropout_prob)
        return x
    if rng is None:
        rng = ptrandom.key_for(seed)
    keep = jax.random.bernoulli(rng, 1.0 - dropout_prob, x.shape)
    if dropout_implementation == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - dropout_prob), 0.0).astype(x.dtype)
    return jnp.where(keep, x, 0.0).astype(x.dtype)


def embedding(ids, weight, padding_idx=None, name=None):
    """lookup_table_op.cc parity: gather rows; padding_idx rows → 0.

    On TPU this is a gather from an HBM-resident table; the distributed
    large-table path lives in paddle_tpu/distributed/sparse.py.
    """
    ids = jnp.asarray(ids)
    squeeze = False
    if ids.ndim and ids.shape[-1] == 1:
        ids, squeeze = ids[..., 0], True
    weight = jnp.asarray(weight)
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None:
        if padding_idx < 0:  # fluid convention: -1 means last row
            padding_idx = weight.shape[0] + padding_idx
        mask = (ids != padding_idx)[..., None]
        out = jnp.where(mask, out, 0.0)
    return out


def one_hot(x, depth, dtype=jnp.float32, name=None):
    x = jnp.asarray(x)
    if x.ndim and x.shape[-1] == 1:
        x = x[..., 0]
    return jax.nn.one_hot(x, depth, dtype=dtype)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    k = label.shape[-1]
    if prior_dist is not None:
        return (1 - epsilon) * label + epsilon * prior_dist
    return (1 - epsilon) * label + epsilon / k


def lrn(x, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    """lrn_op.cc parity: local response norm across channels (NCHW)."""
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i: i + x.shape[1]] for i in range(n))
    return x / jnp.power(k + alpha * acc, beta)


def pad(x, paddings, pad_value=0.0, name=None):
    """pad_op.cc parity: flat [before0, after0, before1, after1, ...]."""
    cfg = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    return jnp.pad(x, cfg, constant_values=pad_value)


def pad2d(x, paddings, mode="constant", pad_value=0.0, data_format="NCHW",
          name=None):
    t, b, l, r = paddings
    cfg = ((0, 0), (0, 0), (t, b), (l, r)) if data_format == "NCHW" \
        else ((0, 0), (t, b), (l, r), (0, 0))
    jmode = {"constant": "constant", "reflect": "reflect", "edge": "edge"}[mode]
    if jmode == "constant":
        return jnp.pad(x, cfg, constant_values=pad_value)
    return jnp.pad(x, cfg, mode=jmode)


def pad_constant_like(x, y, pad_value=0.0, name=None):
    cfg = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    return jnp.pad(y, cfg, constant_values=pad_value)


def interpolate(x, out_shape=None, scale=None, resample="BILINEAR",
                align_corners=True, data_format="NCHW", name=None):
    """interpolate_op.cc parity (nearest / bilinear over NCHW)."""
    n, c, h, w = x.shape
    if out_shape is None:
        out_shape = (int(h * scale), int(w * scale))
    oh, ow = out_shape
    method = "nearest" if resample.upper() == "NEAREST" else "bilinear"
    if method == "nearest" or not align_corners:
        return jax.image.resize(x, (n, c, oh, ow), method=method)
    # align_corners bilinear via explicit gather-interpolation
    ys = jnp.linspace(0, h - 1, oh)
    xs = jnp.linspace(0, w - 1, ow)
    y0 = jnp.floor(ys).astype(jnp.int32)
    x0 = jnp.floor(xs).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (ys - y0)[None, None, :, None]
    wx = (xs - x0)[None, None, None, :]
    g = lambda yi, xi: x[:, :, yi][:, :, :, xi]
    top = g(y0, x0) * (1 - wx) + g(y0, x1) * wx
    bot = g(y1, x0) * (1 - wx) + g(y1, x1) * wx
    return top * (1 - wy) + bot * wy


def resize_nearest(x, out_shape=None, scale=None, align_corners=True, name=None):
    return interpolate(x, out_shape, scale, "NEAREST", align_corners)


def resize_bilinear(x, out_shape=None, scale=None, align_corners=True, name=None):
    return interpolate(x, out_shape, scale, "BILINEAR", align_corners)


def image_resize(x, out_shape=None, scale=None, resample="BILINEAR",
                 align_corners=True, name=None):
    """fluid.layers.image_resize parity (layers/nn.py image_resize):
    the user-facing dispatcher over interpolate_op.cc."""
    if resample.upper() not in ("BILINEAR", "NEAREST"):
        raise ValueError(
            f"image_resize: resample must be BILINEAR or NEAREST, "
            f"got {resample}")
    return interpolate(x, out_shape, scale, resample.upper(), align_corners)


def image_resize_short(x, out_short_len, resample="BILINEAR", name=None):
    """fluid.layers.image_resize_short parity: resize so the SHORT edge
    becomes out_short_len, keeping aspect ratio."""
    n, c, h, w = x.shape
    short = min(h, w)
    oh = int(round(h * out_short_len / short))
    ow = int(round(w * out_short_len / short))
    return image_resize(x, (oh, ow), None, resample)  # shares validation


def pixel_shuffle(x, upscale_factor, name=None):
    """pixel_shuffle_op.cc parity (NCHW)."""
    n, c, h, w = x.shape
    r = upscale_factor
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = x.transpose(0, 1, 4, 2, 5, 3)
    return x.reshape(n, c // (r * r), h * r, w * r)


def affine_channel(x, scale, bias, data_layout="NCHW", name=None):
    bshape = (1, -1) + (1,) * (x.ndim - 2) if data_layout == "NCHW" else (-1,)
    return x * scale.reshape(bshape) + bias.reshape(bshape)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """unfold_op.cc (im2col) parity: [N,C,H,W] → [N, C*kh*kw, L]."""
    kh, kw = _pair(kernel_sizes)
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), _pair(strides),
        [(p, p) for p in _pair(paddings)],
        rhs_dilation=_pair(dilations),
        dimension_numbers=lax.conv_dimension_numbers(
            x.shape, (1, x.shape[1], kh, kw), ("NCHW", "OIHW", "NCHW")))
    n, ckk = patches.shape[0], patches.shape[1]
    return patches.reshape(n, ckk, -1)


def space_to_depth(x, blocksize, name=None):
    n, c, h, w = x.shape
    b = blocksize
    x = x.reshape(n, c, h // b, b, w // b, b)
    x = x.transpose(0, 3, 5, 1, 2, 4)
    return x.reshape(n, c * b * b, h // b, w // b)


def shuffle_channel(x, group, name=None):
    n, c, h, w = x.shape
    x = x.reshape(n, group, c // group, h, w)
    return x.swapaxes(1, 2).reshape(n, c, h, w)


def fc_act(x, act):
    """Apply a named activation (the reference's `act` attr pattern)."""
    if act is None:
        return x
    from paddle_tpu.ops import activation as A
    return getattr(A, act)(x)
