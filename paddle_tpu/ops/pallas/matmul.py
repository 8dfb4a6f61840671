"""Pallas bodies for the static-graph ``fused_matmul`` op.

Two registered kernels back ``_fused_matmul_compute``
(static/opt_passes.py):

- ``fused_matmul`` — fp path: x @ w (+ bias) (+ act) as one blocked MXU
  kernel, fp32 accumulation, bias/act fused into the epilogue of the
  last K step. Differentiable via custom_vjp (backward = the two stock
  matmuls; act grads from saved residuals).
- ``fused_matmul_int8`` — the weight-only PTQ serving variant: the int8
  weight block is dequantized INSIDE the tile loop (convert + per-channel
  scale ride the K-stream in VMEM), so the fp32 sidecar copy of the
  weight the stock body materializes never exists in HBM. Forward-only:
  serving never differentiates a quantized program.

The reference bodies are the exact stock-jnp composition the fused op
has always lowered (pinned by the 220-program equivalence fuzz with the
registry forced on, tests/test_opt_passes.py)."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import registry as _registry
from paddle_tpu.ops.pallas.registry import vmem_spec as _vmem_spec

__all__ = ["try_fused_matmul"]

#: mirrors static/opt_passes.QUANT_BINS (int8 per-channel abs-max:
#: q = round(w / scale * 127)); duplicated to keep this leaf module free
#: of the static-graph import graph
_QUANT_BINS = 127.0

# the epilogue activations, fp32 — identical math to ops/activation.py
# (relu/sigmoid/tanh/gelu with approximate=False)
_ACTS = {
    "relu": lambda v: jnp.maximum(v, 0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "gelu": lambda v: jax.nn.gelu(v, approximate=False),
}


def _round_up(v, m):
    return -(-v // m) * m


def _fmm_kernel(*refs, nk, act, dequant, has_bias):
    """One (m-block, n-block) output tile, K innermost: accumulate fp32
    partial products across the K grid axis, dequantize int8 weight
    blocks in-tile, apply bias+act in the last K step's epilogue."""
    x_ref, w_ref = refs[0], refs[1]
    i = 2
    scale_ref = bias_ref = None
    if dequant:
        scale_ref = refs[i]
        i += 1
    if has_bias:
        bias_ref = refs[i]
        i += 1
    o_ref = refs[i]
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xb = x_ref[...].astype(jnp.float32)
    if dequant:
        wb = w_ref[...].astype(jnp.float32) \
            * (scale_ref[...].astype(jnp.float32) / _QUANT_BINS)
    else:
        wb = w_ref[...].astype(jnp.float32)
    o_ref[...] += jax.lax.dot_general(
        xb, wb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _epilogue():
        r = o_ref[...]
        if has_bias:
            r = r + bias_ref[...].astype(jnp.float32)
        if act is not None:
            r = _ACTS[act](r)
        o_ref[...] = r


def _fmm_call(x2, w, scale, bias, act, interpret):
    """Blocked pallas_call over padded [M,K]@[K,N]; returns fp32 [M,N]."""
    m, kdim = x2.shape
    n = w.shape[1]
    bm = min(128, _round_up(m, 8))
    bn = min(512, _round_up(n, 128))
    # 256 is sublane-safe for every weight dtype (fp32 8, bf16 16, int8 32)
    bk = min(512, _round_up(kdim, 256))
    mp, kp, np_ = _round_up(m, bm), _round_up(kdim, bk), _round_up(n, bn)
    if mp != m or kp != kdim:
        x2 = jnp.pad(x2, ((0, mp - m), (0, kp - kdim)))
    if kp != kdim or np_ != n:
        w = jnp.pad(w, ((0, kp - kdim), (0, np_ - n)))
    grid = (mp // bm, np_ // bn, kp // bk)
    in_specs = [
        _vmem_spec((bm, bk), lambda im, in_, ik: (im, ik)),
        _vmem_spec((bk, bn), lambda im, in_, ik: (ik, in_)),
    ]
    args = [x2, w]
    if scale is not None:
        s1 = jnp.asarray(scale, jnp.float32).reshape(1, -1)
        if np_ != n:
            s1 = jnp.pad(s1, ((0, 0), (0, np_ - n)))
        in_specs.append(_vmem_spec((1, bn), lambda im, in_, ik: (0, in_)))
        args.append(s1)
    if bias is not None:
        b1 = jnp.asarray(bias).reshape(1, -1)
        if np_ != n:
            b1 = jnp.pad(b1, ((0, 0), (0, np_ - n)))
        in_specs.append(_vmem_spec((1, bn), lambda im, in_, ik: (0, in_)))
        args.append(b1)
    kernel = functools.partial(
        _fmm_kernel, nk=grid[2], act=act, dequant=scale is not None,
        has_bias=bias is not None)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=_vmem_spec((bm, bn), lambda im, in_, ik: (im, in_)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="fused_matmul" if scale is None else "fused_matmul_int8",
    )(*args)
    if mp != m or np_ != n:
        out = out[:m, :n]
    return out


# -- fp body (differentiable) ----------------------------------------------

def _fmm_fwd_impl(x2, w, bias, act, interpret):
    """Returns (fp32 out, fp32 act-residual). gelu keeps its epilogue
    OUTSIDE the kernel: its grad needs the pre-activation z, and saving z
    from inside would cost a second HBM output for every fused matmul."""
    kernel_act = None if act == "gelu" else act
    z = _fmm_call(x2, w, None, bias, kernel_act, interpret)
    if act == "gelu":
        return _ACTS["gelu"](z), z
    return z, z


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fmm_fp(x2, w, bias, act, out_dtype, interpret):
    out, _ = _fmm_fwd_impl(x2, w, bias, act, interpret)
    return out.astype(out_dtype)


def _fmm_fp_fwd(x2, w, bias, act, out_dtype, interpret):
    out, res = _fmm_fwd_impl(x2, w, bias, act, interpret)
    return out.astype(out_dtype), (x2, w, bias, res)


def _fmm_fp_bwd(act, out_dtype, interpret, saved, dy):
    x2, w, bias, res = saved
    dy32 = dy.astype(jnp.float32)
    if act == "relu":
        dz = dy32 * (res > 0)           # res = post-act out
    elif act == "sigmoid":
        dz = dy32 * res * (1.0 - res)
    elif act == "tanh":
        dz = dy32 * (1.0 - res * res)
    elif act == "gelu":
        _, vjpf = jax.vjp(_ACTS["gelu"], res)   # res = pre-act z
        dz = vjpf(dy32)[0]
    else:
        dz = dy32
    # backward = the two stock matmuls (XLA's MXU path; the forward win
    # is the fused epilogue/dequant, not the dot itself)
    dx = jax.lax.dot_general(
        dz, w.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x2.dtype)
    dw = jax.lax.dot_general(
        x2.astype(jnp.float32), dz, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(w.dtype)
    db = None if bias is None else \
        jnp.sum(dz, axis=0).astype(jnp.asarray(bias).dtype)
    return dx, dw, db


_fmm_fp.defvjp(_fmm_fp_fwd, _fmm_fp_bwd)


def fused_matmul_pallas(x, w, bias=None, act=None, out_dtype=None,
                        interpret=False):
    """Pallas fp body: x [..., K] @ w [K, N] (+ bias [N]) (+ act)."""
    x = jnp.asarray(x)
    w = jnp.asarray(w)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if out_dtype is None:
        out_dtype = jnp.result_type(x.dtype, w.dtype)
    out = _fmm_fp(x2, w, bias, act, jnp.dtype(out_dtype), bool(interpret))
    return out.reshape(lead + (w.shape[1],))


def fused_matmul_reference(x, w, bias=None, act=None, out_dtype=None):
    """Stock composition: exactly what _fused_matmul_compute lowers for
    the eligible operand pattern (2-D weight, trailing-axis bias)."""
    out = jnp.matmul(jnp.asarray(x), jnp.asarray(w))
    if out_dtype is not None:
        out = out.astype(out_dtype)
    if bias is not None:
        out = out + jnp.asarray(bias)
    if act is not None:
        out = _ACTS[act](out)
    return out


# -- int8 body (forward-only, serving) -------------------------------------

def fused_matmul_int8_pallas(x, w, scale, bias=None, act=None,
                             interpret=False):
    """x [..., K] @ dequant(w int8 [K, N], scale [N]) (+ bias) (+ act).
    Dequant runs inside the tile loop; forward-only (PTQ serving)."""
    x = jnp.asarray(x)
    w = jnp.asarray(w)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    kernel_act = None if act == "gelu" else act
    out = _fmm_call(x2, w, scale, bias, kernel_act, bool(interpret))
    if act == "gelu":
        out = _ACTS["gelu"](out)
    out_dtype = jnp.result_type(x.dtype, jnp.float32)
    return out.astype(out_dtype).reshape(lead + (w.shape[1],))


def fused_matmul_int8_reference(x, w, scale, bias=None, act=None):
    """The existing sidecar-dequant composition (opt_passes PTQ path):
    materialize the fp32 weight, then the stock matmul chain."""
    wd = jnp.asarray(w).astype(jnp.float32) \
        * (jnp.asarray(scale) / _QUANT_BINS)
    out = jnp.matmul(jnp.asarray(x), wd)
    if bias is not None:
        out = out + jnp.asarray(bias)
    if act is not None:
        out = _ACTS[act](out)
    return out


_registry.register_kernel(
    "fused_matmul", fused_matmul_reference, fused_matmul_pallas,
    doc="x @ w (+bias) (+act), fp32 accumulation, fused epilogue")
_registry.register_kernel(
    "fused_matmul_int8", fused_matmul_int8_reference,
    fused_matmul_int8_pallas,
    doc="x @ dequant(w_int8, scale) (+bias) (+act); dequant in-tile")


# -- static-graph dispatch helper ------------------------------------------

def try_fused_matmul(ins, attrs):
    """Pallas fast path for the static ``fused_matmul`` op. Returns the
    op output, or None when the registry selects the stock body or the
    operand pattern is outside the kernels' contract — the caller
    (static/opt_passes._fused_matmul_compute) then runs the stock
    composition, keeping the flag-off path bit-identical."""
    quant = attrs.get("quant")
    name = "fused_matmul_int8" if quant == "int8" else "fused_matmul"
    if not _registry.use_pallas(name):
        return None
    xs = list(ins["X"])
    x, w = jnp.asarray(xs[0]), jnp.asarray(xs[1])
    i = 2
    scale = None
    if quant == "int8":
        scale = xs[i]
        i += 1
        if w.dtype != jnp.int8:
            return None
    elif quant == "bf16":
        # stock path casts the bf16-stored weight to fp32 before the
        # matmul; mirror that so out dtype matches, then ride the fp body
        pass
    elif quant is not None:
        return None
    if w.ndim != 2 or x.ndim < 2 or x.shape[-1] != w.shape[0]:
        return None
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return None
    if quant != "int8" and not (jnp.issubdtype(w.dtype, jnp.floating)):
        return None
    mm_attrs = attrs.get("mm_attrs", {})
    if attrs["mm_type"] == "matmul":
        if mm_attrs.get("transpose_x") or mm_attrs.get("transpose_y") \
                or mm_attrs.get("alpha", 1.0) != 1.0:
            return None
        x_eff = x
        out_shape = x.shape[:-1] + (w.shape[1],)
    elif attrs["mm_type"] == "mul":
        if mm_attrs.get("x_num_col_dims", 1) != 1 \
                or mm_attrs.get("y_num_col_dims", 1) != 1:
            return None
        x_eff = x.reshape((x.shape[0], -1))
        if x_eff.shape[1] != w.shape[0]:
            return None
        out_shape = (x.shape[0], w.shape[1])
    else:
        return None
    bias = None
    if attrs.get("has_bias"):
        b = jnp.asarray(xs[i])
        axis = attrs.get("bias_axis", -1)
        if b.ndim != 1 or b.shape[0] != w.shape[1] \
                or axis not in (-1, len(out_shape) - 1):
            return None
        bias = b
    act = attrs.get("act")
    if act is not None and act not in _ACTS:
        return None
    if quant == "int8":
        out = _registry.dispatch("fused_matmul_int8", x_eff, w, scale,
                                 bias=bias, act=act)
    else:
        out_dtype = jnp.result_type(x.dtype, jnp.float32) \
            if quant == "bf16" else None
        out = _registry.dispatch("fused_matmul", x_eff, w,
                                 bias=bias, act=act, out_dtype=out_dtype)
    return out.reshape(out_shape)
