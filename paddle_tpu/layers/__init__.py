"""fluid.layers parity surface.

Parity: python/paddle/fluid/layers/{nn.py (184 fns), tensor.py,
control_flow.py, learning_rate_scheduler.py, sequence ops, metric_op.py}.

Every function works in BOTH modes, like the reference's layers do
(static program building vs dygraph):
- **eager**: computes immediately via the functional op library
  (paddle_tpu.ops). Parameterized layers (fc, conv2d, …) additionally
  work inside an nn module context, collecting params functionally.
- **static** (inside `program_guard`): appends an op to the current
  Program and returns a symbolic Variable; output shapes are inferred by
  `jax.eval_shape` over the same functional implementation — the op's
  compute IS its shape function, so there is no separate InferShape
  (ref: framework/shape_inference.h is subsumed).
"""

import contextlib
import functools
import inspect

# the fluid surface exports a `range` op (ops.aliases); the auto-wrap
# loop below injects it into this module's globals, so capture the
# builtin before it is shadowed
_builtin_range = range

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import initializer as I
from paddle_tpu import ops as _ops
from paddle_tpu.core.dtypes import convert_dtype
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.framework import ParamAttr, WeightNormParamAttr, unique_name
from paddle_tpu.nn import module as _module
from paddle_tpu.static.program import (
    OP_REGISTRY, Variable, default_main_program, default_startup_program,
    in_static_mode, data,
)
from paddle_tpu.layers import learning_rate_scheduler
from paddle_tpu.layers.control_flow_classes import (
    While, Switch, IfElse, StaticRNN, DynamicRNN,
)
from paddle_tpu.layers.learning_rate_scheduler import (
    noam_decay, exponential_decay, natural_exp_decay, inverse_time_decay,
    polynomial_decay, piecewise_decay, cosine_decay, linear_lr_warmup,
)

# ---------------------------------------------------------------------------
# generic static-dispatch machinery for stateless ops
# ---------------------------------------------------------------------------

# ops whose leading-N args are tensors (default 1)
_NARGS = {
    "elementwise_add": 2, "elementwise_sub": 2, "elementwise_mul": 2,
    "elementwise_div": 2, "elementwise_min": 2, "elementwise_max": 2,
    "elementwise_pow": 2, "elementwise_mod": 2, "elementwise_floordiv": 2,
    "minus": 2, "matmul": 2, "mul": 2, "bmm": 2, "dot": 2,
    "cross_entropy": 2, "softmax_with_cross_entropy": 2,
    "sigmoid_cross_entropy_with_logits": 2, "square_error_cost": 2,
    "smooth_l1": 2, "huber_loss": 2, "log_loss": 2, "hinge_loss": 2,
    "margin_rank_loss": 3, "rank_loss": 3, "kldiv_loss": 2, "bpr_loss": 2,
    "cos_sim": 2, "modified_huber_loss": 2, "mse_loss": 2,
    "teacher_student_sigmoid_loss": 2, "npair_loss": 3,
    "gather": 2, "gather_nd": 2, "scatter": 3, "scatter_nd_add": 3,
    "where": 3, "expand_as": 2, "pad_constant_like": 2,
    "logical_and": 2, "logical_or": 2, "logical_xor": 2,
    "equal": 2, "not_equal": 2, "less_than": 2, "less_equal": 2,
    "greater_than": 2, "greater_equal": 2,
    "accuracy": 2, "auc": 2,
    "fill_constant": 0, "zeros": 0, "ones": 0, "eye": 0,
    "linspace": 0, "arange": 0, "gaussian_random": 0, "uniform_random": 0,
    "truncated_gaussian_random": 0, "randint": 0,
    "prelu": 2, "conv2d": 2, "conv2d_transpose": 2, "conv3d": 2,
    "depthwise_conv2d": 2, "embedding": 2,
    # quantization family
    "fake_quantize_range_abs_max": 3,
    "fake_quantize_moving_average_abs_max": 3,
    "fake_quantize_dequantize_moving_average_abs_max": 3,
    "moving_average_abs_max_scale": 3,
    "fake_dequantize_max_abs": 2, "quantize_linear": 2,
    "dequantize_linear": 2, "fake_channel_wise_dequantize_max_abs": 1,
    "quantized_mul": 2, "quantized_conv2d": 2,
    # crf / ctc families (optional trailing tensors promote dynamically)
    "linear_chain_crf": 3, "crf_decoding": 2, "ctc_loss": 2,
    "warpctc": 2, "edit_distance": 2,
    # detection family
    "iou_similarity": 2, "box_coder": 3, "prior_box": 2,
    "density_prior_box": 2, "bipartite_match": 1, "target_assign": 2,
    "multiclass_nms": 2, "detection_output": 4, "ssd_loss": 5,
    "yolo_box": 2, "yolov3_loss": 3, "box_clip": 2,
    "sigmoid_focal_loss": 3, "roi_align": 2, "roi_pool": 2,
    "roi_perspective_transform": 2, "mine_hard_examples": 4,
    "psroi_pool": 2, "generate_proposals": 5, "box_decoder_and_assign": 4,
    "dice_loss": 2, "sampled_softmax_with_cross_entropy": 2,
    "deformable_roi_pooling": 3, "conv3d_transpose": 2,
    "create_tensor": 0, "hierarchical_sigmoid": 4,
}

# ops whose first arg is a LIST of tensors
_LIST_FIRST = {"concat", "sums", "stack", "multiplex"}

# ops that draw randomness (executor must feed them a key)
_NEEDS_RNG = {"dropout", "gaussian_random", "uniform_random",
              "truncated_gaussian_random", "randint", "sampling_id",
              "random_crop", "shuffle_batch",
              "uniform_random_batch_size_like",
              "gaussian_random_batch_size_like",
              "sampled_softmax_with_cross_entropy"}

_MULTI_OUT = {"topk": 2, "argsort": 2, "ctc_align": 2, "edit_distance": 2,
              "fake_quantize_abs_max": 2,
              "fake_quantize_dequantize_abs_max": 2,
              "fake_channel_wise_quantize_abs_max": 2,
              "fake_channel_wise_quantize_dequantize_abs_max": 2,
              "fake_quantize_range_abs_max": 2,
              "moving_average_abs_max_scale": 3,
              "fake_quantize_moving_average_abs_max": 4,
              "fake_quantize_dequantize_moving_average_abs_max": 4,
              "prior_box": 2,
              "density_prior_box": 2, "anchor_generator": 2,
              "bipartite_match": 2, "yolo_box": 2, "target_assign": 2,
              "generate_proposals": 3,
              "roi_perspective_transform": 3,
              "mine_hard_examples": 2,
              "ctc_greedy_decoder": 2, "unique": 2}


def _bind_tensor_params(tparams, xs):
    """Rebuild {param: tensor-or-list} from the flattened input list."""
    out = {}
    i = 0
    for entry in tparams:
        if isinstance(entry, tuple):
            pname, cnt = entry
            out[pname] = list(xs[i:i + cnt])
            i += cnt
        else:
            out[entry] = xs[i]
            i += 1
    return out


def _register(name, fn):
    n_tensor = _NARGS.get(name, 1)
    listy = name in _LIST_FIRST

    def compute(ins, attrs):
        xs = ins.get("X", [])
        attrs = dict(attrs)
        attrs.pop("_needs_rng", None)
        tparams = attrs.pop("_tensor_params", None)
        if listy:
            out = fn(list(xs), **attrs)
        elif tparams is not None:
            # inputs bound by parameter name (op had optional tensor args
            # promoted from attr positions — e.g. ssd_loss's prior_box_var);
            # (name, count) entries regroup list-valued tensor params
            out = fn(**{**attrs, **_bind_tensor_params(tparams, xs)})
        else:
            out = fn(*xs, **attrs)
        return {"Out": list(out) if isinstance(out, tuple) else [out]}

    OP_REGISTRY[name] = compute
    return n_tensor, listy


def _sub_dyn(shape, val=2):
    return tuple(val if (s is None or s == -1) else int(s) for s in shape)


def _spec_of(v, val=2):
    if v.shape is None:
        raise EnforceNotMet(
            f"variable '{v.name}' has unknown shape (producer op's shape "
            f"inference failed: {getattr(v, '_shape_error', 'unknown')})")
    return jax.ShapeDtypeStruct(_sub_dyn(v.shape, val), v.dtype)


def _append_static(name, fn, tensor_vals, attrs, listy,
                   tensor_params=None, promoted=None):
    """Append one op to the current program.

    ``tensor_params`` names the leading tensor parameters; ``promoted`` is
    an ordered {param: Variable} of OPTIONAL tensor args found in attr
    positions (they must ride the input list, not the attr dict — a
    Variable baked into attrs would crash the executor)."""
    blk = default_main_program().global_block()
    program = default_main_program()
    in_names = []
    specs2, specs3 = [], []
    had_dyn = False
    flat = list(tensor_vals[0] if listy else tensor_vals)
    all_params = list(tensor_params) if tensor_params is not None else []
    if promoted:
        for pname, pval in promoted.items():
            if isinstance(pval, (list, tuple)):
                # a LIST of tensors in an attr position (e.g.
                # fake_channel_wise_dequantize_max_abs's scales):
                # flatten into inputs, record (name, count) to regroup
                flat.extend(pval)
                all_params.append((pname, len(pval)))
            else:
                flat.append(pval)
                all_params.append(pname)
        attrs = {k: v for k, v in attrs.items() if k not in promoted}
    for tv in flat:
        if isinstance(tv, Variable):
            in_names.append(tv.name)
            specs2.append(_spec_of(tv, 2))
            specs3.append(_spec_of(tv, 3))
            if tv.shape and any(s in (-1, None) for s in tv.shape):
                had_dyn = True
        else:
            arr = jnp.asarray(tv)
            cname = unique_name.generate(f"const_{name}")
            blk.create_var(name=cname, shape=arr.shape, dtype=arr.dtype,
                           persistable=False)
            program._constants[cname] = arr
            in_names.append(cname)
            sp = jax.ShapeDtypeStruct(arr.shape, arr.dtype)
            specs2.append(sp)
            specs3.append(sp)

    eval_attrs = dict(attrs)
    if name in _NEEDS_RNG:
        eval_attrs["rng"] = jax.random.PRNGKey(0)

    def infer(specs):
        if listy:
            return jax.eval_shape(lambda *xs: fn(list(xs), **eval_attrs),
                                  *specs)
        if promoted:
            return jax.eval_shape(
                lambda *xs: fn(**{**eval_attrs,
                                  **_bind_tensor_params(all_params, xs)}),
                *specs)
        return jax.eval_shape(lambda *xs: fn(*xs, **eval_attrs), *specs)

    # dynamic dims are probed with two substitute sizes (2 and 3): any
    # output dim that shifts between the probes depends on a dynamic input
    # dim and is recorded as -1, not a literal
    shape_error = None
    legacy_batch_fixup = False
    try:
        out_spec = infer(specs2)
    except Exception as e:  # shape inference failure -> unknown shape
        out_spec = out_spec3 = None
        shape_error = f"{type(e).__name__}: {e}"
    else:
        try:
            out_spec3 = infer(specs3) if had_dyn else out_spec
        except Exception:
            # op only traces at the first probe size (e.g. a reshape attr
            # tied to it): fall back to marking just the batch dim dynamic
            out_spec3 = out_spec
            legacy_batch_fixup = had_dyn

    n_out = _MULTI_OUT.get(name, 1)
    outs = []

    def listify(spec):
        return (list(spec) if isinstance(spec, (tuple, list))
                else [spec] * n_out if spec is None else [spec])

    out_specs = listify(out_spec)
    out_specs3 = listify(out_spec3)
    for i in _builtin_range(n_out):
        sp = out_specs[i] if i < len(out_specs) else None
        sp3 = out_specs3[i] if i < len(out_specs3) else None
        shape = None
        dtype = jnp.float32
        if sp is not None:
            dtype = sp.dtype
            shape = [d if sp3 is None or d == sp3.shape[j] else -1
                     for j, d in enumerate(sp.shape)]
            if legacy_batch_fixup and shape and shape[0] == 2:
                shape[0] = -1
        v = blk.create_var(name=unique_name.generate(f"{name}.out"),
                           shape=shape, dtype=dtype)
        if shape is None:
            v._shape_error = shape_error
        outs.append(v)
    op_attrs = dict(attrs)
    if name in _NEEDS_RNG:
        op_attrs["_needs_rng"] = True
    if promoted:
        op_attrs["_tensor_params"] = tuple(all_params)
    blk.append_op(type=name, inputs={"X": in_names},
                  outputs={"Out": [v.name for v in outs]}, attrs=op_attrs)
    return outs[0] if n_out == 1 else tuple(outs)


def _has_variable(vals):
    for v in vals:
        if isinstance(v, Variable):
            return True
        if isinstance(v, (list, tuple)) and any(
                isinstance(x, Variable) for x in v):
            return True
    return False


def _dual(name, fn):
    n_tensor, listy = _register(name, fn)
    sig = inspect.signature(fn)
    pnames = list(sig.parameters)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        bound = sig.bind(*args, **kwargs)
        bound.apply_defaults()
        vals = bound.arguments
        if listy:
            tensor_vals = [list(vals[pnames[0]])]
            attr_names = pnames[1:]
        else:
            tensor_vals = [vals[p] for p in pnames[:n_tensor]]
            attr_names = pnames[n_tensor:]
        attrs = {p: vals[p] for p in attr_names
                 if p in vals and p not in ("name", "rng")
                 and vals[p] is not inspect.Parameter.empty}
        if in_static_mode():
            promoted = {p: v for p, v in attrs.items()
                        if isinstance(v, Variable)
                        or (isinstance(v, (list, tuple))
                            and any(isinstance(x, Variable) for x in v))}
            if promoted or _has_variable(
                    tensor_vals[0] if listy else tensor_vals):
                return _append_static(name, fn, tensor_vals, attrs, listy,
                                      tensor_params=pnames[:n_tensor],
                                      promoted=promoted)
        return fn(*args, **kwargs)

    return wrapper


# auto-wrap every exported functional op
_EXCLUDE = {"fc_act", "batch_norm", "sequence_mask",
            # host/numpy or list-in/list-out detection ops: exposed
            # directly below, no static-program wrapper
            "rpn_target_assign", "generate_proposal_labels",
            "detection_map", "distribute_fpn_proposals",
            "collect_fpn_proposals", "retinanet_detection_output",
            "retinanet_target_assign", "generate_mask_labels",
            # host/list ops from ops.aliases: no static wrapper either
            "delete_var", "alloc_continuous_space"}
_this = globals()
for _n in dir(_ops):
    if _n.startswith("_") or _n in _EXCLUDE:
        continue
    _f = getattr(_ops, _n)
    if callable(_f) and getattr(_f, "__module__", "").startswith("paddle_tpu.ops"):
        _this[_n] = _dual(_n, _f)

# sequence_mask needs maxlen attr; expose directly (works both modes)
sequence_mask = _dual("sequence_mask", _ops.sequence_mask)


# control flow with callable bodies: the auto-wrap treats every
# positional arg as a tensor, so these get explicit duals. In static
# mode the bodies are traced into serializable sub-programs
# (static/nested.py, ref while_op.cc / recurrent_op.cc sub-blocks);
# eager mode lowers straight to lax.while_loop / lax.scan.
def while_loop(cond, body, loop_vars, is_test=False, name=None):
    lv = loop_vars if isinstance(loop_vars, (list, tuple)) else [loop_vars]
    if in_static_mode() and _has_variable(list(lv)):
        from paddle_tpu.static.nested import static_while_loop
        return static_while_loop(cond, body, loop_vars)
    return _ops.while_loop(cond, body, loop_vars)


def static_rnn(step_fn, inputs, initial_state):
    if in_static_mode() and _has_variable(
            list(inputs if isinstance(inputs, (list, tuple))
                 else [inputs])):
        from paddle_tpu.static.nested import static_rnn_block
        return static_rnn_block(step_fn, inputs, initial_state)
    return _ops.static_rnn(step_fn, inputs, initial_state)

# host/list detection ops: eager-only passthroughs
rpn_target_assign = _ops.rpn_target_assign
generate_proposal_labels = _ops.generate_proposal_labels
detection_map = _ops.detection_map
distribute_fpn_proposals = _ops.distribute_fpn_proposals
collect_fpn_proposals = _ops.collect_fpn_proposals
retinanet_detection_output = _ops.retinanet_detection_output
retinanet_target_assign = _ops.retinanet_target_assign
generate_mask_labels = _ops.generate_mask_labels
delete_var = _ops.delete_var
alloc_continuous_space = _ops.alloc_continuous_space


# ---------------------------------------------------------------------------
# parameterized layer functions
# ---------------------------------------------------------------------------
def _make_param(prefix, shape, dtype, attr, default_init, trainable=True):
    """Create a parameter in whichever context is active (static program
    or nn module frame)."""
    attr = ParamAttr.to_attr(attr) if attr is not None else ParamAttr()
    if isinstance(attr, WeightNormParamAttr):
        return _make_weight_norm_param(prefix, shape, dtype, attr,
                                       default_init, trainable)
    init = attr.initializer or default_init
    if in_static_mode():
        blk = default_main_program().global_block()
        name = attr.name or unique_name.generate(prefix)
        p = blk.create_parameter(
            name, shape, dtype, trainable=attr.trainable and trainable,
            regularizer=attr.regularizer, gradient_clip=attr.gradient_clip,
            optimize_attr={"learning_rate": attr.learning_rate},
            initializer=init)
        sblk = default_startup_program().global_block()
        if not sblk.has_var(name):
            sblk.create_parameter(name, shape, dtype, initializer=init)
            sblk.append_op(
                type="init_param", inputs={},
                outputs={"Out": [name]},
                attrs={"initializer": init, "shape": tuple(shape),
                       "dtype": np.dtype(dtype).name if not isinstance(dtype, str) else dtype,
                       "_needs_rng": True})
        return p
    if _module.in_module_ctx():
        return _module.create_parameter(prefix, shape, dtype,
                                        initializer=init, attr=attr)
    raise EnforceNotMet(
        f"parameterized layer needs a Program (use program_guard) or a "
        f"module context (nn.transform / Layer.init)")


def _make_weight_norm_param(prefix, shape, dtype, attr, default_init,
                            trainable):
    """Weight normalization (WeightNormParamAttr, ref param_attr.py +
    layers/__init__ weight-norm rewrite): reparameterize w = g * v/||v||
    with the norm over every axis except ``dim``. v carries the
    direction, g the magnitude; g is initialized to ||v_init|| so the
    initial effective weight equals the plain initialization."""
    if attr.name:
        base = attr.name
    elif in_static_mode():
        base = unique_name.generate(prefix + "_wn")
    else:
        # module ctx: init AND apply both execute this code, so the name
        # must be deterministic — name by prefix and let the module
        # frame scope it (the rule plain unnamed params follow);
        # unique_name's global counter would diverge between the two
        # passes and apply would miss the param
        base = prefix + "_wn"
    init = attr.initializer or default_init
    plain = ParamAttr(name=base + "_v", initializer=init,
                      learning_rate=attr.learning_rate,
                      regularizer=attr.regularizer,
                      trainable=attr.trainable and trainable,
                      gradient_clip=attr.gradient_clip)
    v = _make_param(prefix + "_v", shape, dtype, plain, init, trainable)
    dim = attr.dim
    # dim=None: one scalar g (norm over everything). dim=k: per-slice g
    # over axis k; when the param is 1-D that means per-element (norm of
    # each slice is just |v_i|) — keep the two cases distinct, an empty
    # axes tuple is NOT the same as "reduce all".
    norm_axes = (None if dim is None else
                 tuple(i for i in _builtin_range(len(shape)) if i != dim))
    g_shape = (shape[dim],) if dim is not None else (1,)

    if in_static_mode():
        gname = base + "_g"
        blk = default_main_program().global_block()
        gp = blk.create_parameter(
            gname, g_shape, dtype, trainable=attr.trainable and trainable,
            regularizer=attr.regularizer,
            gradient_clip=attr.gradient_clip,
            optimize_attr={"learning_rate": attr.learning_rate},
            initializer=I.Constant(1.0))
        sblk = default_startup_program().global_block()
        if not sblk.has_var(gname):
            sblk.create_parameter(gname, g_shape, dtype,
                                  initializer=I.Constant(1.0))
            # g starts at ||v_init||: computed FROM v in the startup
            # program (the reference appends norm ops the same way)
            sblk.append_op(type="weight_norm_init_g",
                           inputs={"X": [base + "_v"]},
                           outputs={"Out": [gname]},
                           attrs={"dim": dim})
        g = gp
    else:
        # g starts at ||v_init||; the initializer closure is only CALLED
        # at parameter creation (module frame mode == "init"), so apply/
        # grad never touch it — and it uses jnp ops, because under
        # nn.transform's init v may be a tracer (np.asarray would crash)
        class _GInit(I.Initializer):
            def __call__(self, key, gshape_, gdtype=jnp.float32):
                return _wn_norm_jnp(v, dim).reshape(gshape_) \
                    .astype(gdtype)
        g = _make_param(prefix + "_g", g_shape, dtype,
                        ParamAttr(name=base + "_g",
                                  initializer=_GInit(),
                                  learning_rate=attr.learning_rate,
                                  regularizer=attr.regularizer,
                                  gradient_clip=attr.gradient_clip,
                                  trainable=attr.trainable and trainable),
                        I.Constant(1.0), trainable)

    # w = g * v / ||v||, built from wrapped ops so it works in BOTH
    # modes (static: appends square/reduce/scale/rsqrt/mul ops)
    if norm_axes is None:
        sq = reduce_sum(square(v), keep_dim=True)
    elif norm_axes:
        sq = reduce_sum(square(v), dim=list(norm_axes), keep_dim=True)
    else:
        sq = square(v)            # 1-D with dim set: per-element norm
    inv = rsqrt(scale(sq, scale=1.0, bias=1e-12))
    gshape = [1] * len(shape)
    if dim is not None:
        gshape[dim] = shape[dim]
    gb = reshape(g, shape=gshape)
    return elementwise_mul(elementwise_mul(v, inv), gb)


def _wn_norm_jnp(v, dim):
    """||v|| over all axes but ``dim`` (all axes when dim is None;
    per-element when v is 1-D and dim is set)."""
    v = jnp.asarray(v)
    if dim is None:
        return jnp.sqrt(jnp.sum(jnp.square(v))).reshape(1)
    axes = tuple(i for i in _builtin_range(v.ndim) if i != dim)
    if not axes:
        return jnp.abs(v)
    return jnp.sqrt(jnp.sum(jnp.square(v), axis=axes))


def _weight_norm_init_g_compute(ins, attrs):
    return {"Out": [_wn_norm_jnp(ins["X"][0], attrs.get("dim"))]}


OP_REGISTRY["weight_norm_init_g"] = _weight_norm_init_g_compute


def register_op_init_param():
    def compute(ins, attrs):
        init = attrs["initializer"]
        rng = attrs.get("rng", jax.random.PRNGKey(0))
        return {"Out": [init(rng, tuple(attrs["shape"]),
                             convert_dtype(attrs["dtype"]))]}
    OP_REGISTRY["init_param"] = compute


register_op_init_param()


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """fluid.layers.create_parameter parity."""
    default = default_initializer or (
        I.Constant(0.0) if is_bias else I.Xavier())
    if attr is None and name is not None:
        attr = ParamAttr(name=name)
    return _make_param(name or "param", tuple(shape), convert_dtype(dtype),
                       attr, default)


def create_global_var(shape, value, dtype="float32", persistable=False,
                      force_cpu=False, name=None):
    """fluid.layers.create_global_var parity (static only)."""
    return _make_param(name or "gvar", tuple(shape), convert_dtype(dtype),
                       ParamAttr(name=name, trainable=False),
                       I.Constant(value), trainable=False)


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """fluid.layers.fc parity (ref: python/paddle/fluid/layers/nn.py fc).

    On TPU this is the canonical MXU op: a flattened matmul + fused bias +
    fused activation (the reference's separate fc/fused-fc ops collapse
    into XLA fusion)."""
    inputs = list(input) if isinstance(input, (list, tuple)) else [input]
    attrs = (list(param_attr) if isinstance(param_attr, (list, tuple))
             else [param_attr] * len(inputs))
    out = None
    for x, pa in zip(inputs, attrs):
        in_dim = 1
        for d in x.shape[num_flatten_dims:]:
            if d in (-1, None):
                raise EnforceNotMet(
                    f"fc: flattened input dims must be static, got shape "
                    f"{x.shape} with num_flatten_dims={num_flatten_dims}")
            in_dim *= int(d)
        w = _make_param("fc_w", (in_dim, size), jnp.float32, pa, I.Xavier())
        o = mul(x, w, x_num_col_dims=num_flatten_dims)
        out = o if out is None else elementwise_add(out, o)
    # one shared bias regardless of how many input branches (fluid layout)
    if bias_attr is not False:
        b = _make_param("fc_b", (size,), jnp.float32, bias_attr,
                        I.Constant(0.0))
        out = elementwise_add(out, b, axis=num_flatten_dims)
    return _apply_act(out, act)


def _apply_act(x, act):
    if act is None:
        return x
    return globals()[act](x)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """fluid.layers.embedding / lookup_table parity. is_sparse/
    is_distributed are advisory on TPU (see distributed/sparse.py for the
    host-sharded big-table path)."""
    w = _make_param("emb_w", tuple(size), convert_dtype(dtype), param_attr,
                    I.Xavier())
    pi = padding_idx if padding_idx is None or padding_idx >= 0 \
        else size[0] + padding_idx
    return _emb_dispatch(input, w, pi)


def _emb_dispatch(input, w, padding_idx):
    if in_static_mode() and isinstance(input, Variable):
        return _append_static("embedding", _ops.embedding, [input, w],
                              {"padding_idx": padding_idx}, False)
    return _ops.embedding(input, w, padding_idx)


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           use_cudnn=True, name=None, data_format="NCHW"):
    """fluid.layers.conv2d parity (use_cudnn accepted and ignored — XLA
    owns kernel choice on TPU)."""
    c_in = int(input.shape[1] if data_format == "NCHW" else input.shape[-1])
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size, filter_size)
    w = _make_param("conv2d_w",
                    (num_filters, c_in // groups) + tuple(fs),
                    jnp.float32, param_attr, I.MSRA(uniform=False))
    out = _conv_dispatch("conv2d", _ops.conv2d, input, w,
                         dict(stride=stride, padding=padding,
                              dilation=dilation, groups=groups,
                              data_format=data_format))
    if bias_attr is not False:
        b = _make_param("conv2d_b", (num_filters,), jnp.float32, bias_attr,
                        I.Constant(0.0))
        out = elementwise_add(out, b, axis=1)
    return _apply_act(out, act)


def _infer_transpose_fs(input, output_size, stride, padding, dilation,
                        nd):
    """conv_transpose filter-size inference when only output_size is
    given (ref layers/nn.py conv2d_transpose: filter_size =
    (output + 2*pad - (in-1)*stride + stride - 1) // dilation, per dim,
    with dilation-adjusted rounding)."""
    outs = output_size if isinstance(output_size, (list, tuple)) \
        else (output_size,) * nd
    sts = stride if isinstance(stride, (list, tuple)) else (stride,) * nd
    pds = padding if isinstance(padding, (list, tuple)) else (padding,) * nd
    dls = dilation if isinstance(dilation, (list, tuple)) \
        else (dilation,) * nd
    fs = []
    for i in _builtin_range(nd):
        in_sz = int(input.shape[2 + i])
        k = (int(outs[i]) + 2 * pds[i] - (in_sz - 1) * sts[i]
             + dls[i] - 1) // dls[i]
        fs.append(k)
    return tuple(fs)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None,
                     use_cudnn=True, name=None):
    c_in = int(input.shape[1])
    if filter_size is None:
        if output_size is None:
            raise EnforceNotMet(
                "conv2d_transpose: one of output_size or filter_size "
                "is required (layers/nn.py conv2d_transpose)")
        filter_size = _infer_transpose_fs(input, output_size, stride,
                                          padding, dilation, 2)
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size, filter_size)
    w = _make_param("conv2dT_w", (c_in, num_filters // groups) + tuple(fs),
                    jnp.float32, param_attr, I.Xavier())
    out = _conv_dispatch("conv2d_transpose", _ops.conv2d_transpose, input, w,
                         dict(stride=stride, padding=padding,
                              dilation=dilation, groups=groups))
    if bias_attr is not False:
        b = _make_param("conv2dT_b", (num_filters,), jnp.float32, bias_attr,
                        I.Constant(0.0))
        out = elementwise_add(out, b, axis=1)
    return _apply_act(out, act)


def _conv_dispatch(name, fn, input, w, attrs):
    if in_static_mode() and isinstance(input, Variable):
        return _append_static(name, fn, [input, w], attrs, False)
    return fn(input, w, **attrs)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None,
               use_global_stats=False):
    """fluid.layers.batch_norm parity. Running stats are persistable state:
    static mode stores them as non-trainable parameters updated by the op;
    module mode uses nn state."""
    c = int(input.shape[1] if data_layout == "NCHW" else input.shape[-1])
    scale = _make_param("bn_scale", (c,), jnp.float32, param_attr,
                        I.Constant(1.0))
    bias = _make_param("bn_bias", (c,), jnp.float32, bias_attr,
                       I.Constant(0.0))
    if in_static_mode() and isinstance(input, Variable):
        mean = _make_param(moving_mean_name or "bn_mean", (c,), jnp.float32,
                           ParamAttr(name=moving_mean_name, trainable=False),
                           I.Constant(0.0), trainable=False)
        var = _make_param(moving_variance_name or "bn_variance", (c,),
                          jnp.float32,
                          ParamAttr(name=moving_variance_name,
                                    trainable=False),
                          I.Constant(1.0), trainable=False)
        blk = default_main_program().global_block()
        out = blk.create_var(name=unique_name.generate("bn.out"),
                             shape=input.shape, dtype=input.dtype)
        blk.append_op(
            type="batch_norm",
            inputs={"X": [input.name, scale.name, bias.name, mean.name,
                          var.name]},
            outputs={"Out": [out.name], "MeanOut": [mean.name],
                     "VarianceOut": [var.name]},
            attrs={"epsilon": epsilon, "momentum": momentum,
                   "is_test": is_test,
                   "data_layout": data_layout,
                   "use_global_stats": use_global_stats})
        return _apply_act(out, act)
    # module/eager path
    mean = _module.create_state("bn_mean", (c,), jnp.float32, 0.0)
    var = _module.create_state("bn_variance", (c,), jnp.float32, 1.0)
    out, m_out, v_out, _, _ = _ops.batch_norm(
        input, scale, bias, mean, var, epsilon, momentum, is_test,
        data_layout, use_global_stats)
    if not is_test:
        _module.set_state("bn_mean", m_out)
        _module.set_state("bn_variance", v_out)
    return _apply_act(out, act)


def _bn_compute(ins, attrs):
    x, scale, bias, mean, var = ins["X"]
    out, m_out, v_out, _, _ = _ops.batch_norm(
        x, scale, bias, mean, var, attrs["epsilon"], attrs["momentum"],
        attrs["is_test"], attrs["data_layout"], attrs["use_global_stats"])
    return {"Out": [out], "MeanOut": [m_out], "VarianceOut": [v_out]}


OP_REGISTRY["batch_norm"] = _bn_compute


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    shape = tuple(int(s) for s in input.shape[begin_norm_axis:])
    flat = 1
    for s in shape:
        flat *= s
    s = _make_param("ln_scale", (flat,), jnp.float32, param_attr,
                    I.Constant(1.0)) if scale else None
    b = _make_param("ln_bias", (flat,), jnp.float32, bias_attr,
                    I.Constant(0.0)) if shift else None
    tensors = [t for t in (input, s, b) if t is not None]
    if in_static_mode() and isinstance(input, Variable):
        attrs = {"begin_norm_axis": begin_norm_axis, "epsilon": epsilon,
                 "has_scale": s is not None, "has_bias": b is not None}
        out = _append_static("layer_norm_flex", _ln_flex, tensors, attrs,
                             False)
        return _apply_act(out, act)
    return _apply_act(_ln_flex(*tensors, begin_norm_axis=begin_norm_axis,
                               epsilon=epsilon, has_scale=s is not None,
                               has_bias=b is not None), act)


def _ln_flex(*tensors, begin_norm_axis=1, epsilon=1e-5, has_scale=True,
             has_bias=True):
    it = iter(tensors)
    x = next(it)
    s = next(it) if has_scale else None
    b = next(it) if has_bias else None
    return _ops.layer_norm(x, s, b, begin_norm_axis, epsilon)


_register("layer_norm_flex", _ln_flex)
_NARGS["layer_norm_flex"] = 3


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    c = int(input.shape[1])
    s = _make_param("gn_scale", (c,), jnp.float32, param_attr,
                    I.Constant(1.0))
    b = _make_param("gn_bias", (c,), jnp.float32, bias_attr,
                    I.Constant(0.0))
    if in_static_mode() and isinstance(input, Variable):
        return _apply_act(
            _append_static("group_norm_p", _gn_p, [input, s, b],
                           {"groups": groups, "epsilon": epsilon}, False),
            act)
    return _apply_act(_gn_p(input, s, b, groups=groups, epsilon=epsilon),
                      act)


def _gn_p(x, s, b, groups=32, epsilon=1e-5):
    return _ops.group_norm(x, s, b, groups, epsilon)


_register("group_norm_p", _gn_p)
_NARGS["group_norm_p"] = 3


def softmax(input, use_cudnn=False, name=None, axis=-1):
    if in_static_mode() and isinstance(input, Variable):
        return _append_static("softmax", _ops.softmax, [input],
                              {"axis": axis}, False)
    return _ops.softmax(input, axis=axis)


def mean(x, name=None):
    if in_static_mode() and isinstance(x, Variable):
        return _append_static("mean", _ops.mean, [x], {}, False)
    return _ops.mean(x)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    if in_static_mode() and isinstance(x, Variable):
        return _append_static(
            "dropout", _ops.dropout, [x],
            {"dropout_prob": dropout_prob, "is_test": is_test,
             "dropout_implementation": dropout_implementation}, False)
    rng = _module.current_rng() if _module.in_module_ctx() and not is_test \
        else None
    return _ops.dropout(x, dropout_prob, is_test, seed,
                        dropout_implementation, rng=rng)


# simple data helpers
def shape(input):
    if isinstance(input, Variable):
        return jnp.array([-1 if s in (None, -1) else s
                          for s in input.shape], jnp.int32)
    return _ops.shape(input)


def linear_chain_crf(input, label, param_attr=None, length=None):
    """fluid.layers.linear_chain_crf parity: creates the ``crfw``
    transition parameter ([num_tags+2, num_tags], ref:
    operators/linear_chain_crf_op.cc OpMaker) and returns the per-sequence
    negative log-likelihood. Decode with crf_decoding(input, crfw)."""
    num_tags = int(input.shape[-1])
    w = _make_param("crfw", (num_tags + 2, num_tags), jnp.float32,
                    param_attr, I.Xavier())
    if in_static_mode() and isinstance(input, Variable):
        tensors = [input, w, label]
        attrs = {}
        if length is not None:
            tensors.append(length)
        return _append_static("linear_chain_crf", _ops.linear_chain_crf,
                              tensors, attrs, False)
    return _ops.linear_chain_crf(input, w, label, length)


# ---------------------------------------------------------------------------
# host ops: Print / py_func (run eagerly between jitted device segments,
# see executor._compile; ref: operators/print_op.cc, operators/py_func_op.cc)
# ---------------------------------------------------------------------------
def _print_cb(msg, summarize, counter, first_n, arr):
    import sys
    counter["n"] += 1
    if first_n and first_n > 0 and counter["n"] > first_n:
        return
    arr = np.asarray(arr)
    flat = arr.reshape(-1)[:summarize] if summarize and summarize > 0 \
        else arr.reshape(-1)
    print(f"{msg}shape={arr.shape} dtype={arr.dtype} "
          f"data={np.array2string(flat, precision=6)}",
          file=sys.stderr)


def _print_compute(ins, attrs):
    x = ins["X"][0]
    # device op, not a host op: jax.debug.callback keeps Print inside
    # the jitted (and differentiated) segment — identity for autodiff,
    # so a mid-network Print never perturbs training (print_op.cc's
    # grad op forwards gradients the same way)
    jax.debug.callback(
        functools.partial(_print_cb, attrs.get("message", ""),
                          attrs.get("summarize", 20),
                          attrs["_counter"], attrs.get("first_n", -1)),
        x)
    return {"Out": [x]}


OP_REGISTRY["print"] = _print_compute


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=False,
          print_phase="both"):
    """fluid.layers.Print parity (operators/print_op.cc): passthrough op
    that logs the tensor's value each execution (at most ``first_n``
    times)."""
    msg = (message + " ") if message else ""
    counter = {"n": 0}
    if in_static_mode() and isinstance(input, Variable):
        blk = input.block
        out = blk.create_var(shape=input.shape, dtype=input.dtype)
        blk.append_op("print", inputs={"X": [input.name]},
                      outputs={"Out": [out.name]},
                      attrs={"message": msg, "summarize": summarize,
                             "first_n": first_n, "_counter": counter})
        return out
    _print_cb(msg, summarize, counter, -1, input)
    return input


def _py_func_compute(ins, attrs):
    fn = attrs["func"]
    outs = fn(*ins["X"])
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    return {"Out": [jnp.asarray(o) for o in outs]}


OP_REGISTRY["py_func"] = _py_func_compute


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """fluid.layers.py_func parity (operators/py_func_op.cc): run an
    arbitrary python callable on host values mid-program. Host op — the
    executor materializes inputs, calls ``func``, and feeds results back
    into the surrounding jitted segments. backward_func is accepted for
    API parity; the autodiff boundary treats py_func outputs as
    constants (like the reference when no backward_func is given)."""
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    if in_static_mode() and all(isinstance(v, Variable) for v in xs):
        blk = xs[0].block
        blk.append_op("py_func",
                      inputs={"X": [v.name for v in xs]},
                      outputs={"Out": [o.name for o in outs]},
                      attrs={"func": func, "_host": True})
        return outs if isinstance(out, (list, tuple)) else outs[0]
    res = _py_func_compute({"X": list(xs)}, {"func": func})["Out"]
    return res if isinstance(out, (list, tuple)) else res[0]


def multi_box_head(inputs, image, base_size, num_classes, aspect_ratios,
                   min_ratio=None, max_ratio=None, min_sizes=None,
                   max_sizes=None, steps=None, step_w=None, step_h=None,
                   offset=0.5, variance=(0.1, 0.1, 0.2, 0.2), flip=True,
                   clip=False, kernel_size=1, pad=0, stride=1, name=None,
                   min_max_aspect_ratios_order=False):
    """SSD multi-box head (ref python/paddle/fluid/layers/detection.py:1737):
    a composite over prior_box + conv2d + transpose/flatten/concat —
    per feature map, priors are generated and two convs predict
    locations (P*4 channels) and confidences (P*num_classes channels);
    everything concatenates across maps. Works in both modes like every
    other layer (the convs create parameters).

    Returns (mbox_locs [N, B, 4], mbox_confs [N, B, num_classes],
    boxes [B, 4], variances [B, 4]) with B = total prior count.
    """
    import math as _math
    if not isinstance(inputs, (list, tuple)):
        raise EnforceNotMet("inputs should be a list or tuple")
    num_layer = len(inputs)
    if num_layer <= 2:
        if min_sizes is None or max_sizes is None or \
                len(min_sizes) != num_layer or len(max_sizes) != num_layer:
            raise EnforceNotMet(
                "with <=2 input layers, min_sizes/max_sizes must be "
                "given per layer")
    elif min_sizes is None and max_sizes is None:
        min_sizes, max_sizes = [], []
        step = int(_math.floor((max_ratio - min_ratio) / (num_layer - 2)))
        for ratio in _builtin_range(min_ratio, max_ratio + 1, step):
            min_sizes.append(base_size * ratio / 100.0)
            max_sizes.append(base_size * (ratio + step) / 100.0)
        min_sizes = [base_size * 0.10] + min_sizes
        max_sizes = [base_size * 0.20] + max_sizes
    if steps:
        step_w = step_h = steps

    # uniqueness of default param names across multiple heads: in the
    # eager module context the FRAME scope uniquifies deterministically
    # (resets every init/apply, so names line up between the two); in
    # static mode the program-level unique_name counter does it
    if _module.in_module_ctx():
        _mbh_scope = _module._frame().scope("multi_box_head")
        _mbh_tag = "mbh"
    else:
        _mbh_scope = contextlib.nullcontext()
        _mbh_tag = name or unique_name.generate("multi_box_head")
    with _mbh_scope:
        return _multi_box_head_body(
            inputs, image, num_classes, aspect_ratios, min_sizes,
            max_sizes, step_w, step_h, offset, variance, flip, clip,
            kernel_size, pad, stride, min_max_aspect_ratios_order,
            name, _mbh_tag)


def _multi_box_head_body(inputs, image, num_classes, aspect_ratios,
                         min_sizes, max_sizes, step_w, step_h, offset,
                         variance, flip, clip, kernel_size, pad, stride,
                         min_max_aspect_ratios_order, name, _mbh_tag):
    mbox_locs, mbox_confs, box_results, var_results = [], [], [], []
    for i, inp in enumerate(inputs):
        min_size = min_sizes[i]
        max_size = max_sizes[i]
        if not isinstance(min_size, (list, tuple)):
            min_size = [min_size]
        if not isinstance(max_size, (list, tuple)):
            max_size = [max_size]
        ar = aspect_ratios[i] if aspect_ratios is not None else []
        if not isinstance(ar, (list, tuple)):
            ar = [ar]
        step = (step_w[i] if step_w else 0.0,
                step_h[i] if step_h else 0.0)
        box, var = prior_box(inp, image, list(min_size), list(max_size),
                             list(ar), list(variance), flip, clip,
                             step, offset,
                             min_max_aspect_ratios_order)
        box_results.append(box)
        var_results.append(var)
        num_boxes = box.shape[2]           # priors per cell

        # explicit per-map param names: repeated bare conv2d calls in
        # one scope would otherwise share a single parameter (and two
        # heads in one network must not share either -> unique default)
        tag = name or _mbh_tag
        loc = conv2d(inp, num_boxes * 4, kernel_size, stride=stride,
                     padding=pad,
                     param_attr=ParamAttr(name=f"{tag}_loc{i}_w"),
                     bias_attr=ParamAttr(name=f"{tag}_loc{i}_b"))
        loc = transpose(loc, perm=[0, 2, 3, 1])
        mbox_locs.append(flatten(loc, axis=1))
        conf = conv2d(inp, num_boxes * num_classes, kernel_size,
                      stride=stride, padding=pad,
                      param_attr=ParamAttr(name=f"{tag}_conf{i}_w"),
                      bias_attr=ParamAttr(name=f"{tag}_conf{i}_b"))
        conf = transpose(conf, perm=[0, 2, 3, 1])
        mbox_confs.append(flatten(conf, axis=1))

    if len(box_results) == 1:
        box, var = box_results[0], var_results[0]
        locs_concat = mbox_locs[0]
        confs_concat = mbox_confs[0]
    else:
        box = concat([flatten(b, axis=3) for b in box_results])
        var = concat([flatten(v, axis=3) for v in var_results])
        locs_concat = concat(mbox_locs, axis=1)
        confs_concat = concat(mbox_confs, axis=1)
    box = reshape(box, shape=[-1, 4])
    var = reshape(var, shape=[-1, 4])
    locs_concat = reshape(locs_concat, shape=[0, -1, 4])
    confs_concat = reshape(confs_concat, shape=[0, -1, num_classes])
    return locs_concat, confs_concat, box, var


# ---------------------------------------------------------------------------
# remaining fluid.layers.nn surface (r3 tail): parameterized 3-D convs,
# hsigmoid, hash, cvm alias, step counter
# ---------------------------------------------------------------------------

def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           use_cudnn=True, name=None):
    """fluid.layers.conv3d parity (conv_op.cc 3-D); NCDHW."""
    c_in = int(input.shape[1])
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size,) * 3
    w = _make_param("conv3d_w", (num_filters, c_in // groups) + tuple(fs),
                    jnp.float32, param_attr, I.MSRA(uniform=False))
    out = _conv_dispatch("conv3d", _ops.conv3d, input, w,
                         dict(stride=stride, padding=padding,
                              dilation=dilation, groups=groups))
    if bias_attr is not False:
        b = _make_param("conv3d_b", (num_filters,), jnp.float32, bias_attr,
                        I.Constant(0.0))
        out = elementwise_add(out, b, axis=1)
    return _apply_act(out, act)


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None,
                     use_cudnn=True, name=None):
    """fluid.layers.conv3d_transpose parity (conv_transpose_op.cc 3-D);
    weight layout IODHW like the reference."""
    c_in = int(input.shape[1])
    if filter_size is None:
        if output_size is None:
            raise EnforceNotMet(
                "conv3d_transpose: one of output_size or filter_size "
                "is required")
        filter_size = _infer_transpose_fs(input, output_size, stride,
                                          padding, dilation, 3)
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size,) * 3
    w = _make_param("conv3dT_w", (c_in, num_filters // groups) + tuple(fs),
                    jnp.float32, param_attr, I.Xavier())
    out = _conv_dispatch("conv3d_transpose", _ops.conv3d_transpose, input, w,
                         dict(stride=stride, padding=padding,
                              dilation=dilation, groups=groups))
    if bias_attr is not False:
        b = _make_param("conv3dT_b", (num_filters,), jnp.float32, bias_attr,
                        I.Constant(0.0))
        out = elementwise_add(out, b, axis=1)
    return _apply_act(out, act)


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None,
             is_custom=False, is_sparse=False):
    """fluid.layers.hsigmoid parity (hierarchical_sigmoid_op.cc): creates
    the internal-node weight/bias like the reference layer, then runs the
    complete-binary-tree walk in ops.misc.hierarchical_sigmoid. Custom
    trees (path_table/path_code) are not supported on this path."""
    if is_custom or path_table is not None or path_code is not None:
        raise NotImplementedError("hsigmoid: default complete tree only")
    dim = int(input.shape[-1])
    w = _make_param("hsigmoid_w", (num_classes - 1, dim), jnp.float32,
                    param_attr, I.Xavier())
    b = (_make_param("hsigmoid_b", (num_classes - 1,), jnp.float32,
                     bias_attr, I.Constant(0.0))
         if bias_attr is not False else jnp.zeros((num_classes - 1,)))
    lab = reshape(label, shape=[-1])      # op walks flat [B] leaf ids
    out = hierarchical_sigmoid(input, w, b, lab, num_classes)
    return reshape(out, shape=[-1, 1])


def hash(input, hash_size, num_hash=1, name=None):  # noqa: A001 (fluid name)
    """fluid.layers.hash parity over ops.misc.hash_embedding_ids
    (hash_op.cc): num_hash independent hashes of the id sequence modulo
    hash_size."""
    return hash_embedding_ids(input, hash_size, num_hash=num_hash)


def continuous_value_model(input, cvm_input=None, use_cvm=True):
    """fluid.layers.continuous_value_model parity (cvm_op.cc). The
    second argument (the raw show/click columns) is part of the input's
    first two columns in this implementation, matching the op kernel."""
    return cvm(input, use_cvm=use_cvm)      # wrapped op: works both modes


def _increment_inplace_compute(ins, attrs):
    return {"Out": [jnp.asarray(ins["X"][0])
                    + jnp.asarray(attrs.get("value", 1)).astype(
                        jnp.asarray(ins["X"][0]).dtype)]}


OP_REGISTRY["increment_inplace"] = _increment_inplace_compute


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """fluid.layers.autoincreased_step_counter parity (layers/nn.py):
    a persistable int64 counter incremented once per executor run (the
    output var IS the counter var, so the whole-block jit writes it back
    to the scope — the in-place semantics of the reference's increment
    op)."""
    name = counter_name or "@STEP_COUNTER@"
    blk = default_main_program().global_block()
    if blk.has_var(name):
        counter = blk.var(name)
    else:
        # reference init is Constant(begin - 1) then increment-by-step,
        # so the first read is begin - 1 + step (layers/nn.py)
        counter = create_global_var([1], float(begin - 1), dtype="int64",
                                    persistable=True, name=name)
    blk.append_op(type="increment_inplace", inputs={"X": [name]},
                  outputs={"Out": [name]}, attrs={"value": step})
    return counter


# fluid.layers.io surface (reader builders; see layers/io.py)
from paddle_tpu.layers import io as io                       # noqa: E402
from paddle_tpu.layers.io import (                           # noqa: E402
    py_reader, create_py_reader_by_data, read_file, double_buffer,
    batch, shuffle, load, open_files, random_data_generator, Preprocessor,
)
