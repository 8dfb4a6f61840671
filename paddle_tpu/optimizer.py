"""Optimizers.

Parity: python/paddle/fluid/optimizer.py (SGD:40, Momentum, DGCMomentum:787,
LarsMomentum, Adagrad, Adam, Adamax, DecayedAdagrad, Adadelta, RMSProp,
Ftrl, Lamb; ModelAverage:2244, ExponentialMovingAverage:2434) and the C++
kernels in operators/optimizers/.

Each optimizer defines a pure per-parameter update rule. Two entry points:

- **functional/eager**: ``state = opt.init(params)`` then
  ``new_params, new_state = opt.apply_gradients(params, grads, state)`` —
  jit-able, used by the eager/module path and by parallel training where
  the whole step is one SPMD computation.
- **static**: ``opt.minimize(loss)`` appends `autodiff` + per-param update
  ops to the Program (the reference's optimizer-op layout), all fused by
  the Executor into the same XLA step.

LR may be a float or a Schedule (layers.learning_rate_scheduler); the
step counter lives in optimizer state, so schedules trace into the
compiled step.
"""

import jax
import jax.numpy as jnp

from paddle_tpu import clip as clip_mod
from paddle_tpu import initializer as I
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.monitor import tensorwatch as _tensorwatch
from paddle_tpu.static.program import (
    OP_REGISTRY, default_main_program, default_startup_program,
    in_static_mode,
)

__all__ = [
    "Optimizer", "SGD", "SGDOptimizer", "Momentum", "MomentumOptimizer",
    "LarsMomentum", "LarsMomentumOptimizer", "DGCMomentumOptimizer",
    "Adagrad", "AdagradOptimizer", "Adam", "AdamOptimizer", "Adamax",
    "AdamaxOptimizer", "DecayedAdagrad", "DecayedAdagradOptimizer",
    "Adadelta", "AdadeltaOptimizer", "RMSProp", "RMSPropOptimizer", "Ftrl",
    "FtrlOptimizer", "Lamb", "LambOptimizer", "ProximalGD",
    "ProximalGDOptimizer", "ProximalAdagrad", "ProximalAdagradOptimizer",
    "ModelAverage", "ExponentialMovingAverage",
    "PipelineOptimizer",
]


class Optimizer:
    _slot_defaults = {}  # name -> init value
    # update rule touches each param element independently (true for
    # every rule here except Lars/Lamb trust ratios) — required by the
    # kReduce/ZeRO sharded layout in parallel/data_parallel.py
    _elementwise = True

    def __init__(self, learning_rate=0.001, regularization=None,
                 grad_clip=None, name=None):
        self.learning_rate = learning_rate
        self.regularization = regularization
        self.grad_clip = grad_clip
        self.name = name

    # -- rule interface ----------------------------------------------------
    def _slots(self, param):
        return {k: jnp.full(param.shape, v, param.dtype)
                for k, v in self._slot_defaults.items()}

    def _update(self, p, g, slots, lr, t):
        raise NotImplementedError

    def _lr_value(self, step):
        if callable(self.learning_rate):
            return self.learning_rate(step)
        return jnp.asarray(self.learning_rate, jnp.float32)

    # -- functional path ---------------------------------------------------
    def init(self, params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "slots": jax.tree.map(self._slots, params),
        }

    def state_shardings(self, opt_state, pshard, mesh):
        """NamedShardings for opt state: each slot mirrors its param's
        sharding (a slot is elementwise state of its param); the step
        counter is replicated. pshard: param tree of NamedSharding."""
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())
        flat_sh, ptreedef = jax.tree.flatten(pshard)
        flat_slots = ptreedef.flatten_up_to(opt_state["slots"])
        slots_sh = jax.tree.unflatten(
            ptreedef,
            [jax.tree.map(lambda _: sh, sd)
             for sh, sd in zip(flat_sh, flat_slots)])
        return {"step": rep, "slots": slots_sh}

    @jax.named_scope("optimizer")   # the update's name in a profile
    def apply_gradients(self, params, grads, state, param_meta=None):
        """Returns (new_params, new_state). params/grads are matching
        pytrees; slots is a tree-of-dicts aligned with params."""
        step = state["step"] + 1
        lr = self._lr_value(step.astype(jnp.float32))
        if self.regularization is not None:
            grads = jax.tree.map(self.regularization, params, grads)
        if self.grad_clip is not None:
            grads = self.grad_clip.clip_tree(grads)

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = jax.tree.flatten(grads)[0]
        flat_s = treedef.flatten_up_to(state["slots"]) \
            if self._slot_defaults else [dict() for _ in flat_p]
        new_p, new_s = [], []
        for p, g, s in zip(flat_p, flat_g, flat_s):
            np_, ns_ = self._update(p, g, s, lr, step)
            new_p.append(np_)
            new_s.append(ns_)
        return (jax.tree.unflatten(treedef, new_p),
                {"step": step, "slots": jax.tree.unflatten(treedef, new_s)})

    # convenience: one-call functional step
    def step(self, params, grads, state=None):
        if state is None:
            state = self.init(params)
        return self.apply_gradients(params, grads, state)

    # -- static path -------------------------------------------------------
    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from paddle_tpu.static.backward import append_backward
        if not in_static_mode():
            raise EnforceNotMet(
                "minimize() is the static-graph API; in eager mode use "
                "apply_gradients(params, grads, state)")
        program = loss.block.program
        blk = program.global_block()
        p_g = append_backward(loss, parameter_list, no_grad_set)
        startup = startup_program or default_startup_program()
        sblk = startup.global_block()

        step_name = f"@opt@{self.name or type(self).__name__}@step"
        if not blk.has_var(step_name):
            blk.create_var(name=step_name, shape=(), dtype=jnp.int32,
                           persistable=True)
            sblk.create_var(name=step_name, shape=(), dtype=jnp.int32,
                            persistable=True)
            sblk.append_op(type="init_param", inputs={},
                           outputs={"Out": [step_name]},
                           attrs={"initializer": I.Constant(0),
                                  "shape": (), "dtype": "int32"})
        blk.append_op(type="increment_step", inputs={"X": [step_name]},
                      outputs={"Out": [step_name]}, attrs={})

        # tensor watch (monitor/tensorwatch.py): bracket the update with
        # two in-graph stats ops — pre-clip grad/param global norms
        # before, update-ratio after. Pre-update params thread through
        # as pass-through outputs so ||new - old|| is computable without
        # a host round-trip; the norms reuse clip.global_norm's exact
        # subgraph, so under GradientClipByGlobalNorm XLA CSEs the two.
        watching = _tensorwatch.is_enabled() and p_g
        pre_names = []
        if watching:
            pre_names = [f"@watch@pre@{p.name}" for p, _ in p_g]
            for (p, _g), pn in zip(p_g, pre_names):
                if not blk.has_var(pn):
                    blk.create_var(name=pn, shape=p.shape, dtype=p.dtype)
            if not blk.has_var(_tensorwatch.PRE_VAR):
                blk.create_var(name=_tensorwatch.PRE_VAR, shape=(2,),
                               dtype="float32")
            blk.append_op(
                type="tensor_watch_pre",
                inputs={"Params": [p.name for p, _ in p_g],
                        "Grads": [g.name for _, g in p_g]},
                outputs={"Norms": [_tensorwatch.PRE_VAR],
                         "PreParams": pre_names},
                attrs={})

        clip = self.grad_clip or clip_mod.get_gradient_clip(program)
        if clip is not None:
            gnames = [g.name for _, g in p_g]
            blk.append_op(type="clip_grads", inputs={"X": gnames},
                          outputs={"Out": gnames}, attrs={"clip": clip})

        ops = []
        for p, g in p_g:
            slot_names = []
            for sname, sval in self._slot_defaults.items():
                full = f"{p.name}@{sname}"
                slot_names.append(full)
                if not blk.has_var(full):
                    blk.create_var(name=full, shape=p.shape, dtype=p.dtype,
                                   persistable=True)
                    sblk.create_var(name=full, shape=p.shape, dtype=p.dtype,
                                    persistable=True)
                    sblk.append_op(
                        type="init_param", inputs={},
                        outputs={"Out": [full]},
                        attrs={"initializer": I.Constant(sval),
                               "shape": tuple(int(s) if s not in (None, -1)
                                              else 1 for s in p.shape),
                               "dtype": jnp.dtype(p.dtype).name})
            op = blk.append_op(
                type="apply_optimizer",
                inputs={"Param": [p.name], "Grad": [g.name],
                        "Slots": slot_names, "Step": [step_name]},
                outputs={"ParamOut": [p.name], "SlotOuts": slot_names},
                attrs={"opt": self, "slot_names": list(self._slot_defaults),
                       "regularizer": p.regularizer,
                       "param_lr": p.optimize_attr.get("learning_rate", 1.0)})
            ops.append(op)
        if watching:
            if not blk.has_var(_tensorwatch.STATS_VAR):
                blk.create_var(name=_tensorwatch.STATS_VAR, shape=(4,),
                               dtype="float32")
            blk.append_op(
                type="tensor_watch_post",
                inputs={"Params": [p.name for p, _ in p_g],
                        "PreParams": pre_names,
                        "PreNorms": [_tensorwatch.PRE_VAR]},
                outputs={"Out": [_tensorwatch.STATS_VAR]},
                attrs={})
        return ops, p_g


def _apply_optimizer_compute(ins, attrs):
    opt = attrs["opt"]
    p, g = ins["Param"][0], ins["Grad"][0]
    step = ins["Step"][0]
    slots = dict(zip(attrs["slot_names"], ins.get("Slots", [])))
    reg = attrs.get("regularizer") or opt.regularization
    if reg is not None:
        g = reg(p, g)
    lr = opt._lr_value(step.astype(jnp.float32)) * attrs.get("param_lr", 1.0)
    new_p, new_slots = opt._update(p, g, slots, lr, step)
    return {"ParamOut": [new_p],
            "SlotOuts": [new_slots[k] for k in attrs["slot_names"]]}


OP_REGISTRY["apply_optimizer"] = _apply_optimizer_compute
OP_REGISTRY["increment_step"] = \
    lambda ins, attrs: {"Out": [ins["X"][0] + 1]}


def _clip_grads_compute(ins, attrs):
    clip = attrs["clip"]
    return {"Out": clip.clip_tree(list(ins["X"]))}


OP_REGISTRY["clip_grads"] = _clip_grads_compute
# in-graph tensor-watch stats (computed in monitor/tensorwatch.py,
# appended by minimize() when the watch is enabled)
OP_REGISTRY["tensor_watch_pre"] = _tensorwatch._watch_pre_compute
OP_REGISTRY["tensor_watch_post"] = _tensorwatch._watch_post_compute


# ---------------------------------------------------------------------------
# concrete optimizers (operators/optimizers/*.cc rules)
# ---------------------------------------------------------------------------
class SGDOptimizer(Optimizer):
    """sgd_op.cc"""

    def _update(self, p, g, slots, lr, t):
        return p - lr * g, slots


class MomentumOptimizer(Optimizer):
    """momentum_op.cc (use_nesterov attr supported)."""
    _slot_defaults = {"velocity": 0.0}

    def __init__(self, learning_rate, momentum=0.9, use_nesterov=False,
                 **kw):
        super().__init__(learning_rate, **kw)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _update(self, p, g, slots, lr, t):
        v = self.momentum * slots["velocity"] + g
        if self.use_nesterov:
            new_p = p - lr * (g + self.momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {"velocity": v}


class LarsMomentumOptimizer(Optimizer):
    """lars_momentum_op.cc: layer-wise adaptive rate scaling."""
    _slot_defaults = {"velocity": 0.0}
    _elementwise = False     # trust ratio needs whole-param norms

    def __init__(self, learning_rate, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, **kw):
        super().__init__(learning_rate, **kw)
        self.momentum = momentum
        self.lars_coeff = lars_coeff
        self.lars_weight_decay = lars_weight_decay

    def _update(self, p, g, slots, lr, t):
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (p_norm > 0) & (g_norm > 0),
            self.lars_coeff * p_norm
            / (g_norm + self.lars_weight_decay * p_norm + 1e-12), 1.0)
        v = self.momentum * slots["velocity"] + lr * local_lr * (
            g + self.lars_weight_decay * p)
        return p - v, {"velocity": v}


class DGCMomentumOptimizer(MomentumOptimizer):
    """DGC (deep gradient compression) momentum (optimizer.py:787).

    On a single computation the top-k sparsification only changes the
    collective payload; the compression transform itself lives in
    parallel/dgc.py and is applied to the gradient tree before allreduce.
    Locally the update rule is momentum-with-correction."""

    def __init__(self, learning_rate, momentum=0.9, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), **kw):
        super().__init__(learning_rate, momentum, **kw)
        self.rampup_begin_step = rampup_begin_step
        self.sparsity = sparsity


class AdagradOptimizer(Optimizer):
    """adagrad_op.cc"""
    _slot_defaults = {"moment": 0.0}

    def __init__(self, learning_rate, epsilon=1e-6, initial_accumulator_value=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self.epsilon = epsilon
        self._slot_defaults = {"moment": initial_accumulator_value}

    def _update(self, p, g, slots, lr, t):
        m = slots["moment"] + jnp.square(g)
        return p - lr * g / (jnp.sqrt(m) + self.epsilon), {"moment": m}


class AdamOptimizer(Optimizer):
    """adam_op.cc (bias-corrected)."""
    _slot_defaults = {"moment1": 0.0, "moment2": 0.0}

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, **kw):
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _update(self, p, g, slots, lr, t):
        t = t.astype(jnp.float32)
        m1 = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        m2 = self.beta2 * slots["moment2"] + (1 - self.beta2) * jnp.square(g)
        bc = jnp.sqrt(1 - self.beta2 ** t) / (1 - self.beta1 ** t)
        new_p = p - lr * bc * m1 / (jnp.sqrt(m2) + self.epsilon)
        return new_p, {"moment1": m1, "moment2": m2}


class AdamaxOptimizer(Optimizer):
    """adamax_op.cc"""
    _slot_defaults = {"moment": 0.0, "inf_norm": 0.0}

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _update(self, p, g, slots, lr, t):
        t = t.astype(jnp.float32)
        m = self.beta1 * slots["moment"] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * slots["inf_norm"], jnp.abs(g))
        new_p = p - lr / (1 - self.beta1 ** t) * m / (u + self.epsilon)
        return new_p, {"moment": m, "inf_norm": u}


class DecayedAdagradOptimizer(Optimizer):
    """decayed_adagrad_op.cc"""
    _slot_defaults = {"moment": 0.0}

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.decay, self.epsilon = decay, epsilon

    def _update(self, p, g, slots, lr, t):
        m = self.decay * slots["moment"] + (1 - self.decay) * jnp.square(g)
        return p - lr * g / (jnp.sqrt(m) + self.epsilon), {"moment": m}


class AdadeltaOptimizer(Optimizer):
    """adadelta_op.cc"""
    _slot_defaults = {"avg_squared_grad": 0.0, "avg_squared_update": 0.0}

    def __init__(self, learning_rate=1.0, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self.epsilon, self.rho = epsilon, rho

    def _update(self, p, g, slots, lr, t):
        g2 = self.rho * slots["avg_squared_grad"] + (1 - self.rho) * jnp.square(g)
        upd = g * jnp.sqrt(slots["avg_squared_update"] + self.epsilon) \
            / jnp.sqrt(g2 + self.epsilon)
        u2 = self.rho * slots["avg_squared_update"] + (1 - self.rho) * jnp.square(upd)
        return p - lr * upd, {"avg_squared_grad": g2,
                              "avg_squared_update": u2}


class RMSPropOptimizer(Optimizer):
    """rmsprop_op.cc (centered option)."""
    _slot_defaults = {"mean_square": 0.0, "mean_grad": 0.0, "momentum": 0.0}

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self.rho, self.epsilon = rho, epsilon
        self.momentum_coef = momentum
        self.centered = centered

    def _update(self, p, g, slots, lr, t):
        ms = self.rho * slots["mean_square"] + (1 - self.rho) * jnp.square(g)
        mg = self.rho * slots["mean_grad"] + (1 - self.rho) * g \
            if self.centered else slots["mean_grad"]
        denom = ms - jnp.square(mg) if self.centered else ms
        mom = self.momentum_coef * slots["momentum"] \
            + lr * g / jnp.sqrt(denom + self.epsilon)
        return p - mom, {"mean_square": ms, "mean_grad": mg,
                         "momentum": mom}


class FtrlOptimizer(Optimizer):
    """ftrl_op.cc"""
    _slot_defaults = {"squared": 0.0, "linear": 0.0}

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self.l1, self.l2, self.lr_power = l1, l2, lr_power

    def _update(self, p, g, slots, lr, t):
        sq, lin = slots["squared"], slots["linear"]
        new_sq = sq + jnp.square(g)
        if self.lr_power == -0.5:
            sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
        else:
            sigma = (new_sq ** -self.lr_power - sq ** -self.lr_power) / lr
        new_lin = lin + g - sigma * p
        if self.lr_power == -0.5:
            denom = jnp.sqrt(new_sq) / lr + 2 * self.l2
        else:
            denom = new_sq ** -self.lr_power / lr + 2 * self.l2
        pre = jnp.clip(new_lin, -self.l1, self.l1) - new_lin
        new_p = pre / denom
        return new_p, {"squared": new_sq, "linear": new_lin}


class ProximalGDOptimizer(Optimizer):
    """proximal_gd_op.cc: forward-backward splitting —
    prox_param = p - lr*g; p = sign(prox)*max(|prox| - lr*l1, 0)
    / (1 + lr*l2)."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self.l1, self.l2 = l1, l2

    def _prox(self, prox, lr):
        return (jnp.sign(prox)
                * jnp.maximum(jnp.abs(prox) - lr * self.l1, 0.0)
                / (1.0 + lr * self.l2))

    def _update(self, p, g, slots, lr, t):
        return self._prox(p - lr * g, lr), slots


class ProximalAdagradOptimizer(ProximalGDOptimizer):
    """proximal_adagrad_op.cc: adagrad-scaled proximal step —
    m += g^2; prox = p - lr*g/sqrt(m); then the l1/l2 shrink."""
    _slot_defaults = {"moment": 0.0}

    def _update(self, p, g, slots, lr, t):
        m = slots["moment"] + jnp.square(g)
        prox = p - lr * g / jnp.sqrt(jnp.maximum(m, 1e-12))
        return self._prox(prox, lr), {"moment": m}


class LambOptimizer(Optimizer):
    """lamb_op.cc: layer-adaptive Adam with weight decay."""
    _slot_defaults = {"moment1": 0.0, "moment2": 0.0}
    _elementwise = False     # trust ratio needs whole-param norms

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6,
                 exclude_from_weight_decay_fn=None, **kw):
        super().__init__(learning_rate, **kw)
        self.wd = lamb_weight_decay
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.exclude_fn = exclude_from_weight_decay_fn

    def _update(self, p, g, slots, lr, t):
        t = t.astype(jnp.float32)
        m1 = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        m2 = self.beta2 * slots["moment2"] + (1 - self.beta2) * jnp.square(g)
        m1h = m1 / (1 - self.beta1 ** t)
        m2h = m2 / (1 - self.beta2 ** t)
        r = m1h / (jnp.sqrt(m2h) + self.epsilon) + self.wd * p
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
        return p - lr * trust * r, {"moment1": m1, "moment2": m2}


class ModelAverage(Optimizer):
    """optimizer.py:2244 parity: maintain a running average of params for
    eval. Functional form: avg_state = ma.init(params);
    avg_state = ma.accumulate(params, avg_state);
    params_for_eval = ma.average(avg_state)."""

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000, **kw):
        super().__init__(0.0, **kw)
        self.max_window = max_average_window

    def init(self, params):
        return {"sum": jax.tree.map(jnp.zeros_like, params),
                "count": jnp.zeros((), jnp.int32)}

    def accumulate(self, params, state):
        return {"sum": jax.tree.map(jnp.add, state["sum"], params),
                "count": state["count"] + 1}

    def average(self, state):
        c = jnp.maximum(state["count"], 1).astype(jnp.float32)
        return jax.tree.map(lambda s: s / c, state["sum"])


class ExponentialMovingAverage:
    """optimizer.py:2434 parity (functional)."""

    def __init__(self, decay=0.999, thres_steps=None):
        self.decay = decay

    def init(self, params):
        return {"ema": jax.tree.map(jnp.array, params),
                "step": jnp.zeros((), jnp.int32)}

    def update(self, params, state):
        step = state["step"] + 1
        d = jnp.minimum(self.decay,
                        (1.0 + step) / (10.0 + step)).astype(jnp.float32)
        ema = jax.tree.map(lambda e, p: d * e + (1 - d) * p,
                           state["ema"], params)
        return {"ema": ema, "step": step}

    def apply(self, state):
        return state["ema"]


# fluid-style short aliases
SGD = SGDOptimizer
Momentum = MomentumOptimizer
LarsMomentum = LarsMomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
ProximalGD = ProximalGDOptimizer
ProximalAdagrad = ProximalAdagradOptimizer


class PipelineOptimizer:
    """fluid.optimizer.PipelineOptimizer parity facade (ref
    optimizer.py:2664: wraps an inner optimizer; PipelineTrainer runs
    program sections over ScopeQueues).

    TPU-native pipelining is the SPMD "pipe" mesh axis —
    parallel.pipeline.PipelineModule(mesh, embed_fn, stage_fn, loss_fn,
    n_micro).make_train_step(inner_opt, schedule="gpipe"|"1f1b") — and
    ``make_train_step`` here delegates straight to it. In the static
    single-program path ``minimize`` applies the inner optimizer over
    the whole (un-cut) program: a one-stage pipeline IS plain training,
    the same collapse the reference performs when cut_list is empty.
    The cut/place/concurrency/queue knobs configure thread pipelines
    over scope queues in the reference; on a TPU mesh their roles are
    played by the pipe-axis size and microbatch count, so they are
    accepted and recorded for inspection only.
    """

    def __init__(self, optimizer, cut_list=None, place_list=None,
                 concurrency_list=None, queue_size=30, sync_steps=1,
                 start_cpu_core_id=0, num_microbatches=None):
        self._inner = optimizer
        self.cut_list = cut_list or []
        self.place_list = place_list or []
        self.concurrency_list = concurrency_list or []
        self.queue_size = queue_size
        self.sync_steps = sync_steps
        self.start_cpu_core_id = start_cpu_core_id
        # only an EXPLICIT num_microbatches is a user contract the mesh
        # path enforces; concurrency_list stays inspection-only
        self._explicit_micro = num_microbatches is not None
        self.num_microbatches = num_microbatches or max(
            len(self.concurrency_list), 1)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        if self.cut_list:
            import warnings
            warnings.warn(
                "PipelineOptimizer: program cuts run un-pipelined in the "
                "static path; use parallel.pipeline.PipelineModule over a "
                "MeshConfig(pipe=N) mesh for real pipeline parallelism")
        return self._inner.minimize(loss, startup_program,
                                    parameter_list, no_grad_set)

    def make_train_step(self, pipeline_module, schedule="gpipe"):
        """The real (mesh) pipeline path: delegate to PipelineModule.
        The module's own n_micro governs; a conflicting explicit
        num_microbatches here is an error, not a silent no-op."""
        mod_micro = getattr(pipeline_module, "n_micro", None)
        if (self._explicit_micro and mod_micro is not None
                and self.num_microbatches != mod_micro):
            raise ValueError(
                f"PipelineOptimizer(num_microbatches="
                f"{self.num_microbatches}) conflicts with the "
                f"PipelineModule's n_micro={mod_micro}")
        return pipeline_module.make_train_step(self._inner,
                                               schedule=schedule)
