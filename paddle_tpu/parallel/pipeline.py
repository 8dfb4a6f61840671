"""GPipe-style microbatch pipeline parallelism over the "pipe" mesh axis.

Reference mechanism: PipelineTrainer + SectionWorker cut a program into
sections, each section a thread pool bound to one device, with scopes
flowing through ScopeQueues between sections (ref: framework/trainer.h:95,
framework/device_worker.h:240, framework/pipeline_trainer.cc,
framework/section_worker.cc; python PipelineOptimizer
ref: python/paddle/fluid/optimizer.py:2664; config
trainer_desc.proto:57-79).

TPU-native redesign: all stages run the SAME jitted SPMD program over a
mesh "pipe" axis. Per-stage parameters are stacked on a leading axis and
sharded over "pipe" (each device holds only its stage's weights). A
lax.scan over M + P - 1 ticks does, per tick: every stage applies its
layer to its current activation, then the activation ring-shifts one
stage forward via lax.ppermute (ICI neighbor hop — the ScopeQueue
equivalent, but double-buffered on-device and overlap-scheduled by XLA).
Microbatch accumulation of gradients replaces the reference's
sync_steps/SyncFunctor cross-pipeline allreduce (device_worker.h:211).

Constraints of the SPMD formulation: every stage's input and output
activation have the same shape (true for stacked transformer blocks /
MLP trunks); ragged stage cuts belong to the embedding/head, which run
outside the pipelined trunk.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

from paddle_tpu.core.flags import define_flag, get_flag
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, DCN_AXIS, PIPE_AXIS

__all__ = ["stack_stage_params", "stage_param_sharding", "pipeline_apply",
           "PipelineModule", "pipeline_train_1f1b", "gpipe_bubble_fraction",
           "one_f_one_b_bubble_fraction", "schedule_occupancy"]

define_flag(
    "overlap_grad_reduce", False,
    "1F1B schedule: issue the data/dcn_data gradient all-reduce "
    "per-bucket INSIDE the backward scan as each tick produces its "
    "gradient contribution (scan-carried partial reductions XLA can "
    "overlap with the next tick's compute), instead of one fused "
    "reduction after the scan drains. Off by default: bench.py shard "
    "A/Bs it per host — on the CPU harness the per-tick collectives "
    "measured 1.24x SLOWER (synchronous CPU collectives cannot hide "
    "under compute; docs/PERFORMANCE.md records the evidence), so "
    "enable it only where the A/B shows a win (TPU ICI)")


def _data_reduce_axes(mesh, data_axis=DATA_AXIS):
    """The data-parallel mesh axes a pipelined trunk's gradients reduce
    over, DCN-outermost — psum over this tuple is mesh.py's
    hierarchical allreduce (within-slice ICI first, one DCN crossing
    per slice). Axes of extent 1 are dropped: a vacuous collective
    still costs a lowering."""
    shape = dict(mesh.shape)
    return tuple(a for a in (DCN_AXIS, data_axis)
                 if shape.get(a, 1) > 1)


def _data_pspec(axes):
    """P(None, axes) microbatch spec: per-microbatch batch dim (axis 1)
    sharded over the data axes (hierarchically when DCN is present)."""
    if not axes:
        return P()
    return P(None, axes[0] if len(axes) == 1 else tuple(axes))


def stack_stage_params(stage_params):
    """Stack a list of per-stage param pytrees into one tree with a
    leading stage axis (shard it over "pipe")."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params)


def _bind_stage_fn(stage_fn, idx):
    """Per-stage heterogeneity (the section_worker.cc stretch): a
    stage_fn may take (params, x) — homogeneous — or (params, x,
    stage_idx), where stage_idx is this device's traced pipe-axis
    index. A 3-arg fn can lax.switch on the index to run different
    computation per stage (activation shapes must still match across
    stages — the SPMD constraint). Truly device-heterogeneous CPU/TPU
    sections live outside the trunk as the embed/head split."""
    try:
        import inspect
        params = inspect.signature(stage_fn).parameters.values()
        # only REQUIRED positional params count — **kwargs or an
        # optional keyword must not be mistaken for the index slot
        n = sum(1 for p in params
                if p.kind in (p.POSITIONAL_ONLY,
                              p.POSITIONAL_OR_KEYWORD)
                and p.default is p.empty)
    except (TypeError, ValueError):
        n = 2
    if n >= 3:
        return lambda p, x: stage_fn(p, x, idx)
    return stage_fn


def stage_param_sharding(mesh, stacked, pipe_axis=PIPE_AXIS):
    """NamedShardings placing each stage's slice on its pipe-axis device."""
    def sh(x):
        spec = [pipe_axis] + [None] * (np.ndim(x) - 1)
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(sh, stacked)


def _pipeline_local(stage_fn, stacked_local, mb, n_micro, axis_name):
    """shard_map body. stacked_local: stage params with leading axis of
    local length 1 (this device's stage). mb: [M, ...] microbatched
    activations, replicated. Returns [M, ...] outputs of the LAST stage
    (replicated via final collective)."""
    n_stages = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    stage_fn = _bind_stage_fn(stage_fn, idx)
    my_params = jax.tree.map(lambda x: x[0], stacked_local)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    mb_shape = mb.shape[1:]
    state = jnp.zeros(mb_shape, mb.dtype) + mb[0] * 0.0  # varying-axes seed
    outputs = jnp.zeros((n_micro,) + mb_shape, mb.dtype) + mb * 0.0

    def tick(carry, t):
        state, outputs = carry
        x_in = lax.dynamic_index_in_dim(
            mb, jnp.clip(t, 0, n_micro - 1), keepdims=False)
        x = jnp.where(idx == 0, x_in, state)
        y = stage_fn(my_params, x)
        # last stage banks its result for microbatch (t - (P-1))
        out_slot = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        bank = (idx == n_stages - 1) & (t >= n_stages - 1)
        cur = lax.dynamic_index_in_dim(outputs, out_slot, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(bank, y, cur), out_slot, axis=0)
        state = lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    (_, outputs), _ = lax.scan(tick, (state, outputs),
                               jnp.arange(n_micro + n_stages - 1))
    # outputs live on the last stage; broadcast so every stage returns the
    # same value (out_specs replicated over pipe)
    outputs = lax.psum(
        jnp.where(idx == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)
    return outputs


def pipeline_apply(mesh, stage_fn, stacked_params, microbatches,
                   pipe_axis=PIPE_AXIS, data_axis=DATA_AXIS):
    """Run microbatches [M, mb, ...] through the stage pipeline.

    stage_fn(params_of_one_stage, x) -> y with y.shape == x.shape.
    stacked_params: leading stage axis == mesh pipe-axis size.
    The per-microbatch batch dim (axis 1) is sharded over "data" when
    the mesh carries one (DP x PP: each data replica pipelines its own
    slice of every microbatch — mb must divide by the data-axis size).
    Returns [M, mb, ...] final-stage outputs. Differentiable (grads flow
    through ppermute + scan); donate/accumulate at the caller.
    """
    n_micro = int(microbatches.shape[0])
    pspec = jax.tree.map(
        lambda x: P(*([pipe_axis] + [None] * (np.ndim(x) - 1))),
        stacked_params)
    dspec = _data_pspec(_data_reduce_axes(mesh, data_axis))
    body = functools.partial(_pipeline_local, stage_fn, n_micro=n_micro,
                             axis_name=pipe_axis)

    def f(sp, mb):
        return body(sp, mb)

    return shard_map(f, mesh=mesh,
                     in_specs=(pspec, dspec), out_specs=dspec,
                     check_vma=False)(stacked_params, microbatches)


class PipelineModule:
    """PipelineOptimizer-parity convenience (ref: optimizer.py:2664):
    wraps embed -> pipelined trunk -> head + loss into one jitted,
    microbatch-accumulated train step.

    embed_fn(embed_params, batch_x) -> activation
    stage_fn(stage_params, activation) -> activation
    loss_fn(head_params, activation, batch_y) -> scalar mean loss
    """

    def __init__(self, mesh, embed_fn, stage_fn, loss_fn, n_micro,
                 pipe_axis=PIPE_AXIS):
        self.mesh = mesh
        self.embed_fn = embed_fn
        self.stage_fn = stage_fn
        self.loss_fn = loss_fn
        self.n_micro = n_micro
        self.pipe_axis = pipe_axis

    def _microbatch(self, x):
        return x.reshape((self.n_micro, x.shape[0] // self.n_micro)
                         + x.shape[1:])

    def sharding_spec(self):
        """The module's placement as the unified ShardingSpec
        (parallel/spec.py): stage params tiled over "pipe" on their
        leading stage axis, embed/head replicated. One annotation
        source for init placement, executor interop, and
        ``checkpoint_axes`` (save(axes=) derivation)."""
        from paddle_tpu.parallel.spec import ShardingSpec
        return ShardingSpec(self.mesh,
                            rules=[("stages/*", P(self.pipe_axis))])

    def loss(self, params, batch_x, batch_y):
        """Full-batch loss: embed -> pipeline trunk -> mean of per-
        microbatch losses (= the reference's microbatch gradient
        accumulation when differentiated)."""
        emb = self.embed_fn(params["embed"], batch_x)
        mb = self._microbatch(emb)
        out = pipeline_apply(self.mesh, self.stage_fn, params["stages"],
                             mb, pipe_axis=self.pipe_axis)
        yb = self._microbatch(batch_y)
        losses = jax.vmap(lambda a, y: self.loss_fn(params["head"], a, y)
                          )(out, yb)
        return jnp.mean(losses)

    def make_train_step(self, optimizer, schedule="gpipe",
                        overlap_grad_reduce=None):
        """schedule='gpipe' differentiates the forward scan (activations
        for all M microbatches live through the backward, plus a
        full-activation output psum); schedule='1f1b' uses the
        interleaved fwd/bwd schedule (bounded residuals, grads stay
        pipe-sharded, no activation broadcast).
        ``overlap_grad_reduce`` (1f1b only; default
        FLAGS_overlap_grad_reduce) issues the data-axes gradient
        all-reduce per bucket inside the backward scan — see
        pipeline_train_1f1b."""
        mesh = self.mesh

        if schedule == "1f1b":
            def loss_and_grads(params, batch_x, batch_y):
                emb, embed_vjp = jax.vjp(
                    lambda ep: self.embed_fn(ep, batch_x),
                    params["embed"])
                mb = self._microbatch(emb)
                yb = self._microbatch(batch_y)

                def out_grad(hp, y, lab):
                    def head_loss(hp, y):
                        return self.loss_fn(hp, y, lab)
                    l, (ghp, gy) = jax.value_and_grad(
                        head_loss, argnums=(0, 1))(hp, y)
                    return l, gy, ghp

                loss, sg, hg, dx = pipeline_train_1f1b(
                    mesh, self.stage_fn, params["stages"], mb,
                    out_grad, yb, head_params=params["head"],
                    pipe_axis=self.pipe_axis,
                    overlap_grad_reduce=overlap_grad_reduce)
                # 1F1B sums per-microbatch grads; the GPipe loss is the
                # MEAN over microbatches — match it
                sg = jax.tree.map(lambda g: g / self.n_micro, sg)
                (g_embed,) = embed_vjp(
                    dx.reshape(emb.shape) / self.n_micro)
                return loss, {"embed": g_embed, "stages": sg,
                              "head": hg}
        elif schedule == "gpipe":
            def loss_and_grads(params, batch_x, batch_y):
                return jax.value_and_grad(self.loss)(
                    params, batch_x, batch_y)
        else:
            raise ValueError(
                f"unknown pipeline schedule {schedule!r}: "
                f"expected 'gpipe' or '1f1b'")

        @jax.jit
        def step(params, opt_state, batch_x, batch_y):
            loss, grads = loss_and_grads(params, batch_x, batch_y)
            new_params, new_opt = optimizer.apply_gradients(
                params, grads, opt_state)
            return loss, new_params, new_opt

        def init_fn(params):
            # placement flows from the ONE spec (stages over "pipe",
            # embed/head replicated) — the same object callers hand to
            # the executor or derive save(axes=) from
            pshard = self.sharding_spec().tree_shardings(params)
            params = jax.device_put(params, pshard)
            opt_state = optimizer.init(params)
            opt_state = jax.device_put(
                opt_state, optimizer.state_shardings(opt_state, pshard,
                                                     mesh))
            return params, opt_state

        return init_fn, step


# ---------------------------------------------------------------------------
# 1F1B schedule (VERDICT-r2 next-step #8; ref section_worker.cc runs
# sections concurrently — 1F1B is the TPU-native expression of that
# concurrency with bounded activation memory)
# ---------------------------------------------------------------------------
def gpipe_bubble_fraction(n_micro, n_stages):
    """GPipe bubble: 1 - M/(M+P-1) — all-forward-then-all-backward keeps
    every device idle for P-1 of M+P-1 ticks in each phase."""
    return 1.0 - n_micro / (n_micro + n_stages - 1)


def one_f_one_b_bubble_fraction(n_micro, n_stages):
    """1F1B bubble: forward+backward both run inside one M+2(P-1)-tick
    grid, each device busy 2M of 2(M+2(P-1)) work slots."""
    return 1.0 - n_micro / (n_micro + 2 * (n_stages - 1))


def schedule_occupancy(n_micro, n_stages):
    """Exact tick-grid occupancy of the 1F1B schedule implemented by
    pipeline_train_1f1b: stage s forwards microbatch t-s and backwards
    microbatch t-(2(P-1)-s) at tick t. Returns (busy_slots,
    total_slots, bubble_fraction) counted from the schedule itself (a
    test cross-checks this against the closed form)."""
    M, Pn = n_micro, n_stages
    T = M + 2 * (Pn - 1)
    busy = 0
    for s in range(Pn):
        for t in range(T):
            if 0 <= t - s < M:
                busy += 1                      # forward slot
            if 0 <= t - (2 * (Pn - 1) - s) < M:
                busy += 1                      # backward slot
    total = 2 * T * Pn
    return busy, total, 1.0 - busy / total


def pipeline_train_1f1b(mesh, stage_fn, stacked_params, microbatches,
                        out_grad_fn, labels, head_params=None,
                        pipe_axis=PIPE_AXIS, data_axis=DATA_AXIS,
                        overlap_grad_reduce=None):
    """One fused 1F1B forward+backward pass over the pipelined trunk.

    Unlike pipeline_apply (GPipe: autodiff over the whole forward scan,
    activations for all M microbatches live until the backward), this
    schedules forward and backward per tick: stage s runs fwd of
    microbatch t-s and bwd of microbatch t-(2(P-1)-s) in the same tick,
    holding at most 2P-1 residuals. Activations hop forward and grads
    hop backward via lax.ppermute each tick. There is NO full-activation
    psum epilogue — the trunk emits only the scalar loss, the per-stage
    parameter grads (which STAY sharded over "pipe", exactly where the
    optimizer update needs them), the head grads, and the stage-0 input
    grads for the embed backward.

    stage_fn(stage_params, x) -> y, y.shape == x.shape.
    out_grad_fn(head_params, y_mb, label_mb) ->
    (loss_m, dy_mb, head_grads_m) — the head + loss on one final-stage
    microbatch output (use jax.value_and_grad over the head inside it).
    labels: [M, ...] microbatched targets, delivered per tick (they
    ride the shard_map explicitly — closures over traced arrays are
    not supported). head_params ride replicated (pass {} when the head
    is stateless).
    Returns (mean_loss, stage_grads [stacked, pipe-sharded],
    head_grads, dx [M, ...] input cotangents for the embed backward).

    ``overlap_grad_reduce`` (default: FLAGS_overlap_grad_reduce) moves
    the data/dcn_data gradient all-reduce INSIDE the scan: each tick's
    gradient contribution is pmean'd over the data axes as the backward
    produces it (one collective per parameter bucket per tick,
    scan-carried partial sums), so XLA overlaps the reduction with the
    next tick's fwd/bwd compute instead of serializing one big fused
    reduction after the scan drains. Same math — sum of per-tick means
    == mean of summed grads — so on/off is a pure scheduling A/B
    (bench.py shard measures it; float association differs at the ulp
    level only). Under a hybrid mesh the reduction spans
    ("dcn_data", "data"): hierarchical allreduce, DCN crossed once.
    """
    n_micro = int(microbatches.shape[0])
    n_stages = int(dict(mesh.shape)[pipe_axis])
    resid_len = min(2 * n_stages - 1, n_micro) if n_micro else 1
    ticks = n_micro + 2 * (n_stages - 1)
    if overlap_grad_reduce is None:
        overlap_grad_reduce = bool(get_flag("overlap_grad_reduce"))
    red_axes = _data_reduce_axes(mesh, data_axis)
    shape = dict(mesh.shape)
    n_red = 1
    for a in red_axes:
        n_red *= shape[a]
    overlap = bool(overlap_grad_reduce) and bool(red_axes)

    if head_params is None:
        head_params = {}
    pspec = jax.tree.map(
        lambda x: P(*([pipe_axis] + [None] * (np.ndim(x) - 1))),
        stacked_params)
    dspec = _data_pspec(red_axes)
    hspec = jax.tree.map(lambda _: P(), head_params)
    lspec = dspec

    def body(stacked_local, mb, lb, hp):
        idx = lax.axis_index(pipe_axis)
        fn = _bind_stage_fn(stage_fn, idx)
        params = jax.tree.map(lambda x: x[0], stacked_local)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
        mb_shape = mb.shape[1:]
        zero_act = jnp.zeros(mb_shape, mb.dtype) + mb[0] * 0.0

        # head-grad accumulator mirrors head param structure
        hg_zero = jax.tree.map(
            lambda p: jnp.zeros(jnp.shape(p), jnp.result_type(p))
            + zero_act.ravel()[0] * 0, hp)
        gp_zero = jax.tree.map(lambda x: jnp.zeros_like(x) + x * 0, params)

        carry0 = dict(
            fwd_in=zero_act,
            bwd_in=zero_act,
            resid=jnp.zeros((resid_len,) + mb_shape, mb.dtype)
            + zero_act * 0.0,
            grad_acc=gp_zero,
            head_acc=hg_zero,
            loss_acc=zero_act.ravel()[0] * 0.0,
            dx_bank=jnp.zeros((n_micro,) + mb_shape, mb.dtype)
            + mb * 0.0,
        )

        def tick(c, t):
            mf = t - idx                               # fwd microbatch
            mbk = t - (2 * (n_stages - 1) - idx)       # bwd microbatch
            fwd_valid = (mf >= 0) & (mf < n_micro)
            bwd_valid = (mbk >= 0) & (mbk < n_micro)

            # ---- forward ----
            x_feed = lax.dynamic_index_in_dim(
                mb, jnp.clip(mf, 0, n_micro - 1), keepdims=False)
            x = jnp.where(idx == 0, x_feed, c["fwd_in"])
            y = fn(params, x)
            resid = lax.dynamic_update_index_in_dim(
                c["resid"], x, jnp.clip(mf, 0, n_micro - 1) % resid_len,
                axis=0)
            resid = jnp.where(fwd_valid, resid, c["resid"])

            # head/loss on the last stage the tick a microbatch finishes
            is_last = idx == n_stages - 1
            lab_m = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(
                    a, jnp.clip(mf, 0, n_micro - 1), keepdims=False),
                lb)
            loss_m, dy_m, hg_m = out_grad_fn(hp, y, lab_m)
            take_head = fwd_valid & is_last
            loss_acc = c["loss_acc"] + jnp.where(take_head, loss_m, 0.0)
            if overlap:
                # per-bucket data-axes reduction as the tick produces
                # the contribution (scan-carried partial mean)
                head_acc = jax.tree.map(
                    lambda a, g: a + lax.pmean(
                        jnp.where(take_head, g, 0.0), red_axes),
                    c["head_acc"], hg_m)
            else:
                head_acc = jax.tree.map(
                    lambda a, g: a + jnp.where(take_head, g, 0.0),
                    c["head_acc"], hg_m)

            # ---- backward (recompute-from-residual vjp) ----
            x_saved = lax.dynamic_index_in_dim(
                c["resid"], jnp.clip(mbk, 0, n_micro - 1) % resid_len,
                keepdims=False)
            g_in = jnp.where(is_last, dy_m, c["bwd_in"])
            # on the last stage fwd and bwd of a microbatch share the
            # tick, so the residual for mbk is this tick's x
            x_for_bwd = jnp.where(is_last, x, x_saved)
            _, vjp_fn = jax.vjp(fn, params, x_for_bwd)
            gp, gx = vjp_fn(g_in)
            if overlap:
                # the gradient all-reduce over data/dcn_data, issued
                # per bucket (per param leaf) the tick the backward
                # produces it — XLA overlaps these with the next
                # tick's compute; the carry accumulates ALREADY-
                # reduced partial sums, so the epilogue reduction
                # disappears
                grad_acc = jax.tree.map(
                    lambda a, g: a + lax.pmean(
                        jnp.where(bwd_valid, g, 0.0), red_axes),
                    c["grad_acc"], gp)
            else:
                grad_acc = jax.tree.map(
                    lambda a, g: a + jnp.where(bwd_valid, g, 0.0),
                    c["grad_acc"], gp)
            dx_bank = lax.dynamic_update_index_in_dim(
                c["dx_bank"],
                jnp.where(bwd_valid & (idx == 0), gx,
                          lax.dynamic_index_in_dim(
                              c["dx_bank"],
                              jnp.clip(mbk, 0, n_micro - 1),
                              keepdims=False)),
                jnp.clip(mbk, 0, n_micro - 1), axis=0)

            # ---- ring hops ----
            fwd_in = lax.ppermute(y, pipe_axis, fwd_perm)
            bwd_in = lax.ppermute(jnp.where(bwd_valid, gx, 0.0 * gx),
                                  pipe_axis, bwd_perm)
            return dict(fwd_in=fwd_in, bwd_in=bwd_in, resid=resid,
                        grad_acc=grad_acc, head_acc=head_acc,
                        loss_acc=loss_acc, dx_bank=dx_bank), None

        c, _ = lax.scan(tick, carry0, jnp.arange(ticks))
        # scalar/param-sized epilogues only — no activation broadcast.
        # Under DP x PP each data replica computed its slice's local
        # mean loss: the global loss is the data-axes mean, and every
        # param grad is likewise the data-axes mean (dx stays sharded
        # over data, scaled by 1/n_red). With overlap on, the grad/head
        # reductions already happened per tick inside the scan.
        grad_acc = c["grad_acc"]
        head_acc = c["head_acc"]
        loss = lax.psum(c["loss_acc"], pipe_axis) / n_micro
        dx_local = c["dx_bank"]
        if red_axes:
            loss = lax.pmean(loss, red_axes)
            if not overlap:
                grad_acc = jax.tree.map(
                    lambda g: lax.pmean(g, red_axes), grad_acc)
                head_acc = jax.tree.map(
                    lambda g: lax.pmean(g, red_axes), head_acc)
            dx_local = dx_local / n_red
        # stage grads stay pipe-local (re-stack the leading axis of
        # length 1 so the output matches stacked_params' pipe sharding)
        stage_grads = jax.tree.map(lambda g: g[None], grad_acc)
        head_grads = jax.tree.map(
            lambda g: lax.psum(g, pipe_axis) / n_micro, head_acc)
        dx = lax.psum(
            jnp.where(idx == 0, dx_local, jnp.zeros_like(dx_local)),
            pipe_axis)
        return loss, stage_grads, head_grads, dx

    return shard_map(
        body, mesh=mesh,
        in_specs=(pspec, dspec,
                  jax.tree.map(lambda _: lspec, labels), hspec),
        out_specs=(P(), pspec, hspec, dspec),
        check_vma=False)(stacked_params, microbatches, labels,
                     head_params)
