"""SPMD data-parallel training.

Replaces the reference's whole multi-device stack: ParallelExecutor's
per-device graph cloning + allreduce insertion
(ref: ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:204,454,
details/all_reduce_op_handle.cc:86) becomes ONE jitted computation with
sharding annotations: batch sharded over the "data" axis, params
replicated (AllReduce strategy) or sharded over the data axis (the
reference's Reduce strategy, ref: build_strategy.h:38-57 kReduce,
details/reduce_op_handle.cc + broadcast_op_handle.cc — realized here as
a ZeRO layout: params + optimizer state live sharded 1/N per device;
each step all-gathers params for the forward and reduce-scatters
gradients into the local shard's update, via explicit shard_map
collectives so the reduce-scatter/all-gather pair is guaranteed in the
compiled HLO, not left to a partitioner heuristic).

Gradient accumulation reproduces multi_batch_merge_pass
(ref: ir/multi_batch_merge_pass.cc) as a lax.scan over microbatches.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.ops.pallas.registry import mesh_scope
from paddle_tpu.parallel.mesh import DATA_AXIS, data_axes, get_mesh


__all__ = ["shard_batch", "replicate", "zero_param_specs",
           "DataParallelTrainer"]


def shard_batch(mesh, batch, axis_name=DATA_AXIS):
    """Place host batch sharded along the data axis (batch dim 0)."""
    def put(x):
        spec = P(axis_name) if jnp.ndim(x) >= 1 else P()
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(put, batch)


def replicate(mesh, tree):
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree)


def zero_param_specs(mesh, params, axes=None):
    """ZeRO/kReduce placement policy: for each param leaf, shard its
    LARGEST dimension divisible by the data-axes extent; leaves with no
    such dimension stay replicated. Returns a PartitionSpec tree.

    This is the SPMD expression of ReduceStrategy::kReduce
    (build_strategy.h:57): every device owns 1/N of each parameter and
    its optimizer state instead of the whole thing.
    """
    axes = axes or data_axes(mesh)
    n = int(np.prod([dict(mesh.shape)[a] for a in axes]))

    def spec(x):
        shape = jnp.shape(x)
        best, best_dim = None, -1
        for d, s in enumerate(shape):
            if s % n == 0 and s > best_dim:
                best, best_dim = d, s
        if best is None or n == 1:
            return P()
        entries = [None] * len(shape)
        entries[best] = axes if len(axes) > 1 else axes[0]
        return P(*entries)

    return jax.tree.map(spec, params)


def _sharded_dim(spec):
    """Index of the (single) sharded dimension in a zero spec, or None."""
    for d, e in enumerate(spec):
        if e is not None:
            return d
    return None


class DataParallelTrainer:
    """Compiled SPMD train step.

    loss_fn(params, state, rng, batch) -> (loss, new_state) — pure, as
    produced by nn.Layer.apply. The trainer jits
    (params, opt_state, state, rng, batch) -> (loss, params, opt_state,
    state) with in/out shardings pinned so batch math runs sharded over
    "data" and the grad reduction rides ICI.

    param_sharding selects the reference's ReduceStrategy
    (build_strategy.h:38-57):
      - None            -> kAllReduce: params + opt state replicated,
                           XLA all-reduces gradients.
      - "reduce"/"zero" -> kReduce as ZeRO layout: params + opt state
                           sharded 1/N over the data axis
                           (zero_param_specs). The step all-gathers
                           param shards for the forward and
                           reduce-scatters gradients so each device
                           updates only its own shard — explicit
                           collectives, guaranteed in the HLO.
      - a PartitionSpec tree -> explicit per-param placement; entries
                           may reference the data axis only (model-axis
                           sharding belongs to the megatron specs in
                           models/, not this trainer).

    kReduce requires an ELEMENTWISE optimizer update rule (every rule in
    optimizer.py except Lars/Lamb, whose trust ratios need whole-param
    norms); non-elementwise optimizers raise at construction.

    accumulate_steps>1 reproduces gradient accumulation (batch-merge):
    the batch's leading dim is split into microbatches scanned
    sequentially before one update.
    """

    def __init__(self, loss_fn, optimizer, mesh=None, axis_name=DATA_AXIS,
                 accumulate_steps=1, param_sharding=None, donate=True):
        self.loss_fn = loss_fn
        self.opt = optimizer
        self.mesh = mesh or get_mesh()
        self.axis = axis_name
        self.accum = accumulate_steps
        self.param_sharding = param_sharding
        if param_sharding is not None:
            if not getattr(optimizer, "_elementwise", True):
                raise EnforceNotMet(
                    f"param_sharding={param_sharding!r} needs an "
                    f"elementwise optimizer update; "
                    f"{type(optimizer).__name__} computes whole-parameter "
                    f"norms — use the replicated strategy")
            clip = getattr(optimizer, "grad_clip", None)
            if clip is not None and type(clip).__name__ not in (
                    "GradientClipByValue",):
                # norm-based clips would compute per-SHARD norms inside
                # the shard_map body: wrong scale, and device-divergent
                # for replicated leaves
                raise EnforceNotMet(
                    f"param_sharding={param_sharding!r} is incompatible "
                    f"with norm-based gradient clipping "
                    f"({type(clip).__name__}): the norm would be taken "
                    f"over local shards only. Use GradientClipByValue "
                    f"or the replicated strategy")
        # resolved at init() when param shapes are known; read at trace
        # time by the step closure (jit traces on first call, after
        # init), so the shard_map specs bind to the actual placement.
        self._param_specs = None

        rep = NamedSharding(self.mesh, P())
        data_sh = NamedSharding(self.mesh, P(self.axis))

        def grads_of(params, state, rng, batch):
            def lf(p):
                loss, new_state = self.loss_fn(p, state, rng, batch)
                return loss, new_state
            (loss, new_state), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            return loss, grads, new_state

        def fwd_bwd(params, state, rng, batch):
            """(loss, grads, new_state) with optional microbatch scan."""
            if self.accum == 1:
                return grads_of(params, state, rng, batch)

            def micro(carry, mb):
                acc, st, k = carry
                k, sub = jax.random.split(k)
                l, g, st = grads_of(params, st, sub, mb)
                acc = jax.tree.map(jnp.add, acc, g)
                return (acc, st, k), l

            mbs = jax.tree.map(
                lambda x: x.reshape((self.accum, -1) + x.shape[1:]),
                batch)
            zero = jax.tree.map(jnp.zeros_like, params)
            (gsum, new_state, _), losses = jax.lax.scan(
                micro, (zero, state, rng), mbs)
            grads = jax.tree.map(lambda g: g / self.accum, gsum)
            return jnp.mean(losses), grads, new_state

        def plain_step(params, opt_state, state, rng, batch):
            # GSPMD partitions this step; zero_step's shard_map body
            # runs per shard and needs no scope
            with mesh_scope(self.mesh):
                loss, grads, new_state = fwd_bwd(params, state, rng,
                                                 batch)
            new_params, new_opt = self.opt.apply_gradients(
                params, grads, opt_state)
            return loss, new_params, new_opt, new_state

        def zero_step(params, opt_state, state, rng, batch):
            """kReduce: shard_map over the data axis with explicit
            all-gather (params, broadcast_op_handle.cc's role) and
            reduce-scatter (grads, reduce_op_handle.cc's role)."""
            specs = self._param_specs
            ax = self.axis
            n = dict(self.mesh.shape)[ax]

            def gather(p, spec):
                d = _sharded_dim(spec)
                return p if d is None else lax.all_gather(
                    p, ax, axis=d, tiled=True)

            def scatter(g, spec):
                d = _sharded_dim(spec)
                if d is None:
                    return lax.pmean(g, ax)
                return lax.psum_scatter(
                    g, ax, scatter_dimension=d, tiled=True) / n

            slot_specs = (self._slot_specs(opt_state["slots"])
                          if isinstance(opt_state, dict)
                          and "slots" in opt_state else None)
            opt_specs = jax.tree.map(lambda _: P(), opt_state)
            if slot_specs is not None:
                opt_specs = dict(opt_specs)
                opt_specs["slots"] = slot_specs
            state_specs = jax.tree.map(lambda _: P(), state)
            batch_specs = jax.tree.map(
                lambda x: P(ax) if jnp.ndim(x) >= 1 else P(), batch)

            def body(p_sh, o_sh, st, k, b):
                p_full = jax.tree.map(gather, p_sh, specs)
                loss, g_full, new_st = fwd_bwd(p_full, st, k, b)
                g_sh = jax.tree.map(scatter, g_full, specs)
                loss = lax.pmean(loss, ax)
                new_p, new_o = self.opt.apply_gradients(p_sh, g_sh, o_sh)
                return loss, new_p, new_o, new_st

            return shard_map(
                body, mesh=self.mesh,
                in_specs=(specs, opt_specs, state_specs, P(), batch_specs),
                out_specs=(P(), specs, opt_specs, state_specs),
                check_vma=False)(params, opt_state, state, rng, batch)

        def step(params, opt_state, state, rng, batch):
            if self._param_specs is None:
                return plain_step(params, opt_state, state, rng, batch)
            return zero_step(params, opt_state, state, rng, batch)

        in_sh = (None, None, None, rep, data_sh)
        self._step = jax.jit(
            step,
            in_shardings=in_sh,
            donate_argnums=(0, 1, 2) if donate else (),
        )

    # -- placement ---------------------------------------------------------
    def _resolve_specs(self, params):
        if self.param_sharding is None:
            return None
        if isinstance(self.param_sharding, str):
            if self.param_sharding not in ("reduce", "zero"):
                raise EnforceNotMet(
                    f"param_sharding={self.param_sharding!r}: expected "
                    f"None, 'reduce'/'zero', a PartitionSpec tree, or "
                    f"a parallel.ShardingSpec")
            return zero_param_specs(self.mesh, params, axes=(self.axis,))
        from paddle_tpu.parallel.spec import ShardingSpec
        if isinstance(self.param_sharding, ShardingSpec):
            # the unified spec as placement source: entries must stay
            # on THIS trainer's data axis — the explicit gather/scatter
            # collectives below reduce over self.axis, so a model-axis
            # entry would silently shard without ever being gathered
            specs = self.param_sharding.tree_specs(params)
            for sp in jax.tree.leaves(
                    specs, is_leaf=lambda s: isinstance(s, P)):
                for entry in sp:
                    if entry is not None and entry != self.axis:
                        raise EnforceNotMet(
                            f"DataParallelTrainer(param_sharding=Shard"
                            f"ingSpec): entry {sp} references axis "
                            f"{entry!r}, but this trainer's explicit "
                            f"all-gather/reduce-scatter pair runs over "
                            f"{self.axis!r} only — model-axis "
                            f"placement belongs to the megatron specs "
                            f"or the executor's spec path")
            return specs
        return self.param_sharding

    def _slot_specs(self, slots):
        """Each optimizer slot mirrors its param's spec (slots are
        elementwise state of their param)."""
        flat_specs, ptreedef = jax.tree.flatten(
            self._param_specs,
            is_leaf=lambda x: isinstance(x, P))
        flat_slots = ptreedef.flatten_up_to(slots)
        return jax.tree.unflatten(
            ptreedef,
            [jax.tree.map(lambda _: sp, sd)
             for sp, sd in zip(flat_specs, flat_slots)])

    def param_shardings(self, params):
        """NamedSharding tree for params under the active strategy
        (replicated when param_sharding is None)."""
        specs = self._resolve_specs(params)
        if specs is None:
            return jax.tree.map(
                lambda _: NamedSharding(self.mesh, P()), params)
        return jax.tree.map(
            lambda _, s: NamedSharding(self.mesh, s), params, specs,
            is_leaf=lambda x: isinstance(x, P))

    def init(self, init_fn, rng, sample_batch):
        """init_fn(rng, batch) -> (params, state). Params land replicated
        or sharded per the strategy — the analog of BCastParamsToDevices
        (ref: parallel_executor.h:81) for kAllReduce, and of the
        owner-device param layout of kReduce (reduce_op_handle.cc) for
        "reduce"/"zero"."""
        params, state = init_fn(rng, sample_batch)
        self._param_specs = self._resolve_specs(params)
        pshard = self.param_shardings(params)
        params = jax.tree.map(jax.device_put, params, pshard)
        state = replicate(self.mesh, state)
        opt_state = self.opt.init(params)
        opt_sh = self.opt.state_shardings(opt_state, pshard, self.mesh)
        opt_state = jax.tree.map(jax.device_put, opt_state, opt_sh)
        return params, opt_state, state

    def prepare_sharding(self, params):
        """Resolve + pin the param placement for params NOT produced by
        init() (e.g. restored from a checkpoint): returns the params
        placed per the strategy; also sizes the optimizer-state
        shardings used by subsequent step() traces."""
        self._param_specs = self._resolve_specs(params)
        return jax.tree.map(jax.device_put, params,
                            self.param_shardings(params))

    def step(self, params, opt_state, state, rng, batch):
        if self.param_sharding is not None and self._param_specs is None:
            raise EnforceNotMet(
                "param_sharding was requested but placement is "
                "unresolved — call init(), or prepare_sharding(params) "
                "when restoring from a checkpoint; running now would "
                "silently train fully replicated")
        batch = shard_batch(self.mesh, batch, self.axis)
        return self._step(params, opt_state, state, rng, batch)
