"""The jitted training step of a decoder language model, for every model
built from ``models/blocks.py``: what ``olmoe.make_train_step`` and
``kimi_linear.make_train_step`` have in common, which is everything but the
three functions a model brings (its parameters, their partition specs, its
loss). ``models/bert.py`` and ``models/transformer.py`` carry their own
(ROADMAP C10).
"""

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, get_mesh
from paddle_tpu.profiler import RecordEvent

__all__ = ["make_train_step"]


def make_train_step(cfg, optimizer, mesh, init_params, param_specs, loss_fn,
                    after_update=None):
    """Returns (init_fn, step_fn) jitted over the mesh with dp/tp shardings
    pinned. ``init_params(rng, cfg=cfg)`` makes the float32 parameters,
    ``param_specs(cfg)`` their PartitionSpecs over ("model",),
    ``loss_fn(params, cfg, batch, mesh=mesh)`` the scalar loss. With
    ``after_update`` the loss function returns (loss, aux) and
    ``after_update(new_params, aux)`` the parameters the step hands back:
    for a rule that moves a parameter outside the gradient (a router's
    selection bias, by the load it saw).
    step(params, opt_state, batch) -> (loss, params, opt_state); params and
    opt_state are donated. ``step_fn.jitted`` and ``step_fn.place`` as
    ``bert.make_train_step`` hands them out. With ``after_update`` the
    jitted step returns the aux as a fourth result and ``step_fn.aux`` holds
    that of the last step enqueued (device arrays: the counter a reader
    takes a step's routing from; reading it waits for that step)."""
    mesh = mesh or get_mesh()
    pspecs = param_specs(cfg)
    if mesh.shape.get(MODEL_AXIS, 1) == 1:
        pspecs = jax.tree.map(lambda s: P(), pspecs,
                              is_leaf=lambda s: isinstance(s, P))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda s: isinstance(s, P))

    def init_fn(rng):
        with RecordEvent("trainer/init"):
            params = jax.jit(functools.partial(init_params, cfg=cfg),
                             out_shardings=pshard)(rng)
            opt_state = optimizer.init(params)
            opt_state = jax.device_put(
                opt_state, optimizer.state_shardings(opt_state, pshard, mesh))
        return params, opt_state

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, mesh=mesh),
            has_aux=after_update is not None)(params)
        if after_update is not None:
            loss, aux = loss
        new_params, new_opt = optimizer.apply_gradients(
            params, grads, opt_state)
        if after_update is None:
            return loss, new_params, new_opt
        return loss, after_update(new_params, aux), new_opt, aux

    jit_step = jax.jit(step, donate_argnums=(0, 1))
    dshard = NamedSharding(mesh, P(DATA_AXIS))

    def place(batch):
        """Put a host batch on the mesh: rows over "data"."""
        return {name: jax.device_put(v, dshard) for name, v in batch.items()}

    def step_fn(params, opt_state, batch):
        with RecordEvent("trainer/place"):
            batch = place(batch)
        with RecordEvent("trainer/enqueue"):
            loss, params, opt_state, *step_fn.aux = jit_step(
                params, opt_state, batch)
        return loss, params, opt_state

    step_fn.aux = []
    step_fn.place = place
    step_fn.jitted = jit_step
    return init_fn, step_fn
