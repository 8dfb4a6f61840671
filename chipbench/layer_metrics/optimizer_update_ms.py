"""Layer: optimizer. ``optimizer.apply_gradients`` jitted alone on the cell's
parameter, gradient and state arrays, on the cell's mesh, outside the window:
the median of 20 calls that each end in ``block_until_ready``.

It times the layer from outside the step, so it cannot see what XLA overlaps
or fuses with the backward pass; the ``tracing`` issue replaces it with the
in-step time by scope name."""

import time

import numpy as np

CALLS = 20


def metric(facts):
    import jax
    from paddle_tpu.ops import pallas

    job = facts["job"]
    params, opt_state = facts["state"]

    @jax.jit
    def update(params, grads, opt_state):
        with pallas.mesh_scope(job.mesh):     # as the trainers' steps do
            return job.optimizer.apply_gradients(params, grads, opt_state)

    grads = jax.jit(lambda p: jax.tree.map(lambda a: 1e-3 * a, p))(params)
    jax.block_until_ready(update(params, grads, opt_state))
    times = []
    for _ in range(CALLS):
        t0 = time.perf_counter()
        jax.block_until_ready(update(params, grads, opt_state))
        times.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(times))
