"""LocalSGD: per-replica training with periodic parameter averaging.

Parity: transpiler/collective.py:263 LocalSGD (the reference rewrites the
program so each trainer steps independently and inserts a broadcast/
allreduce of PARAMETERS every k steps, instead of per-step gradient
allreduce).

TPU-first shape: params carry a leading replica axis sharded over the
data mesh axis; the per-replica step runs under shard_map (no collective
at all), and every ``k`` steps one pmean synchronises parameters — the
only cross-replica traffic. This is the communication-avoiding regime
LocalSGD exists for; on ICI it trades a per-step psum for a per-k pmean.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from paddle_tpu.parallel.mesh import DATA_AXIS, get_mesh

__all__ = ["LocalSGDTrainer"]


class LocalSGDTrainer:
    """loss_fn(params, batch) -> scalar loss; plain SGD per replica,
    parameter pmean every ``sync_steps`` steps."""

    def __init__(self, loss_fn, learning_rate=0.01, sync_steps=4,
                 mesh=None, axis_name=DATA_AXIS):
        self.loss_fn = loss_fn
        self.lr = learning_rate
        self.k = int(sync_steps)
        self.mesh = mesh or get_mesh()
        self.axis = axis_name
        self._step = None

    def init(self, params):
        """Replicate initial params to a leading replica axis
        [n_replicas, ...] (all replicas start equal — the reference's
        startup broadcast, transpiler/collective.py _transpile_startup)."""
        n = self.mesh.shape[self.axis]
        stacked = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), params)
        return {"params": stacked, "step": jnp.zeros((), jnp.int32)}

    def _build(self, state, batch):
        mesh = self.mesh
        ax = self.axis
        k = self.k
        lr = self.lr
        loss_fn = self.loss_fn

        pspec = jax.tree.map(lambda _: P(ax), state["params"])
        bspec = jax.tree.map(lambda _: P(ax), batch)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(pspec, P(), bspec), out_specs=(P(ax), P()),
            check_vma=False)
        def step(params, stepno, local_batch):
            p = jax.tree.map(lambda t: t[0], params)   # this replica's
            loss, grads = jax.value_and_grad(loss_fn)(p, local_batch)
            p = jax.tree.map(lambda t, g: t - lr * g, p, grads)
            do_sync = ((stepno + 1) % k) == 0
            p = jax.tree.map(
                lambda t: lax.cond(do_sync,
                                   lambda x: lax.pmean(x, ax),
                                   lambda x: x, t), p)
            mean_loss = lax.pmean(loss, ax)
            return jax.tree.map(lambda t: t[None], p), mean_loss

        return jax.jit(step)

    def train_step(self, state, batch):
        """batch leading dim divides the replica count. Returns
        (mean loss, new state)."""
        if self._step is None:
            self._step = self._build(state, batch)
        params, loss = self._step(state["params"], state["step"], batch)
        return loss, {"params": params, "step": state["step"] + 1}

    def sync_params(self, state):
        """Final average (the reference's end-of-training allreduce)."""
        avg = jax.tree.map(lambda t: jnp.mean(t, axis=0), state["params"])
        return avg
