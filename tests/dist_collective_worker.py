"""Two-process collective-DP worker, driven by
``paddle_tpu.distributed.launch`` in collective mode.

The analog of the reference's NCCL2-mode loopback trainer
(ref: python/paddle/fluid/tests/unittests/test_dist_base.py:618
_run_cluster_nccl2 + dist_mnist.py): each rank joins the job through
``init_parallel_env`` (jax.distributed rendezvous — the gen_nccl_id
role), builds a global data mesh spanning both processes, and trains
the same deterministic linear problem with cross-process gradient
all-reduce. Rank 0 writes the per-step losses as JSON for the test to
compare against the single-process run.
"""

import json
import os
import sys

# CPU backend, one virtual device per process: must be pinned before
# jax is imported.
# Only when executed as the worker script — importing this module from
# the test process must NOT clobber the conftest's 8-device env.
if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def make_problem():
    """Deterministic linear-regression batch, identical in every
    process and in the single-process reference run."""
    rng = np.random.RandomState(7)
    x = rng.rand(16, 4).astype(np.float32)
    w = np.linspace(-1.0, 1.0, 4).astype(np.float32)[:, None]
    y = x @ w + 0.1
    return {"x": x, "y": y.astype(np.float32)}


def loss_fn(params, state, rng, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2), state


def init_fn(rng, batch):
    del rng, batch
    params = {"w": jnp.zeros((4, 1), jnp.float32),
              "b": jnp.zeros((1,), jnp.float32)}
    return params, {}


def train(trainer_cls, mesh, steps=6):
    import paddle_tpu as pt
    from paddle_tpu.parallel.data_parallel import shard_batch

    trainer = trainer_cls(loss_fn, pt.optimizer.Momentum(0.5, 0.9),
                          mesh=mesh)
    batch = make_problem()
    params, opt_state, state = trainer.init(
        init_fn, jax.random.PRNGKey(0), shard_batch(mesh, batch))
    losses = []
    for _ in range(steps):
        loss, params, opt_state, state = trainer.step(
            params, opt_state, state, jax.random.PRNGKey(0),
            shard_batch(mesh, batch))
        losses.append(float(np.asarray(loss)))
    return losses


def main():
    out_path = sys.argv[1]
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    endpoints = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
    # rank 0's trainer endpoint doubles as the jax.distributed
    # coordinator address (the launcher guarantees the port is free)
    from paddle_tpu.parallel.env import ParallelEnv, init_parallel_env
    env = init_parallel_env(coordinator_address=endpoints[0],
                            num_processes=world, process_id=rank)
    assert isinstance(env, ParallelEnv)
    assert env.local_rank == rank and env.nranks == world
    assert jax.process_count() == world, jax.process_count()
    assert jax.device_count() == world, jax.device_count()

    from paddle_tpu.parallel.data_parallel import DataParallelTrainer
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=jax.device_count()))
    losses = train(DataParallelTrainer, mesh)
    if rank == 0:
        with open(out_path, "w") as f:
            json.dump({"world": world, "losses": losses}, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
