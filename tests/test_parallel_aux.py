"""Aux parallel features: sync BN, DGC compression, LocalSGD.

Run on the 8-device virtual CPU mesh (conftest sets
xla_force_host_platform_device_count=8). Pattern: distributed result ==
dense/local result (TestDistBase discipline).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from paddle_tpu.ops.nn import batch_norm, sync_batch_norm
from paddle_tpu.parallel import dgc
from paddle_tpu.parallel.local_sgd import LocalSGDTrainer
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])


class TestSyncBatchNorm:
    def test_matches_global_stats(self, mesh4):
        """sync BN over 4 shards == plain BN over the full batch."""
        rng = np.random.RandomState(0)
        x = rng.randn(8, 3, 4, 4).astype(np.float32)
        scale = rng.rand(3).astype(np.float32) + 0.5
        bias = rng.randn(3).astype(np.float32)
        mean = np.zeros(3, np.float32)
        var = np.ones(3, np.float32)

        want = batch_norm(jnp.asarray(x), jnp.asarray(scale),
                          jnp.asarray(bias), jnp.asarray(mean),
                          jnp.asarray(var))

        fn = shard_map(
            functools.partial(sync_batch_norm, epsilon=1e-5,
                              axis_name="data"),
            mesh=mesh4,
            in_specs=(P("data"), P(), P(), P(), P()),
            out_specs=(P("data"), P(), P(), P(), P()),
            check_vma=False)
        got = fn(jnp.asarray(x), jnp.asarray(scale), jnp.asarray(bias),
                 jnp.asarray(mean), jnp.asarray(var))
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-5)

    def test_single_replica_fallback(self):
        x = np.random.RandomState(1).randn(4, 2, 3, 3).astype(np.float32)
        args = (jnp.asarray(x), jnp.ones(2), jnp.zeros(2), jnp.zeros(2),
                jnp.ones(2))
        got = sync_batch_norm(*args, axis_name=None)
        want = batch_norm(*args)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   atol=1e-6)


class TestDGC:
    def test_sparsity_honored(self):
        g = jnp.asarray(np.random.RandomState(2).randn(100))
        u = jnp.zeros(100)
        v = jnp.zeros(100)
        send, nu, nv = dgc.dgc_compress(g, u, v, sparsity=0.9,
                                        momentum=0.0)
        nz = int(jnp.sum(send != 0))
        assert nz <= 10 + 1
        # error feedback: untransmitted mass retained in residual
        np.testing.assert_allclose(np.asarray(send + nv), np.asarray(g),
                                   atol=1e-6)

    def test_error_feedback_eventually_sends(self):
        # a smaller component accumulates in the residual until it wins
        # the top-k (round 1 sends g[0]=1.0; by round 2, v[1]=1.2 > 1.0)
        g = jnp.asarray([1.0, 0.6])
        u = jnp.zeros(2)
        v = jnp.zeros(2)
        sent_small = 0.0
        for _ in range(4):
            send, u, v = dgc.dgc_compress(g, u, v, sparsity=0.5,
                                          momentum=0.0)
            sent_small += float(send[1])
        assert sent_small > 0.0

    def test_rampup_schedule(self):
        assert dgc.dgc_sparsity_at(0, rampup_begin_step=5) == 0.0
        assert dgc.dgc_sparsity_at(5, 5, 5) == 0.75
        assert dgc.dgc_sparsity_at(100, 5, 5) == 0.999

    def test_allreduce_grads_tree(self, mesh4):
        rng = np.random.RandomState(3)
        grads = {"w": jnp.asarray(rng.randn(4, 16).astype(np.float32)),
                 "b": jnp.asarray(rng.randn(4, 4).astype(np.float32))}
        params = {"w": jnp.zeros((16,)), "b": jnp.zeros((4,))}

        def inner(g):
            gl = jax.tree.map(lambda x: x[0], g)   # local shard's grads
            st = dgc.dgc_init(params)
            out, st = dgc.dgc_allreduce_grads(
                gl, st, step=100, axis_name="data", momentum=0.0)
            return out

        fn = shard_map(inner, mesh=mesh4,
                       in_specs=(jax.tree.map(lambda _: P("data"), grads),),
                       out_specs=jax.tree.map(lambda _: P(), params),
                       check_vma=False)
        out = fn(grads)
        assert out["w"].shape == (16,)
        # sparsity 0.999 with 16 elems → keep 1 per replica minimum;
        # result is finite and nonzero somewhere
        assert np.isfinite(np.asarray(out["w"])).all()

    def test_dense_when_no_rampup(self, mesh4):
        """sparsity 0 (pre-rampup) must equal plain pmean of grads."""
        rng = np.random.RandomState(4)
        grads = {"w": jnp.asarray(rng.randn(4, 8).astype(np.float32))}

        def inner(g):
            st = {"u": jax.tree.map(lambda x: jnp.zeros(x.shape[1:]), g),
                  "v": jax.tree.map(lambda x: jnp.zeros(x.shape[1:]), g)}
            gl = jax.tree.map(lambda x: x[0], g)
            out, _ = dgc.dgc_allreduce_grads(
                gl, st, step=0, axis_name="data", momentum=0.0,
                rampup_begin_step=10)
            return out

        fn = shard_map(inner, mesh=mesh4,
                       in_specs=(P("data"),), out_specs=P(),
                       check_vma=False)
        out = fn(grads["w"][:, None])
        want = grads["w"].mean(0)[None]
        np.testing.assert_allclose(np.asarray(out).reshape(-1),
                                   np.asarray(want).reshape(-1), atol=1e-5)


class TestLocalSGD:
    def test_converges_and_syncs(self, mesh4):
        rng = np.random.RandomState(5)
        w_true = rng.randn(6).astype(np.float32)
        x = rng.randn(32, 6).astype(np.float32)
        y = x @ w_true

        def loss_fn(params, batch):
            xb, yb = batch
            return jnp.mean((xb @ params["w"] - yb) ** 2)

        tr = LocalSGDTrainer(loss_fn, learning_rate=0.1, sync_steps=4,
                             mesh=mesh4)
        state = tr.init({"w": jnp.zeros(6)})
        batch = (jnp.asarray(x), jnp.asarray(y))
        losses = []
        for _ in range(120):
            loss, state = tr.train_step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.1
        w = tr.sync_params(state)["w"]
        np.testing.assert_allclose(np.asarray(w), w_true, atol=0.2)

    def test_replicas_equal_after_sync_step(self, mesh4):
        rng = np.random.RandomState(6)
        x = rng.randn(16, 3).astype(np.float32)
        y = x.sum(1)

        def loss_fn(params, batch):
            xb, yb = batch
            return jnp.mean((xb @ params["w"] - yb) ** 2)

        tr = LocalSGDTrainer(loss_fn, learning_rate=0.05, sync_steps=2,
                             mesh=mesh4)
        state = tr.init({"w": jnp.zeros(3)})
        batch = (jnp.asarray(x), jnp.asarray(y))
        _, state = tr.train_step(state, batch)   # step 1: local only
        p = np.asarray(state["params"]["w"])
        assert not np.allclose(p[0], p[1])       # replicas diverged
        _, state = tr.train_step(state, batch)   # step 2: sync
        p = np.asarray(state["params"]["w"])
        np.testing.assert_allclose(p[0], p[1], atol=1e-6)
        np.testing.assert_allclose(p[0], p[3], atol=1e-6)


class TestDygraphDataParallel:
    """dygraph.parallel.DataParallel name-level parity (ref
    dygraph/parallel.py:84): scale_loss + apply_collective_grads ==
    cross-replica mean gradients."""

    def test_scale_and_collect_equals_pmean(self):
        from jax.sharding import PartitionSpec as P
        from paddle_tpu import nn
        from paddle_tpu.parallel import (DataParallel, ParallelStrategy)
        from paddle_tpu.parallel.mesh import (DATA_AXIS, MeshConfig,
                                              make_mesh)

        mesh = make_mesh(MeshConfig(data=8))
        model = nn.Linear(4, 2)
        params, state = model.init(jax.random.PRNGKey(0),
                                   jnp.ones((2, 4)))
        dp = DataParallel(model, ParallelStrategy(nranks=8))

        x = jnp.asarray(np.random.RandomState(0).randn(16, 4),
                        jnp.float32)

        def local(p, xs):
            def loss_fn(p):
                out, _ = model.apply(p, state, jax.random.PRNGKey(0), xs)
                return dp.scale_loss(jnp.sum(out ** 2))
            g = jax.grad(loss_fn)(p)
            return dp.apply_collective_grads(g)

        pspecs = jax.tree.map(lambda _: P(), params)
        g_dp = jax.jit(lambda p, xs: shard_map(
            local, mesh=mesh, in_specs=(pspecs, P(DATA_AXIS)),
            out_specs=pspecs, check_vma=False)(p, xs))(params, x)

        def global_loss(p):
            out, _ = model.apply(p, state, jax.random.PRNGKey(0), x)
            return jnp.sum(out ** 2) / 8.0
        g_ref = jax.grad(global_loss)(params)
        for a, b in zip(jax.tree.leaves(g_dp), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_single_rank_identity(self):
        from paddle_tpu.parallel import DataParallel, ParallelStrategy
        from paddle_tpu import nn
        dp = DataParallel(nn.Linear(2, 2), ParallelStrategy(nranks=1))
        assert float(dp.scale_loss(jnp.asarray(3.0))) == 3.0
        g = {"w": jnp.ones((2,))}
        assert dp.apply_collective_grads(g) is g
