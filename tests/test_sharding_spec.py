"""Unified mesh partitioner (parallel/spec.py + executor integration):
one ShardingSpec from program-level annotations down to pjit
in/out shardings and with_sharding_constraint on the compiled device
segments — plus the sharded-leaf
residency fast path, comm-bytes cost analytics, and checkpoint
save(axes=) derivation. Runs on the 8-device virtual CPU mesh."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.framework import unique_name
from paddle_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, PIPE_AXIS, MeshConfig, make_mesh,
)
from paddle_tpu.parallel.spec import ShardingSpec
from paddle_tpu.static.executor import Scope, scope_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(data=4, model=2, **kw):
    return make_mesh(MeshConfig(data=data, model=model, **kw))


# ---------------------------------------------------------------------------
# spec lookup / validation
# ---------------------------------------------------------------------------
class TestSpecLookup:
    def test_exact_then_rule_then_replicated(self):
        spec = ShardingSpec(_mesh(),
                            params={"w0": P(None, MODEL_AXIS)},
                            rules=[("w*", P(MODEL_AXIS, None))])
        assert spec.param_spec("w0") == P(None, MODEL_AXIS)   # exact wins
        assert spec.param_spec("w7") == P(MODEL_AXIS, None)   # rule
        assert spec.param_spec("bias") == P()                 # default

    def test_rule_order_first_match_wins(self):
        spec = ShardingSpec(_mesh(), rules=[
            ("blocks/wo", P(None, MODEL_AXIS)),
            ("blocks/*", P(MODEL_AXIS)),
        ])
        assert spec.param_spec("blocks/wo") == P(None, MODEL_AXIS)
        assert spec.param_spec("blocks/w1") == P(MODEL_AXIS)

    def test_feed_defaults_batch_dim_over_data(self):
        spec = ShardingSpec(_mesh())
        assert spec.feed_spec("x", 2) == P(DATA_AXIS)
        assert spec.feed_spec("scalar", 0) == P()   # scalars replicated

    def test_feed_default_hierarchical_on_hybrid_mesh(self):
        mesh = make_mesh(MeshConfig(data=2, model=2, dcn_data=2))
        spec = ShardingSpec(mesh)
        assert spec.feed_batch_axes == ("dcn_data", "data")
        assert spec.feed_spec("x", 2) == P(("dcn_data", "data"))

    def test_unknown_axis_rejected(self):
        with pytest.raises(EnforceNotMet, match="mesh axis 'nope'"):
            ShardingSpec(_mesh(), params={"w": P("nope")})

    def test_axis_reuse_rejected(self):
        with pytest.raises(EnforceNotMet, match="more than one dim"):
            ShardingSpec(_mesh(),
                         params={"w": P(MODEL_AXIS, MODEL_AXIS)})

    def test_divisibility_validated_with_param_named(self):
        spec = ShardingSpec(_mesh(), params={"w": P(None, MODEL_AXIS)})
        spec.validate_leaf("w", (3, 4))          # 4 % 2 ok
        with pytest.raises(EnforceNotMet, match="'w'.*not divisible"):
            spec.validate_leaf("w", (4, 3))      # 3 % 2 bad

    def test_feed_divisibility_checks_data_extent_not_mesh_size(self):
        """model x data: the batch divides the DATA axes (4), not the
        whole 8-device mesh — the pre-spec executor required % 8."""
        spec = ShardingSpec(_mesh(data=4, model=2))
        out = spec.shard_feeds({"x": np.zeros((4, 3), np.float32)})
        assert out["x"].sharding.spec == P(DATA_AXIS)
        with pytest.raises(EnforceNotMet, match="not divisible"):
            spec.shard_feeds({"x": np.zeros((6, 3), np.float32)})

    def test_tree_specs_by_path(self):
        spec = ShardingSpec(_mesh(), rules=[("stages/*", P(MODEL_AXIS))])
        tree = {"stages": {"w": np.zeros((4, 2)), "b": np.zeros((4,))},
                "head": {"w": np.zeros((2, 2))}}
        specs = spec.tree_specs(tree)
        assert specs["stages"]["w"] == P(MODEL_AXIS)
        assert specs["stages"]["b"] == P(MODEL_AXIS)
        assert specs["head"]["w"] == P()

    def test_constraint_for_covers_grads(self):
        spec = ShardingSpec(_mesh(), params={"w0": P(None, MODEL_AXIS)})
        t = spec.constraint_for("w0@GRAD")
        assert t is not None and t.spec == P(None, MODEL_AXIS)
        assert spec.constraint_for("unspecced") is None
        assert spec.constraint_for("unspecced@GRAD") is None


# ---------------------------------------------------------------------------
# executor end to end: program -> spec -> pjit
# ---------------------------------------------------------------------------
def _build_mlp():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), unique_name.guard():
        x = pt.static.data("x", shape=[16])
        y = pt.static.data("y", shape=[1])
        h = pt.layers.fc(x, size=32, param_attr="w0", bias_attr="b0",
                         act="relu")
        pred = pt.layers.fc(h, size=1, param_attr="w1", bias_attr="b1")
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.Momentum(0.05, momentum=0.9).minimize(loss)
    return main, startup, loss


def _batch(B=8, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(B, 16).astype(np.float32),
            rs.randn(B, 1).astype(np.float32))


class TestExecutorSpec:
    def test_mesh_sharding_trains_and_state_stays_sharded(self):
        pt.enable_static()
        try:
            main, startup, loss = _build_mlp()
            mesh = _mesh(data=4, model=2)
            spec = ShardingSpec(mesh, params={"w0": P(None, MODEL_AXIS),
                                              "b0": P(MODEL_AXIS)})
            compiled = pt.CompiledProgram(main).with_mesh_sharding(
                spec, loss_name=loss.name)
            scope = Scope()
            xb, yb = _batch()
            with scope_guard(scope):
                exe = pt.static.Executor()
                exe.run(startup)
                losses = []
                for _ in range(25):
                    (lv,) = exe.run(compiled,
                                    feed={"x": xb, "y": yb},
                                    fetch_list=[loss])
                    losses.append(float(lv))
                assert losses[-1] < losses[0] * 0.5, losses[::6]
                w0 = scope.find_var("w0")
                assert w0.sharding.spec == P(None, MODEL_AXIS)
                # really tiled: each device holds 1/2 of the model dim
                assert w0.addressable_shards[0].data.shape == (16, 16)
        finally:
            pt.disable_static()

    def test_spec_run_matches_plain_run(self):
        """The partitioned program is the SAME math: per-step losses
        match the unsharded single-program run to float tolerance."""
        pt.enable_static()
        try:
            xb, yb = _batch()

            def run(compiled_fn):
                main, startup, loss = _build_mlp()
                prog = compiled_fn(main, loss)
                scope = Scope()
                with scope_guard(scope):
                    exe = pt.static.Executor()
                    exe.run(startup)
                    return [float(exe.run(prog,
                                          feed={"x": xb, "y": yb},
                                          fetch_list=[loss])[0])
                            for _ in range(10)]

            plain = run(lambda m, l: m)
            mesh = _mesh(data=4, model=2)
            spec = ShardingSpec(mesh,
                                params={"w0": P(None, MODEL_AXIS),
                                        "b0": P(MODEL_AXIS),
                                        "w1": P(MODEL_AXIS, None)})
            sharded = run(lambda m, l: pt.CompiledProgram(m)
                          .with_mesh_sharding(spec, loss_name=l.name))
            np.testing.assert_allclose(plain, sharded, rtol=2e-4,
                                       atol=1e-6)
        finally:
            pt.disable_static()

    def test_1x1_mesh_lowering_parity(self):
        """spec -> pjit on a 1x1 mesh: annotations lower to constraints
        that are placement no-ops, bit-comparable to the plain run."""
        pt.enable_static()
        try:
            xb, yb = _batch()
            main, startup, loss = _build_mlp()
            scope = Scope()
            with scope_guard(scope):
                exe = pt.static.Executor()
                exe.run(startup)
                plain = [float(exe.run(main, feed={"x": xb, "y": yb},
                                       fetch_list=[loss])[0])
                         for _ in range(5)]
            mesh = make_mesh(MeshConfig(data=1, model=1),
                             devices=jax.devices()[:1])
            spec = ShardingSpec(mesh, params={"w0": P(None, MODEL_AXIS)})
            compiled = pt.CompiledProgram(main).with_mesh_sharding(
                spec, loss_name=loss.name)
            scope2 = Scope()
            with scope_guard(scope2):
                exe2 = pt.static.Executor()
                exe2.run(startup)
                spec_run = [float(exe2.run(compiled,
                                           feed={"x": xb, "y": yb},
                                           fetch_list=[loss])[0])
                            for _ in range(5)]
            np.testing.assert_allclose(plain, spec_run, rtol=1e-6)
        finally:
            pt.disable_static()

    def test_model_x_data_feed_divisibility(self):
        """A batch of 4 on a data=4 x model=2 mesh is legal (divides
        the data axes) — the pre-spec path demanded mesh.size (8)."""
        pt.enable_static()
        try:
            main, startup, loss = _build_mlp()
            spec = ShardingSpec(_mesh(data=4, model=2))
            compiled = pt.CompiledProgram(main).with_mesh_sharding(
                spec, loss_name=loss.name)
            xb, yb = _batch(B=4)
            scope = Scope()
            with scope_guard(scope):
                exe = pt.static.Executor()
                exe.run(startup)
                (lv,) = exe.run(compiled, feed={"x": xb, "y": yb},
                                fetch_list=[loss])
                assert np.isfinite(float(lv))
        finally:
            pt.disable_static()

    def test_prepare_aot_records_comm_bytes(self):
        """Executor.prepare on a multi-device spec'd program records
        segment_comm_bytes (gradient all-reduce exists only post-SPMD,
        in the compiled executable)."""
        from paddle_tpu.monitor import cost
        pt.enable_static()
        try:
            cost.reset()
            main, startup, loss = _build_mlp()
            spec = ShardingSpec(_mesh(data=8, model=1))
            compiled = pt.CompiledProgram(main).with_mesh_sharding(
                spec, loss_name=loss.name)
            scope = Scope()
            with scope_guard(scope):
                exe = pt.static.Executor()
                exe.run(startup)
                ok = exe.prepare(
                    compiled,
                    feed={"x": ((8, 16), np.float32),
                          "y": ((8, 1), np.float32)},
                    fetch_list=[loss])
                assert ok
            assert cost.comm_bytes_per_step() > 0
            segs = cost.segments()
            assert any("collectives" in a for a in segs.values())
        finally:
            pt.disable_static()
            cost.reset()


class TestShardedResidency:
    """Satellite: the PR 2 device-resident fast path must extend to
    SHARDED leaves — a leaf already carrying its spec's NamedSharding
    passes through without a per-step re-put."""

    def test_sharded_state_not_reput_once_resident(self):
        pt.enable_static()
        try:
            main, startup, loss = _build_mlp()
            mesh = _mesh(data=4, model=2)
            spec = ShardingSpec(mesh, params={"w0": P(None, MODEL_AXIS),
                                              "b0": P(MODEL_AXIS)})
            compiled = pt.CompiledProgram(main).with_mesh_sharding(
                spec, loss_name=loss.name)
            scope = Scope()
            xb, yb = _batch()
            with scope_guard(scope):
                exe = pt.static.Executor()
                exe.run(startup)
                for _ in range(3):      # settle into steady state
                    exe.run(compiled, feed={"x": xb, "y": yb},
                            fetch_list=[loss])
                calls = {"n": 0}
                orig = jax.device_put

                def counting(x, *a, **kw):
                    calls["n"] += 1
                    return orig(x, *a, **kw)

                def count_one_step():
                    calls["n"] = 0
                    jax.device_put = counting
                    try:
                        exe.run(compiled, feed={"x": xb, "y": yb},
                                fetch_list=[loss])
                    finally:
                        jax.device_put = orig
                    return calls["n"]

                fast = count_one_step()
                pt.set_flags({"executor_fast_path": False})
                try:
                    exe.run(compiled, feed={"x": xb, "y": yb},
                            fetch_list=[loss])   # legacy warm step
                    legacy = count_one_step()
                finally:
                    pt.set_flags({"executor_fast_path": True})
            # steady state pays feed traffic only (2 feeds x asarray +
            # sharded placement = 4 puts); every sharded AND replicated
            # state leaf passes through. Legacy re-puts all 9 state
            # leaves (4 params + 5 optimizer slots) on top every step.
            assert fast <= 4, fast
            assert legacy >= fast + 9, (fast, legacy)
        finally:
            pt.disable_static()


# ---------------------------------------------------------------------------
# comm-bytes estimator units
# ---------------------------------------------------------------------------
class TestEstimateComm:
    def test_counts_result_buffer_bytes(self):
        from paddle_tpu.monitor import cost
        txt = """
  %ar = f32[128]{0} all-reduce(f32[128]{0} %a), replica_groups={}
  %ag = bf16[4,8]{1,0} all-gather(bf16[2,8]{1,0} %b), dimensions={0}
  %cp = f32[16]{0} collective-permute(f32[16]{0} %c)
"""
        got = cost.estimate_comm(txt)
        assert got["collectives"] == {"all-reduce": 1, "all-gather": 1,
                                      "collective-permute": 1}
        assert got["comm_bytes"] == 128 * 4 + 4 * 8 * 2 + 16 * 4

    def test_async_pairs_count_done_result_not_start_tuple(self):
        """A -start op's result tuple bundles operands + results (+
        context on TPU), so counting it would tally ~2x; the -done
        result is exactly the collective result on every backend."""
        from paddle_tpu.monitor import cost
        txt = """
  %s = (f32[64]{0}, f32[64]{0}, u32[], u32[]) all-reduce-start(f32[64]{0} %a)
  %d = f32[64]{0} all-reduce-done((f32[64]{0}) %s)
  %gs = (f32[32]{0}, f32[256]{0}) all-gather-start(f32[32]{0} %b)
  %gd = f32[256]{0} all-gather-done((f32[32]{0}) %gs)
"""
        got = cost.estimate_comm(txt)
        assert got["collectives"] == {"all-reduce": 1, "all-gather": 1}
        assert got["comm_bytes"] == 64 * 4 + 256 * 4

    def test_no_text_yields_none_not_zero(self):
        """A backend without HLO text must report "unknown", never a
        confident 0 bytes."""
        from paddle_tpu.monitor import cost
        assert cost.estimate_comm(None) is None
        assert cost.estimate_comm("") is None
        # a real module with NO collectives is a true zero
        assert cost.estimate_comm("%x = f32[4]{0} add(...)") == \
            {"comm_bytes": 0.0, "collectives": {}}


# ---------------------------------------------------------------------------
# checkpoint interop: spec -> save(axes=) (satellite)
# ---------------------------------------------------------------------------
class TestCheckpointAxes:
    def test_single_axis_derivation(self):
        spec = ShardingSpec(_mesh(data=4, model=2),
                            params={"w0": P(None, MODEL_AXIS),
                                    "emb": P(DATA_AXIS, None)})
        axes = spec.checkpoint_axes({"w0": np.zeros((4, 4)),
                                     "emb": np.zeros((8, 2)),
                                     "b": np.zeros((3,))})
        assert axes == {"w0": 1, "emb": 0, "b": None}

    def test_extent_one_axis_is_replicated(self):
        spec = ShardingSpec(_mesh(data=8, model=1),
                            params={"w": P(None, MODEL_AXIS)})
        assert spec.checkpoint_axes({"w": np.zeros((2, 2))}) == \
            {"w": None}

    def test_two_sharded_dims_refused(self):
        from paddle_tpu.io_checkpoint import CheckpointTopologyError
        spec = ShardingSpec(_mesh(data=4, model=2),
                            params={"m": P(DATA_AXIS, MODEL_AXIS)})
        with pytest.raises(CheckpointTopologyError,
                           match="'m'.*2 dimensions"):
            spec.checkpoint_axes({"m": np.zeros((4, 4))})

    def test_axis_tuple_tiling_refused(self):
        from paddle_tpu.io_checkpoint import CheckpointTopologyError
        mesh = make_mesh(MeshConfig(data=2, model=2, dcn_data=2))
        spec = ShardingSpec(mesh,
                            params={"w": P(("dcn_data", DATA_AXIS))})
        with pytest.raises(CheckpointTopologyError, match="axis tuple"):
            spec.checkpoint_axes({"w": np.zeros((8, 2))})

    def test_pipeline_module_spec_annotates_stages(self):
        from paddle_tpu.parallel import pipeline as pl
        mesh = make_mesh(MeshConfig(data=2, pipe=4, model=1, seq=1,
                                    axis_order=("data", "pipe",
                                                "model", "seq")))
        mod = pl.PipelineModule(mesh, lambda e, x: x, lambda s, x: x,
                                lambda h, a, y: 0.0, n_micro=2)
        tree = {"embed": {"w": np.zeros((4, 8))},
                "stages": {"w": np.zeros((4, 8, 8)),
                           "b": np.zeros((4, 8))},
                "head": {"w": np.zeros((8, 1))}}
        axes = mod.sharding_spec().checkpoint_axes(tree)
        assert axes["stages"]["w"] == 0 and axes["stages"]["b"] == 0
        assert axes["embed"]["w"] is None and axes["head"]["w"] is None

    def test_axes_round_trip_through_checkpoint_manager(self, tmp_path):
        """The derived annotations are exactly what save(axes=) wants:
        a sharded-annotated save restores and records array_info."""
        from paddle_tpu.io_checkpoint import CheckpointManager
        spec = ShardingSpec(_mesh(data=4, model=2),
                            params={"w0": P(None, MODEL_AXIS)})
        tree = {"w0": np.arange(16, dtype=np.float32).reshape(4, 4),
                "b0": np.ones((3,), np.float32)}
        axes = spec.checkpoint_axes(tree)
        mgr = CheckpointManager(str(tmp_path), async_save=False,
                                save_interval_steps=1, keep_max=2)
        mgr.save(0, tree, axes=axes)
        got, step = mgr.restore()
        assert step == 0
        mgr.close()
        np.testing.assert_array_equal(got["w0"], tree["w0"])
        np.testing.assert_array_equal(got["b0"], tree["b0"])


# ---------------------------------------------------------------------------
# the other parallel idioms consume the SAME spec
# ---------------------------------------------------------------------------
class TestSpecUnification:
    def test_from_tree_round_trips_transformer_specs(self):
        """models.transformer.param_specs — the megatron tree — loads
        into a ShardingSpec and round-trips through tree_specs, so
        checkpoint_axes works on the real model layout."""
        from paddle_tpu.models import transformer as T
        cfg = T.transformer_tiny()
        mesh = _mesh(data=4, model=2)
        tree = T.param_specs(cfg)
        spec = ShardingSpec.from_tree(mesh, tree)
        got = spec.tree_specs(tree)     # congruent tree of specs
        for a, b in zip(jax.tree.leaves(tree,
                                        is_leaf=lambda s:
                                        isinstance(s, P)),
                        jax.tree.leaves(got,
                                        is_leaf=lambda s:
                                        isinstance(s, P))):
            assert a == b
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        axes = spec.checkpoint_axes(params)
        # every megatron entry is single-named-axis: derivable
        flat_axes = jax.tree.leaves(
            jax.tree.map(lambda a: -1 if a is None else a, axes))
        assert any(a >= 0 for a in flat_axes)

    def test_data_parallel_trainer_accepts_spec(self):
        from paddle_tpu.parallel.data_parallel import DataParallelTrainer
        mesh = make_mesh(MeshConfig(data=8))
        D = 16
        spec = ShardingSpec(mesh, rules=[("w*", P(DATA_AXIS))])

        def loss_fn(p, state, rng, batch):
            out = jnp.tanh(batch["x"] @ p["w1"]) @ p["w2"]
            return jnp.mean((out - batch["y"]) ** 2), state

        def init(rng, batch):
            k1, k2 = jax.random.split(rng)
            return {"w1": jax.random.normal(k1, (D, D)) * 0.3,
                    "w2": jax.random.normal(k2, (D, D)) * 0.3}, {}

        tr = DataParallelTrainer(loss_fn, pt.optimizer.Adam(1e-3),
                                 mesh=mesh, param_sharding=spec)
        batch = {"x": jnp.ones((16, D)), "y": jnp.ones((16, D))}
        p, o, s = tr.init(init, jax.random.PRNGKey(0), batch)
        # ZeRO-style layout from the spec: each device holds 1/8
        assert p["w1"].addressable_shards[0].data.size == \
            p["w1"].size // 8
        l, p, o, s = tr.step(p, o, s, jax.random.PRNGKey(1), batch)
        assert np.isfinite(float(l))

    def test_data_parallel_trainer_rejects_off_axis_spec(self):
        from paddle_tpu.parallel.data_parallel import DataParallelTrainer
        mesh = _mesh(data=4, model=2)
        spec = ShardingSpec(mesh, rules=[("w*", P(MODEL_AXIS))])

        def loss_fn(p, state, rng, batch):
            return jnp.mean(p["w1"] ** 2), state

        tr = DataParallelTrainer(loss_fn, pt.optimizer.Adam(1e-3),
                                 mesh=mesh, param_sharding=spec)
        with pytest.raises(EnforceNotMet, match="model-axis placement"):
            tr.prepare_sharding({"w1": jnp.ones((8, 8))})

    def test_moe_sharding_spec_derives_checkpoint_axes(self):
        from paddle_tpu.parallel import moe
        mesh = make_mesh(MeshConfig(data=4, expert=2))
        spec = moe.moe_sharding_spec(mesh)
        cfg = moe.MoEConfig(d_model=4, d_hidden=8, num_experts=4,
                            top_k=2)
        params = moe.init_moe_params(jax.random.PRNGKey(0), cfg)
        axes = spec.checkpoint_axes(params)
        assert axes["w1"] == 0 and axes["w2"] == 0
        assert axes["gate_w"] is None


# ---------------------------------------------------------------------------
# slow MULTICHIP e2e: bench.py shard per topology at n_devices=8
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.timeout(560)
@pytest.mark.parametrize("topo,min_comm",
                         [("dp", 1), ("modelxdata", 1),
                          ("pipexdata", 1)])
def test_multichip_shard_topology(topo, min_comm):
    """`bench.py shard` on the 8-device harness emits the per-topology
    JSON line with MFU, ms/step, and nonzero collective bytes (proof
    the step actually partitioned — an unpartitioned program has no
    collectives)."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "BENCH_WINDOWS": "2",
        "BENCH_SHARD_STEPS": "2",
        "BENCH_SHARD_PAIRS": "2",
        "BENCH_SHARD_LAYERS": "4",
        "BENCH_SHARD_HIDDEN": "32",
        "BENCH_SHARD_FFN": "64",
        "BENCH_SHARD_SEQ": "16",
        "BENCH_SHARD_VOCAB": "64",
        "BENCH_SHARD_HEADS": "2",
        "BENCH_SHARD_TOPOS": topo,
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        "shard"], capture_output=True, text=True,
                       timeout=540, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    by = {ln["metric"]: ln for ln in lines}
    row = by[f"shard_{topo}_step_ms"]
    assert row["value"] > 0 and row["unit"] == "ms"
    # the CPU has no peak on record (monitor/cost.PEAK_FLOPS): no MFU
    assert row["mfu"] is None
    assert row["comm_bytes_per_step"] >= min_comm, row
    assert row["layout"]["n_devices"] == 8
    assert len(row["windows_ms_per_step"]) >= 2
    if topo == "pipexdata":
        ov = by["shard_overlap_step_ratio"]
        assert ov["value"] > 0 and len(ov["pair_ratios"]) == 2
        assert ov["overlap_on_comm_bytes"] > 0
