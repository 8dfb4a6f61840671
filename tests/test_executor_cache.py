"""Executor dispatch fast path + persistent compilation cache.

Covers the ISSUE 2 tentpole contracts:

- no retrace across steps with a same-signature feed; a retrace on
  shape change (via the executor's own trace counter — Python inside
  the jitted segment runs at trace time only);
- the prepared-runner memoization (state scans happen once, not per
  step) and DP state residency (no re-device_put once placed);
- return_numpy=False returns non-blocking jax arrays;
- AOT warm-start (`Executor.prepare`) + the on-disk compilation cache:
  a second executor — and, in the slow e2e, a second PROCESS via
  kill → relaunch (testing/faults.py) — compiles from disk (cache hit
  counter > 0, no extra trace).
"""

import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "warm_restart_worker.py")


def _build(seed=0):
    main, startup = pt.Program(), pt.Program()
    with pt.static.program_guard(main, startup):
        x = pt.static.data("x", shape=[13])
        y = pt.static.data("y", shape=[1])
        pred = pt.layers.fc(x, size=1, param_attr="w", bias_attr="b")
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.SGDOptimizer(0.05).minimize(loss)
    return main, startup, loss


@pytest.fixture
def data():
    rs = np.random.RandomState(0)
    xb = rs.randn(32, 13).astype(np.float32)
    return xb, (xb[:, :1] * 0.7).astype(np.float32)


@pytest.fixture
def static_mode():
    pt.enable_static()
    yield
    pt.disable_static()


class TestNoRetrace:
    def test_same_signature_never_retraces(self, static_mode, data,
                                           fresh_programs):
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
        t0 = exe.trace_count
        assert t0 == 1
        for _ in range(5):
            exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
        assert exe.trace_count == t0

    def test_shape_change_retraces_once(self, static_mode, data,
                                        fresh_programs):
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
        t0 = exe.trace_count
        exe.run(main, feed={"x": xb[:16], "y": yb[:16]},
                fetch_list=[loss])
        assert exe.trace_count == t0 + 1
        # both signatures now cached: alternating stays trace-free
        exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
        exe.run(main, feed={"x": xb[:16], "y": yb[:16]},
                fetch_list=[loss])
        assert exe.trace_count == t0 + 1

    def test_state_scans_run_once_not_per_step(self, static_mode, data,
                                               fresh_programs,
                                               monkeypatch):
        """The prepared runner memoizes the program/state rescans the
        legacy path redid every call (the dispatch hot-path claim)."""
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        calls = {"n": 0}
        orig = pt.static.Executor._state_names

        def counting(self, program, scope):
            calls["n"] += 1
            return orig(self, program, scope)

        monkeypatch.setattr(pt.static.Executor, "_state_names", counting)
        for _ in range(6):
            exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
        # exactly one prepare on first sight (the step counter is
        # pre-created so it cannot invalidate the runner): never
        # per-step
        assert calls["n"] == 1, calls["n"]

    def test_legacy_flag_restores_per_step_scans(self, static_mode,
                                                 data, fresh_programs,
                                                 monkeypatch):
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        pt.set_flags({"executor_fast_path": False})
        try:
            calls = {"n": 0}
            orig = pt.static.Executor._state_names

            def counting(self, program, scope):
                calls["n"] += 1
                return orig(self, program, scope)

            monkeypatch.setattr(pt.static.Executor, "_state_names",
                                counting)
            for _ in range(4):
                exe.run(main, feed={"x": xb, "y": yb},
                        fetch_list=[loss])
            assert calls["n"] == 4          # the old cost, per step
        finally:
            pt.set_flags({"executor_fast_path": True})

    def test_fast_and_legacy_paths_agree(self, static_mode, data,
                                         fresh_programs):
        """Same losses step for step with the fast path on and off —
        the optimization must not change the math."""
        xb, yb = data

        def run_mode(fast):
            from paddle_tpu.static.executor import Scope, scope_guard
            pt.set_flags({"executor_fast_path": fast})
            try:
                with scope_guard(Scope()):
                    main, startup, loss = _build()
                    exe = pt.static.Executor()
                    exe.run(startup)
                    return [float(exe.run(main,
                                          feed={"x": xb, "y": yb},
                                          fetch_list=[loss])[0])
                            for _ in range(6)]
            finally:
                pt.set_flags({"executor_fast_path": True})

        np.testing.assert_allclose(run_mode(True), run_mode(False),
                                   rtol=1e-6)


class TestAsyncFetch:
    def test_return_numpy_false_returns_device_arrays(
            self, static_mode, data, fresh_programs):
        import jax
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                        fetch_list=[loss], return_numpy=False)
        assert isinstance(lv, jax.Array)
        assert np.isfinite(float(np.asarray(lv)))

    def test_async_fetch_of_donated_state_survives_next_step(
            self, static_mode, data, fresh_programs):
        """Fetching a var that is ALSO donated state (a parameter):
        async callers must get a copy, or the next step's donation
        deletes the buffer under them."""
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        fetched = []
        for _ in range(3):
            lv, w = exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss, "w"],
                            return_numpy=False)
            fetched.append(w)
        # every historical fetch is still materializable — including
        # ones whose source buffer later steps donated
        mats = [np.asarray(w) for w in fetched]
        assert all(np.isfinite(m).all() for m in mats)
        # and they differ step to step (training moved the param)
        assert not np.allclose(mats[0], mats[-1])

    def test_train_from_dataset_prints_only_at_period(
            self, static_mode, data, fresh_programs, capsys):
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        batches = [{"x": xb, "y": yb}] * 7
        out = exe.train_from_dataset(main, dataset=iter(batches),
                                     fetch_list=[loss],
                                     print_period=3)
        printed = capsys.readouterr().out
        assert "step 3:" in printed and "step 6:" in printed
        assert "step 7:" not in printed and "step 1:" not in printed
        # the return stays materialized numpy (parity contract)
        assert isinstance(out[0], np.ndarray)


class TestDPResidency:
    def test_state_not_reput_once_resident(self, static_mode, data,
                                           fresh_programs):
        """After the first DP step the persistable state is already
        replicated on the mesh; steady-state steps must not re-
        device_put it (the legacy path paid one eager transfer per
        parameter per step)."""
        import jax
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        compiled = pt.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        for _ in range(3):          # prepare + settle into steady state
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
                    fetch_list=[loss])     # legacy-mode warm step
            legacy = count_one_step()
        finally:
            pt.set_flags({"executor_fast_path": True})
        # steady state transfers the per-step feeds only (2 H2D
        # stagings + 2 mesh placements for x, y); legacy re-put the
        # state (w, b, optimizer counter) on top, every step
        assert fast <= 4, (fast, legacy)
        assert legacy >= fast + 3, (fast, legacy)

    def test_dp_losses_unchanged_by_residency(self, static_mode, data,
                                              fresh_programs):
        from paddle_tpu.static.executor import Scope, scope_guard
        xb, yb = data

        def run_mode(fast):
            pt.set_flags({"executor_fast_path": fast})
            try:
                with scope_guard(Scope()):
                    main, startup, loss = _build()
                    exe = pt.static.Executor()
                    exe.run(startup)
                    compiled = pt.CompiledProgram(main) \
                        .with_data_parallel(loss_name=loss.name)
                    return [float(exe.run(compiled,
                                          feed={"x": xb, "y": yb},
                                          fetch_list=[loss])[0])
                            for _ in range(5)]
            finally:
                pt.set_flags({"executor_fast_path": True})

        np.testing.assert_allclose(run_mode(True), run_mode(False),
                                   rtol=1e-6)


class TestPersistentCache:
    def test_aot_prepare_then_run_hits_disk_cache(
            self, static_mode, data, fresh_programs, tmp_path,
            monkeypatch):
        """prepare() lowers+compiles eagerly, writing the cache entry
        (the same executor's first real step then reuses that
        executable in memory, with no compile request at all); a fresh
        executor (fresh jit objects, same program, same call path)
        compiles purely from disk what another fresh executor wrote —
        the in-process proof of the warm-restart path."""
        from paddle_tpu.core import compile_cache
        xb, yb = data
        # a cold cache of the test's own: stand in for the fixed
        # in-checkout path (JAX_COMPILATION_CACHE_DIR is read by jax at
        # import, too early for a test to place the cache with it)
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                            str(tmp_path / "cache"))
        assert compile_cache.enable() == str(tmp_path / "cache")
        compile_cache.reset_stats()
        try:
            main, startup, loss = _build()
            exe = pt.static.Executor()
            exe.run(startup)
            full = exe.prepare(main, feed={"x": xb, "y": yb},
                               fetch_list=[loss])
            assert full                     # single device segment
            assert compile_cache.stats()["misses"] > 0
            exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
            # a fresh executor = fresh jit functions = the restarted-
            # process shape, minus the process boundary. Both take the
            # same call path, as a restarted process does: the traced
            # operations' names and source locations are part of the key
            # (compile_cache.enable()), so the program that prepare()
            # traced above, from another call site, is another entry.
            hits = []
            for _ in range(2):
                pt.static.Executor().run(main, feed={"x": xb, "y": yb},
                                         fetch_list=[loss])
                hits.append(compile_cache.stats()["hits"])
            assert hits[1] > hits[0]
        finally:
            compile_cache.disable()

    def test_prepare_with_shape_specs_only(self, static_mode, data,
                                           fresh_programs):
        """prepare() accepts (shape, dtype) pairs — no sample batch
        needed, the AOT entry point for serving warm-up."""
        xb, yb = data
        main, startup, loss = _build()
        exe = pt.static.Executor()
        exe.run(startup)
        assert exe.prepare(main,
                           feed={"x": ((32, 13), np.float32),
                                 "y": ((32, 1), np.float32)},
                           fetch_list=[loss])
        t0 = exe.trace_count
        assert t0 == 1                      # the AOT lowering traced
        (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                        fetch_list=[loss])
        assert np.isfinite(float(lv))
        # prepare's .lower() primed the jit tracing cache too: the
        # first real step neither retraces nor re-lowers
        assert exe.trace_count == t0

    def test_profiler_surfaces_counters(self, tmp_path, monkeypatch):
        from paddle_tpu import profiler
        from paddle_tpu.core import compile_cache
        s = profiler.compilation_cache_stats()
        assert set(s) >= {"hits", "misses", "requests"}
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                            str(tmp_path / "c"))
        compile_cache.enable()
        try:
            assert "compilation cache:" in profiler.summary()
        finally:
            compile_cache.disable()


@pytest.mark.slow
@pytest.mark.timeout(420)     # launch timeout=240 + startup/teardown —
                              # above the conftest guard's 300s default
class TestWarmRestartEndToEnd:
    def test_kill_relaunch_reuses_disk_cache(self, tmp_path):
        """kill → relaunch under the elastic launcher: the restarted
        incarnation's compiles come off the on-disk cache (hit counter
        > 0) with no extra executor trace — the ISSUE 2 acceptance
        shape, fault injection via testing/faults.py."""
        from paddle_tpu.distributed.launch import launch_collective
        out = tmp_path / "wr"
        log_dir = tmp_path / "logs"
        env_extra = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", ""),
            "PT_FAULT_CRASH_AT_STEP": "2",
            "PT_FAULT_ONCE_DIR": str(tmp_path / "once"),
            # the cache is placed from outside, by jax's own variable
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
        }
        rc = launch_collective(
            [WORKER, str(out), "4"], nproc=1, log_dir=str(log_dir),
            env_extra=env_extra, timeout=240, max_restarts=1)
        if rc != 0:
            logs = ""
            for p in sorted(log_dir.glob("*.log")):
                logs += f"\n--- {p.name} ---\n" + p.read_text()[-2000:]
            pytest.fail(f"launch rc={rc}{logs}")
        cold = json.loads((tmp_path / "wr.inc0.json").read_text())
        warm = json.loads((tmp_path / "wr.inc1.json").read_text())
        # both incarnations kept their cache where the variable said,
        # and nowhere else
        assert cold["cache_dir"] == str(tmp_path / "cache")
        assert warm["cache_dir"] == cold["cache_dir"]
        assert os.listdir(tmp_path / "cache")
        assert not (log_dir / "xla_cache").exists()
        # cold start compiled for real; warm restart compiled from disk
        assert cold["misses"] > 0
        assert warm["hits"] > 0
        # no extra trace in the restarted process: same trace count as
        # the cold incarnation (tracing is per-process, compiling was
        # the part the cache removed)
        assert warm["trace_count"] == cold["trace_count"]
        # and it actually trained through the restart
        assert warm["losses"][-1] < warm["losses"][0]
