"""Hot model swap tests (paddle_tpu/serving/swap.py, docs/SERVING.md
"Hot model swap").

The state machine is pinned stage by stage on tiny frozen models whose
OUTPUT IS THEIR VERSION (``out = scale * x`` — a request's answer says
exactly which version served it, so cutover atomicity and rollback are
assertable from results alone): gate refusals (integrity, spec drift,
re-gate after an in-place rewrite), standby quarantine (failure and
wedge), canary verdicts (non-finiteness, parity bounds, caller hook),
batch-boundary cutover under concurrent submitters, watchdog-driven
rollback via the chaos error storm, the watch-dir continuous-deploy
loop, and the pool role machinery that lets two pools coexist without
gauge fights. The slow e2e (tests/swap_worker.py) runs the whole story
under open-loop load with per-request accounting and .prom evidence.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.monitor.registry import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "swap_worker.py")


def _counter(name, **labels):
    m = REGISTRY.get(name)
    return m.value(**labels) if m else 0.0


def _freeze_scale(dirname, scale, aot=False, width=16, layers_extra=0):
    """out = scale * x: the answer IS the version. ``layers_extra``
    grows the graph so fetch names drift (a gate-incompatibility
    probe); ``width`` changes the feed spec."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import unique_name

    pt.enable_static()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), unique_name.guard():
        x = pt.static.data("x", [width], dtype="float32")
        out = layers.scale(x, scale=float(scale))
        for _ in range(layers_extra):
            out = layers.scale(out, scale=1.0)
    scope = pt.static.Scope()
    with pt.static.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        pt.io.save_inference_model(
            dirname, ["x"], [out], exe, main_program=main,
            aot_shapes=([{"x": ((2, width), "float32")}] if aot
                        else None))
    return dirname


def _server(model_dir, **cfg):
    from paddle_tpu.serving import InferenceServer, ServingConfig
    cfg.setdefault("max_batch", 4)
    cfg.setdefault("max_wait_ms", 1.0)
    return InferenceServer(model_dir, ServingConfig(**cfg))


def _ones(rows=1, width=16):
    return {"x": np.ones((rows, width), np.float32)}


def _bitflip_first_artifact(model_dir):
    from paddle_tpu.inference import AOT_DIR, AOT_INDEX
    idx = json.load(open(os.path.join(model_dir, AOT_DIR, AOT_INDEX)))
    entry = next(e for e in idx if isinstance(e, dict) and "xla" in e)
    path = os.path.join(model_dir, AOT_DIR, entry["xla"])
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(blob))
    return os.path.basename(path)


class TestModelVersion:
    """Satellite: export_aot stamps a model_version (content hash +
    timestamp) into the integrity manifest; verify_aot_dir returns it;
    read_aot_version is the cheap index-only probe."""

    def test_export_stamps_version_and_verify_returns_it(self, tmp_path):
        from paddle_tpu.inference import (read_aot_version,
                                          verify_aot_dir)
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        r = verify_aot_dir(d)
        assert r == 2                       # int contract intact
        assert r.model_version              # stamped
        assert r.model_version == read_aot_version(d)
        chash, _, micros = r.model_version.partition(".")
        assert len(chash) == 12 and int(micros) > 0

    def test_republish_changes_version_same_content_hash(self, tmp_path):
        """Identical bits re-exported get a NEW version (the timestamp
        is the publish event watch_dir keys on) with the SAME content
        hash (the 'is it the same model' half for operators)."""
        from paddle_tpu.inference import read_aot_version
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        v1 = read_aot_version(d)
        _freeze_scale(str(tmp_path), 2.0, aot=True)
        v2 = read_aot_version(d)
        assert v1 != v2
        assert v1.split(".")[0] == v2.split(".")[0]
        d2 = _freeze_scale(str(tmp_path / "other"), 3.0, aot=True)
        assert read_aot_version(d2).split(".")[0] != v2.split(".")[0]

    def test_read_version_survives_corruption_verify_refuses(
            self, tmp_path):
        """The watcher's cheap probe must still NAME the corrupt
        version (so the failed-version memo can skip it) while the
        gate's full verify refuses it."""
        from paddle_tpu.inference import (AOTIntegrityError,
                                          read_aot_version,
                                          verify_aot_dir)
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        v = read_aot_version(d)
        _bitflip_first_artifact(d)
        assert read_aot_version(d) == v
        with pytest.raises(AOTIntegrityError):
            verify_aot_dir(d)

    def test_unversioned_dirs_read_none(self, tmp_path):
        from paddle_tpu.inference import (read_aot_version,
                                          verify_aot_dir)
        d = _freeze_scale(str(tmp_path), 2.0, aot=False)
        r = verify_aot_dir(d)
        assert r == 0 and r.model_version is None
        assert read_aot_version(d) is None
        assert read_aot_version(str(tmp_path / "nowhere")) is None


class TestSwapGate:
    def test_boot_logs_served_version(self, tmp_path, capfd):
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        from paddle_tpu.inference import read_aot_version
        v = read_aot_version(d)
        srv = _server(d)
        try:
            assert srv.model_version == v
            assert f"serving model version {v}" in capfd.readouterr().err
        finally:
            srv.close(timeout=60)

    def test_regate_catches_inplace_rewrite_corruption(self, tmp_path):
        """Satellite fix: verify_aot_dir used to run only at boot — a
        server outliving an artifact rewrite served from stale memory
        silently. swap() re-gates, so the corruption is caught at the
        next deploy and the live (in-memory) version keeps serving."""
        from paddle_tpu.serving import SwapFailedError
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        srv = _server(d)             # boot-time verify passes
        try:
            name = _bitflip_first_artifact(d)   # rot AFTER boot
            g0 = _counter("serving_swaps_total", outcome="gate_failed")
            with pytest.raises(SwapFailedError, match=name) as ei:
                srv.swap(d)
            assert ei.value.stage == "gate"
            assert _counter("serving_swaps_total",
                            outcome="gate_failed") - g0 == 1
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
        finally:
            srv.close(timeout=60)

    def test_feed_spec_drift_refused(self, tmp_path):
        from paddle_tpu.serving import SwapFailedError
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0, width=8)
        srv = _server(d1)
        try:
            with pytest.raises(SwapFailedError,
                               match="feed sample specs") as ei:
                srv.swap(d2)
            assert ei.value.stage == "gate"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
        finally:
            srv.close(timeout=60)

    def test_fetch_contract_drift_refused(self, tmp_path):
        from paddle_tpu.serving import SwapFailedError
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0, layers_extra=1)
        srv = _server(d1)
        try:
            with pytest.raises(SwapFailedError,
                               match="fetch names") as ei:
                srv.swap(d2)
            assert ei.value.stage == "gate"
        finally:
            srv.close(timeout=60)

    def test_concurrent_swap_refused_at_gate(self, tmp_path):
        from paddle_tpu.serving import SwapFailedError
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        srv = _server(d1)
        try:
            ctl = srv._swap_ctl()
            assert ctl._swap_lock.acquire(False)
            try:
                with pytest.raises(SwapFailedError,
                                   match="already in progress") as ei:
                    srv.swap(d2)
                assert ei.value.stage == "gate"
            finally:
                ctl._swap_lock.release()
        finally:
            srv.close(timeout=60)

    def test_missing_model_dir_refused_typed(self, tmp_path):
        from paddle_tpu.serving import SwapFailedError
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        srv = _server(d1)
        try:
            with pytest.raises(SwapFailedError) as ei:
                srv.swap(str(tmp_path / "nowhere"))
            assert ei.value.stage == "gate"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
        finally:
            srv.close(timeout=60)


class TestSwapPipeline:
    def test_successful_swap_flips_results_and_version(self, tmp_path,
                                                       capfd):
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0, aot=True)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0, aot=True)
        from paddle_tpu.inference import read_aot_version
        v1, v2 = read_aot_version(d1), read_aot_version(d2)
        ok0 = _counter("serving_swaps_total", outcome="ok")
        srv = _server(d1)
        try:
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            rep = srv.swap(d2, watchdog_ms=100)
            assert rep["outcome"] == "ok"
            assert rep["model_version"] == v2
            assert rep["previous_version"] == v1
            assert set(rep["stage_ms"]) == {
                "gate", "admit", "standby", "canary", "cutover",
                "watchdog"}
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 3.0)
            assert srv.model_version == v2
            assert _counter("serving_swaps_total",
                            outcome="ok") - ok0 == 1
            # satellite: the served version is logged after cutover too
            assert f"serving model version {v2}" in \
                capfd.readouterr().err
            # version gauge: exactly one live series, the old removed
            g = REGISTRY.get("serving_model_version")
            assert g.value(version=v2) == 1
            assert (("version", v1),) not in g.samples()
        finally:
            srv.close(timeout=60)
        # a closed server serves nothing: the series is dropped
        g = REGISTRY.get("serving_model_version")
        assert (("version", v2),) not in g.samples()

    def test_submit_during_swap_no_loss_no_version_split(self,
                                                         tmp_path):
        """The cutover contract under concurrent submitters: every
        request admitted mid-swap completes (zero hangs, zero drops),
        every request's answer is WHOLLY one version (a multi-row
        request never straddles the cutover), and traffic ends on the
        new version."""
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        srv = _server(d1, max_batch=4, max_wait_ms=0.5, max_queue=4096)
        results, errors = [], []
        stop = threading.Event()

        def client(rows):
            while not stop.is_set():
                try:
                    out = srv.infer(_ones(rows=rows), timeout=60)[0]
                except Exception as e:   # pragma: no cover
                    errors.append(e)
                    return
                vals = set(np.unique(out).tolist())
                results.append(vals)
                time.sleep(0.001)

        try:
            ts = [threading.Thread(target=client, args=(r,))
                  for r in (1, 2, 3)]
            for t in ts:
                t.start()
            time.sleep(0.1)
            rep = srv.swap(d2, watchdog_ms=50)
            assert rep["outcome"] == "ok"
            time.sleep(0.15)
            stop.set()
            for t in ts:
                t.join(60)
            assert not errors, errors
            assert results
            for vals in results:
                # one version per request — never a mixed answer
                assert vals in ({2.0}, {3.0}), vals
            assert results[-1] == {3.0}
            np.testing.assert_allclose(
                srv.infer(_ones(rows=3), timeout=30)[0], 3.0)
        finally:
            stop.set()
            srv.close(timeout=60)

    def test_canary_nonfinite_refused_live_untouched(self, tmp_path):
        """A new version producing non-finite output on golden input
        fails the canary: standby released, live serving, typed stage,
        counted canary_failed — real traffic NEVER touched the broken
        version (the ok counter window proves it)."""
        from paddle_tpu.serving import SwapFailedError
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        dbad = _freeze_scale(str(tmp_path / "vbad"), float("inf"))
        c0 = _counter("serving_swaps_total", outcome="canary_failed")
        srv = _server(d1)
        try:
            with pytest.raises(SwapFailedError,
                               match="non-finite") as ei:
                srv.swap(dbad)
            assert ei.value.stage == "canary"
            assert _counter("serving_swaps_total",
                            outcome="canary_failed") - c0 == 1
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            assert srv.model_version is None    # unversioned dir, v1
        finally:
            srv.close(timeout=60)

    def test_canary_parity_bounds(self, tmp_path):
        """Caller-supplied parity: a weight-identical refactor swap
        passes tight bounds; a genuinely different version fails them
        (and passes without them)."""
        from paddle_tpu.serving import SwapFailedError
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        dsame = _freeze_scale(str(tmp_path / "vsame"), 2.0)
        ddiff = _freeze_scale(str(tmp_path / "vdiff"), 3.0)
        srv = _server(d1)
        try:
            feeds = [_ones(rows=2)]
            rep = srv.swap(dsame, canary_feeds=feeds,
                           parity_rtol=1e-6, watchdog_ms=0)
            assert rep["outcome"] == "ok"
            with pytest.raises(SwapFailedError, match="parity") as ei:
                srv.swap(ddiff, canary_feeds=feeds, parity_rtol=1e-3)
            assert ei.value.stage == "canary"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            rep = srv.swap(ddiff, canary_feeds=feeds, watchdog_ms=0)
            assert rep["outcome"] == "ok"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 3.0)
        finally:
            srv.close(timeout=60)

    def test_canary_check_hook(self, tmp_path):
        from paddle_tpu.serving import SwapFailedError
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        srv = _server(d1)
        try:
            with pytest.raises(SwapFailedError,
                               match="returned False") as ei:
                srv.swap(d2, canary_check=lambda f, o: False)
            assert ei.value.stage == "canary"
            with pytest.raises(SwapFailedError, match="raised") as ei:
                srv.swap(d2, canary_check=lambda f, o: 1 / 0)
            assert ei.value.stage == "canary"
            # the hook sees the NEW version's sliced outputs
            seen = []
            rep = srv.swap(
                d2, watchdog_ms=0,
                canary_check=lambda f, o: bool(
                    seen.append(float(o[0].ravel()[0])) or True))
            assert rep["outcome"] == "ok"
            assert all(v == 0.0 for v in seen)  # zeros * 3
        finally:
            srv.close(timeout=60)

    def test_standby_failure_quarantines_swap(self, tmp_path,
                                              monkeypatch):
        from paddle_tpu.serving import SwapFailedError
        from paddle_tpu.serving.swap import SwapController
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        r0 = _counter("serving_swaps_total", outcome="rolled_back")
        srv = _server(d1)
        try:
            monkeypatch.setattr(
                SwapController, "_build_standby_pool",
                lambda self, bundle: (_ for _ in ()).throw(
                    RuntimeError("compile exploded")))
            with pytest.raises(SwapFailedError,
                               match="compile exploded") as ei:
                srv.swap(d2)
            assert ei.value.stage == "standby"
            assert _counter("serving_swaps_total",
                            outcome="rolled_back") - r0 == 1
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
        finally:
            srv.close(timeout=60)

    def test_standby_wedge_times_out_live_unaffected(self, tmp_path,
                                                     monkeypatch):
        """A wedged standby compile must quarantine the SWAP within
        standby_timeout_ms — the caller gets the typed stage and live
        traffic flows throughout; the abandoned build's eventual pool
        is discarded, never promoted."""
        from paddle_tpu.serving import SwapFailedError
        from paddle_tpu.serving.swap import SwapController
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        srv = _server(d1)
        release = threading.Event()
        orig = SwapController._build_standby_pool
        late_pools = []

        def wedged(self, bundle):
            release.wait(30)
            pool = orig(self, bundle)
            late_pools.append(pool)
            return pool

        try:
            monkeypatch.setattr(SwapController, "_build_standby_pool",
                                wedged)
            t0 = time.perf_counter()
            with pytest.raises(SwapFailedError, match="wedged") as ei:
                srv.swap(d2, standby_timeout_ms=200)
            assert ei.value.stage == "standby"
            assert time.perf_counter() - t0 < 10
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            release.set()
            # review round 3: the late-built pool is disposed through
            # the TRACKED drain path — closed AND released (params +
            # executables dropped), never a silent untracked thread
            # close() could report "stopped" over
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and \
                    not (late_pools and
                         late_pools[0]._by_device == {}):
                time.sleep(0.02)
            assert late_pools and late_pools[0]._by_device == {}
            assert not any(r.is_alive()
                           for r in late_pools[0].replicas)
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
        finally:
            release.set()
            srv.close(timeout=60)


class TestSwapChaosHooks:
    """The env-driven chaos hooks (testing/faults.py): each proves the
    same invariant from a different stage — the live version keeps
    serving."""

    def _clear(self, *tags):
        from paddle_tpu.testing import faults
        for t in tags:
            faults._serving_fired.discard(t)

    def test_bitflip_hook_gate_refuses(self, tmp_path, monkeypatch):
        from paddle_tpu.serving import SwapFailedError
        from paddle_tpu.testing import faults
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0, aot=True)
        self._clear("swap_bitflip")
        monkeypatch.setenv("PT_FAULT_SWAP_BITFLIP", "1")
        uninstall = faults.install_swap_faults()
        assert uninstall
        srv = _server(d1)
        try:
            with pytest.raises(SwapFailedError) as ei:
                srv.swap(d2)
            assert ei.value.stage == "gate"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            # fire-once: the second attempt sees the (already corrupt)
            # artifact refused again, but no new flip happens — and a
            # FRESH export swaps clean
            d3 = _freeze_scale(str(tmp_path / "v3"), 3.0, aot=True)
            rep = srv.swap(d3, watchdog_ms=0)
            assert rep["outcome"] == "ok"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 3.0)
        finally:
            uninstall()
            srv.close(timeout=60)

    def test_standby_stall_hook_quarantines(self, tmp_path,
                                            monkeypatch):
        from paddle_tpu.serving import SwapFailedError
        from paddle_tpu.testing import faults
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        self._clear("swap_standby_stall")
        monkeypatch.setenv("PT_FAULT_SWAP_STANDBY_STALL", "1")
        monkeypatch.setenv("PT_FAULT_STALL_SECS", "2")
        uninstall = faults.install_swap_faults()
        srv = _server(d1)
        try:
            with pytest.raises(SwapFailedError, match="wedged") as ei:
                srv.swap(d2, standby_timeout_ms=200)
            assert ei.value.stage == "standby"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            # fire-once: the pool heals — the very next swap succeeds
            rep = srv.swap(d2, watchdog_ms=0)
            assert rep["outcome"] == "ok"
        finally:
            uninstall()
            srv.close(timeout=60)

    def test_error_storm_trips_watchdog_rollback(self, tmp_path,
                                                 monkeypatch):
        """The acceptance chaos case: post-cutover dispatch errors
        trip the watchdog, traffic reverts to the old version at a
        batch boundary, the caller gets the typed stage, and
        post-rollback requests are answered by the OLD version — all
        with zero hangs."""
        from paddle_tpu.serving import SwapFailedError
        from paddle_tpu.testing import faults
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        self._clear("swap_error_storm")
        monkeypatch.setenv("PT_FAULT_SWAP_ERROR_STORM", "8")
        uninstall = faults.install_swap_faults()
        r0 = _counter("serving_swaps_total", outcome="rolled_back")
        srv = _server(d1, max_queue=4096)
        stop = threading.Event()
        outcomes = []

        def traffic():
            while not stop.is_set():
                try:
                    out = srv.infer(_ones(), timeout=60)[0]
                    outcomes.append(float(out.ravel()[0]))
                except RuntimeError:
                    outcomes.append("error")
                time.sleep(0.002)

        ts = [threading.Thread(target=traffic) for _ in range(2)]
        try:
            for t in ts:
                t.start()
            time.sleep(0.05)
            with pytest.raises(SwapFailedError,
                               match="watchdog tripped") as ei:
                srv.swap(d2, watchdog_ms=2000, watchdog_max_errors=2)
            assert ei.value.stage == "watchdog"
            assert _counter("serving_swaps_total",
                            outcome="rolled_back") - r0 == 1
            stop.set()
            for t in ts:
                t.join(60)
            assert "error" in outcomes          # the storm was real
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            assert outcomes[-1] in (2.0, "error") or \
                outcomes[-1] == 2.0
        finally:
            stop.set()
            uninstall()
            srv.close(timeout=60)


class TestWatchdogAttribution:
    """Review round 3: the post-cutover error verdict counts the NEW
    pool's own batch failures — errors from elsewhere in the process
    (the old pool's draining stragglers, another server) can never
    roll back a healthy new version."""

    def test_watchdog_uses_errors_fn_not_global_counter(self):
        from paddle_tpu.serving import SwapWatchdog
        from paddle_tpu.serving.scheduler import _m_requests
        box = {"n": 0}
        wd = SwapWatchdog(window_ms=10_000, max_errors=2,
                          errors_fn=lambda: box["n"]).start()
        # global error traffic (an old pool's stragglers) is invisible
        _m_requests.inc(3, outcome="error")
        assert wd.verdict() is None
        # the new pool's own failures trip it
        box["n"] = 2
        assert "2 request error(s)" in wd.verdict()

    def test_pool_attributes_its_own_batch_failures(self, tmp_path):
        d = _freeze_scale(str(tmp_path), 2.0)
        srv = _server(d)
        try:
            pool = srv.pool
            assert pool.batch_failures == 0
            r = pool.replicas[0]
            orig = r.run_batch
            fired = []

            def boom(bucket, feeds):
                if not fired:
                    fired.append(1)
                    raise RuntimeError("one poisoned batch")
                return orig(bucket, feeds)

            r.run_batch = boom
            with pytest.raises(RuntimeError, match="poisoned"):
                srv.infer(_ones(), timeout=30)
            assert pool.batch_failures == 1
            # healthy traffic doesn't count
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            assert pool.batch_failures == 1
        finally:
            srv.close(timeout=60)

    def test_old_pool_errors_during_window_never_roll_back(
            self, tmp_path, monkeypatch):
        """The sharp end: a swap whose watchdog window overlaps
        FAILING old-pool work must still commit — rolling back to the
        pool that is actually failing would be the worst possible
        verdict."""
        from paddle_tpu.serving.swap import SwapController
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        srv = _server(d1)
        orig_cut = SwapController._cutover

        def cut_then_old_pool_fails(self, standby, bundle):
            out = orig_cut(self, standby, bundle)
            old_pool = out[0]
            # the old pool fails "draining" batches inside the window
            old_pool._note_batch_failures(10)
            from paddle_tpu.serving.scheduler import _m_requests
            _m_requests.inc(10, outcome="error")
            return out

        monkeypatch.setattr(SwapController, "_cutover",
                            cut_then_old_pool_fails)
        try:
            rep = srv.swap(d2, watchdog_ms=300, watchdog_max_errors=2)
            assert rep["outcome"] == "ok"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 3.0)
        finally:
            srv.close(timeout=60)


class TestWatchDir:
    def test_watcher_picks_up_new_publish(self, tmp_path):
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        from paddle_tpu.inference import read_aot_version
        srv = _server(d)
        try:
            v1 = srv.model_version
            srv.watch_dir(poll_ms=30, watchdog_ms=0)
            _freeze_scale(str(tmp_path), 3.0, aot=True)  # republish
            v2 = read_aot_version(d)
            deadline = time.monotonic() + 30
            while srv.model_version != v2 and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert srv.model_version == v2 != v1
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 3.0)
            assert srv._swap_ctl().stop_watch() is True
        finally:
            srv.close(timeout=60)

    def test_watcher_remembers_failed_version_no_crash_loop(
            self, tmp_path):
        """A corrupt publish is attempted ONCE (one gate_failed, one
        loud line), then skipped until the publisher writes a new
        version — which swaps clean."""
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        from paddle_tpu.inference import read_aot_version
        srv = _server(d)
        try:
            # publish + corrupt BEFORE arming the watcher, so its very
            # first observation of the new version is the corrupt one
            _freeze_scale(str(tmp_path), 3.0, aot=True)
            bad_v = read_aot_version(d)
            _bitflip_first_artifact(d)
            g0 = _counter("serving_swaps_total", outcome="gate_failed")
            srv.watch_dir(poll_ms=30, watchdog_ms=0)
            deadline = time.monotonic() + 30
            while _counter("serving_swaps_total",
                           outcome="gate_failed") - g0 < 1 and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert _counter("serving_swaps_total",
                            outcome="gate_failed") - g0 == 1
            time.sleep(0.2)                 # several poll periods
            assert _counter("serving_swaps_total",
                            outcome="gate_failed") - g0 == 1
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            _freeze_scale(str(tmp_path), 4.0, aot=True)  # good publish
            good_v = read_aot_version(d)
            assert good_v != bad_v
            deadline = time.monotonic() + 30
            while srv.model_version != good_v and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert srv.model_version == good_v
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 4.0)
        finally:
            srv.close(timeout=60)

    def test_bad_watch_kwargs_stop_watcher_no_blacklist(
            self, tmp_path, capfd):
        """Review round 4: an EnforceNotMet from the watcher's OWN
        swap_kwargs says nothing about the artifact — the watcher
        stops loudly (fix the config) instead of blacklisting a
        never-judged publish or retrying a config error forever."""
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        srv = _server(d)
        try:
            ctl = srv.watch_dir(poll_ms=30, canary_feeds=[])
            _freeze_scale(str(tmp_path), 3.0, aot=True)
            deadline = time.monotonic() + 30
            while ctl._watch_thread.is_alive() and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert not ctl._watch_thread.is_alive()
            assert ctl._watch_failed_version is None  # never judged
            assert "STOPPING the watcher" in capfd.readouterr().err
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
        finally:
            srv.close(timeout=60)

    def test_unversioned_dir_never_autoswaps(self, tmp_path):
        d = _freeze_scale(str(tmp_path), 2.0, aot=False)
        ok0 = _counter("serving_swaps_total", outcome="ok")
        srv = _server(d)
        try:
            srv.watch_dir(poll_ms=20)
            _freeze_scale(str(tmp_path), 3.0, aot=False)  # no manifest
            time.sleep(0.2)
            assert _counter("serving_swaps_total",
                            outcome="ok") - ok0 == 0
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
        finally:
            srv.close(timeout=60)

    def test_concurrent_refusal_not_blacklisted(self, tmp_path):
        """Review fix: a publish whose swap was refused only because
        ANOTHER swap held the lock was never judged — memoizing it as
        failed would silently strand a good deploy. The watcher must
        retry it on the next poll once the lock frees."""
        d = _freeze_scale(str(tmp_path), 2.0, aot=True)
        from paddle_tpu.inference import read_aot_version
        srv = _server(d)
        try:
            ctl = srv._swap_ctl()
            assert ctl._swap_lock.acquire(False)   # a "running" swap
            srv.watch_dir(poll_ms=30, watchdog_ms=0)
            _freeze_scale(str(tmp_path), 3.0, aot=True)
            v2 = read_aot_version(d)
            time.sleep(0.25)        # several refused-and-deferred polls
            assert ctl._watch_failed_version is None
            assert srv.model_version != v2
            ctl._swap_lock.release()
            deadline = time.monotonic() + 30
            while srv.model_version != v2 and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert srv.model_version == v2
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 3.0)
        finally:
            srv.close(timeout=60)

    def test_double_watch_refused_stop_idempotent(self, tmp_path):
        d = _freeze_scale(str(tmp_path), 2.0)
        srv = _server(d)
        try:
            ctl = srv.watch_dir(poll_ms=50)
            with pytest.raises(EnforceNotMet, match="already running"):
                srv.watch_dir(poll_ms=50)
            assert ctl.stop_watch() is True
            assert ctl.stop_watch() is True
            srv.watch_dir(poll_ms=50)       # restartable after stop
        finally:
            srv.close(timeout=60)


class TestCloseSwapRace:
    def test_close_waits_for_inflight_swap_no_leaked_series(
            self, tmp_path, monkeypatch):
        """Review fix: close() racing a running swap used to let the
        cutover commit AFTER close finished — publishing a version
        series nothing would ever clear and promoting a pool nothing
        would ever close. shutdown() now waits on the swap lock, so
        whatever the swap's outcome, close() drains the final live
        pool and drops the final version series."""
        from paddle_tpu.serving.swap import SwapController
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0, aot=True)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0, aot=True)
        srv = _server(d1)
        orig = SwapController._build_standby_pool
        started = threading.Event()

        def slow_build(self, bundle):
            started.set()
            time.sleep(0.4)         # close() arrives mid-standby
            return orig(self, bundle)

        monkeypatch.setattr(SwapController, "_build_standby_pool",
                            slow_build)
        outcome = {}

        def do_swap():
            try:
                outcome["report"] = srv.swap(d2, watchdog_ms=50)
            except Exception as e:
                outcome["error"] = e

        t = threading.Thread(target=do_swap, daemon=True)
        t.start()
        assert started.wait(30)
        assert srv.close(timeout=120) is True
        t.join(60)
        assert outcome, "swap thread never finished"
        # whatever won, nothing leaks: no live version series, and the
        # pool the server ended on is truly stopped
        g = REGISTRY.get("serving_model_version")
        assert not any(dict(k).get("version")
                       for k in g.samples()), g.samples()
        assert not any(r.is_alive() for r in srv.pool.replicas)

    def test_timed_out_close_aborts_swap_before_cutover(
            self, tmp_path, monkeypatch):
        """Review round 2: when close()'s bounded wait on an in-flight
        swap EXPIRES, close returns False ('call again') — and the
        swap, once its standby finally builds, must abort at the
        cutover gate instead of promoting a pool on a closing server
        and resurrecting the version series close will have cleared."""
        from paddle_tpu.serving import SwapFailedError
        from paddle_tpu.serving.swap import SwapController
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0, aot=True)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0, aot=True)
        from paddle_tpu.inference import read_aot_version
        v2 = read_aot_version(d2)
        srv = _server(d1)
        orig = SwapController._build_standby_pool
        gate = threading.Event()
        started = threading.Event()

        def gated_build(self, bundle):
            started.set()
            gate.wait(60)           # outlives close's bounded wait
            return orig(self, bundle)

        monkeypatch.setattr(SwapController, "_build_standby_pool",
                            gated_build)
        outcome = {}

        def do_swap():
            try:
                outcome["report"] = srv.swap(d2, watchdog_ms=50)
            except SwapFailedError as e:
                outcome["error"] = e

        t = threading.Thread(target=do_swap, daemon=True)
        t.start()
        assert started.wait(30)
        t_close = time.perf_counter()
        assert srv.close(timeout=0.3) is False   # gave up on the swap
        # review round 3: ONE shared deadline — close(0.3) must bound
        # the whole shutdown near 0.3s, not pay it per phase
        assert time.perf_counter() - t_close < 2.0
        gate.set()                               # standby now builds
        t.join(60)
        err = outcome.get("error")
        assert err is not None, outcome
        assert err.stage == "cutover" and err.retryable
        # nothing promoted, nothing resurrected
        g = REGISTRY.get("serving_model_version")
        assert (("version", v2),) not in g.samples(), g.samples()
        assert srv.model_version != v2
        assert srv.close(timeout=120) is True    # second close finishes
        assert not any(dict(k).get("version")
                       for k in g.samples()), g.samples()


class TestPoolRoles:
    """The replica.py surgery that lets two pools coexist: a standby
    pool never publishes the gauges, promote/demote hand ownership
    over, and a demoted pool's close never zeroes the new owner's
    series."""

    def test_standby_pool_does_not_touch_live_gauges(self, tmp_path):
        from paddle_tpu.serving.server import _boot_pool
        d = _freeze_scale(str(tmp_path), 2.0)
        srv = _server(d)
        try:
            g = REGISTRY.get("serving_replicas")
            assert g.value() == 1
            standby = _boot_pool(srv._bundle, srv.config,
                                 role="standby")
            assert g.value() == 1           # untouched by the boot
            standby.demote()                # no-op, still standby
            assert standby.close(timeout=60) is True
            assert g.value() == 1           # close didn't zero either
            standby.release()
            assert standby._by_device == {}
            assert standby.replicas[0]._executables == {}
        finally:
            srv.close(timeout=60)
        assert REGISTRY.get("serving_replicas").value() == 0

    def test_promote_takes_gauge_ownership(self, tmp_path):
        from paddle_tpu.serving.server import _boot_pool
        d = _freeze_scale(str(tmp_path), 2.0)
        srv = _server(d)
        try:
            standby = _boot_pool(srv._bundle, srv.config,
                                 role="standby")
            old = srv.pool
            standby.promote()
            old.demote()
            assert REGISTRY.get("serving_replicas").value() == 1
            # hand back so close() zeroes through the original pool
            standby.demote()
            old.promote()
            assert standby.close(timeout=60) is True
        finally:
            srv.close(timeout=60)


class TestRoundFourHardening:
    def test_dispatch_after_true_close_fails_typed_not_hangs(
            self, tmp_path):
        """Review round 4: the batcher can load a pool's dispatch,
        stall, and put only after a committed swap's drain fully
        closed that pool — the post-put sweep must fail the riders
        typed instead of stranding them on a dead queue."""
        from paddle_tpu.serving import ReplicaLostError
        from paddle_tpu.serving import scheduler as sch
        d = _freeze_scale(str(tmp_path), 2.0)
        srv = _server(d)
        pool = srv.pool
        srv.close(timeout=60)           # true close: sweep flag set
        req = sch._Request({"x": np.ones((1, 16), np.float32)}, 1)
        mb = sch.MicroBatch([req], 1, ("x",))
        pool.dispatch(mb)               # put lands on the dead queue
        with pytest.raises(ReplicaLostError, match="already closed"):
            req.pending.result(timeout=5)

    def test_cutover_flip_failure_reverts_partial_flips(
            self, tmp_path, monkeypatch):
        """Review round 4: if a flip raises partway through cutover,
        the already-applied flips revert before the standby drains —
        'dispatch was not committed' must be the truth, and the
        scheduler must not keep targeting a closing pool."""
        from paddle_tpu.serving import SwapFailedError
        from paddle_tpu.serving.replica import ReplicaPool
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        srv = _server(d1)
        orig_promote = ReplicaPool.promote
        boom = {"armed": True}

        def exploding_promote(self):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("promote exploded")
            return orig_promote(self)

        try:
            monkeypatch.setattr(ReplicaPool, "promote",
                                exploding_promote)
            with pytest.raises(SwapFailedError,
                               match="not committed") as ei:
                srv.swap(d2)
            assert ei.value.stage == "cutover"
            # dispatch reverted: live traffic still serves v1
            assert srv.pool.role == "live"
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
        finally:
            srv.close(timeout=60)

    def test_latency_verdict_without_baseline_logs_loudly(
            self, tmp_path, capfd):
        """Review round 4: opting into watchdog_latency_x with no
        pre-swap request to baseline against must SAY the verdict is
        disabled, not silently skip it."""
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        # monkeypatch the latency read to report an empty histogram
        # (a fresh registry would be invasive)
        from paddle_tpu.serving.resilience import SwapWatchdog
        srv = _server(d1)
        try:
            # the class's own entry: the attribute read would unwrap the
            # staticmethod, and putting that back leaves a method that is
            # handed `self` in every later test of the process
            orig = SwapWatchdog.__dict__["_latency"]
            SwapWatchdog._latency = staticmethod(lambda: (0.0, 0))
            try:
                rep = srv.swap(d2, watchdog_ms=50,
                               watchdog_latency_x=2.0)
            finally:
                SwapWatchdog._latency = orig
            assert rep["outcome"] == "ok"
            assert "latency verdict is DISABLED" in \
                capfd.readouterr().err
        finally:
            srv.close(timeout=60)


class TestRoundFiveHardening:
    def test_rollback_racing_close_drains_not_promotes(
            self, tmp_path, monkeypatch):
        """Review round 5: a watchdog rollback racing server.close()
        must not promote the old pool (republishing gauges close just
        zeroed) or leave its replicas running past a True close — on
        a closing server the reverted-to pool drains out too."""
        from paddle_tpu.serving import SwapFailedError
        from paddle_tpu.serving.swap import SwapController
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0, aot=True)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0, aot=True)
        srv = _server(d1)
        ctl = srv._swap_ctl()
        in_window = threading.Event()
        may_trip = threading.Event()

        def gated_window(self, *a):
            in_window.set()
            may_trip.wait(30)
            return "synthetic trip (test)"

        monkeypatch.setattr(SwapController, "_watch_window",
                            gated_window)
        outcome, closed = {}, {}

        def do_swap():
            try:
                srv.swap(d2, watchdog_ms=1000)
            except SwapFailedError as e:
                outcome["e"] = e

        t = threading.Thread(target=do_swap, daemon=True)
        t.start()
        assert in_window.wait(60)       # cutover committed
        ct = threading.Thread(
            target=lambda: closed.update(ok=srv.close(timeout=120)),
            daemon=True)
        ct.start()
        deadline = time.monotonic() + 30
        while not ctl._closed and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ctl._closed               # begin_shutdown landed
        may_trip.set()                   # rollback fires mid-close
        t.join(60)
        ct.join(120)
        assert closed.get("ok") is True
        assert outcome["e"].stage == "watchdog"
        # nothing survived the close: the reverted-to old pool is
        # drained, not promoted, and the gauges stay zeroed
        assert not any(r.is_alive() for r in srv.pool.replicas)
        assert REGISTRY.get("serving_replicas").value() == 0
        g = REGISTRY.get("serving_model_version")
        assert not any(dict(k).get("version")
                       for k in g.samples()), g.samples()

    def test_swap_and_watch_refused_on_closed_server(self, tmp_path):
        """Review round 5: a controller created LAZILY after close()
        inherits the closed state — swap()/watch_dir() on a closed
        server refuse typed instead of booting a pool nothing will
        ever close."""
        from paddle_tpu.serving import SwapFailedError
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        srv = _server(d1)
        assert srv.close(timeout=60) is True
        assert srv._swap_controller is None      # never swapped
        with pytest.raises(SwapFailedError, match="closing") as ei:
            srv.swap(d2)
        assert ei.value.stage == "gate" and ei.value.retryable
        with pytest.raises(EnforceNotMet, match="closed"):
            srv.watch_dir(poll_ms=50)
        g = REGISTRY.get("serving_model_version")
        assert not any(dict(k).get("version") for k in g.samples())

    def test_malformed_canary_feeds_are_argument_errors(self, tmp_path):
        """Review round 5: canary_feeds shape/missing-feed problems
        judge the CALLER (the gate guarantees specs are identical
        across versions), so they raise EnforceNotMet with NO swap
        outcome counted — not a canary_failed verdict watch_dir would
        blacklist the publish over."""
        d1 = _freeze_scale(str(tmp_path / "v1"), 2.0)
        d2 = _freeze_scale(str(tmp_path / "v2"), 3.0)
        srv = _server(d1)
        try:
            before = {o: _counter("serving_swaps_total", outcome=o)
                      for o in ("ok", "gate_failed", "canary_failed",
                                "rolled_back")}
            with pytest.raises(EnforceNotMet, match="sample shape"):
                srv.swap(d2, canary_feeds=[
                    {"x": np.zeros((1, 3), np.float32)}])
            with pytest.raises(EnforceNotMet, match="missing feeds"):
                srv.swap(d2, canary_feeds=[{}])
            after = {o: _counter("serving_swaps_total", outcome=o)
                     for o in before}
            assert after == before       # no outcome counted
            np.testing.assert_allclose(
                srv.infer(_ones(), timeout=30)[0], 2.0)
            # a well-formed swap still works afterwards (standby from
            # the failed attempts was disposed, lock released)
            rep = srv.swap(d2, watchdog_ms=0)
            assert rep["outcome"] == "ok"
        finally:
            srv.close(timeout=60)


class TestDispatchIndirection:
    def test_set_dispatch_flips_at_batch_boundary(self):
        """Scheduler-level pin of the cutover primitive: batches
        formed before the flip land on A, after it on B — no batch
        ever observed by both."""
        from paddle_tpu.serving.scheduler import MicroBatchScheduler

        class Sink:
            def __init__(self):
                self.batches = []

            def __call__(self, mb):
                self.batches.append(mb)
                mb.complete([mb.feeds["x"] * 2.0])

        a, b = Sink(), Sink()
        s = MicroBatchScheduler(a, ("x",), max_batch=2,
                                max_wait_ms=0.0).start()
        s.submit({"x": np.ones((1, 2), np.float32)}).result(timeout=10)
        s.set_dispatch(b)
        s.submit({"x": np.ones((1, 2), np.float32)}).result(timeout=10)
        s.close(timeout=10)
        assert len(a.batches) == 1 and len(b.batches) == 1


# ---------------------------------------------------------------------------
# slow e2e: open-loop load through export v2 -> swap -> corrupt v3 ->
# gate refusal -> error-storm v4 -> watchdog rollback, with .prom
# evidence and per-request accounting
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.timeout(600)
class TestSwapEndToEnd:
    """Acceptance run (ISSUE 13): under sustained open-loop load, a
    successful swap completes with zero dropped/hung requests and a
    bounded swap-window p99; a corrupted new version refuses at the
    gate and an error-storming one rolls back automatically — both
    leaving the previous version serving, with
    serving_swaps_total{outcome} evidence in .prom."""

    def test_swap_under_load_end_to_end(self, tmp_path):
        from paddle_tpu.monitor import exporter
        hb = tmp_path / "hb"
        hb.mkdir()
        out = tmp_path / "result.json"
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "PADDLE_HEARTBEAT_DIR": str(hb),
            "PADDLE_TRAINER_ID": "0",
        })
        r = subprocess.run(
            [sys.executable, WORKER, str(tmp_path / "work"), str(out)],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=REPO)
        assert r.returncode == 0, \
            f"rc={r.returncode}\n{r.stderr[-4000:]}"
        with open(out) as f:
            res = json.load(f)
        # -- per-request accounting: nothing hung, nothing lost --
        assert res["hangs"] == 0, res
        assert res["total"] == res["ok"] + res["errors"], res
        # -- the good swap committed and v2 serves to the end --
        assert res["swap_ok"] == 1, res
        assert res["final_scale"] == 3.0, res
        assert res["final_version"] == res["v2_version"], res
        # -- the corrupt v3 refused at the gate, storm v4 rolled back,
        #    both leaving v2 serving --
        assert res["gate_failed_stage"] == "gate", res
        assert res["rolled_back_stage"] == "watchdog", res
        assert res["storm_errors"] >= 1, res
        # -- swap-window tail: p99 of requests overlapping the good
        #    swap <= 1.5x steady-state (plus a small absolute grace
        #    for shared-host scheduler noise at ms-scale latencies) --
        assert res["p99_overlap_ms"] <= \
            1.5 * res["p99_steady_ms"] + 50.0, res
        # -- .prom evidence of every outcome --
        _types, samples = exporter.parse_text(
            (hb / "rank0.prom").read_text())
        outcomes = {dict(labels).get("outcome"): v
                    for (name, labels), v in samples.items()
                    if name == "serving_swaps_total"}
        assert outcomes.get("ok", 0) >= 1, outcomes
        assert outcomes.get("gate_failed", 0) >= 1, outcomes
        assert outcomes.get("rolled_back", 0) >= 1, outcomes
