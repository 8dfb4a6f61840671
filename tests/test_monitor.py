"""Unified telemetry tests: metrics registry, Prometheus exporter,
flight recorder, XLA cost/MFU analytics, and their instrumentation of
the executor / checkpoint / prefetch / launcher layers.

The subprocess end-to-end run (watchdog kill -> postmortem dump +
per-rank /metrics snapshot) carries the `slow` marker; everything else
is tier-1 fast. Metrics are process-global and cumulative, so tests
assert DELTAS, never absolute values.
"""

import json
import os
import signal
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.distributed import health
from paddle_tpu.monitor import cost, exporter, flight_recorder
from paddle_tpu.monitor.registry import (
    REGISTRY, Counter, Gauge, Histogram, Registry,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "monitor_worker.py")

sys.path.insert(0, os.path.join(REPO, "tools"))
import check_metrics  # noqa: E402


# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_labels_and_merge(self):
        r = Registry()
        c = r.counter("t_reqs_total", "help", labels=("code",))
        c.inc(code=200)
        c.inc(2.5, code=500)
        c.inc(code=200)
        assert c.value(code=200) == 2.0
        assert c.value(code=500) == 2.5
        assert c.samples() == {("200",): 2.0, ("500",): 2.5}

    def test_counter_threaded_increments_sum(self):
        r = Registry()
        c = r.counter("t_threaded_total")

        def work():
            for _ in range(10_000):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value() == 40_000

    def test_counter_rejects_negative(self):
        c = Registry().counter("t_neg_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_get_or_create_identity_and_conflict(self):
        r = Registry()
        a = r.counter("t_same_total")
        assert r.counter("t_same_total") is a
        with pytest.raises(ValueError):
            r.gauge("t_same_total")
        with pytest.raises(ValueError):
            r.counter("t_same_total", labels=("x",))

    def test_invalid_names_rejected(self):
        r = Registry()
        with pytest.raises(ValueError):
            r.counter("bad name")
        with pytest.raises(ValueError):
            r.counter("ok_total", labels=("bad-label",))

    def test_gauge_last_write_wins(self):
        g = Registry().gauge("t_depth")
        g.set(3)
        g.set(1)
        g.inc(2)
        assert g.value() == 3.0

    def test_histogram_buckets_sum_count(self):
        h = Registry().histogram("t_ms", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 50.0, 500.0):
            h.observe(v)
        ((cum, total, count),) = [h.samples()[()]]
        assert cum == [1, 2, 3, 4]          # cumulative incl +Inf
        assert total == 555.5 and count == 4
        assert h.count() == 4 and h.sum() == 555.5

    def test_histogram_explicit_bucket_conflict_raises(self):
        r = Registry()
        r.histogram("t_b_ms", buckets=(1.0, 10.0, 100.0))
        with pytest.raises(ValueError):
            r.histogram("t_b_ms", buckets=(0.1, 0.5))
        # the default sentinel means "whatever is registered"
        assert r.histogram("t_b_ms") is r.get("t_b_ms")

    def test_dead_thread_shards_fold_without_losing_sums(self):
        """Thread churn must not grow the shard list without bound —
        and folding a dead thread's shard must preserve its counts."""
        r = Registry()
        c = r.counter("t_churn_total")
        h = r.histogram("t_churn_ms", buckets=(10.0,))
        for _ in range(20):
            t = threading.Thread(
                target=lambda: (c.inc(3), h.observe(1.0)))
            t.start()
            t.join()
        c.inc()                      # registration path sweeps
        h.observe(1.0)
        assert c.value() == 61
        assert h.count() == 21
        # main + at most one straggler still registered
        assert len(c._shards.items()) <= 2

    def test_histogram_threaded_merge(self):
        h = Registry().histogram("t_tms", buckets=(10.0,))

        def work():
            for _ in range(5000):
                h.observe(1.0)

        ts = [threading.Thread(target=work) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count() == 15_000


# ---------------------------------------------------------------------------
class TestExporter:
    def _registry(self):
        r = Registry()
        r.counter("t_steps_total", "steps").inc(7)
        r.gauge("t_flops", "flops", labels=("segment",)).set(
            1.5e9, segment="0")
        h = r.histogram("t_lat_ms", "lat", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        return r

    def test_render_parse_roundtrip(self):
        text = exporter.render_text(self._registry())
        assert text.rstrip().endswith(exporter.EOF_MARKER)
        types, samples = exporter.parse_text(text)
        assert types["t_steps_total"] == "counter"
        assert types["t_lat_ms"] == "histogram"
        assert samples[("t_steps_total", ())] == 7.0
        assert samples[("t_flops", (("segment", "0"),))] == 1.5e9
        assert samples[("t_lat_ms_bucket", (("le", "10"),))] == 2.0
        assert samples[("t_lat_ms_count", ())] == 2.0

    def test_parse_rejects_torn_snapshot(self):
        text = exporter.render_text(self._registry())
        with pytest.raises(ValueError):
            exporter.parse_text(text[:len(text) // 2])

    def test_label_escaping_roundtrip(self):
        r = Registry()
        r.counter("t_esc_total", labels=("p",)).inc(
            p='we"ird\\path\nx')
        _, samples = exporter.parse_text(exporter.render_text(r))
        ((name, pairs),) = list(samples)
        assert pairs == (("p", 'we"ird\\path\nx'),)

    def test_atomic_write_reader_never_sees_torn(self, tmp_path):
        """Hammer write_snapshot while readers parse the same path:
        every read must be a complete snapshot (the # EOF guard) —
        the exporter's atomicity contract."""
        r = self._registry()
        path = str(tmp_path / "rank0.prom")
        exporter.write_snapshot(path, r)
        stop = threading.Event()
        errors = []

        def writer():
            while not stop.is_set():
                exporter.write_snapshot(path, r)

        def reader():
            for _ in range(300):
                try:
                    with open(path) as f:
                        exporter.parse_text(f.read())
                except Exception as e:      # pragma: no cover
                    errors.append(e)

        wt = threading.Thread(target=writer)
        wt.start()
        try:
            rs = [threading.Thread(target=reader) for _ in range(2)]
            for t in rs:
                t.start()
            for t in rs:
                t.join()
        finally:
            stop.set()
            wt.join()
        assert not errors
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(".tmp")]

    def test_aggregate_sums_counters_maxes_gauges(self):
        parsed = []
        for steps, flops in ((5, 100.0), (7, 90.0)):
            r = Registry()
            r.counter("t_steps_total").inc(steps)
            r.gauge("t_flops").set(flops)
            parsed.append(exporter.parse_text(exporter.render_text(r)))
        types, samples = exporter.aggregate(parsed)
        assert samples[("t_steps_total", ())] == 12.0
        assert samples[("t_flops", ())] == 100.0      # max, not sum
        text = exporter.render_parsed(types, samples)
        _, again = exporter.parse_text(text)
        assert again == samples

    def test_aggregate_restart_count_not_double_counted(self):
        """Each rank reports its incarnation index and the launcher
        counts the same restart events: one gang restart of 2 ranks
        must aggregate to 1, not 3."""
        parsed = []
        for _ in range(3):          # rank0, rank1, launcher
            r = Registry()
            r.counter("restarts_total").inc(1)
            parsed.append(exporter.parse_text(exporter.render_text(r)))
        _, samples = exporter.aggregate(parsed)
        assert samples[("restarts_total", ())] == 1.0

    def test_rank_snapshots_and_job_view(self, tmp_path):
        for rank, steps in ((0, 10), (1, 12)):
            r = Registry()
            r.counter("executor_steps_total").inc(steps)
            h = r.histogram("executor_step_ms")
            for _ in range(steps):
                h.observe(4.0)
            r.gauge("segment_flops", labels=("segment",)).set(
                2e6, segment="0")
            if rank == 1:       # one rank on a device with a known peak
                r.gauge("device_peak_flops").set(197e12)
            exporter.write_snapshot(
                health.metrics_path(str(tmp_path), rank), r)
            if rank == 0:
                # no rank has published a peak yet: no mfu field
                assert "mfu=" not in exporter.job_status_line(
                    str(tmp_path))
        snaps = exporter.read_rank_snapshots(str(tmp_path))
        assert sorted(snaps) == [0, 1]
        line = exporter.job_status_line(str(tmp_path), restarts=3)
        assert "step=12" in line and "restarts=3" in line
        assert "ms/step=4.0" in line and "mfu=" in line
        out = exporter.write_job_snapshot(
            str(tmp_path), str(tmp_path / "metrics.prom"))
        types, samples = exporter.parse_text(
            (tmp_path / "metrics.prom").read_text())
        assert samples[("executor_steps_total", ())] == 22.0
        assert out == str(tmp_path / "metrics.prom")

    def test_job_status_line_empty_dir(self, tmp_path):
        assert exporter.job_status_line(str(tmp_path)) is None
        assert exporter.job_status_line(str(tmp_path / "nope")) is None

    def test_metrics_server_serves_prometheus_text(self):
        r = self._registry()
        srv = exporter.MetricsServer(port=0, registry=r).start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics",
                    timeout=10) as resp:
                assert resp.status == 200
                assert "text/plain" in resp.headers["Content-Type"]
                body = resp.read().decode()
            types, samples = exporter.parse_text(body)
            assert samples[("t_steps_total", ())] == 7.0
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/other", timeout=10)
        finally:
            srv.stop()

    def test_read_rank_snapshots_skips_broken_files(self, tmp_path):
        """Missing / zero-byte / torn (partial) rank files must be
        skipped, not poison the job view (the next exporter tick
        replaces them)."""
        good = Registry()
        good.counter("executor_steps_total").inc(9)
        h = good.histogram("executor_step_ms")
        h.observe(4.0)
        exporter.write_snapshot(
            health.metrics_path(str(tmp_path), 0), good)
        # rank1: zero-byte (a crashed writer's empty file)
        open(health.metrics_path(str(tmp_path), 1), "w").close()
        # rank2: torn — valid prefix, no # EOF marker
        full = exporter.render_text(good)
        with open(health.metrics_path(str(tmp_path), 2), "w") as f:
            f.write(full[:len(full) // 2])
        # rank3: binary junk
        with open(health.metrics_path(str(tmp_path), 3), "wb") as f:
            f.write(b"\x00\xffnot prometheus")
        # a non-rank file that must not be picked up at all
        (tmp_path / "metrics.prom").write_text("junk")
        snaps = exporter.read_rank_snapshots(str(tmp_path))
        assert sorted(snaps) == [0]
        # and the aggregate/status built on them still works
        line = exporter.job_status_line(str(tmp_path))
        assert "step=9" in line and "ranks=1" in line
        out = exporter.write_job_snapshot(
            str(tmp_path), str(tmp_path / "job.prom"))
        _, samples = exporter.parse_text(
            (tmp_path / "job.prom").read_text())
        assert samples[("executor_steps_total", ())] == 9.0
        assert out == str(tmp_path / "job.prom")

    def test_write_job_snapshot_no_ranks_no_registry(self, tmp_path):
        assert exporter.write_job_snapshot(
            str(tmp_path / "empty"), str(tmp_path / "out.prom")) is None
        assert not (tmp_path / "out.prom").exists()

    def test_metrics_server_concurrent_scrapes(self):
        """N threads hammering /metrics while a writer mutates the
        registry: every response parses complete (ThreadingHTTPServer
        + GIL-atomic shard reads — no torn scrape)."""
        r = Registry()
        c = r.counter("t_scrape_total")
        srv = exporter.MetricsServer(port=0, registry=r).start()
        stop = threading.Event()
        errors = []

        def writer():
            while not stop.is_set():
                c.inc()

        def scraper():
            for _ in range(25):
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{srv.port}/metrics",
                            timeout=10) as resp:
                        assert resp.status == 200
                        _, samples = exporter.parse_text(
                            resp.read().decode())
                        assert ("t_scrape_total", ()) in samples
                except Exception as e:      # pragma: no cover
                    errors.append(e)

        wt = threading.Thread(target=writer)
        wt.start()
        try:
            ts = [threading.Thread(target=scraper) for _ in range(6)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        finally:
            stop.set()
            wt.join()
            srv.stop()
        assert not errors

    def test_rank_exporter_writes_and_final_snapshot(self, tmp_path):
        env = {health.ENV_DIR: str(tmp_path), health.ENV_RANK: "2",
               "PADDLE_RESTART_COUNT": "1"}
        exp = exporter.RankExporter.from_env(env=env, interval=0.05)
        assert exp is not None
        assert exporter.RankExporter.from_env(env={}) is None
        exp.start()
        time.sleep(0.2)
        exp.stop()
        path = health.metrics_path(str(tmp_path), 2)
        types, samples = exporter.parse_text(open(path).read())
        assert samples[("restarts_total", ())] >= 1.0


# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fr = flight_recorder.FlightRecorder(capacity=4)
        for i in range(10):
            fr.note("step", "s", i=i)
        evs = fr.events()
        assert len(evs) == 4
        assert evs[-1]["data"]["i"] == 9 and evs[0]["data"]["i"] == 6

    def test_in_flight_spans_named_in_dump(self, tmp_path):
        fr = flight_recorder.FlightRecorder()
        fr.span_push("train/step")
        fr.span_push("executor.run/dispatch")
        path = fr.dump(path=str(tmp_path / "d.json"), reason="test")
        doc = json.load(open(path))
        names = [s["name"] for s in doc["in_flight_spans"]]
        assert names == ["train/step", "executor.run/dispatch"]
        assert doc["reason"] == "test"
        assert "metrics" in doc
        fr.span_pop("executor.run/dispatch", 0.01)
        fr.span_pop("train/step", 0.02)
        assert fr.in_flight() == []
        assert fr.events()[-1]["name"] == "train/step"

    def test_dump_without_dir_returns_none(self):
        assert flight_recorder.FlightRecorder().dump(reason="x") is None

    def test_record_event_feeds_recorder_when_enabled(self):
        ring_before = len(flight_recorder.RECORDER.events())
        try:
            flight_recorder.enable()
            with profiler.RecordEvent("t_span"):
                inflight = flight_recorder.RECORDER.in_flight()
                assert any(s["name"] == "t_span" for s in inflight)
        finally:
            flight_recorder.disable()
        evs = flight_recorder.RECORDER.events()[ring_before:]
        assert any(e["name"] == "t_span" and e["kind"] == "span"
                   for e in evs)

    def test_sigterm_dump_chains_previous_handler(self, tmp_path):
        fr = flight_recorder.FlightRecorder()
        called = []
        prev = signal.signal(signal.SIGTERM,
                             lambda s, f: called.append(s))
        undo = fr.install(str(tmp_path))
        try:
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.time() + 5
            while not called and time.time() < deadline:
                time.sleep(0.01)
            assert called == [signal.SIGTERM]
            dumps = [f for f in os.listdir(tmp_path)
                     if f.endswith(".json")]
            assert len(dumps) == 1 and "sigterm" in dumps[0]
        finally:
            undo()
            signal.signal(signal.SIGTERM, prev)

    def test_excepthook_dump_chains_previous_hook(self, tmp_path):
        fr = flight_recorder.FlightRecorder()
        seen = []
        orig = sys.excepthook
        sys.excepthook = lambda *a: seen.append(a)
        undo = fr.install(str(tmp_path))
        try:
            try:
                raise RuntimeError("boom")
            except RuntimeError:
                sys.excepthook(*sys.exc_info())
            assert len(seen) == 1
            dumps = [f for f in os.listdir(tmp_path)
                     if "exception" in f and f.endswith(".json")]
            assert len(dumps) == 1
            doc = json.load(open(tmp_path / dumps[0]))
            assert "boom" in doc["exception"]
        finally:
            undo()
            sys.excepthook = orig

    def test_install_from_env(self, tmp_path, monkeypatch):
        assert flight_recorder.install_from_env(env={}) is None
        # no global install here: just the env contract
        monkeypatch.setattr(flight_recorder.RECORDER, "install",
                            lambda d: d)
        try:
            got = flight_recorder.install_from_env(
                env={flight_recorder.ENV_DIR: str(tmp_path)})
            assert got is flight_recorder.RECORDER
            assert flight_recorder.is_enabled()
        finally:
            flight_recorder.disable()


# ---------------------------------------------------------------------------
class TestCost:
    def test_analyze_lowered_real_program(self):
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda a: (a @ a).sum())
        a = cost.analyze_lowered(f.lower(jnp.zeros((32, 32))))
        assert a is not None and a["flops"] > 0

    def test_record_and_mfu_math(self, monkeypatch):
        cost.reset()
        try:
            assert cost.estimate_mfu(ms_per_step=10.0) is None
            cost.record_segment("g1", 0, {"flops": 1e9, "bytes": 1e6})
            cost.record_segment("g1", 1, {"flops": 1e9, "bytes": 1e6})
            assert cost.flops_per_step() == 2e9
            assert cost.bytes_per_step() == 2e6
            # latest group supersedes, never accumulates
            cost.record_segment("g2", 0, {"flops": 5e8, "bytes": 1e6})
            assert cost.flops_per_step() == 5e8
            # the CPU has no entry in the peaks table: no peak, no MFU
            assert cost.estimate_mfu(ms_per_step=10.0) is None
            monkeypatch.setattr(cost, "_device_kind",
                                lambda: "TPU v5 lite")
            mfu = cost.estimate_mfu(ms_per_step=10.0)
            assert mfu == pytest.approx(5e8 / 0.01 / 197e12)
        finally:
            cost.reset()

    def test_superseded_step_drops_stale_gauge_series(self):
        """A recompile from 2 segments down to 1 must not leave the
        old segment=1 series inflating gauge-sum consumers (the
        launcher's MFU line sums segment_flops)."""
        cost.reset()
        try:
            cost.record_segment("old", 0, {"flops": 1e3, "bytes": 1.0})
            cost.record_segment("old", 1, {"flops": 1e3, "bytes": 1.0})
            cost.record_segment("new", 0, {"flops": 7e2, "bytes": 1.0})
            samples = REGISTRY.get("segment_flops").samples()
            assert samples == {("0",): 7e2}
        finally:
            cost.reset()

    def test_nan_value_renders_and_parses(self):
        r = Registry()
        r.gauge("t_nan").set(float("nan"))
        r.gauge("t_inf").set(float("-inf"))
        types, samples = exporter.parse_text(exporter.render_text(r))
        assert samples[("t_nan", ())] != samples[("t_nan", ())]  # NaN
        assert samples[("t_inf", ())] == float("-inf")
        with pytest.raises(ValueError):
            r.counter("t_nan_total").inc(float("nan"))

    def test_peak_flops_is_one_table_keyed_by_device_kind(self):
        assert cost.peak_flops("TPU v5 lite") == 197e12
        with pytest.raises(cost.UnknownDevicePeak, match="TPU v9"):
            cost.peak_flops("TPU v9")
        with pytest.raises(cost.UnknownDevicePeak):
            cost.peak_flops()       # tests run on the CPU: no entry


# ---------------------------------------------------------------------------
def _build_and_run(steps=3):
    pt.enable_static()
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.static.data("x", [4], dtype="float32")
            y = pt.static.data("y", [1], dtype="float32")
            pred = pt.layers.fc(x, size=1)
            loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
            pt.optimizer.SGDOptimizer(0.05).minimize(loss)
        scope = pt.static.Scope()
        with pt.static.scope_guard(scope):
            exe = pt.static.Executor()
            exe.run(startup)
            xv = np.random.RandomState(0).rand(8, 4).astype(np.float32)
            yv = xv.sum(1, keepdims=True).astype(np.float32)
            for _ in range(steps):
                exe.run(main, feed={"x": xv, "y": yv},
                        fetch_list=[loss])
        return exe
    finally:
        pt.disable_static()


class TestExecutorInstrumentation:
    def test_run_moves_step_metrics_and_cost(self, monkeypatch):
        steps0 = REGISTRY.get("executor_steps_total").value()
        h = REGISTRY.get("executor_step_ms")
        hc0 = h.count()
        fetch0 = REGISTRY.get("executor_fetch_ms").count()
        cost.reset()
        _build_and_run(steps=3)
        assert REGISTRY.get("executor_steps_total").value() == steps0 + 3
        assert h.count() == hc0 + 3
        assert REGISTRY.get("executor_fetch_ms").count() == fetch0 + 3
        # lazy cost analysis on the compiled step's first execution
        assert cost.flops_per_step() > 0
        flops = REGISTRY.get("segment_flops")
        assert any(v > 0 for v in flops.samples().values())
        # a device with no entry in the peaks table gets no MFU line
        assert "MFU estimate" not in profiler.summary()
        monkeypatch.setattr(cost, "_device_kind", lambda: "TPU v5 lite")
        assert profiler.summary().count("MFU estimate") == 1

    def test_startup_run_not_counted_as_step(self):
        steps0 = REGISTRY.get("executor_steps_total").value()
        pt.enable_static()
        try:
            main, startup = pt.Program(), pt.Program()
            with pt.program_guard(main, startup):
                x = pt.static.data("x", [2], dtype="float32")
                pt.layers.fc(x, size=2)
            scope = pt.static.Scope()
            with pt.static.scope_guard(scope):
                pt.static.Executor().run(startup)
        finally:
            pt.disable_static()
        assert REGISTRY.get("executor_steps_total").value() == steps0

    def test_retrace_counter_mirrors_trace_count(self):
        r0 = REGISTRY.get("executor_retraces_total").value()
        exe = _build_and_run(steps=2)
        assert REGISTRY.get("executor_retraces_total").value() - r0 \
            == exe.trace_count

    def test_cost_flag_off_does_not_latch(self):
        """FLAGS_monitor_cost=0 at a step's first execution must not
        permanently disable cost recording for that compiled step."""
        from paddle_tpu.core.flags import set_flags
        cost.reset()
        pt.enable_static()
        try:
            main, startup = pt.Program(), pt.Program()
            with pt.program_guard(main, startup):
                x = pt.static.data("x", [4], dtype="float32")
                pred = pt.layers.fc(x, size=1)
            scope = pt.static.Scope()
            with pt.static.scope_guard(scope):
                exe = pt.static.Executor()
                exe.run(startup)
                xv = np.zeros((8, 4), np.float32)
                set_flags({"FLAGS_monitor_cost": False})
                exe.run(main, feed={"x": xv}, fetch_list=[pred])
                assert cost.flops_per_step() == 0
                set_flags({"FLAGS_monitor_cost": True})
                exe.run(main, feed={"x": xv}, fetch_list=[pred])
                assert cost.flops_per_step() > 0
        finally:
            set_flags({"FLAGS_monitor_cost": True})
            pt.disable_static()

    def test_prefetch_metrics_move(self):
        from paddle_tpu.static.executor import background_prefetch
        items0 = REGISTRY.get("prefetch_items_total").value()
        out = list(background_prefetch(iter(range(17)),
                                       lambda v: v + 1, depth=2))
        assert out == list(range(1, 18))
        assert REGISTRY.get("prefetch_items_total").value() \
            == items0 + 17


class TestCheckpointMetrics:
    def test_save_moves_counters(self, tmp_path):
        from paddle_tpu.io_checkpoint import CheckpointManager
        saves0 = REGISTRY.get("checkpoint_saves_total").value()
        bytes0 = REGISTRY.get("checkpoint_bytes_total").value()
        ms0 = REGISTRY.get("checkpoint_save_ms").count()
        mgr = CheckpointManager(str(tmp_path), async_save=False,
                                save_interval_steps=1)
        mgr.save(1, {"w": np.zeros(64, np.float32)})
        mgr.close()
        assert REGISTRY.get("checkpoint_saves_total").value() \
            == saves0 + 1
        assert REGISTRY.get("checkpoint_bytes_total").value() \
            == bytes0 + 256
        assert REGISTRY.get("checkpoint_save_ms").count() == ms0 + 1

    def test_auto_checkpoint_exports_snapshot_under_supervisor(
            self, tmp_path, monkeypatch):
        """A plain auto_checkpoint job under the launcher env leaves a
        metrics snapshot without any per-script wiring."""
        from paddle_tpu.io_checkpoint import auto_checkpoint
        hb = tmp_path / "hb"
        monkeypatch.setenv(health.ENV_DIR, str(hb))
        monkeypatch.setenv(health.ENV_RANK, "0")
        monkeypatch.setenv("PADDLE_RESTART_COUNT", "1")
        out = auto_checkpoint(str(tmp_path / "ck"), lambda: {"w": 0.0},
                              3, lambda s, st: {"w": st["w"] + 1.0},
                              save_interval_steps=1)
        assert out["w"] == 3.0
        snap = open(health.metrics_path(str(hb), 0)).read()
        _, samples = exporter.parse_text(snap)
        assert samples[("restarts_total", ())] >= 1.0
        assert samples[("checkpoint_saves_total", ())] >= 3.0

    def test_retry_counter(self, tmp_path):
        from paddle_tpu.io_checkpoint import CheckpointManager

        class Flaky(CheckpointManager):
            retry_backoff = 0.01
            fails = 2

            def _write(self, payload):
                if self.fails:
                    self.fails -= 1
                    raise OSError(28, "injected")
                return super()._write(payload)

        r0 = REGISTRY.get("checkpoint_retries_total").value()
        mgr = Flaky(str(tmp_path), async_save=False,
                    save_interval_steps=1)
        mgr.save(1, {"w": 1.0})
        mgr.close()
        assert REGISTRY.get("checkpoint_retries_total").value() \
            == r0 + 2


# ---------------------------------------------------------------------------
class TestProfilerSatellites:
    def test_event_ring_capped(self):
        profiler.reset_profiler()
        prev = profiler.set_max_events(100)
        try:
            profiler.start_profiler()
            for _ in range(500):
                with profiler.RecordEvent("spin"):
                    pass
            profiler.stop_profiler()
            from paddle_tpu.profiler import _events
            assert len(_events) == 100
        finally:
            profiler.set_max_events(prev)
            profiler.reset_profiler()

    def test_warn_once_is_once_per_key(self):
        import warnings

        from paddle_tpu.core.enforce import warn_once
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            warn_once.reset_for_tests("t_key_a")
            warn_once.reset_for_tests("t_key_b")
            assert warn_once("t_key_a", "first")
            assert not warn_once("t_key_a", "second")
            assert warn_once("t_key_b", "other")
        assert [str(x.message) for x in w] == ["first", "other"]

    def test_warn_once_reset_for_tests(self):
        """The test-visible reset hook: after reset, the same key warns
        again — so pytest.warns assertions on once-per-process shims no
        longer depend on being the process's first caller."""
        import warnings

        from paddle_tpu.core.enforce import warn_once
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert warn_once("t_reset_key", "one")
            assert not warn_once("t_reset_key", "suppressed")
            warn_once.reset_for_tests("t_reset_key")
            assert warn_once("t_reset_key", "again")
            # keyless reset clears everything
            warn_once.reset_for_tests()
            assert warn_once("t_reset_key", "third")
        assert [str(x.message) for x in w] == ["one", "again", "third"]

    def test_once_only_shims_route_through_warn_once(self):
        """cuda_profiler and the compile-cache mid-process path dedupe
        via warn_once keys; the reset hook makes the firing assertable
        regardless of which test invoked the shim first."""
        import warnings

        from paddle_tpu.core import compile_cache, enforce
        from paddle_tpu.core.enforce import warn_once
        warn_once.reset_for_tests("cuda_profiler")
        with pytest.warns(UserWarning, match="cuda_profiler is a no-op"):
            with profiler.cuda_profiler():
                pass
        assert "cuda_profiler" in enforce._warned_keys
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with profiler.cuda_profiler():     # second call: silent
                pass
        assert compile_cache._mid_process()  # jax backend is up here

    def test_chrome_trace_invariants_and_flows(self, tmp_path):
        profiler.reset_profiler()
        profiler.start_profiler()
        _build_and_run(steps=3)
        profiler.stop_profiler()
        path = profiler.export_chrome_trace(str(tmp_path / "t.json"))
        profiler.reset_profiler()
        with open(path) as f:
            trace = json.load(f)
        evs = trace["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in metas)
        assert any(e["name"] == "thread_name" for e in metas)
        slices = [e for e in evs if e["ph"] == "X"]
        assert slices, "no spans exported"
        for e in slices:
            assert "pid" in e and "tid" in e
        by_tid = {}
        for e in slices:
            by_tid.setdefault(e["tid"], []).append(e["ts"])
        for ts_list in by_tid.values():
            assert ts_list == sorted(ts_list), "ts not monotonic per tid"
        # flow events pair dispatch -> fetch with matching ids
        starts = {e["id"] for e in evs
                  if e["ph"] == "s" and e["name"] == "dispatch->fetch"}
        finishes = {e["id"] for e in evs
                    if e["ph"] == "f" and e["name"] == "dispatch->fetch"}
        assert len(starts) == 3 and finishes and finishes <= starts
        # steps/s counter track from consecutive dispatches
        rates = [e for e in evs
                 if e["ph"] == "C" and e["name"] == "steps/s"]
        assert len(rates) == 2
        assert all(e["args"]["steps/s"] > 0 for e in rates)


# ---------------------------------------------------------------------------
class TestHealthEdgeCases:
    def test_stale_ranks_dir_deleted_mid_scan(self, tmp_path):
        d = tmp_path / "hb"
        d.mkdir()
        health.Heartbeat(str(d), 0, interval=0.0).beat()
        real = health.last_beat

        def racy(dirname, rank):
            # rank 0 resolves, then the dir vanishes before rank 1
            out = real(dirname, rank)
            if rank == 0:
                import shutil
                shutil.rmtree(dirname, ignore_errors=True)
            return out

        try:
            health.last_beat = racy
            assert health.stale_ranks(str(d), 3, timeout=3600) == []
        finally:
            health.last_beat = real
        assert health.silent_ranks(str(d), 2) == [0, 1]
        assert health.stale_ranks(str(d), 2, timeout=0.0) == []

    def test_zero_byte_heartbeat_counts_by_mtime(self, tmp_path):
        p = health.heartbeat_path(str(tmp_path), 0)
        open(p, "w").close()                      # zero-byte beat
        assert os.path.getsize(p) == 0
        assert health.stale_ranks(str(tmp_path), 1, timeout=3600) == []
        old = time.time() - 60
        os.utime(p, (old, old))
        stale = health.stale_ranks(str(tmp_path), 1, timeout=5.0)
        assert [r for r, _ in stale] == [0]
        assert health.silent_ranks(str(tmp_path), 1) == []

    def test_metrics_path_beside_heartbeat(self, tmp_path):
        hb = health.heartbeat_path(str(tmp_path), 3)
        mp = health.metrics_path(str(tmp_path), 3)
        assert os.path.dirname(hb) == os.path.dirname(mp)
        assert mp.endswith("rank3.prom")


# ---------------------------------------------------------------------------
class TestMetricsCatalogueLint:
    def test_tree_and_docs_in_sync(self):
        assert check_metrics.main() == 0

    def test_lint_detects_drift(self, tmp_path):
        pkg = tmp_path / "paddle_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            'c = counter(\n    "t_undocumented_total", "x")\n'
            'g = _gauge("t_aliased", "x")\n'
            'x = counter("t_conflicted", "x")\n'
            'y = gauge("t_conflicted", "x")\n')
        (tmp_path / "bench.py").write_text("")
        names = check_metrics.code_metrics(repo=str(tmp_path))
        # name -> kinds seen: aliased factories (_gauge) included, and
        # two sites disagreeing on a kind surface as a 2-element set
        assert names == {"t_undocumented_total": {"counter"},
                         "t_aliased": {"gauge"},
                         "t_conflicted": {"counter", "gauge"}}
        doc = tmp_path / "doc.md"
        doc.write_text("| `t_documented_total` | counter | – | x |\n"
                       "| `t_aliased` | histogram | – | wrong kind |\n")
        assert check_metrics.doc_metrics(str(doc)) == \
            {"t_documented_total": "counter", "t_aliased": "histogram"}

    def test_outcome_vocabulary_lint(self, tmp_path):
        """Every outcome=-labeled counter must document its FULL label
        vocabulary in the catalogue row: the values are gathered from
        the registering file's outcome="..." keywords, and a row
        missing one (a new outcome added in code but not docs) fails
        the lint."""
        pkg = tmp_path / "paddle_tpu"
        pkg.mkdir()
        # t_plain_total sits immediately BEFORE the outcome-labeled
        # registration: its scan window must stop at the next
        # registration and never swallow the neighbor's
        # labels=("outcome",) (that misclassification would demand
        # the neighbor's vocabulary in t_plain_total's doc row)
        (pkg / "m.py").write_text(
            'plain = counter("t_plain_total", "no labels")\n'
            'c = counter("t_reqs_total", "by outcome",\n'
            '            labels=("outcome",))\n'
            'c.inc(outcome="ok")\n'
            'c.inc(outcome="deadline")\n'
            'd = counter("t_other_total", "also by outcome",\n'
            '            labels=("outcome",))\n'
            'd.inc(outcome="hit")\n')
        (tmp_path / "bench.py").write_text("")
        vocab = check_metrics.outcome_vocabularies(repo=str(tmp_path))
        # the vocabulary is the registering FILE's union — coarse on
        # purpose: a value reaching inc() through a helper variable is
        # still caught at its literal call site, where finer
        # attribution would let it escape the lint. The plain neighbor
        # just before t_reqs_total is never misclassified by window
        # bleed (it gets NO vocabulary).
        assert vocab == {"t_reqs_total": {"ok", "deadline", "hit"},
                         "t_other_total": {"ok", "deadline", "hit"}}
        doc = tmp_path / "doc.md"
        doc.write_text(
            "| `t_reqs_total` | counter | `outcome` | `ok` only |\n")
        rows = check_metrics.doc_rows(str(doc))
        missing = sorted((n, v) for n, vs in vocab.items()
                         for v in sorted(vs)
                         if f"`{v}`" not in rows.get(n, ""))
        # t_reqs_total's row lacks `deadline` (and the union's `hit`)
        assert ("t_reqs_total", "deadline") in missing
        # the real tree is clean (main() green is pinned above); the
        # serving counter's row must carry the full vocabulary
        real = check_metrics.outcome_vocabularies()
        assert {"ok", "rejected", "error", "deadline", "shed"} <= \
            real["serving_requests_total"]


# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.timeout(600)
class TestTelemetryEndToEnd:
    """The acceptance run: 2 ranks, rank 1 hangs mid-training -> the
    watchdog kills and restarts the gang -> the job finishes, the hung
    rank's flight-recorder dump names the in-flight span, and the
    surviving snapshots/status/aggregate all check out."""

    TOTAL = 12

    def test_hang_leaves_postmortem_and_metrics(self, tmp_path, capfd):
        from paddle_tpu.distributed.launch import launch_collective
        prefix = tmp_path / "mon.out"
        log_dir = tmp_path / "logs"
        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO + os.pathsep + os.environ.get(
                "PYTHONPATH", ""),
            "PT_FAULT_HANG_AT_STEP": "3",
            "PT_FAULT_RANK": "1",
            "PT_FAULT_ONCE_DIR": str(tmp_path / "once"),
        }
        rc = launch_collective(
            [WORKER, str(prefix), str(self.TOTAL), "0.1"],
            nproc=2, log_dir=str(log_dir), env_extra=env,
            timeout=240, max_restarts=2, hang_timeout=3.0,
            grace_period=5.0)
        err = capfd.readouterr().err

        def logs():
            out = err
            for p in sorted(log_dir.glob("*.log")):
                out += f"\n--- {p.name} ---\n" + p.read_text()[-2000:]
            return out

        assert rc == 0, logs()
        assert "watchdog" in err
        assert "status step=" in err        # the periodic job one-liner

        # -- postmortem: the hung rank dumped, naming its stuck span --
        pm = log_dir / "postmortem"
        dumps = sorted(pm.glob("rank1.*.json"))
        assert dumps, f"no rank1 postmortem in {pm}: " \
            f"{sorted(os.listdir(pm))}\n{logs()}"
        doc = json.loads(dumps[0].read_text())
        names = [s["name"] for s in doc["in_flight_spans"]]
        assert "train/step" in names, doc
        assert doc["reason"] == "sigterm"
        assert any(e["kind"] == "step" for e in doc["events"])

        # -- surviving rank's /metrics snapshot parses + key series --
        snap = (log_dir / "heartbeat" / "rank0.prom").read_text()
        types, samples = exporter.parse_text(snap)
        assert types["executor_step_ms"] == "histogram"
        steps = samples[("executor_steps_total", ())]
        assert steps >= self.TOTAL
        assert any(n == "executor_step_ms_bucket"
                   for (n, _l) in samples)
        assert samples[("restarts_total", ())] == 1.0
        seg = [v for (n, _l), v in samples.items()
               if n == "segment_flops"]
        assert seg and max(seg) > 0

        # -- job-level aggregate + worker reports ---------------------
        assert (log_dir / "metrics.prom").exists()
        exporter.parse_text((log_dir / "metrics.prom").read_text())
        for rank in (0, 1):
            rep = json.loads(
                (tmp_path / f"mon.out.rank{rank}.json").read_text())
            assert rep["steps"] == self.TOTAL
            # CPU workers: no entry in the peaks table, so no MFU line
            assert "MFU estimate" not in rep["summary"]
            assert rep["restart_count"] == 1
