"""Smoke tests for the r5 bench modes (int8, serving): each mode must
run end-to-end on the CPU backend and emit well-formed JSON metric
lines. Guards the bench CLI against API drift — the driver runs these
modes on the real chip, where an import error or renamed kwarg would
otherwise only surface at capture time."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_mode(mode, timeout=600, extra_env=None):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "BENCH_WINDOWS": "2",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.update(extra_env or {})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), mode],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, r.stdout[-500:]
    # every mode exits through bench.main's finally hook, which samples
    # device memory once and emits peak_hbm_bytes — assert it here so a
    # mode can't silently lose the field
    peaks = [ln for ln in lines if ln.get("metric") == "peak_hbm_bytes"]
    assert peaks, r.stdout[-500:]
    assert peaks[-1]["unit"] == "bytes" and peaks[-1]["value"] >= 0
    assert "sampled_continuously" in peaks[-1]
    return lines


class TestBenchModes:
    def test_int8_mode_emits_speedup_rows(self):
        lines = _run_mode("int8")
        metrics = {ln["metric"] for ln in lines}
        assert any(m.startswith("int8_mlp") for m in metrics)
        assert any(m.startswith("int8_resnet50convs") for m in metrics)
        assert any(m.startswith("int8_bert_layer") for m in metrics)
        for ln in lines:
            if not ln["metric"].startswith("int8_"):
                continue        # e.g. the mode-agnostic peak_hbm_bytes
            assert ln["unit"] == "x" and ln["value"] > 0
            assert ln["int8_ms"] > 0 and ln["bf16_ms"] > 0

    def test_serving_mode_emits_openloop_rows(self, tmp_path):
        """`bench.py serving` must drive OPEN-LOOP Poisson load through
        both the single-request Predictor baseline and the
        micro-batching InferenceServer at equal offered load, emit
        well-formed QPS/latency/fill JSON lines, and land the
        serving_* metrics in the registry snapshot (tiny request
        count: CLI/shape smoke — the honest QPS comparison runs with
        the full default load)."""
        metrics_out = str(tmp_path / "serving_metrics.prom")
        lines = _run_mode("serving",
                          extra_env={"BENCH_SERVING_REQS": "40",
                                     "BENCH_SERVING_TRACE_PAIRS": "2",
                                     "BENCH_SERVING_TRACE_WIN": "60",
                                     "BENCH_SERVING_MEM_PAIRS": "2",
                                     "BENCH_SERVING_GOODPUT_PAIRS": "2",
                                     "BENCH_METRICS_OUT": metrics_out})
        by = {ln["metric"]: ln for ln in lines}
        for tag in ("serving_baseline_qps", "serving_server_qps"):
            row = by.get(tag)
            assert row is not None, by.keys()
            assert row["value"] > 0 and row["unit"] == "req/s"
            assert row["offered_qps"] > 0
            assert row["p50_ms"] > 0
            assert row["p50_ms"] <= row["p99_ms"]
        srv = by["serving_server_qps"]
        assert srv["max_batch"] >= 1
        assert 0 < srv["batch_fill_ratio"] <= 1.0
        ratio = by["serving_server_vs_baseline_qps"]
        assert ratio["unit"] == "x" and ratio["value"] > 0
        # p99 attribution: traced open-loop pass must split the
        # slowest decile's time into phase shares that sum sanely
        attr = by["serving_p99_attribution"]
        assert attr["unit"] == "ms" and attr["value"] > 0
        assert attr["n_slowest"] >= 1
        shares = [attr[k] for k in
                  ("queue_wait_share", "batch_form_share",
                   "dispatch_wait_share", "execute_share",
                   "deliver_share")]
        assert all(s is not None and 0 <= s <= 1 for s in shares), attr
        assert sum(shares) > 0.3, attr       # phases cover the latency
        # tracing overhead: interleaved ABBA open-loop p50 A/B must
        # stay within 1.05x (the ISSUE's hot-path-cheapness bound)
        ov = by["serving_trace_overhead_ratio"]
        assert ov["unit"] == "x" and ov["value"] > 0
        assert ov["value"] < 1.05, ov
        assert ov["traced_p50_ms"] > 0 and ov["untraced_p50_ms"] > 0
        # HBM-poller overhead: same ABBA protocol, poller on vs off —
        # sampled live-array accounting must stay inside the 1.05x
        # hot-path bound on the serving path
        mem = by["memory_overhead_ratio"]
        assert mem["path"] == "serving" and mem["unit"] == "x"
        assert mem["value"] < 1.05, mem
        assert mem["polled_p50_ms"] > 0 and mem["unpolled_p50_ms"] > 0
        assert len(mem["pair_ratios"]) >= 2
        # goodput-ledger overhead: armed vs disarmed on the same ABBA
        # protocol — wall-clock attribution must stay inside the same
        # 1.05x hot-path bound
        gp = by["goodput_overhead_ratio"]
        assert gp["path"] == "serving" and gp["unit"] == "x"
        assert gp["value"] < 1.05, gp
        assert gp["armed_p50_ms"] > 0 and gp["disarmed_p50_ms"] > 0
        assert len(gp["pair_ratios"]) >= 2
        with open(metrics_out) as f:
            snap = f.read()
        for name in ("serving_requests_total", "serving_queue_depth",
                     "serving_batch_fill_ratio",
                     "serving_padded_waste_total",
                     "serving_request_latency_ms",
                     "trace_spans_total", "trace_traces_kept_total"):
            assert name in snap, f"{name} missing from snapshot"

    def test_serving_chaos_mode_emits_resilience_rows(self):
        """`bench.py serving` with BENCH_SERVING_CHAOS=1 must run the
        resilience A/Bs end to end (tiny request count: CLI/shape
        smoke): the one-replica-stall p99 ratio with zero hangs and a
        respawn, the shed-precision row (precision in [0,1] when
        anything shed; the control pass must observe misses under the
        sustained overload), and the shed controller's clean-path
        ABBA overhead under the 1.05x bound."""
        lines = _run_mode("serving",
                          extra_env={"BENCH_SERVING_CHAOS": "1",
                                     "BENCH_SERVING_CHAOS_REQS": "40",
                                     "BENCH_SERVING_SHED_PAIRS": "2",
                                     "BENCH_SERVING_SHED_WIN": "40"})
        by = {ln["metric"]: ln for ln in lines}
        chaos = by["serving_chaos_p99_ratio"]
        assert chaos["unit"] == "x" and chaos["value"] > 0
        assert chaos["clean_p99_ms"] > 0
        assert chaos["chaos_p99_ok_ms"] > 0
        assert chaos["hangs"] == 0, chaos       # zero hangs, always
        assert chaos["lost_requests"] >= 1, chaos
        assert chaos["respawns"] >= 1, chaos
        shed = by["serving_shed_precision"]
        assert shed["n_missed_control"] > 0, shed
        if shed["n_shed"] > 0:
            assert 0.0 <= shed["value"] <= 1.0, shed
        else:
            assert shed["value"] is None
        ov = by["serving_shed_overhead_ratio"]
        assert ov["unit"] == "x" and ov["value"] > 0
        assert ov["value"] < 1.05, ov
        assert len(ov["pair_ratios"]) >= 2

    def test_serving_swap_mode_emits_swap_rows(self):
        """`bench.py serving` with BENCH_SERVING_SWAP=1 must run one
        open-loop schedule with a mid-run hot swap (tiny request
        count: CLI/shape smoke) and emit the swap-window p99 ratio
        and cutover-blip rows: swap committed (outcome ok), zero
        hangs, both request groups populated."""
        lines = _run_mode("serving",
                          extra_env={"BENCH_SERVING_SWAP": "1",
                                     "BENCH_SERVING_SWAP_REQS": "60"})
        by = {ln["metric"]: ln for ln in lines}
        ratio = by["serving_swap_p99_ratio"]
        assert ratio["unit"] == "x"
        assert ratio["outcome"] == "ok", ratio
        assert ratio["hangs"] == 0, ratio
        assert ratio["n_overlap"] >= 1 and ratio["n_steady"] >= 1
        assert ratio["value"] is not None and ratio["value"] > 0
        assert ratio["p99_overlap_ms"] > 0
        assert ratio["p99_steady_ms"] > 0
        assert ratio["swap_ms"] > 0
        blip = by["serving_swap_blip_ms"]
        assert blip["unit"] == "ms" and blip["value"] >= 0
        assert blip["swap_window_ms"] > 0

    def test_serving_http_mode_emits_wire_ratio(self):
        """`bench.py serving` with BENCH_SERVING_HTTP=1 must run the
        front-door wire-vs-in-process A/B end to end (tiny request
        count: CLI/shape smoke) and emit the
        serving_http_vs_inproc_p99_ratio row: ABBA pair ratios
        populated, both window p99s measured, every wire request
        accounted (the window asserts internally — a hang or an
        untyped status fails the subprocess)."""
        lines = _run_mode("serving",
                          extra_env={"BENCH_SERVING_HTTP": "1",
                                     "BENCH_SERVING_HTTP_REQS": "30",
                                     "BENCH_SERVING_HTTP_PAIRS": "1",
                                     "BENCH_SERVING_HTTP_CONNS": "4"})
        by = {ln["metric"]: ln for ln in lines}
        ratio = by["serving_http_vs_inproc_p99_ratio"]
        assert ratio["unit"] == "x" and ratio["value"] > 0
        assert ratio["http_p99_ms"] > 0
        assert ratio["inproc_p99_ms"] > 0
        assert len(ratio["pair_ratios"]) >= 1
        assert ratio["n_per_window"] == 30
        assert ratio["client_conns"] == 4

    def test_dispatch_mode_emits_trace_overhead_and_attribution(self):
        """`bench.py dispatch` must A/B per-step tracing on ABBA
        micro-windows and attribute the slowest decile of traced steps
        to prepare/dispatch/fetch shares. A liveness check: the mode
        runs and every row is well formed. The ratios are CPU wall
        clock, taken while the other xdist workers share the cores
        (1.06 and 1.11 were seen there against 0.97 to 1.03 alone), so
        none is held to a bound here: ROADMAP's north star on CPU
        timings, and C, "Tier-1"."""
        lines = _run_mode("dispatch",
                          extra_env={"BENCH_DISPATCH_STEPS": "10",
                                     "BENCH_DISPATCH_TRACE_PAIRS": "6",
                                     "BENCH_DISPATCH_TRACE_WIN": "8",
                                     "BENCH_DISPATCH_MEM_PAIRS": "2",
                                     "BENCH_DISPATCH_GOODPUT_PAIRS":
                                     "2",
                                     "XLA_FLAGS":
                                     "--xla_force_host_platform_"
                                     "device_count=8"},
                          )
        by = {ln["metric"]: ln for ln in lines}
        ov = by["dispatch_trace_overhead_ratio"]
        assert ov["unit"] == "x" and ov["value"] > 0
        assert ov["traced_ms_per_step"] > 0
        # >= the base pair count (the bench gathers more pairs when
        # the first estimate straddles its bound)
        assert len(ov["pair_ratios"]) >= 6
        assert all(r > 0 for r in ov["pair_ratios"])
        attr = by["dispatch_p99_attribution"]
        assert attr["value"] > 0 and attr["n_slowest"] >= 1
        assert attr["dispatch_share"] is not None \
            and 0 < attr["dispatch_share"] <= 1, attr
        assert attr["prepare_share"] is not None \
            and 0 <= attr["prepare_share"] <= 1
        # HBM-poller overhead on the dispatch hot path — same ABBA
        # protocol as the serving-side check
        mem = by["memory_overhead_ratio"]
        assert mem["path"] == "dispatch" and mem["unit"] == "x"
        assert mem["value"] > 0
        assert mem["polled_ms_per_step"] > 0
        assert mem["unpolled_ms_per_step"] > 0
        # goodput-ledger overhead on the dispatch hot path — armed vs
        # disarmed ABBA windows
        gp = by["goodput_overhead_ratio"]
        assert gp["path"] == "dispatch" and gp["unit"] == "x"
        assert gp["value"] > 0
        assert gp["armed_ms_per_step"] > 0
        assert gp["disarmed_ms_per_step"] > 0
        assert len(gp["pair_ratios"]) >= 2

    def test_numerics_mode_emits_overhead_ratio(self):
        """`bench.py numerics` must A/B the check_nan_inf sentinels on
        interleaved windows and emit a well-formed ratio line (the
        real overhead measurement runs with full windows; this is the
        CLI/shape smoke)."""
        lines = _run_mode("numerics",
                          extra_env={"BENCH_NUMERICS_STEPS": "15",
                                     "BENCH_NUMERICS_PAIRS": "2"})
        (row,) = [ln for ln in lines
                  if ln["metric"] == "numerics_check_overhead_ratio"]
        assert row["unit"] == "x" and row["value"] > 0
        assert row["check_on_ms_per_step"] > 0
        assert row["check_off_ms_per_step"] > 0
        assert len(row["pair_ratios"]) == 2
        assert all(r > 0 for r in row["pair_ratios"])

    def test_shard_mode_emits_per_topology_rows(self):
        """`bench.py shard` must sweep every topology (1-device tiny
        config here: each collapses to a 1x1 mesh but the whole
        spec->pjit->compile->measure path runs) and emit one JSON line
        per topology carrying ms/step, MFU, and comm bytes — so the
        mode can't rot between MULTICHIP runs."""
        lines = _run_mode("shard", extra_env={
            "BENCH_SHARD_STEPS": "2",
            "BENCH_SHARD_LAYERS": "2",
            "BENCH_SHARD_HIDDEN": "32",
            "BENCH_SHARD_FFN": "64",
            "BENCH_SHARD_SEQ": "16",
            "BENCH_SHARD_VOCAB": "64",
            "BENCH_SHARD_HEADS": "2",
            "BENCH_SHARD_MICRO": "2",
            "BENCH_SHARD_BATCH": "4",
        })
        by = {ln["metric"]: ln for ln in lines}
        for topo in ("dp", "modelxdata", "pipexdata"):
            row = by.get(f"shard_{topo}_step_ms")
            assert row is not None, by.keys()
            assert row["value"] > 0 and row["unit"] == "ms"
            # the CPU has no peak on record (monitor/cost.PEAK_FLOPS): no MFU
            assert row["mfu"] is None
            assert "comm_bytes_per_step" in row
            assert row["layout"]["n_devices"] == 1
            assert len(row["windows_ms_per_step"]) >= 2

    def test_data_mode_emits_loader_ab_and_h2d_rows(self):
        """`bench.py data` must A/B the native-stateful loader against
        the Python oracle on interleaved pairs, report the stateless
        reference row, and A/B the device-side double buffer (tiny
        dataset: CLI/shape smoke — the honest >= 2x number runs with
        the defaults)."""
        lines = _run_mode("data", extra_env={
            "BENCH_DATA_FILES": "2",
            "BENCH_DATA_ROWS": "3000",
            "BENCH_DATA_BATCH": "64",
            "BENCH_DATA_BATCHES": "10",
            "BENCH_DATA_PAIRS": "2",
            "BENCH_DATA_SHUFFLE": "128",
        })
        by = {ln["metric"]: ln for ln in lines}
        for tag in ("data_native_stateful_records_per_sec",
                    "data_python_stateful_records_per_sec",
                    "data_stateless_records_per_sec"):
            row = by.get(tag)
            assert row is not None, by.keys()
            assert row["value"] > 0 and row["unit"] == "rec/s"
        ratio = by["data_native_vs_python_ratio"]
        assert ratio["unit"] == "x" and ratio["value"] > 0
        assert len(ratio["pair_ratios"]) == 2
        h2d = by["data_h2d_overlap_ratio"]
        assert h2d["unit"] == "x" and h2d["value"] > 0
        assert h2d["on_ms_per_step"] > 0
        assert h2d["off_ms_per_step"] > 0

    def test_ckpt_mode_emits_save_restore_and_verify_ratio(self):
        """`bench.py ckpt` must time save/restore on a real
        CheckpointManager and A/B digest verification on interleaved
        restore windows (small payload: CLI/shape smoke; the real
        overhead number runs with the default 64 MB)."""
        lines = _run_mode("ckpt", extra_env={"BENCH_CKPT_MB": "4",
                                             "BENCH_CKPT_PAIRS": "2"})
        by = {ln["metric"]: ln for ln in lines}
        save = by["ckpt_save_ms"]
        assert save["value"] > 0 and save["save_mb_per_sec"] > 0
        assert save["payload_mb"] > 3
        restore = by["ckpt_restore_ms"]
        assert restore["verify_on_ms"] > 0
        assert restore["verify_off_ms"] > 0
        ratio = by["ckpt_verify_overhead_ratio"]
        assert ratio["unit"] == "x" and ratio["value"] > 0
        assert len(ratio["pair_ratios"]) == 2

    def test_passes_mode_emits_ratio_and_evidence(self, tmp_path):
        """`bench.py passes` must A/B the pass pipeline on/off over
        both models (tiny windows: CLI/shape smoke — the <= 1.0x
        acceptance ratio runs with the on-chip defaults), prove the
        optimized program computes the same fetches, report nonzero
        ops-removed on the BERT trunk, and land the program_pass_*
        metrics in the registry snapshot."""
        metrics_out = str(tmp_path / "passes_metrics.prom")
        lines = _run_mode("passes",
                         extra_env={"BENCH_PASSES_STEPS": "3",
                                    "BENCH_PASSES_PAIRS": "1",
                                    "BENCH_METRICS_OUT": metrics_out})
        by = {ln["metric"]: ln for ln in lines}
        for tag in ("passes_step_ratio_serving_mlp",
                    "passes_step_ratio_bert_trunk"):
            row = by.get(tag)
            assert row is not None, by.keys()
            assert row["unit"] == "x" and row["value"] > 0
            assert row["on_ms_per_step"] > 0
            assert row["off_ms_per_step"] > 0
            assert row["outputs_match"] is True, row
            assert row["ops_before"] > row["ops_after"]
            per_pass = {p["pass"]: p for p in row["per_pass"]}
            assert "fuse_matmul_bias_act" in per_pass, row
            # satellite evidence: the live compile runs under
            # FLAGS_pass_cost_evidence, so per-pass predicted
            # FLOPs/bytes deltas ride the row
            deltas = row["pass_cost_deltas"]
            assert deltas, row
            for d in deltas.values():
                assert set(d) == {"flops_delta", "bytes_delta"}
        trunk = by["passes_step_ratio_bert_trunk"]
        assert trunk["ops_removed"] > 0, trunk
        head = by["passes_step_ratio"]
        assert head["unit"] == "x" and head["value"] > 0
        assert head["vs_baseline"] > 0
        with open(metrics_out) as f:
            snap = f.read()
        for name in ("program_pass_runs_total",
                     "program_pass_ops_removed_total",
                     "program_pass_ms",
                     "program_pass_flops_delta",
                     "program_pass_bytes_delta"):
            assert name in snap, f"{name} missing from snapshot"

    def test_serving_quant_mode_emits_ab_rows(self):
        """`bench.py serving` with BENCH_SERVING_QUANT=1 must freeze a
        same-weights fp/int8 pair, serve both under one open-loop
        schedule (tiny request count: CLI/shape smoke) and emit the
        QPS rows, the resident-param-bytes ratio (int8 must be well
        under the 0.55x acceptance bar even on the small MLP) and a
        small fixture accuracy delta."""
        lines = _run_mode("serving",
                         extra_env={"BENCH_SERVING_QUANT": "1",
                                    "BENCH_SERVING_QUANT_REQS": "40"})
        by = {ln["metric"]: ln for ln in lines}
        for tag in ("serving_fp_qps", "serving_quant_qps"):
            row = by.get(tag)
            assert row is not None, by.keys()
            assert row["value"] > 0 and row["unit"] == "req/s"
            assert row["param_bytes"] > 0
            assert row["p50_ms"] > 0
            assert row["p50_ms"] <= row["p99_ms"]
        assert by["serving_quant_qps"]["quantize"] == "int8"
        assert (by["serving_quant_qps"]["param_bytes"]
                < by["serving_fp_qps"]["param_bytes"])
        ratio = by["serving_quant_vs_fp_qps"]
        assert ratio["unit"] == "x" and ratio["value"] > 0
        pb = by["serving_quant_param_bytes_ratio"]
        assert 0 < pb["value"] <= 0.55, pb
        acc = by["serving_quant_accuracy_delta"]
        assert acc["unit"] == "rel"
        # per-channel int8 weight-only on a 3-layer MLP: relative
        # output error stays at the percent level
        assert 0 <= acc["value"] < 0.05, acc
