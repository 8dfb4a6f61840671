"""CPU rehearsal of the harness: the same ``run_cell`` the command calls, on
fixture configuration and traffic files at toy size.

The fixture benchmark (``fixtures/benchmark.json``) also shows what a later
PR may do without editing a file under ``chipbench/``: it lists a second
directory in ``paths`` that brings configurations, traffic mixes and one more
per-layer metric (``steps_completed``) as files of their own.
"""

import json

import numpy as np
import pytest

from chipbench import run
from chipbench.catalog import ROOT, Catalog

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
#: the CPU stands in for a chip here only: a table that has it, with no
#: device planes to demand of its trace. Never a measurement.
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12}}
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark.json")


def last_line(capsys, argv, **kw):
    run.main(argv, **kw)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", [
    "bert_toy.mlm_toy", "bert_toy.mlm_toy_dp4", "transformer_toy.wmt_toy"])
def test_untraced_line_has_exactly_the_contract_keys(workload, catalog,
                                                     capsys):
    out = last_line(capsys, ["--workload", workload, "--seed", "3",
                             "--seconds", "0.5", "--trace", "0"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert set(out) == KEYS
    assert set(out["device"]) == DEVICE_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 2
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_traced_line_reports_the_per_layer_metrics(catalog, capsys):
    """On the CPU the trace has no device plane: the metrics that read it
    return nothing and are left out; the rest, and the fixture's own
    metric, are there."""
    out = last_line(capsys, ["--workload", "bert_toy.mlm_toy", "--seed", "0",
                             "--seconds", "0.5", "--trace", "1"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert set(out) == KEYS            # + breakdown where a device traced
    assert set(out["metrics"]) == {
        "mfu_pct", "window_stall_pct", "pallas_bodies_selected",
        "step_hbm_gib", "steps_completed"}
    assert out["metrics"]["steps_completed"]["value"] == out["attempted"] - 1


def test_traced_line_with_a_device_trace_adds_breakdown_and_busy(
        catalog, capsys, monkeypatch):
    """The CPU writes no device plane, so the planes of the trace recorded on
    the v5e stand in for this run's: the line then has ``breakdown``,
    ``busy_s`` and ``window_s``, and every per-layer metric."""
    from chipbench import xplane
    recorded = xplane.load(
        FIXTURES / "traces" / "bert_toy.mlm_toy_dp4.xplane.pb.gz")
    monkeypatch.setattr(run.xplane, "load", lambda path: recorded)
    peaks = {"cpu": dict(CPU_PEAKS["cpu"], device_planes="/device:TPU:")}
    out = last_line(capsys, ["--workload", "bert_toy.mlm_toy_dp4", "--seed",
                             "0", "--seconds", "0.5", "--trace", "1"],
                    catalog=catalog, peaks=peaks)
    assert set(out) == KEYS | {"breakdown"}
    assert set(out["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in out["breakdown"].values())
    assert out["breakdown"]["device_ops"][0][0] == "all-reduce"
    assert set(out["metrics"]) == {
        m["name"] for m in catalog.metrics("per_layer",
                                           "bert_toy.mlm_toy_dp4")}
    assert out["metrics"]["collective_exposed_ms"]["value"] == \
        pytest.approx(81195.25 / 2 / 1e6)


def test_a_traced_run_takes_one_trace_and_its_readers_compile_nothing(
        catalog, capsys, monkeypatch):
    """The profiler is started once in a traced run, no reader asks jax for
    a compilation, and the trace's directory is gone when the run returns."""
    import jax
    started, counts = [], []
    start_trace, read_metrics = jax.profiler.start_trace, run.read_metrics

    def counting_start(*a, **kw):
        started.append(a[0])
        return start_trace(*a, **kw)

    def counting_read(catalog, workload, facts, compiles):
        counts.append(compiles.count)
        values = read_metrics(catalog, workload, facts, compiles)
        counts.append(compiles.count)
        return values

    monkeypatch.setattr(jax.profiler, "start_trace", counting_start)
    monkeypatch.setattr(run, "read_metrics", counting_read)
    out = last_line(capsys, ["--workload", "transformer_toy.wmt_toy",
                             "--seed", "7", "--seconds", "0.5",
                             "--trace", "1"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert out["correct"] is True
    assert len(started) == 1
    assert counts[0] > 0 and counts[0] == counts[1]
    assert not (run.OUT_DIR / "trace" / "transformer_toy.wmt_toy").exists()


def test_a_reader_that_compiles_is_refused(capsys):
    """``read_metrics`` gives no result where a reader made jax compile."""
    import types
    import jax

    class OneReader:
        def __init__(self, metric):
            self.metric = metric

        def metrics(self, group, workload):
            return [{"name": "one"}]

        def module(self, kind, name):
            return types.SimpleNamespace(metric=self.metric)

    compiles = run.CompileCounter()
    assert run.read_metrics(OneReader(lambda facts: 1.5), "cell", {},
                            compiles) == {"one": 1.5}
    with pytest.raises(SystemExit):
        run.read_metrics(
            OneReader(lambda facts: float(jax.jit(lambda x: x * 3 + 1)(2.0))),
            "cell", {}, compiles)
    assert "1 compile requests" in capsys.readouterr().out


def test_no_reader_file_runs_the_device():
    """The rule of chipbench/README.md, as far as a text can show it: no
    file under a ``layer_metrics`` directory jits, waits for the device or
    puts an array on it."""
    for spec in (None, FIXTURES / "benchmark.json",
                 FIXTURES / "benchmark_scopes.json"):
        for d in (Catalog(spec) if spec else Catalog()).dirs:
            for f in sorted((d / "layer_metrics").glob("*.py")):
                text = f.read_text()
                for call in ("jax.jit", "block_until_ready", "device_put",
                             "start_trace(", "step_fn("):
                    assert call not in text, (f, call)


def window_stall(catalog, rate, window_rate):
    return catalog.module("layer_metrics", "window_stall_pct").metric(
        {"tokens_per_s": rate, "window_tokens_per_s": window_rate})


def test_a_stall_leaves_the_rate_and_is_reported(catalog):
    """One moment in which the host was not run (0.6 s in a window of 0.1 s
    steps: the device idled, every later completion is late) leaves
    ``train_tokens_per_s`` where it was, lowers the whole-window rate, and is
    what ``window_stall_pct`` reads."""
    steady = np.arange(101) * 0.1
    stalled = steady + np.where(np.arange(101) > 40, 0.6, 0.0)
    for done, lost in ((steady, 0.0), (stalled, 0.6)):
        rate, window_rate, intervals = run.rates(done, 8192)
        assert rate == pytest.approx(81920.0)
        assert window_rate == pytest.approx(8192 * 100 / (10.0 + lost))
        assert len(intervals) == 100
        assert window_stall(catalog, rate, window_rate) == pytest.approx(
            100 * lost / (10.0 + lost), abs=1e-9)
    assert window_stall(catalog, 1.0, None) is None


@pytest.mark.parametrize("late_ms,stalls", [(0.15, 0), (3, 2), (10, 0),
                                            (10, 2), (10, 5)])
def test_the_rate_is_steady_under_late_clock_readings_and_stalls(late_ms,
                                                                 stalls):
    """Twelve windows of 115 ms steps, every completion read late by an
    exponential time of mean ``late_ms`` and ``stalls`` stalls of 50 to 600
    ms in each: the rates spread (quartile distance over median) by under a
    fifth of the 0.5% a cell is admitted with and sit within 0.1% of the
    truth, where the whole-window rate spreads by over 0.5% once there are
    stalls."""
    rs = np.random.RandomState(int(late_ms * 100) + stalls)
    step, rates, window_rates = 0.115, [], []
    for _ in range(12):
        done = np.arange(175) * step + rs.exponential(late_ms / 1e3, 175)
        for at in rs.randint(1, 174, stalls):
            done[at:] += rs.uniform(0.05, 0.6)
        rate, window_rate, _ = run.rates(done, 1.0)
        rates.append(rate * step)
        window_rates.append(window_rate * step)

    def spread(v):
        q1, q2, q3 = np.percentile(v, [25, 50, 75])
        return (q3 - q1) / q2

    assert spread(rates) < 0.001
    assert abs(np.median(rates) - 1) < 0.001
    if stalls:
        assert spread(window_rates) > 0.005


def test_the_rate_falls_back_to_the_median_interval():
    """Where most intervals are odd there are no steady stretches to take
    pairs from, and with two completions there is one interval."""
    done = np.cumsum([0.1, 0.3] * 10)
    assert run.step_seconds(done) == pytest.approx(0.2)
    assert run.step_seconds([1.0, 1.25]) == pytest.approx(0.25)


def test_a_traced_run_with_no_device_operation_gives_no_result(catalog):
    """Where the table says the device writes device planes and the trace
    has none, the run is refused."""
    peaks = {"cpu": dict(CPU_PEAKS["cpu"], device_planes="/device:TPU:")}
    with pytest.raises(SystemExit):
        run.run_cell("transformer_toy.wmt_toy", 0, 0.5, True,
                     catalog=catalog, peaks=peaks)


def test_a_metric_limited_to_other_cells_is_left_out(catalog, capsys):
    out = last_line(capsys, ["--workload", "transformer_toy.wmt_toy",
                             "--seed", "0", "--seconds", "0.5",
                             "--trace", "1"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert "steps_completed" not in out["metrics"]


def test_same_seed_same_inputs_other_seed_other_inputs(catalog):
    cell, config, traffic = catalog.cell("bert_toy.mlm_toy")
    import jax
    runner = catalog.module("runners", config["runner"])
    job = runner.build(config, traffic, jax.devices()[:1])
    a, b, c = job.pool(5), job.pool(5), job.pool(6)
    assert len(a) >= 8
    assert all(np.array_equal(x["input_ids"], y["input_ids"])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
    assert not np.array_equal(a[0]["input_ids"], a[1]["input_ids"])


def test_a_device_that_is_not_in_the_table_gives_no_result(catalog, capsys):
    """The real table has no CPU: the command's own check refuses it."""
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "bert_toy.mlm_toy", "--seed", "0",
                  "--seconds", "0.5", "--trace", "0"], catalog=catalog)
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def test_a_cell_that_wants_more_chips_than_there_are_gives_no_result(
        catalog, monkeypatch):
    import jax
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    with pytest.raises(SystemExit):
        run.run_cell("bert_toy.mlm_toy_dp4", 0, 0.5, False, catalog=catalog,
                     peaks=CPU_PEAKS)


def round_mantissa(x, bits):
    """x rounded to ``bits`` stored bits of mantissa (bfloat16 stores 7, fp8
    e4m3 stores 3), as a low-precision matmul would leave it."""
    m, e = np.frexp(np.asarray(x, np.float32))
    return np.ldexp(np.round(m * 2.0 ** (bits + 1)) / 2.0 ** (bits + 1), e)


@pytest.mark.parametrize("workload", ["bert_toy.mlm_toy",
                                      "transformer_toy.wmt_toy"])
def test_reference_comparison_fails_on_4_bits_of_mantissa(workload, catalog):
    import jax
    cell, config, traffic = catalog.cell(workload)
    job = catalog.module("runners", config["runner"]).build(
        config, traffic, jax.devices()[:1])
    reference = catalog.module("reference", config["reference"])
    params, _ = job.init_fn(jax.random.PRNGKey(0))
    sample = job.sample(0)
    loss, outputs = job.probe(params, job.place(sample))
    want = reference.loss_and_outputs(params, config, sample)
    ok, errors = run.compare((loss, outputs), want, reference.TOLERANCE)
    assert ok, errors
    # bfloat16's own 7 bits are what the program computes in, and pass
    ok, _ = run.compare((loss, round_mantissa(outputs, 7)), want,
                        reference.TOLERANCE)
    assert ok
    ok, errors = run.compare((loss, round_mantissa(outputs, 4)), want,
                             reference.TOLERANCE)
    assert not ok and errors["outputs"] > reference.TOLERANCE["outputs"], \
        errors
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]


def test_the_real_benchmark_file_resolves_every_name():
    """Every cell of BENCHMARK.json finds its configuration, traffic mix,
    runner, flops, reference and per-layer metric files."""
    catalog = Catalog()
    spec = catalog.spec
    assert spec["command"] == ["python3", "-m", "chipbench.run"]
    for w in spec["workloads"]:
        cell, config, traffic = catalog.cell(w["name"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        for kind in ("runner", "flops", "reference"):
            catalog.module(f"{kind}s" if kind == "runner" else kind,
                           config[kind])
        flops = catalog.module("flops", config["flops"])
        assert flops.flops_per_token(config, traffic) > 1e8
        for m in catalog.metrics("per_layer", w["name"]):
            assert callable(catalog.module("layer_metrics",
                                           m["name"]).metric)
    peaks = catalog.json("peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert "cpu" not in peaks
