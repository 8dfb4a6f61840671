"""CPU rehearsal of the language-model cell: ``run_cell`` on the fixture
``olmoe_toy.lm_toy`` (``fixtures/benchmark_olmoe.json``: the toy
configuration and traffic, every general per-layer metric of the real
benchmark and the five ``olmoe_1b_7b.lm_s4096`` brings), with a peaks table
that has the CPU, as ``test_rehearsal.py`` does for the other cells."""

import json

import numpy as np
import pytest

from chipbench import run
from chipbench.catalog import ROOT, Catalog
from chipbench.tests.test_rehearsal import (CPU_PEAKS, DEVICE_KEYS, KEYS,
                                            round_mantissa)

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
CELL = "olmoe_toy.lm_toy"
NEW = {"moe_experts_ms", "moe_routing_ms", "moe_experts_roofline_pct",
       "flash_causal_roofline_pct", "moe_load_max_over_mean"}


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark_olmoe.json")


@pytest.fixture(scope="module")
def job(catalog):
    import jax
    _, config, traffic = catalog.cell(CELL)
    return catalog.module("runners", config["runner"]).build(
        config, traffic, jax.devices()[:1])


def last_line(capsys, argv, **kw):
    run.main(argv, **kw)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_untraced_line_has_exactly_the_contract_keys(catalog, capsys):
    out = last_line(capsys, ["--workload", CELL, "--seed", "2500000201",
                             "--seconds", "0.5", "--trace", "0"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert set(out) == KEYS and set(out["device"]) == DEVICE_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 2
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_traced_line_reports_what_a_cpu_trace_can(catalog, capsys):
    """No device plane on the CPU: the readers of the trace return nothing
    and do not raise; the counters and the host-clock metrics are there,
    the experts' load among them."""
    out = last_line(capsys, ["--workload", CELL, "--seed", "3",
                             "--seconds", "0.5", "--trace", "1"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "mfu_pct", "window_stall_pct", "pallas_bodies_selected",
        "step_hbm_gib", "moe_load_max_over_mean"}
    assert 1.0 <= out["metrics"]["moe_load_max_over_mean"]["value"] <= 8.0


def test_the_scoped_readers_read_a_reduction(catalog):
    """The five new readers on a hand-made reduction: the experts' time and
    roofline share from the scope ``moe_experts``, routing from the two
    scopes around it, the flash share through ``flash_roofline_pct``'s own
    reader with the configuration's head size and causal half."""
    cell, config, traffic = catalog.cell(CELL)
    scope_ns = {name: {"forward": 0, "backward": 0, "total": total}
                for name, total in (("moe_experts", 40e6), ("moe_router", 2e6),
                                    ("moe_dispatch", 3e6))}
    reduced = {"scope_ns": scope_ns, "kernel_ns": {"flash_fwd": 1e6,
                                                    "flash_bwd": 3e6}}
    facts = {"scope_profile": reduced, "cell": cell, "config": config,
             "traffic": traffic, "catalog": catalog,
             "peak": {"bf16_flops_per_s": 1e12}, "job": object()}
    got = {name: catalog.module("layer_metrics", name).metric(facts)
           for name in NEW}
    assert got["moe_experts_ms"] == 40.0 and got["moe_routing_ms"] == 5.0
    experts = 3 * 4 * 32 * 2 * 3 * 2 * 64 * 32 * 2
    assert catalog.module("flops", "moe_experts").flops_per_step(
        config, traffic) == experts
    assert got["moe_experts_roofline_pct"] == pytest.approx(
        100 * (experts / 1e12) / 40e-3)
    flash = 2 * 6 * 2 * 4 * 4 * (32 * 32 // 2) * 16
    assert got["flash_causal_roofline_pct"] == pytest.approx(
        100 * (flash / 1e12) / 4e-3)
    assert got["moe_load_max_over_mean"] is None
    # a program without the scopes (the parent's): nothing, and no raise
    facts["scope_profile"] = None
    assert all(catalog.module("layer_metrics", name).metric(facts) is None
               for name in NEW)


def test_flops_per_token_counts_the_published_model():
    """At the published depth the experts are 62% and the head 8% of a
    token's operations; at depth 1 the head is 57% (PERF.md section 4)."""
    catalog = Catalog()
    _, config, traffic = catalog.cell("olmoe_1b_7b.lm_s4096")
    flops = catalog.module("flops", "olmoe")
    h, f, v = 2048, 1024, 50304
    experts, head = 3 * 8 * 3 * 2 * h * f, 3 * 2 * h * v
    assert head / flops.flops_per_token(config, traffic) == pytest.approx(
        0.57, abs=0.01)
    full = flops.flops_per_token(dict(config, num_hidden_layers=16), traffic)
    assert 16 * experts / full == pytest.approx(0.62, abs=0.01)
    assert head / full == pytest.approx(0.08, abs=0.005)
    assert catalog.module("flops", "moe_experts").flops_per_step(
        config, traffic) == 8192 * experts


def test_token_ids_follow_the_zipf_law_and_labels_are_the_next_ids(job):
    a, b, c = job.pool(2**31 + 5), job.pool(2**31 + 5), job.pool(6)
    assert len(a) == 8 and a[0]["input_ids"].shape == (4, 32)
    assert all(np.array_equal(x["input_ids"], y["input_ids"])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
    for batch in a:
        assert np.array_equal(batch["input_ids"][:, 1:],
                              batch["labels"][:, :-1])
    ids = np.concatenate([x["input_ids"].ravel() for x in a + c])
    # P(id = 0) = 1 / H(512) = 0.146; uniform ids would give 0.002
    assert 0.08 < np.mean(ids == 0) < 0.22
    assert job.tokens_per_step == 4 * 32


def test_reference_comparison_fails_on_4_bits_of_mantissa(catalog, job):
    import jax
    _, config, _ = catalog.cell(CELL)
    reference = catalog.module("reference", config["reference"])
    params, _ = job.init_fn(jax.random.PRNGKey(0))
    sample = job.sample(0)
    loss, outputs = job.probe(params, job.place(sample))
    assert job.routing_counts.shape == (2, 8)
    assert (job.routing_counts.sum(axis=1) == 2 * 2 * 32).all()
    want = reference.loss_and_outputs(params, config, sample)
    ok, errors = run.compare((loss, outputs), want, reference.TOLERANCE)
    assert ok, errors
    ok, _ = run.compare((loss, round_mantissa(outputs, 7)), want,
                        reference.TOLERANCE)
    assert ok
    ok, errors = run.compare((loss, round_mantissa(outputs, 4)), want,
                             reference.TOLERANCE)
    assert not ok and errors["outputs"] > reference.TOLERANCE["outputs"]
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]
