"""CPU rehearsal of the Kimi Linear cell: ``run_cell`` on the fixture
``kimi_linear_toy.lm_toy_s80`` (``fixtures/benchmark_kimi.json``: the toy
configuration, 80 positions, every general per-layer metric of the real
benchmark and the eight ``kimi_linear_48b_a3b.lm_s8192`` brings), with a peaks
table that has the CPU, as ``test_olmoe_cell.py`` does for its cell."""

import json
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.catalog import ROOT, Catalog
from chipbench.tests.test_rehearsal import (DEVICE_KEYS, KEYS,
                                            round_mantissa)

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
CELL = "kimi_linear_toy.lm_toy_s80"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
NEW = {"kda_core_ms", "kda_core_roofline_pct", "kda_conv_gate_ms",
       "mla_flash_roofline_pct", "moe_held_experts_ms",
       "moe_held_rows_per_expert", "moe_held_routing_ms",
       "moe_held_share_pct"}


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark_kimi.json")


@pytest.fixture(scope="module")
def config(catalog):
    """The one dict the runner is built with and the readers are handed:
    the probe leaves its counts in it."""
    return catalog.cell(CELL)[1]


@pytest.fixture(scope="module")
def job(catalog, config):
    import jax
    return catalog.module("runners", config["runner"]).build(
        config, catalog.cell(CELL)[2], jax.devices()[:1])


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
    the rows held an expert among them."""
    out = last_line(capsys, ["--workload", CELL, "--seed", "3",
                             "--seconds", "0.5", "--trace", "1"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "mfu_pct", "window_stall_pct", "pallas_bodies_selected",
        "step_hbm_gib", "moe_held_rows_per_expert", "moe_held_share_pct"}
    # 2 x 80 tokens x 4 choices over 16 experts: 40 rows an expert at par
    assert 10 < out["metrics"]["moe_held_rows_per_expert"]["value"] < 120
    # the last step's own count, from the trainer: 4 of 16 held, 25% at par
    assert 5 < out["metrics"]["moe_held_share_pct"]["value"] < 75


def test_the_scoped_readers_read_a_reduction(catalog):
    """The new readers on a hand-made reduction, against their counts."""
    cell, config, traffic = catalog.cell(CELL)
    scope_ns = {name: {"forward": 0, "backward": 0, "total": total}
                for name, total in (("kda_core", 50e6), ("short_conv", 2e6),
                                    ("kda_gate", 3e6), ("moe_experts", 7e6),
                                    ("moe_router", 4e6),
                                    ("moe_dispatch", 9e6))}
    reduced = {"scope_ns": scope_ns, "kernel_ns": {"flash_fwd": 1e6,
                                                    "flash_bwd": 3e6}}
    facts = {"scope_profile": reduced, "cell": cell, "config": config,
             "traffic": traffic, "catalog": catalog,
             "peak": CPU_PEAKS["cpu"], "job": types.SimpleNamespace(
                 step_fn=lambda *a: None)}
    got = {name: catalog.module("layer_metrics", name).metric(facts)
           for name in NEW}
    assert got["kda_core_ms"] == 50.0 and got["kda_conv_gate_ms"] == 5.0
    assert got["moe_held_experts_ms"] == 7.0
    assert got["moe_held_routing_ms"] == 13.0
    assert got["moe_held_rows_per_expert"] is None
    assert got["moe_held_share_pct"] is None    # a trainer with no counter
    # the trainer's counter of its last step: the fullest layer's share
    counts = np.full((4, 16), 10)
    counts[2, 4:8] = 30                 # 120 of 240 on the experts 4 to 7
    facts["job"].step_fn.aux = [counts]
    assert catalog.module("layer_metrics", "moe_held_share_pct").metric(
        facts) == pytest.approx(50.0)
    facts["job"].step_fn.aux = []
    # four KDA layers of 2 x 80 positions and 4 heads of 16, the op's chunk
    from paddle_tpu.ops import kda
    counts = catalog.module("flops", "kda_core")
    n, d = 4 * 160 * 4, 16
    assert counts.chunk_size() == kda.CHUNK == 32
    assert counts.flops_per_step(config, traffic) \
        == 3 * n * (5 * 32 * d + 6 * d * d)
    assert counts.bytes_per_step(config, traffic) == n * (34 * d + 12)
    assert got["kda_core_roofline_pct"] == pytest.approx(100 * max(
        counts.flops_per_step(config, traffic) / 1e12,
        counts.bytes_per_step(config, traffic) / 1e11) / 50e-3)
    # one MLA layer, 2 rows, 4 heads, half of 80 x 80, 24 and 16 channels
    flash = 1 * 2 * 4 * (80 * 80 // 2) * 2 * 3 * (24 + 16)
    assert catalog.module("flops", "mla_flash").flops_per_step(
        config, traffic) == flash
    assert got["mla_flash_roofline_pct"] == pytest.approx(
        100 * (flash / 1e12) / 4e-3)
    # a program without the scopes (the parent's): nothing, and no raise
    facts["scope_profile"] = None
    assert all(catalog.module("layer_metrics", name).metric(facts) is None
               for name in NEW)


def test_flops_per_token_counts_the_cut_and_the_probe_s_rows():
    """At the cell's sizes the mixers' and feed-forwards' projections are
    most of a token's operations; the held assignments are counted as the
    probe counted them once it has run, at par before."""
    catalog = Catalog()
    _, config, traffic = catalog.cell("kimi_linear_48b_a3b.lm_s8192")
    flops = catalog.module("flops", "kimi_linear")
    h = 2304
    at_par = flops.flops_per_token(config, traffic)
    head = 3 * 2 * h * 20480
    assert 0.10 < head / at_par < 0.14
    expert = 3 * 3 * 2 * h * 1024
    probed = dict(config, probe={"held_rows": [4096, 2048, 1024, 1024],
                                 "tokens": 8192})
    assert flops.flops_per_token(probed, traffic) - at_par \
        == pytest.approx(4 * (0.25 - 8 * 8 / 256) * expert, abs=1.0)
    scan = catalog.module("flops", "kda_core").flops_per_step(
        config, traffic) / 8192
    assert 0.015 < scan / at_par < 0.03
    flash = catalog.module("flops", "mla_flash").flops_per_step(
        config, traffic) / 8192
    assert 0.09 < flash / at_par < 0.13


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row's config is in the file under its
    key, but the three the cut changes, which ``reduced`` lists."""
    catalog = Catalog()
    entry = {c["name"]: c for c in catalog.spec["configs"]}[
        "kimi_linear_48b_a3b"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    _, config, traffic = catalog.cell("kimi_linear_48b_a3b.lm_s8192")
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "model_max_length": 1048576, "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128}
    assert {k: config[k] for k in published} == published
    assert config["linear_attn_config"] == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 20480)
    assert config["published"] == {"num_hidden_layers": 27,
                                   "num_experts": 256, "vocab_size": 163840}
    assert config["router_width"] == 256 and config["experts_held"] == [0, 8]
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)


def test_token_ids_follow_the_zipf_law_over_the_slice(job):
    a, b, c = job.pool(2**31 + 5), job.pool(2**31 + 5), job.pool(6)
    assert len(a) == 8 and a[0]["input_ids"].shape == (2, 80)
    assert all(np.array_equal(x["input_ids"], y["input_ids"])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
    for batch in a:
        assert np.array_equal(batch["input_ids"][:, 1:],
                              batch["labels"][:, :-1])
        assert batch["input_ids"].max() < 512
    ids = np.concatenate([x["input_ids"].ravel() for x in a + c])
    assert 0.08 < np.mean(ids == 0) < 0.22
    assert job.tokens_per_step == 2 * 80


def test_reference_comparison_fails_on_4_bits_of_mantissa(catalog, job,
                                                           config):
    """Every control of ``reference/kimi_linear.py`` through the harness's
    own ``compare`` at the committed limits: the program agrees; what every
    part hands on in 4 stored bits, and the delta rule's state in 4 stored
    bits, fail by the outputs, each several times the program's reading;
    bfloat16's 7 bits in either place pass; a loss in 4 bits fails by the
    loss."""
    import jax
    reference = catalog.module("reference", config["reference"])
    params, _ = job.init_fn(jax.random.PRNGKey(0))
    sample = job.sample(0)
    loss, outputs = job.probe(params, job.place(sample))
    assert job.routing_counts.shape == (4, 16)
    assert (job.routing_counts.sum(axis=1) == 4 * 2 * 80).all()
    assert (job.held_rows == job.routing_counts[:, 4:8].sum(axis=1)).all()
    assert config["probe"]["tokens"] == 160
    assert outputs.shape == sample["program_stream"].shape == (12, 2, 80, 64)
    want = reference.loss_and_outputs(params, config, sample)
    ok, sound = run.compare((loss, outputs), want, reference.TOLERANCE)
    assert ok, sound
    for control in ({"state_bits": 4}, {"kda_state_bits": 4}):
        low = reference.loss_and_outputs(params, config, sample, **control)
        ok, errors = run.compare(low, want, reference.TOLERANCE)
        assert not ok and errors["outputs"] > reference.TOLERANCE["outputs"]
        assert errors["outputs"] > 3 * sound["outputs"], control
    for control in ({"state_bits": 7}, {"kda_state_bits": 7}):
        same = reference.loss_and_outputs(params, config, sample, **control)
        ok, errors = run.compare(same, want, reference.TOLERANCE)
        assert ok, (control, errors)
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]
