"""CPU rehearsal of the Laguna cell: ``run_cell`` on the fixture
``laguna_toy.lm_toy_s80`` (``fixtures/benchmark_laguna.json``: the toy
configuration, 80 positions, every general per-layer metric of the real
benchmark and the seven ``laguna_xs2.lm_s16384`` brings), with a peaks table
that has the CPU, as ``test_kimi_linear_cell.py`` does for its cell; and the
real cell's configuration, counts and files."""

import json
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.catalog import ROOT, Catalog
from chipbench.tests.test_rehearsal import (DEVICE_KEYS, KEYS,
                                            round_mantissa)

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
CELL = "laguna_toy.lm_toy_s80"
REAL = "laguna_xs2.lm_s16384"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
NEW = {"swa_core_ms", "swa_flash_roofline_pct", "gqa_flash_roofline_pct",
       "swa_tiles_visited_pct", "rope_gate_ms", "moe_e32_layer_ms",
       "moe_e32_share_pct"}


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark_laguna.json")


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
    and do not raise; the counters and the host-clock metrics are there."""
    out = last_line(capsys, ["--workload", CELL, "--seed", "3",
                             "--seconds", "0.5", "--trace", "1"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "mfu_pct", "window_stall_pct", "pallas_bodies_selected",
        "step_hbm_gib", "swa_tiles_visited_pct", "moe_e32_share_pct"}
    # 80 positions are one tile a side: nothing to skip
    assert out["metrics"]["swa_tiles_visited_pct"]["value"] == 100.0
    # the last step's own count, from the trainer: 4 of 16 held, 25% at par;
    # a toy router drifts far from par within the window's steps
    assert 0 < out["metrics"]["moe_e32_share_pct"]["value"] <= 100


def test_the_real_benchmark_has_the_cell_and_its_seven_metrics():
    spec = Catalog().spec
    cell, config, traffic = Catalog().cell(REAL)
    assert cell["chips"] == 1 and cell["config"] == "laguna_xs2"
    assert len(spec["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    new = [m for m in spec["per_layer"] if m.get("workloads") == [REAL]]
    assert {m["name"] for m in new} == NEW
    assert all(m["moves"] == "train_tokens_per_s" for m in new)
    for m in new:                        # every reader is a file of its own
        assert callable(Catalog().module("layer_metrics", m["name"]).metric)
    assert (traffic["batch"], traffic["seq_len"]) == (1, 16384)
    assert traffic["token"] == "input_positions"
    assert all(len(w["why"]) <= 200 for w in spec["workloads"])
    assert all(len(c["why"]) <= 200 for c in spec["configs"])


def test_the_scoped_readers_read_a_reduction(catalog):
    """The new readers on a hand-made reduction, against their counts."""
    cell, config, traffic = catalog.cell(CELL)
    scope_ns = {name: {"forward": 0, "backward": 0, "total": total}
                for name, total in (("attention_window", 40e6),
                                    ("rope", 2e6), ("attn_gate", 3e6),
                                    ("moe_experts", 7e6), ("moe_router", 4e6),
                                    ("moe_dispatch", 9e6),
                                    ("moe_shared", 1e6))}
    reduced = {"scope_ns": scope_ns, "kernel_ns": {
        "flash_fwd": 1e6, "flash_bwd": 3e6, "flash_fwd_window": 2e6,
        "flash_bwd_window": 6e6}}
    facts = {"scope_profile": reduced, "cell": cell, "config": config,
             "traffic": traffic, "catalog": catalog,
             "peak": CPU_PEAKS["cpu"], "job": types.SimpleNamespace(
                 step_fn=lambda *a: None)}
    got = {name: catalog.module("layer_metrics", name).metric(facts)
           for name in NEW}
    assert got["swa_core_ms"] == 40.0 and got["rope_gate_ms"] == 5.0
    assert got["moe_e32_layer_ms"] == 21.0
    assert got["swa_tiles_visited_pct"] is None      # a job with no counter
    assert got["moe_e32_share_pct"] is None          # a trainer with none
    counts = np.full((4, 16), 10)
    counts[2, 4:8] = 30                 # 120 of 240 on the experts 4 to 7
    facts["job"].step_fn.aux = [counts]
    facts["job"].swa_tiles_visited_pct = 11.9
    assert catalog.module("layer_metrics", "moe_e32_share_pct").metric(
        facts) == pytest.approx(50.0)
    assert catalog.module("layer_metrics", "swa_tiles_visited_pct").metric(
        facts) == 11.9
    # three sliding layers, 2 rows, 16 heads over 2 of 16 channels, a window
    # of 24 over 80 positions: the band's pairs, counted exactly
    swa = catalog.module("flops", "swa_flash")
    pairs = 24 * 25 // 2 + (80 - 24) * 24
    assert swa.band_pairs(80, 24) == pairs == sum(
        min(i + 1, 24) for i in range(80))
    assert swa.flops_per_step(config, traffic) \
        == 3 * 16 * 2 * pairs * 6 * 2 * 16
    assert swa.bytes_per_step(config, traffic) \
        == 3 * (6 * 16 + 6 * 2) * 2 * 80 * 16 * 2
    assert got["swa_flash_roofline_pct"] == pytest.approx(100 * max(
        swa.flops_per_step(config, traffic) / 1e12,
        swa.bytes_per_step(config, traffic) / 1e11) / 8e-3)
    # two full layers, 12 heads over 2, half of 80 x 80
    gqa = catalog.module("flops", "gqa_flash")
    assert gqa.flops_per_step(config, traffic) \
        == 2 * 12 * 2 * (80 * 80 // 2) * 6 * 2 * 16
    assert gqa.bytes_per_step(config, traffic) \
        == 2 * (6 * 12 + 6 * 2) * 2 * 80 * 16 * 2
    assert got["gqa_flash_roofline_pct"] == pytest.approx(100 * max(
        gqa.flops_per_step(config, traffic) / 1e12,
        gqa.bytes_per_step(config, traffic) / 1e11) / 4e-3)
    # a step that runs no windowed call: nothing
    reduced["kernel_ns"] = {"flash_fwd": 1e6}
    assert catalog.module("layer_metrics", "swa_flash_roofline_pct").metric(
        facts) is None
    # a program without the scopes (the parent's): nothing, and no raise
    facts["scope_profile"] = None
    facts["job"] = types.SimpleNamespace(step_fn=lambda *a: None)
    assert all(catalog.module("layer_metrics", name).metric(facts) is None
               for name in NEW)


def test_flops_count_a_band_and_the_probe_s_rows():
    """At the cell's sizes the two full layers' scores are two fifths of a
    token's operations and the three sliding layers' a twentieth; the held
    assignments are counted as the probe counted them once it has run, at
    par before."""
    catalog = Catalog()
    _, config, traffic = catalog.cell(REAL)
    flops = catalog.module("flops", "laguna")
    at_par = flops.flops_per_token(config, traffic)
    assert 2.9e9 < at_par < 3.1e9
    head = 3 * 2 * 2048 * 12544
    assert 0.04 < head / at_par < 0.06
    expert = 3 * 3 * 2 * 2048 * 512
    probed = dict(config, probe={"held_rows": [32768, 16384, 8192, 8192],
                                 "tokens": 16384})
    assert flops.flops_per_token(probed, traffic) - at_par \
        == pytest.approx(4 * (1.0 - 8 * 32 / 256) * expert, abs=1.0)
    swa = catalog.module("flops", "swa_flash").flops_per_step(
        config, traffic) / 16384
    gqa = catalog.module("flops", "gqa_flash").flops_per_step(
        config, traffic) / 16384
    assert 0.38 < gqa / at_par < 0.42 and 0.04 < swa / at_par < 0.06
    # the band, not the triangle: a masked window would count 64 / 48 x 3 / 2
    # of the full layers' operations
    assert swa < gqa / 8
    assert catalog.module("flops", "swa_flash").band_pairs(16384, 512) \
        == 512 * 513 // 2 + (16384 - 512) * 512


PUBLISHED = {
    "model_type": "laguna", "hidden_size": 2048, "intermediate_size": 8192,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "moe_routed_scaling_factor": 2.5}


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row's config is in the file under its
    key, but the three the cut changes, which ``reduced`` lists; the lists a
    layer an entry keep their 40."""
    catalog = Catalog()
    entry = {c["name"]: c for c in catalog.spec["configs"]}["laguna_xs2"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/poolside/Laguna-XS.2/"
                               "blob/main/config.json")
    _, config, _ = catalog.cell(REAL)
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert config["layer_types"] == period * 10
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 32, 12544)
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 256, "vocab_size": 100352}
    assert config["router_width"] == 256 and config["experts_held"] == [0, 32]
    assert "one of 8 chips that share each layer" in config["deployment"]
    assert len(config["reduced"]) == 3
    for assumed in ("gating", "router score", "router_bias_update_rate",
                    "router_bias_settle", "initialisation"):
        assert assumed in config["assumed"]
    assert set(config["scopes"]) == {
        "rope", "attn_gate", "attention_window", "moe_router",
        "moe_dispatch", "moe_experts", "moe_shared"}
    # and the program's configuration of it is the published model's cut
    from paddle_tpu.models import laguna
    cfg = catalog.module("runners", "train_laguna").model_config(config)
    assert cfg == laguna.laguna_xs2(
        num_layers=5, vocab_size=12544, experts_held=(0, 32),
        bias_rate=config["router_bias_update_rate"])


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
    assert job.swa_tiles_visited_pct == 100.0


def test_selection_biases_start_at_rest(job, config):
    """``init_fn`` hands over the seed's weights with the selection biases
    settled on draws of the cell's law (``router_bias_settle``): on those
    draws the fullest expert of every router holds less than with the
    biases at zero, and the state's shapes can be had without its arrays."""
    import jax
    from paddle_tpu.models import laguna
    from chipbench.runners import train_laguna
    cfg = train_laguna.model_config(config)
    params, _ = job.init_fn(jax.random.PRNGKey(3))
    flat = dict(params, layers=[
        dict(lp, router_bias=0 * lp["router_bias"]) if "router_bias" in lp
        else lp for lp in params["layers"]])
    assert all(np.any(np.asarray(lp["router_bias"]))
               for lp in params["layers"][1:])
    rs = np.random.RandomState(0)
    draws = [job.draw_batch(rs, job.sample_sequences)
             for _ in range(config["router_bias_settle"]["sequences"])]
    fullest = {name: sum(laguna.routing_stats(p, cfg, b) for b in draws)
               .max(axis=1) for name, p in (("rest", params), ("zero", flat))}
    assert (fullest["rest"] < fullest["zero"]).all(), fullest
    shapes = jax.eval_shape(job.init_fn, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, shapes[0]) \
        == jax.tree.map(lambda a: a.shape, params)


def test_reference_comparison_fails_below_the_configuration_s_precision(
        catalog, job, config):
    """The controls of ``reference/laguna.py`` through the harness's own
    ``compare`` at the committed limits: the program agrees; what every part
    hands on in 4 stored bits fails by the outputs, several times the
    program's reading; bfloat16's 7 bits there pass; a router that chooses
    by scores of 5 stored bits fails the routing check (7 bits do on the
    cell's 16 384 tokens; 160 are too few to meet a close pair); a loss in
    4 bits fails by the loss."""
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
    assert sample["program_stream"].dtype.name == "bfloat16"
    want = reference.loss_and_outputs(params, config, sample)
    ok, sound = run.compare((loss, outputs), want, reference.TOLERANCE)
    assert ok, sound
    low = reference.loss_and_outputs(params, config, sample, state_bits=4)
    ok, errors = run.compare(low, want, reference.TOLERANCE)
    assert not ok and errors["outputs"] > reference.TOLERANCE["outputs"]
    assert errors["outputs"] > 3 * sound["outputs"]
    same = reference.loss_and_outputs(params, config, sample, state_bits=7)
    ok, errors = run.compare(same, want, reference.TOLERANCE)
    assert ok, errors
    routed = reference.loss_and_outputs(params, config, sample,
                                        router_bits=5)
    ok, errors = run.compare(routed, want, reference.TOLERANCE)
    assert not ok and np.isnan(errors["outputs"])
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]
