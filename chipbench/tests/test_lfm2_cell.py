"""CPU rehearsal of the LFM2 cell: ``run_cell`` on the fixture
``lfm2_toy.lm_toy_s80`` (``fixtures/benchmark_lfm2.json``: the toy
configuration, 80 positions, every general per-layer metric of the real
benchmark and the eight ``lfm2_24b_a2b.lm_b4_s8192`` brings), with a peaks
table that has the CPU, as ``test_qwen3_next_cell.py`` does for its cell; and
the real cell's configuration, counts and files."""

import json
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.catalog import ROOT, Catalog
from chipbench.tests.test_rehearsal import (DEVICE_KEYS, KEYS,
                                            round_mantissa)

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
CELL = "lfm2_toy.lm_toy_s80"
REAL = "lfm2_24b_a2b.lm_b4_s8192"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
NEW = ["gated_conv_ms", "gated_conv_roofline_pct", "gqa64_flash_roofline_pct",
       "qk_rope64_ms", "moe_e64_layer_ms", "moe_e64_experts_roofline_pct",
       "moe_e64_rows_per_expert", "moe_e64_share_pct"]


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark_lfm2.json")


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
        "step_hbm_gib", "moe_e64_rows_per_expert", "moe_e64_share_pct"}
    # the last step's own count, from the trainer: 4 of 16 held, 25% and
    # 2 x 80 x 4 x 4 / 16 / 4 = 40 rows an expert at par
    assert 0 < out["metrics"]["moe_e64_share_pct"]["value"] <= 100
    assert 0 < out["metrics"]["moe_e64_rows_per_expert"]["value"] <= 160


def test_the_real_benchmark_has_the_cell_and_its_eight_metrics():
    spec = Catalog().spec
    cell, config, traffic = Catalog().cell(REAL)
    assert cell["chips"] == 1 and cell["config"] == "lfm2_24b_a2b"
    assert cell["traffic"] == "lm_b4_s8192"
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    new = [m for m in spec["per_layer"] if m.get("workloads") == [REAL]]
    assert [m["name"] for m in new] == NEW
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 8] == NEW                  # appended together
    assert all(m["moves"] == "train_tokens_per_s" for m in new)
    layers = {m["name"]: m["layer"] for m in new}
    assert layers["gated_conv_ms"] == layers["gated_conv_roofline_pct"] \
        == layers["gqa64_flash_roofline_pct"] \
        == layers["moe_e64_experts_roofline_pct"] == "kernels"
    assert layers["qk_rope64_ms"] == layers["moe_e64_layer_ms"] \
        == layers["moe_e64_rows_per_expert"] \
        == layers["moe_e64_share_pct"] == "functional trainers"
    sources = {m["name"]: m["source"] for m in new}
    assert sources["moe_e64_rows_per_expert"] \
        == sources["moe_e64_share_pct"] == "program_counter"
    for m in new:                        # every reader is a file of its own
        assert callable(Catalog().module("layer_metrics", m["name"]).metric)
    assert (traffic["batch"], traffic["seq_len"]) == (4, 8192)
    assert traffic["zipf_exponent"] == 1.0 and traffic["pool_batches"] == 8
    assert traffic["token"] == "input_positions"
    assert traffic["mesh"] == {"data": 1}
    assert set(traffic) == set(Catalog().json("traffic/lm_s8192.json"))
    assert all(len(w["why"]) <= 200 for w in spec["workloads"])
    assert all(len(c["why"]) <= 200 for c in spec["configs"])


def test_the_scoped_readers_read_a_reduction(catalog):
    """The new readers on a hand-made reduction, against their counts."""
    cell, config, traffic = catalog.cell(CELL)
    scope_ns = {name: {"forward": 0, "backward": 0, "total": total}
                for name, total in (("gated_conv", 8e6), ("qk_norm", 2e6),
                                    ("rope", 3e6), ("moe_experts", 7e6),
                                    ("moe_router", 4e6),
                                    ("moe_dispatch", 9e6))}
    reduced = {"scope_ns": scope_ns,
               "kernel_ns": {"flash_fwd": 1e6, "flash_bwd": 3e6}}
    facts = {"scope_profile": reduced, "cell": cell, "config": config,
             "traffic": traffic, "catalog": catalog,
             "peak": CPU_PEAKS["cpu"], "job": types.SimpleNamespace(
                 step_fn=lambda *a: None)}

    def read(name):
        return catalog.module("layer_metrics", name).metric(facts)

    assert read("gated_conv_ms") == 8.0 and read("qk_rope64_ms") == 5.0
    assert read("moe_e64_layer_ms") == 20.0
    # a trainer that keeps no counter: nothing
    assert read("moe_e64_share_pct") is None
    assert read("moe_e64_rows_per_expert") is None
    assert read("moe_e64_experts_roofline_pct") is None
    counts = np.full((4, 16), 10)
    counts[2, 4:8] = 30                 # 120 of 240 on the experts 4 to 7
    facts["job"].step_fn.aux = [counts]
    assert read("moe_e64_share_pct") == pytest.approx(50.0)
    assert read("moe_e64_rows_per_expert") == pytest.approx(30.0)
    # four conv layers of 2 rows of 80 positions and 64 channels: 11
    # bfloat16 values a position and channel each way together
    conv = catalog.module("flops", "gated_conv")
    assert conv.bytes_per_step(config, traffic) == 4 * 2 * 80 * 64 * 22
    assert conv.flops_per_step(config, traffic) == 4 * 2 * 80 * 64 * 31
    assert read("gated_conv_roofline_pct") == pytest.approx(
        100 * conv.bytes_per_step(config, traffic) / 1e11 / 8e-3)
    # one attention layer, 8 heads over 2 of 8 channels, half of 80 x 80
    gqa = catalog.module("flops", "gqa64_flash")
    assert gqa.flops_per_step(config, traffic) \
        == 1 * 8 * 2 * (80 * 80 // 2) * 6 * 2 * 8
    assert gqa.bytes_per_step(config, traffic) \
        == 1 * (6 * 8 + 6 * 2) * 2 * 80 * 8 * 2
    assert read("gqa64_flash_roofline_pct") == pytest.approx(100 * max(
        gqa.flops_per_step(config, traffic) / 1e12,
        gqa.bytes_per_step(config, traffic) / 1e11) / 4e-3)
    # the held experts' rows of that counter: 3 layers of 40 and one of 120,
    # nine products of 2 x 64 x 32 a row
    experts = catalog.module("flops", "moe_e64_experts")
    rows = 3 * 40 + 120
    assert experts.flops_per_step(config, traffic, rows) \
        == 3 * rows * 3 * 2 * 64 * 32
    assert experts.rows_at_par(config, traffic) == 4 * 2 * 80 * 4 * 4 // 16
    assert experts.flops_per_step(config, traffic) \
        == experts.flops_per_step(config, traffic, 640)
    assert read("moe_e64_experts_roofline_pct") == pytest.approx(
        100 * experts.flops_per_step(config, traffic, rows) / 1e12 / 7e-3)
    # a step that runs no flash call: nothing
    reduced["kernel_ns"] = {}
    assert read("gqa64_flash_roofline_pct") is None
    # a trace without the convolution's scope, or one of the norm's: nothing
    del scope_ns["gated_conv"], scope_ns["qk_norm"], scope_ns["moe_router"]
    assert read("gated_conv_ms") is None
    assert read("gated_conv_roofline_pct") is None
    assert read("qk_rope64_ms") is None and read("moe_e64_layer_ms") is None
    # a program without the scopes or the counter (the parent's): nothing,
    # and no raise
    facts["scope_profile"] = None
    facts["job"] = types.SimpleNamespace(step_fn=lambda *a: None)
    assert all(read(name) is None for name in NEW)


def test_flops_count_what_the_equations_say():
    """One layer of each kind by hand at the cell's sizes, the probe's rows
    once it has run, and the kernels' counts beside the step's."""
    catalog = Catalog()
    _, config, traffic = catalog.cell(REAL)
    flops = catalog.module("flops", "lfm2")
    assert flops.layer_kinds(config) == [
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False)]
    at_par = flops.flops_per_token(config, traffic)
    h = 2048
    # a convolution operator's matmuls a token: [B | C | u] and out
    conv_op = 2 * h * 3 * h + 2 * h * h
    # the attention operator: q, k, v, out, and the causal half of 32 heads'
    # scores and context at 64
    attn_op = 2 * h * (2048 + 2 * 512) + 2 * 2048 * h \
        + 32 * (8192 // 2) * 4 * 64
    dense = 3 * 2 * h * 11776
    # an expert layer at par: the router and 4 x 8 / 64 experts of three
    # matmuls; no shared expert
    expert = 3 * 2 * h * 1536
    moe_layer = 2 * h * 64 + 4 * 8 / 64 * expert
    head = 2 * h * 8192
    assert at_par == 3 * (4 * conv_op + attn_op + dense + 4 * moe_layer
                          + head)
    assert round(at_par / 3 / 1e6) == 406                  # ISSUE 40's count
    assert 0.35 < 3 * dense / at_par < 0.37
    assert 0.08 < 3 * head / at_par < 0.09
    probed = dict(config, probe={"held_rows": [16384, 8192, 4096, 4096],
                                 "tokens": 8192})
    assert flops.flops_per_token(probed, traffic) - at_par \
        == pytest.approx(3 * 4 * (32768 / 4 / 8192 - 0.5) * expert, abs=1.0)
    conv = catalog.module("flops", "gated_conv")
    assert conv.bytes_per_step(config, traffic) \
        == 4 * 4 * 8192 * 2048 * 11 * 2
    # 0.54 GB forward + 0.94 GB backward a layer (ISSUE 40)
    assert round(4 * 8192 * 2048 * 8 / 1e9, 2) == 0.54
    assert round(4 * 8192 * 2048 * 14 / 1e9, 2) == 0.94
    gqa = catalog.module("flops", "gqa64_flash")
    assert gqa.flops_per_step(config, traffic) \
        == 32 * 4 * (8192 ** 2 // 2) * 6 * 2 * 64
    assert gqa.bytes_per_step(config, traffic) \
        == (6 * 32 + 6 * 8) * 4 * 8192 * 64 * 2
    experts = catalog.module("flops", "moe_e64_experts")
    assert experts.rows_at_par(config, traffic) == 4 * 16384
    assert experts.flops_per_step(config, traffic) \
        == 3 * 4 * 16384 * expert
    assert experts.flops_per_step(config, traffic) / (4 * 8192) \
        == pytest.approx(3 * 4 * 0.5 * expert)


PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True}


def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's config is in the file with its value,
    but the four the cut changes, which ``reduced`` lists."""
    catalog = Catalog()
    entry = {c["name"]: c for c in catalog.spec["configs"]}["lfm2_24b_a2b"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "num_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/LiquidAI/LFM2-24B-A2B"
                               "/blob/main/config.json")
    assert "one of 8 chips" in entry["why"]
    _, config, _ = catalog.cell(REAL)
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["layer_types"] == (["conv", "conv", "full_attention",
                                      "conv"] * 10)      # whole, as published
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 8192)
    assert config["published"] == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "vocab_size": 65536}
    assert config["first_layer"] == 1
    assert config["router_width"] == 64 and config["experts_held"] == [0, 8]
    assert config["tie_word_embeddings"] is True
    assert "one of 8 chips that share each layer" in config["deployment"]
    assert len(config["reduced"]) == 4
    for assumed in ("tie_word_embeddings", "intermediate_size", "conv taps",
                    "initialisation", "router_bias_update_rate",
                    "router_bias_settle"):
        assert assumed in config["assumed"]
    assert any("1e-20" in d and "1e-6" in d for d in config["departures"])
    assert config["scopes"] == ["gated_conv", "qk_norm", "rope",
                                "moe_router", "moe_dispatch", "moe_experts"]
    for part in ("dtype", "attention", "experts", "recomputation",
                 "precision"):
        assert config["program"][part]
    # and the program's configuration of it is the published model's cut
    from paddle_tpu.models import lfm2
    cfg = catalog.module("runners", "train_lfm2").model_config(config)
    assert cfg == lfm2.lfm2_24b_a2b(
        num_layers=5, layer_types=lfm2.lfm2_24b_a2b().layer_types[1:6],
        num_dense_layers=1, vocab_size=8192, experts_held=(0, 8))
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")


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


def test_the_selection_biases_start_at_rest(catalog, job, config):
    """``router_bias_settle``: ``init_fn`` hands out the seed's weights with
    the selection biases moved; the same seed gives the same biases, and the
    load of the law's own draws is nearer even than with the biases at
    zero."""
    import jax
    params, _ = job.init_fn(jax.random.PRNGKey(4))
    again, _ = job.init_fn(jax.random.PRNGKey(4))
    biases = [np.asarray(lp["router_bias"]) for lp in params["layers"][1:]]
    assert "router_bias" not in params["layers"][0]    # the dense layer
    assert all(b.any() for b in biases)
    assert all(np.array_equal(b, np.asarray(lp["router_bias"]))
               for b, lp in zip(biases, again["layers"][1:]))
    steps, first = (config["router_bias_settle"][k]
                    for k in ("steps", "first_rate"))
    assert all(np.abs(b).max() <= 2 * steps * first for b in biases)
    from paddle_tpu.models import lfm2
    cfg = catalog.module("runners", config["runner"]).model_config(config)
    zero = dict(params, layers=[
        dict(lp, router_bias=0 * lp["router_bias"]) if "router_bias" in lp
        else lp for lp in params["layers"]])
    batch = job.draw_batch(np.random.RandomState(0), 2)

    def unevenness(p):
        counts = lfm2.routing_stats(p, cfg, batch)
        return float((counts.max(axis=1) / counts.mean(axis=1)).mean())

    assert unevenness(params) < unevenness(zero)


def test_reference_comparison_fails_below_the_configuration_s_precision(
        catalog, job, config):
    """The controls of ``reference/lfm2.py`` through the harness's own
    ``compare`` at the committed limits: the program agrees; what every part
    hands on in 4 stored bits fails by the outputs, several times the
    program's reading; bfloat16's 7 bits there pass; a router that chooses
    by scores of 4 stored bits fails the routing check (7 bits do on the
    cell's 32 768 tokens; 160 tokens are too few to meet a close pair); a
    loss in 4 bits fails by the loss."""
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
                                        router_bits=4)
    ok, errors = run.compare(routed, want, reference.TOLERANCE)
    assert not ok and np.isnan(errors["outputs"])
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]
