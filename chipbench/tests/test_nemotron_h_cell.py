"""CPU rehearsal of the Nemotron-3-Super cell: ``run_cell`` on the fixture
``nemotron_h_toy.lm_toy_s80`` (``fixtures/benchmark_nemotron_h.json``: the
toy configuration with every kind of layer and the MTP module, 80 positions,
no multiple of its chunk of 16, every general per-layer metric of the real
benchmark and the seven ``nemotron_3_super_120b_a12b.lm_s8192`` brings), with
a peaks table that has the CPU, as ``test_kanana_2_cell.py`` does for its
cell; and the real cell's configuration, counts and files."""

import json
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.catalog import ROOT, Catalog
from chipbench.tests.test_rehearsal import (DEVICE_KEYS, KEYS,
                                            round_mantissa)

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
CELL = "nemotron_h_toy.lm_toy_s80"
REAL = "nemotron_3_super_120b_a12b.lm_s8192"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
NEW = ["ssd_core_ms", "ssd_core_roofline_pct", "mamba_conv_gate_ms",
       "latent_moe_layer_ms", "latent_moe_share_pct",
       "latent_moe_rows_per_expert", "mtp_merge_ms"]
SCOPES = ["ssd_core", "short_conv", "ssd_gate", "moe_latent", "moe_router",
          "moe_dispatch", "moe_experts", "moe_shared", "mtp_merge"]


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark_nemotron_h.json")


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
        "step_hbm_gib", "latent_moe_rows_per_expert", "latent_moe_share_pct"}
    # the last step's own count, from the trainer: 4 of 16 held, 25% and
    # 2 x 80 x 4 x 4 / 16 / 4 = 40 rows an expert at par
    assert 0 < out["metrics"]["latent_moe_share_pct"]["value"] <= 100
    assert 0 < out["metrics"]["latent_moe_rows_per_expert"]["value"] <= 160


def test_the_real_benchmark_has_the_cell_and_its_seven_metrics():
    spec = Catalog().spec
    cell, config, traffic = Catalog().cell(REAL)
    assert cell["chips"] == 1
    assert cell["config"] == "nemotron_3_super_120b_a12b"
    assert cell["traffic"] == "lm_s8192"
    assert len(spec["configs"]) >= 9 and len(spec["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    new = [m for m in spec["per_layer"] if m.get("workloads") == [REAL]]
    assert [m["name"] for m in new] == NEW
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 7] == NEW                  # appended together
    assert all(m["moves"] == "train_tokens_per_s" for m in new)
    layers = {m["name"]: m["layer"] for m in new}
    assert layers.pop("ssd_core_ms") == "kernels"
    assert layers.pop("ssd_core_roofline_pct") == "kernels"
    assert set(layers.values()) == {"functional trainers"}
    sources = {m["name"]: m["source"] for m in new}
    assert sources.pop("latent_moe_rows_per_expert") \
        == sources.pop("latent_moe_share_pct") == "program_counter"
    assert set(sources.values()) == {"device_trace"}
    for m in new:                        # every reader is a file of its own
        assert callable(Catalog().module("layer_metrics", m["name"]).metric)
    # the traffic is Kimi Linear's file, as it stood
    assert (traffic["batch"], traffic["seq_len"]) == (1, 8192)
    assert traffic["zipf_exponent"] == 1.0 and traffic["pool_batches"] == 8
    assert traffic["token"] == "input_positions"
    assert traffic["mesh"] == {"data": 1}
    assert all(len(w["why"]) <= 200 for w in spec["workloads"])
    assert all(len(c["why"]) <= 200 for c in spec["configs"])
    assert "352 rows an expert (deployment 5632)" in cell["why"]


def test_the_scoped_readers_read_a_reduction(catalog):
    """The new readers on a hand-made reduction, against their counts."""
    cell, config, traffic = catalog.cell(CELL)
    scope_ns = {name: {"forward": 0, "backward": 0, "total": total}
                for name, total in zip(SCOPES, (8e6, 3e6, 2e6, 1e6, 4e6, 9e6,
                                                7e6, 5e6, 6e6))}
    facts = {"scope_profile": {"scope_ns": scope_ns, "kernel_ns": {}},
             "cell": cell, "config": config, "traffic": traffic,
             "catalog": catalog, "peak": CPU_PEAKS["cpu"],
             "job": types.SimpleNamespace(step_fn=lambda *a: None)}

    def read(name):
        return catalog.module("layer_metrics", name).metric(facts)

    assert read("ssd_core_ms") == 8.0
    assert read("mamba_conv_gate_ms") == 5.0
    assert read("latent_moe_layer_ms") == 26.0
    assert read("mtp_merge_ms") == 6.0
    # a trainer that keeps no counter: nothing
    assert read("latent_moe_share_pct") is None
    assert read("latent_moe_rows_per_expert") is None
    counts = np.full((3, 16), 10)
    counts[2, 4:8] = 30                 # 120 of 240 on the experts 4 to 7
    facts["job"].step_fn.aux = [counts, np.zeros(2)]
    assert read("latent_moe_share_pct") == pytest.approx(50.0)
    assert read("latent_moe_rows_per_expert") == pytest.approx(30.0)
    # two M layers of 8 heads of 8 with a state of 16, 4 heads a group, in
    # chunks of 16, on 2 x 80 positions
    scan = catalog.module("flops", "ssd_core")
    assert scan.flops_per_step(config, traffic) \
        == 3 * 2 * 2 * 80 * 8 * (16 * 16 // 4 + 16 * 8 + 4 * 8 * 16)
    operands = 2 * 8 + 4 + 4 + 2 * 2 * 16 // 4
    assert scan.bytes_per_step(config, traffic) \
        == 2 * 2 * 80 * 8 * (3 * operands + 2 * 2 * 8)
    assert read("ssd_core_roofline_pct") == pytest.approx(100 * max(
        scan.flops_per_step(config, traffic) / 1e12,
        scan.bytes_per_step(config, traffic) / 1e11) / 8e-3)
    # a trace without one of the scopes: nothing
    del scope_ns["ssd_gate"], scope_ns["moe_latent"], scope_ns["ssd_core"]
    assert read("mamba_conv_gate_ms") is None
    assert read("latent_moe_layer_ms") is None
    assert read("ssd_core_ms") is None
    assert read("ssd_core_roofline_pct") is None
    # a program without the scopes or the counter (the parent's): nothing,
    # and no raise
    facts["scope_profile"] = None
    facts["job"] = types.SimpleNamespace(step_fn=lambda *a: None)
    assert all(read(name) is None for name in NEW)


def test_flops_count_what_the_equations_say():
    """One layer of each kind by hand at the cell's sizes (ISSUE 48's
    arithmetic), the probe's rows once it has run, and the scan's count
    beside the step's."""
    catalog = Catalog()
    _, config, traffic = catalog.cell(REAL)
    flops = catalog.module("flops", "nemotron_h")
    h, s = 4096, 8192
    layer = flops.layer_flops(config, traffic)
    assert layer["M"] == 2 * h * 4640 + 2 * 2048 * h
    assert round(layer["M"] / 2e6, 1) == 27.4           # multiply-adds
    assert layer["*"] == 2 * h * (1024 + 256) + 2 * 1024 * h \
        + 8 * (s // 2) * 2 * 256
    expert = 2 * 2 * 1024 * 2688
    assert layer["E"] == 2 * h * 512 + 4 * h * 1024 + 4 * h * 5376 \
        + 22 * 8 / 512 * expert
    assert round(layer["E"] / 2e6, 1) == 56.4
    head = 2 * h * 16384
    scan = catalog.module("flops", "ssd_core")
    assert scan.flops_per_step(config, traffic) \
        == 3 * 5 * s * 32 * (128 * 128 // 16 + 128 * 64 + 4 * 64 * 128)
    assert scan.bytes_per_step(config, traffic) == 5 * s * 32 * 760
    at_par = flops.flops_per_token(config, traffic)
    assert at_par == pytest.approx(
        3 * (5 * layer["M"] + 2 * layer["*"] + 6 * layer["E"] + 2 * head
             + 4 * h * h) + scan.flops_per_step(config, traffic) / s)
    assert round(at_par / 1e9, 2) == 4.09
    assert round(at_par * s / 1e12, 1) == 33.5
    # the mechanisms that are new are three quarters of the products
    new = 5 * layer["M"] + 6 * layer["E"] + 4 * h * h
    assert 0.70 < 3 * new / at_par < 0.80
    probed = dict(config, probe={"held_rows": [2816] * 6, "tokens": s})
    assert flops.flops_per_token(probed, traffic) == pytest.approx(at_par)
    probed["probe"]["held_rows"][5] = 2 * 2816
    assert flops.flops_per_token(probed, traffic) - at_par \
        == pytest.approx(3 * 2816 / s * expert, rel=1e-6)


def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's config is in the file with its value,
    but the seven the cut changes, which ``reduced`` lists, and the pattern,
    which is one period of the published one."""
    catalog = Catalog()
    entry = {c["name"]: c for c in catalog.spec["configs"]}[
        "nemotron_3_super_120b_a12b"]
    cut = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "mamba_num_heads", "n_groups", "num_attention_heads",
           "num_key_value_heads"]
    assert entry["reduced"] == cut
    assert entry["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
        "BF16/blob/main/config.json")
    assert "one of 64 chips" in entry["why"]
    _, config, _ = catalog.cell(REAL)
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 2688,
        "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_experts_per_tok": 22, "num_logits_to_keep": 1,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}
    assert {k: config[k] for k in published} == published
    assert [config[k] for k in cut] == [11, 8, 16384, 32, 2, 8, 1]
    assert config["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "mamba_num_heads": 128, "n_groups": 8,
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "hybrid_override_pattern": (
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")}
    whole = config["published"]["hybrid_override_pattern"]
    assert len(whole) == 88
    assert config["hybrid_override_pattern"] == whole[26:37] == "EMEMEMEMEM*"
    assert 8 * 16384 == 131072 and 16384 % 128 == 0
    assert config["router_width"] == 512 and config["experts_held"] == [0, 8]
    for says in ("one of 64 chips that share each layer", "64 ways",
                 "8 ways", "4 ways", "out_proj", "o_proj"):
        assert says in config["deployment"]
    assert len(config["reduced"]) == 7
    for assumed in ("no rotary positions", "gated norm", "dt",
                    "mamba initialisation", "router score", "latent",
                    "shared expert", "mtp", "router_bias_update_rate",
                    "router_bias_settle", "initialisation"):
        assert "alternative" in config["assumed"][assumed].lower(), assumed
    assert "FITTED TO THIS CELL" in config["assumed"]["router_bias_settle"]
    assert any("FITTED TO THIS CELL" in d for d in config["departures"])
    assert any("1e-20" in d for d in config["departures"])
    assert config["scopes"] == SCOPES
    for part in ("dtype", "mamba", "attention", "experts", "mtp",
                 "recomputation", "precision"):
        assert config["program"][part]
    # and the program's configuration of it is the published model's cut
    from paddle_tpu.models import nemotron_h
    cfg = catalog.module("runners", "train_nemotron_h").model_config(config)
    assert cfg == nemotron_h.nemotron_3_super_120b_a12b(
        pattern="EMEMEMEMEM*", vocab_size=16384, mamba_heads=32,
        mamba_groups=2, num_heads=8, num_kv_heads=1, experts_held=(0, 8))
    assert cfg.scoring.scale == 5.0 and cfg.expert_act == "relu2"


def test_a_configuration_the_program_has_no_form_for_is_refused():
    catalog = Catalog()
    _, config, _ = catalog.cell(REAL)
    model_config = catalog.module("runners",
                                  "train_nemotron_h").model_config
    for key, value, says in (("mamba_proj_bias", True, "convolution bias"),
                             ("n_group", 8, "one group"),
                             ("sliding_window", 4096, "no attention window"),
                             ("num_hidden_layers", 12, "a mixer a layer"),
                             ("num_nextn_predict_layers", 2, "one multi"),
                             ("n_routed_experts", 512, "held here")):
        with pytest.raises(ValueError, match=says):
            model_config(dict(config, **{key: value}))
    assert model_config(dict(config, num_nextn_predict_layers=0)) \
        .mtp_pattern == ""


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


def routers(params):
    return [lp["router_bias"] for lp
            in params["layers"] + params["mtp"]["layers"]
            if "router_bias" in lp]


def test_the_selection_biases_start_at_rest(catalog, job, config):
    """``router_bias_settle``: ``init_fn`` hands out the seed's weights with
    the selection biases of all three routers moved, the module's among
    them; the same seed gives the same biases, and the load of the law's own
    draws is nearer even than with the biases at zero."""
    import jax
    params, _ = job.init_fn(jax.random.PRNGKey(4))
    again, _ = job.init_fn(jax.random.PRNGKey(4))
    biases = [np.asarray(b) for b in routers(params)]
    assert len(biases) == 3 and all(b.any() for b in biases)
    assert all(np.array_equal(b, np.asarray(c))
               for b, c in zip(biases, routers(again)))
    steps, first = (config["router_bias_settle"][k]
                    for k in ("steps", "first_rate"))
    assert all(np.abs(b).max() <= 2 * steps * first for b in biases)
    from paddle_tpu.models import nemotron_h
    cfg = catalog.module("runners", config["runner"]).model_config(config)
    zero = jax.tree_util.tree_map_with_path(
        lambda path, a: 0 * a if path[-1].key == "router_bias" else a,
        params)
    batch = job.draw_batch(np.random.RandomState(0), 2)

    def unevenness(p):
        counts = nemotron_h.routing_stats(p, cfg, batch)
        return float((counts.max(axis=1) / counts.mean(axis=1)).mean())

    assert unevenness(params) < unevenness(zero)


def test_reference_comparison_fails_below_the_configuration_s_precision(
        catalog, job, config):
    """The controls of ``reference/nemotron_h.py`` through the harness's own
    ``compare`` at the committed limits: the program agrees; what every part
    hands on in 4 stored bits fails by the outputs, several times the
    program's reading; bfloat16's 7 bits there pass; a router that chooses
    by scores of 4 stored bits fails the routing check (7 bits do on the
    cell's 8192 tokens; 160 tokens are too few to meet a close pair);
    bfloat16 parameters are refused; a loss in 4 bits fails by the loss."""
    import jax
    import jax.numpy as jnp
    reference = catalog.module("reference", config["reference"])
    params, _ = job.init_fn(jax.random.PRNGKey(0))
    sample = job.sample(0)
    loss, outputs = job.probe(params, job.place(sample))
    assert job.routing_counts.shape == (3, 16)
    assert (job.routing_counts.sum(axis=1) == 4 * 2 * 80).all()
    assert (job.held_rows == job.routing_counts[:, 4:8].sum(axis=1)).all()
    assert config["probe"]["tokens"] == 160
    # the embedding, five layers, the final states, the module's merged
    # state, its two layers, its final states
    assert outputs.shape == sample["program_stream"].shape == (11, 2, 80, 64)
    assert sample["program_stream"].dtype.name == "bfloat16"
    assert sample["program_choice"].shape == (3, 2, 80, 4)
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
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype),
                           params)
    ok, errors = run.compare(
        reference.loss_and_outputs(rounded, config, sample), want,
        reference.TOLERANCE)
    assert not ok and np.isnan(errors["outputs"])
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]
