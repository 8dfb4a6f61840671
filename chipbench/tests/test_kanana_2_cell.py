"""CPU rehearsal of the Kanana-2 cell: ``run_cell`` on the fixture
``kanana_2_toy.lm_toy_s80`` (``fixtures/benchmark_kanana_2.json``: the toy
configuration, 80 positions, every general per-layer metric of the real
benchmark and the five ``kanana_2_30b_a3b.lm_s16384`` brings), with a peaks
table that has the CPU, as ``test_lfm2_cell.py`` does for its cell; and the
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
CELL = "kanana_2_toy.lm_toy_s80"
REAL = "kanana_2_30b_a3b.lm_s16384"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
NEW = ["mla_rope_flash_roofline_pct", "mla_expand_rope_ms",
       "moe_e128_layer_ms", "moe_e128_share_pct", "moe_e128_rows_per_expert"]


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark_kanana_2.json")


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
        "step_hbm_gib", "moe_e128_rows_per_expert", "moe_e128_share_pct"}
    # the last step's own count, from the trainer: 4 of 16 held, 25% and
    # 2 x 80 x 4 x 4 / 16 / 4 = 40 rows an expert at par
    assert 0 < out["metrics"]["moe_e128_share_pct"]["value"] <= 100
    assert 0 < out["metrics"]["moe_e128_rows_per_expert"]["value"] <= 160


def test_the_real_benchmark_has_the_cell_and_its_five_metrics():
    spec = Catalog().spec
    cell, config, traffic = Catalog().cell(REAL)
    assert cell["chips"] == 1 and cell["config"] == "kanana_2_30b_a3b"
    assert cell["traffic"] == "lm_s16384"
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    new = [m for m in spec["per_layer"] if m.get("workloads") == [REAL]]
    assert [m["name"] for m in new] == NEW
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 5] == NEW                  # appended together
    assert all(m["moves"] == "train_tokens_per_s" for m in new)
    layers = {m["name"]: m["layer"] for m in new}
    assert layers.pop("mla_rope_flash_roofline_pct") == "kernels"
    assert set(layers.values()) == {"functional trainers"}
    sources = {m["name"]: m["source"] for m in new}
    assert sources["moe_e128_rows_per_expert"] \
        == sources["moe_e128_share_pct"] == "program_counter"
    for m in new:                        # every reader is a file of its own
        assert callable(Catalog().module("layer_metrics", m["name"]).metric)
    # the traffic is Laguna's and Qwen3-Next's file, as it stood
    assert (traffic["batch"], traffic["seq_len"]) == (1, 16384)
    assert traffic["zipf_exponent"] == 1.0 and traffic["pool_batches"] == 8
    assert traffic["token"] == "input_positions"
    assert traffic["mesh"] == {"data": 1}
    assert all(len(w["why"]) <= 200 for w in spec["workloads"])
    assert all(len(c["why"]) <= 200 for c in spec["configs"])
    assert "768 rows an expert (deployment 6144)" in cell["why"]


def test_the_scoped_readers_read_a_reduction(catalog):
    """The new readers on a hand-made reduction, against their counts."""
    cell, config, traffic = catalog.cell(CELL)
    scope_ns = {name: {"forward": 0, "backward": 0, "total": total}
                for name, total in (("mla_expand", 8e6), ("rope", 3e6),
                                    ("moe_experts", 7e6), ("moe_router", 4e6),
                                    ("moe_dispatch", 9e6),
                                    ("moe_shared", 5e6))}
    reduced = {"scope_ns": scope_ns,
               "kernel_ns": {"flash_fwd": 1e6, "flash_bwd": 3e6}}
    facts = {"scope_profile": reduced, "cell": cell, "config": config,
             "traffic": traffic, "catalog": catalog,
             "peak": CPU_PEAKS["cpu"], "job": types.SimpleNamespace(
                 step_fn=lambda *a: None)}

    def read(name):
        return catalog.module("layer_metrics", name).metric(facts)

    assert read("mla_expand_rope_ms") == 11.0
    assert read("moe_e128_layer_ms") == 25.0
    # a trainer that keeps no counter: nothing
    assert read("moe_e128_share_pct") is None
    assert read("moe_e128_rows_per_expert") is None
    counts = np.full((4, 16), 10)
    counts[2, 4:8] = 30                 # 120 of 240 on the experts 4 to 7
    facts["job"].step_fn.aux = [counts]
    assert read("moe_e128_share_pct") == pytest.approx(50.0)
    assert read("moe_e128_rows_per_expert") == pytest.approx(30.0)
    # five layers of 4 heads, 2 rows, half of 80 x 80 at 24 and 16 channels
    flash = catalog.module("flops", "mla_rope_flash")
    assert flash.flops_per_step(config, traffic) \
        == 5 * 2 * 4 * (80 * 80 // 2) * 2 * 3 * (24 + 16)
    assert flash.bytes_per_step(config, traffic) \
        == 5 * 2 * 4 * 80 * 2 * 6 * (24 + 16)
    assert read("mla_rope_flash_roofline_pct") == pytest.approx(100 * max(
        flash.flops_per_step(config, traffic) / 1e12,
        flash.bytes_per_step(config, traffic) / 1e11) / 4e-3)
    # a step that runs no flash call: nothing
    reduced["kernel_ns"] = {}
    assert read("mla_rope_flash_roofline_pct") is None
    # a trace without one of the scopes: nothing
    del scope_ns["rope"], scope_ns["moe_shared"]
    assert read("mla_expand_rope_ms") is None
    assert read("moe_e128_layer_ms") is None
    # a program without the scopes or the counter (the parent's): nothing,
    # and no raise
    facts["scope_profile"] = None
    facts["job"] = types.SimpleNamespace(step_fn=lambda *a: None)
    assert all(read(name) is None for name in NEW)


def test_flops_count_what_the_equations_say():
    """One layer of each kind by hand at the cell's sizes (ISSUE 44's
    arithmetic), the probe's rows once it has run, and the kernels' count
    beside the step's."""
    catalog = Catalog()
    _, config, traffic = catalog.cell(REAL)
    flops = catalog.module("flops", "deepseek_v3")
    at_par = flops.flops_per_token(config, traffic)
    h, s = 2048, 16384
    projections = 2 * h * 32 * 192 + 2 * h * 576 + 2 * 512 * 32 * 256 \
        + 2 * 32 * 128 * h
    core = 32 * (s // 2) * 2 * (192 + 128)
    assert flops.mixer_flops(config, traffic) == (projections, core)
    assert round(projections / 1e6, 1) == 52.7 and core == 10240 * s
    expert = 3 * 2 * h * 768
    # an expert layer at par: the router, 6 x 16 / 128 experts of three
    # matmuls, and the shared feed-forward of two experts' width
    moe_layer = 2 * h * 128 + (6 * 16 / 128 + 2) * expert
    assert round(6 * 16 / 128 * expert / 1e6) == 7
    assert round(2 * expert / 1e6, 1) == 18.9
    dense = 3 * 2 * h * 6144
    head = 2 * h * 16128
    assert at_par == 3 * (5 * (projections + core) + dense + 4 * moe_layer
                          + head)
    assert round(at_par / 1e9, 2) == 4.05 and round(head / 1e6) == 66
    assert round((projections + core + dense) / 1e6) == 296
    # the core is 68% of an expert layer, the mixer 89%
    layer = projections + core + moe_layer
    assert round(100 * core / layer) == 68
    assert round(100 * (projections + core) / layer) == 89
    probed = dict(config, probe={"held_rows": [12288, 6144, 24576, 6144],
                                 "tokens": 16384})
    assert flops.flops_per_token(probed, traffic) == at_par
    probed["probe"]["held_rows"][0] = 2 * 12288
    assert flops.flops_per_token(probed, traffic) - at_par \
        == pytest.approx(3 * 12288 / 16384 * expert, abs=1.0)
    flash = catalog.module("flops", "mla_rope_flash")
    assert flash.flops_per_step(config, traffic) \
        == 5 * 32 * (s * s // 2) * 2 * 3 * 320 == 3 * 5 * core * s
    assert flash.bytes_per_step(config, traffic) \
        == 5 * 32 * s * 2 * 6 * 320
    # what Kimi Linear's count gives its one MLA layer in five at this
    # length, a layer
    kimi = catalog.module("flops", "mla_flash")
    _, kimi_config, _ = catalog.cell("kimi_linear_48b_a3b.lm_s8192")
    assert flash.flops_per_step(config, traffic) \
        == 5 * kimi.flops_per_step(kimi_config, traffic)


PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128}


def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's config is in the file with its value,
    but the three the cut changes, which ``reduced`` lists."""
    catalog = Catalog()
    entry = {c["name"]: c
             for c in catalog.spec["configs"]}["kanana_2_30b_a3b"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/kakaocorp/"
                               "kanana-2-30b-a3b-instruct-2601/blob/main/"
                               "config.json")
    assert "one of 8 chips" in entry["why"]
    _, config, _ = catalog.cell(REAL)
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16128)
    assert config["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128,
        "vocab_size": 128256}
    assert 8 * 16128 >= 128256 > 8 * (16128 - 128) and 16128 % 128 == 0
    assert config["router_width"] == 128 and config["experts_held"] == [0, 16]
    assert "one of 8 chips that share each layer" in config["deployment"]
    assert len(config["reduced"]) == 3
    for assumed in ("vocab_size", "head_dim", "rope_interleave",
                    "n_shared_experts", "router score", "initialisation",
                    "router_bias_update_rate", "router_bias_settle"):
        assert assumed in config["assumed"]
    assert "FITTED TO THIS CELL" in config["assumed"]["router_bias_settle"]
    assert any("1e-20" in d for d in config["departures"])
    assert any("Instruct" in d for d in config["departures"])
    assert config["scopes"] == ["mla_expand", "rope", "moe_router",
                                "moe_dispatch", "moe_experts", "moe_shared"]
    for part in ("dtype", "attention", "experts", "recomputation",
                 "precision"):
        assert config["program"][part]
    # and the program's configuration of it is the published model's cut
    from paddle_tpu.models import deepseek_v3
    cfg = catalog.module("runners", "train_deepseek_v3").model_config(config)
    assert cfg == deepseek_v3.kanana_2_30b_a3b(
        num_layers=5, vocab_size=16128, experts_held=(0, 16))
    assert cfg.rope_interleave is True and cfg.scoring.scale == 2.448


def test_a_configuration_the_program_has_no_form_for_is_refused():
    catalog = Catalog()
    _, config, _ = catalog.cell(REAL)
    model_config = catalog.module("runners",
                                  "train_deepseek_v3").model_config
    for key, value, says in (("q_lora_rank", 1536, "query latent"),
                             ("rope_scaling", {"type": "yarn"}, "scaling"),
                             ("n_group", 8, "one group"),
                             ("scoring_func", "softmax", "sigmoid"),
                             ("qk_head_dim", 128, "nope and rope"),
                             ("n_routed_experts", 128, "held here")):
        with pytest.raises(ValueError, match=says):
            model_config(dict(config, **{key: value}))
    assert model_config(dict(config, rope_interleave=False)) \
        .rope_interleave is False


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
    from paddle_tpu.models import deepseek_v3
    cfg = catalog.module("runners", config["runner"]).model_config(config)
    zero = dict(params, layers=[
        dict(lp, router_bias=0 * lp["router_bias"]) if "router_bias" in lp
        else lp for lp in params["layers"]])
    batch = job.draw_batch(np.random.RandomState(0), 2)

    def unevenness(p):
        counts = deepseek_v3.routing_stats(p, cfg, batch)
        return float((counts.max(axis=1) / counts.mean(axis=1)).mean())

    assert unevenness(params) < unevenness(zero)


def test_reference_comparison_fails_below_the_configuration_s_precision(
        catalog, job, config):
    """The controls of ``reference/deepseek_v3.py`` through the harness's own
    ``compare`` at the committed limits: the program agrees; what every part
    hands on in 4 stored bits fails by the outputs, several times the
    program's reading; bfloat16's 7 bits there pass; a router that chooses
    by scores of 4 stored bits fails the routing check (7 bits do on the
    cell's 16 384 tokens; 160 tokens are too few to meet a close pair);
    bfloat16 parameters are refused; a loss in 4 bits fails by the loss."""
    import jax
    import jax.numpy as jnp
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
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype),
                           params)
    ok, errors = run.compare(
        reference.loss_and_outputs(rounded, config, sample), want,
        reference.TOLERANCE)
    assert not ok and np.isnan(errors["outputs"])
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]
