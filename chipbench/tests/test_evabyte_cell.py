"""CPU rehearsal of the EvaByte cell: ``run_cell`` on the fixture
``evabyte_toy.lm_toy_s80`` (``fixtures/benchmark_evabyte.json``: the toy
configuration, two windows of 32 and half a third at 80 positions, every
general per-layer metric of the real benchmark and the five
``evabyte.lm_s16384`` brings), with a peaks table that has the CPU, as
``test_nemotron_h_cell.py`` does for its cell; and the real cell's
configuration, counts and files."""

import json
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.catalog import ROOT, Catalog
from chipbench.tests.test_rehearsal import (DEVICE_KEYS, KEYS,
                                            round_mantissa)

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
CELL = "evabyte_toy.lm_toy_s80"
REAL = "evabyte.lm_s16384"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
NEW = ["eva_core_ms", "eva_core_roofline_pct", "eva_summary_ms",
       "eva_tiles_visited_pct", "multibyte_head_ms"]
SCOPES = ["eva_summary", "eva_core", "ffn", "multibyte_head", "rope"]
SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark_evabyte.json")


@pytest.fixture(scope="module")
def config(catalog):
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
    and do not raise; the counters and the host-clock metrics are there
    (80 positions are no whole windows of 32: the reference body runs and
    the tiles' counter has nothing to count)."""
    out = last_line(capsys, ["--workload", CELL, "--seed", "3",
                             "--seconds", "0.5", "--trace", "1"],
                    catalog=catalog, peaks=CPU_PEAKS)
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "mfu_pct", "window_stall_pct", "pallas_bodies_selected",
        "step_hbm_gib"}


def test_the_real_benchmark_has_the_cell_and_its_five_metrics():
    spec = Catalog().spec
    cell, config, traffic = Catalog().cell(REAL)
    assert cell["chips"] == 1 and cell["config"] == "evabyte"
    assert cell["traffic"] == "lm_s16384"
    assert len(spec["configs"]) >= 10 and len(spec["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    new = [m for m in spec["per_layer"] if m.get("workloads") == [REAL]]
    assert [m["name"] for m in new] == NEW
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 5] == NEW                  # appended together
    assert all(m["moves"] == "train_tokens_per_s" for m in new)
    layers = {m["name"]: m["layer"] for m in new}
    assert layers == {"eva_core_ms": "kernels",
                      "eva_core_roofline_pct": "kernels",
                      "eva_summary_ms": "functional trainers",
                      "eva_tiles_visited_pct": "kernels",
                      "multibyte_head_ms": "functional trainers"}
    sources = {m["name"]: m["source"] for m in new}
    assert sources.pop("eva_tiles_visited_pct") == "program_counter"
    assert set(sources.values()) == {"device_trace"}
    for m in new:                        # every reader is a file of its own
        assert callable(Catalog().module("layer_metrics", m["name"]).metric)
    # the traffic is the Laguna cell's file, as it stood
    assert (traffic["batch"], traffic["seq_len"]) == (1, 16384)
    assert traffic["zipf_exponent"] == 1.0 and traffic["pool_batches"] == 8
    assert traffic["token"] == "input_positions"
    assert traffic["mesh"] == {"data": 1}
    assert all(len(w["why"]) <= 200 for w in spec["workloads"])
    assert all(len(c["why"]) <= 200 for c in spec["configs"])
    assert all(len(c["source"]) <= 200 for c in spec["configs"])
    for says in ("8 windows", "30%", "no expert layer"):
        assert says in cell["why"]


def test_the_scoped_readers_read_a_reduction(catalog):
    """The new readers on a hand-made reduction, against their counts."""
    cell, config, traffic = catalog.cell(CELL)
    scope_ns = {name: {"forward": 0, "backward": 0, "total": total}
                for name, total in zip(SCOPES, (3e6, 8e6, 9e6, 2e6, 1e6))}
    facts = {"scope_profile": {
        "scope_ns": scope_ns,
        "kernel_ns": {"flash_fwd_eva": 1e6, "flash_bwd_eva": 3e6,
                      "flash_fwd": 5e6}},
        "cell": cell, "config": config, "traffic": traffic,
        "catalog": catalog, "peak": CPU_PEAKS["cpu"],
        "job": types.SimpleNamespace(eva_tiles_visited_pct=20.5)}

    def read(name):
        return catalog.module("layer_metrics", name).metric(facts)

    assert read("eva_core_ms") == 8.0
    assert read("eva_summary_ms") == 3.0
    assert read("multibyte_head_ms") == 2.0
    assert read("eva_tiles_visited_pct") == 20.5
    # two layers of 4 heads of 16 on 2 x 80 positions, a window of 32 in
    # chunks of 8: two whole windows and 16 positions of a third
    counts = catalog.module("flops", "eva_core")
    tokens = 2 * 32 * 33 // 2 + 16 * 17 // 2
    summaries = 4 * (32 * 1 + 16 * 2)
    assert counts.visible_pairs(80, 32, 8) == (tokens, summaries)
    assert counts.flops_per_step(config, traffic) \
        == 2 * 2 * 4 * (tokens + summaries) * 6 * 2 * 16
    assert counts.bytes_per_step(config, traffic) \
        == 2 * 2 * 4 * 16 * 2 * (12 * 80 + 6 * 10)
    # the two EVA calls' time and no other kernel's
    assert read("eva_core_roofline_pct") == pytest.approx(100 * max(
        counts.flops_per_step(config, traffic) / 1e12,
        counts.bytes_per_step(config, traffic) / 1e11) / 4e-3)
    # a trace without the scopes or the calls: nothing
    facts["scope_profile"] = {"scope_ns": {}, "kernel_ns": {"flash_fwd": 5e6}}
    assert read("eva_core_ms") is None and read("eva_summary_ms") is None
    assert read("multibyte_head_ms") is None
    assert read("eva_core_roofline_pct") is None
    # a program without the scopes or the counter (the parent's): nothing,
    # and no raise
    facts["scope_profile"] = None
    facts["job"] = types.SimpleNamespace()
    assert all(read(name) is None for name in NEW)


def test_flops_count_what_the_equations_say():
    """The cell's step by hand (ISSUE 53's arithmetic)."""
    catalog = Catalog()
    _, config, traffic = catalog.cell(REAL)
    core = catalog.module("flops", "eva_core")
    flops = catalog.module("flops", "evabyte")
    tokens, summaries = core.visible_pairs(16384, 2048, 16)
    assert tokens == 8 * 2048 * 2049 // 2 == 16_785_408
    assert summaries == 2048 * 128 * 28 == 7_340_032
    assert round(100 * summaries / (tokens + summaries)) == 30
    assert 16384 * 16385 // 2 == 134_225_920         # full causal attention
    assert core.flops_per_step(config, traffic) \
        == 4 * 32 * (tokens + summaries) * 6 * 2 * 128
    assert round(core.flops_per_step(config, traffic) / 1e12, 2) == 4.74
    assert core.bytes_per_step(config, traffic) \
        == 4 * 32 * 128 * 2 * (12 * 16384 + 6 * 1024)
    h, f, s = 4096, 11008, 16384
    matmuls = 4 * (4 * h * h + 3 * h * f) + h * 8 * 320
    per_token = flops.flops_per_token(config, traffic)
    assert per_token == pytest.approx(
        3 * (2 * matmuls + 4 * 32 * 4 * 2 * 128)
        + core.flops_per_step(config, traffic) / s)
    assert round(per_token * s / 1e12, 1) == 85.4
    # the aggregation is 5.6% of the step's operations
    assert 0.05 < core.flops_per_step(config, traffic) / (per_token * s) \
        < 0.06
    # a sequence of one window: plain causal attention, no summary
    assert core.visible_pairs(1024, 2048, 16) == (1024 * 1025 // 2, 0)


def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's config is in the file with its value,
    but the one the cut changes, which ``reduced`` lists."""
    catalog = Catalog()
    entry = {c["name"]: c for c in catalog.spec["configs"]}["evabyte"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"].startswith(SOURCE + ";")
    assert "arXiv:2302.04542" in entry["source"]
    _, config, traffic = catalog.cell(REAL)
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True,
        "max_position_embeddings": 32768, "max_seq_length": 32768,
        "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 4
    assert config["published"] == {"num_hidden_layers": 32}
    assert len(config["reduced"]) == 1
    for says in ("one of 8 chips", "pipeline of 4 layers each",
                 "821.4 M parameters", "9.18 GiB"):
        assert says in config["deployment"]
    for assumed in ("summaries", "visibility", "summary vectors' start",
                    "initialisation", "heads' weights", "rotation"):
        assert "alternative" in config["assumed"][assumed].lower(), assumed
    for says in ("adaptive_mu_k", "adaptive_phi", "-|k|^2 / 2"):
        assert says in config["assumed"]["summaries"]
    assert any("1e-4" in d for d in config["departures"])
    assert any("fp32_ln" in d for d in config["departures"])
    assert any("image" in d for d in config["departures"])
    assert config["scopes"] == SCOPES
    assert config["optimizer"] == {"name": "Adam", "learning_rate": 0.0001}
    for part in ("dtype", "attention", "feed_forward", "head",
                 "recomputation", "precision"):
        assert config["program"][part]
    # and the program's configuration of it is the published model's cut
    import jax
    from paddle_tpu.models import evabyte
    cfg = catalog.module("runners", "train_evabyte").model_config(config,
                                                                  traffic)
    assert cfg == evabyte.evabyte_6b5(num_layers=4)
    shapes = jax.eval_shape(lambda: evabyte.init_params(
        jax.random.PRNGKey(0), cfg))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 4 * 202_391_552 + 320 * 4096 + 4096 * 2560 + 4096 \
        == 821_366_784
    assert round(12 * count / 2**30, 2) == 9.18


def test_a_configuration_the_program_has_no_form_for_is_refused():
    catalog = Catalog()
    _, config, traffic = catalog.cell(REAL)
    model_config = catalog.module("runners", "train_evabyte").model_config
    for key, value, says in (("attention_bias", True, "no bias"),
                             ("attention_class", "softmax", "EVA attention"),
                             ("num_key_value_heads", 8, "one key/value"),
                             ("fp32_skip_add", False, "residual stream"),
                             ("chunk_size", 24, "whole chunks"),
                             ("rope_scaling", {"type": "yarn"}, "rotary")):
        with pytest.raises(ValueError, match=says):
            model_config(dict(config, **{key: value}), traffic)


def test_token_ids_follow_the_zipf_law_over_the_bytes(job):
    a, b, c = job.pool(2**31 + 5), job.pool(2**31 + 5), job.pool(6)
    assert len(a) == 8 and a[0]["input_ids"].shape == (2, 80)
    assert all(np.array_equal(x["input_ids"], y["input_ids"])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
    for batch in a:
        assert np.array_equal(batch["input_ids"][:, 1:],
                              batch["labels"][:, :-1])
        assert batch["input_ids"].max() < 32
    ids = np.concatenate([x["input_ids"].ravel() for x in a + c])
    assert 0.15 < np.mean(ids == 0) < 0.35          # 1 / H_32 = 0.246
    assert job.tokens_per_step == 2 * 80
    assert job.eva_tiles_visited_pct is None         # 80 = 2.5 windows


def test_reference_comparison_fails_below_the_configuration_s_precision(
        catalog, job, config):
    """The controls of ``reference/evabyte.py`` through the harness's own
    ``compare`` at the committed limits: the program agrees; a bfloat16
    residual stream is refused, as are a softmax whose logsumexp moves in
    steps of 1/32 (bfloat16's 7 stored bits at the cell's logsumexp of 4 to
    8, 6 bits at this toy's of 2 to 4) and summaries left out of its
    denominator, both by the mixers' own limit; bfloat16 parameters are
    refused; a loss in 4 bits fails by the loss."""
    import jax
    import jax.numpy as jnp
    reference = catalog.module("reference", config["reference"])
    params, _ = job.init_fn(jax.random.PRNGKey(0))
    sample = job.sample(0)
    loss, outputs = job.probe(params, job.place(sample))
    # the embedding, two parts a layer, the final states; then the logits
    assert len(sample["program_stream"]) == 6
    assert all(p.shape == (2, 80, 64) and p.dtype == np.float32
               for p in sample["program_stream"])
    assert sample["program_logits"].shape == (2, 80, 3 * 32)
    assert outputs.shape == (6 * 2 * 80 * 64 + 2 * 80 * 96,)
    assert job.head_losses.shape == (3,)
    assert float(loss) == pytest.approx(float(job.head_losses.mean()))
    want = reference.loss_and_outputs(params, config, sample)
    ok, sound = run.compare((loss, outputs), want, reference.TOLERANCE)
    assert ok, sound
    for control in ({"state_bits": 7}, {"softmax_bits": 6},
                    {"summaries_in_sum": False}):
        low = reference.loss_and_outputs(params, config, sample, **control)
        ok, errors = run.compare(low, want, reference.TOLERANCE)
        assert not ok and np.isnan(errors["outputs"]), (control, errors)
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype),
                           params)
    ok, errors = run.compare(
        reference.loss_and_outputs(rounded, config, sample), want,
        reference.TOLERANCE)
    assert not ok and np.isnan(errors["outputs"])
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]
