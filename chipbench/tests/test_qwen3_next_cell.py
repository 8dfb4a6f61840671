"""CPU rehearsal of the Qwen3-Next cell: ``run_cell`` on the fixture
``qwen3_next_toy.lm_toy_s80`` (``fixtures/benchmark_qwen3_next.json``: the toy
configuration, 80 positions, every general per-layer metric of the real
benchmark and the seven ``qwen3_next_80b_a3b.lm_s16384`` brings), with a
peaks table that has the CPU, as ``test_laguna_cell.py`` does for its cell;
and the real cell's configuration, counts and files."""

import json
import types

import numpy as np
import pytest

from chipbench import run
from chipbench.catalog import ROOT, Catalog
from chipbench.tests.test_rehearsal import (DEVICE_KEYS, KEYS,
                                            round_mantissa)

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
CELL = "qwen3_next_toy.lm_toy_s80"
REAL = "qwen3_next_80b_a3b.lm_s16384"
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
NEW = {"gdn_core_ms", "gdn_core_roofline_pct", "gdn_conv_gate_ms",
       "gqa256_flash_roofline_pct", "rope_gate256_ms", "moe_e512_layer_ms",
       "moe_e512_share_pct"}


@pytest.fixture(scope="module")
def catalog():
    return Catalog(FIXTURES / "benchmark_qwen3_next.json")


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
        "step_hbm_gib", "moe_e512_share_pct"}
    # the last step's own count, from the trainer: 4 of 16 held, 25% at par
    assert 0 < out["metrics"]["moe_e512_share_pct"]["value"] <= 100


def test_the_real_benchmark_has_the_cell_and_its_seven_metrics():
    spec = Catalog().spec
    cell, config, traffic = Catalog().cell(REAL)
    assert cell["chips"] == 1 and cell["config"] == "qwen3_next_80b_a3b"
    assert cell["traffic"] == "lm_s16384"
    assert spec["workloads"][-1]["name"] == REAL
    assert spec["configs"][-1]["name"] == "qwen3_next_80b_a3b"
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    new = [m for m in spec["per_layer"] if m.get("workloads") == [REAL]]
    assert {m["name"] for m in new} == NEW
    assert [m["name"] for m in spec["per_layer"][-7:]] \
        == [m["name"] for m in new]                     # appended, last
    assert all(m["moves"] == "train_tokens_per_s" for m in new)
    layers = {m["name"]: m["layer"] for m in new}
    assert layers["gdn_core_ms"] == layers["gqa256_flash_roofline_pct"] \
        == "kernels"
    assert layers["moe_e512_share_pct"] == "functional trainers"
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
                for name, total in (("gdn_core", 30e6), ("gdn_gate", 11e6),
                                    ("short_conv", 6e6), ("rope", 2e6),
                                    ("attn_gate", 3e6), ("moe_experts", 7e6),
                                    ("moe_router", 4e6),
                                    ("moe_dispatch", 9e6),
                                    ("moe_shared", 1e6))}
    reduced = {"scope_ns": scope_ns,
               "kernel_ns": {"flash_fwd": 1e6, "flash_bwd": 3e6}}
    facts = {"scope_profile": reduced, "cell": cell, "config": config,
             "traffic": traffic, "catalog": catalog,
             "peak": CPU_PEAKS["cpu"], "job": types.SimpleNamespace(
                 step_fn=lambda *a: None)}
    got = {name: catalog.module("layer_metrics", name).metric(facts)
           for name in NEW}
    assert got["gdn_core_ms"] == 30.0 and got["gdn_conv_gate_ms"] == 17.0
    assert got["rope_gate256_ms"] == 5.0
    assert got["moe_e512_layer_ms"] == 21.0
    assert got["moe_e512_share_pct"] is None          # a trainer with none
    counts = np.full((4, 16), 10)
    counts[2, 4:8] = 30                 # 120 of 240 on the experts 4 to 7
    facts["job"].step_fn.aux = [counts]
    assert catalog.module("layer_metrics", "moe_e512_share_pct").metric(
        facts) == pytest.approx(50.0)
    # three delta-rule layers, 2 rows of 80 positions, 4 value heads of 16
    # over 2 key heads, by hand at the op's chunk
    from paddle_tpu.ops import kda
    gdn = catalog.module("flops", "gdn_core")
    c, d, n = kda.CHUNK_HEAD, 16, 2
    positions_heads = 3 * 2 * 80 * 4
    assert gdn.chunk_size() == c
    assert gdn.flops_per_step(config, traffic) \
        == 3 * positions_heads * ((3 + 2 / n) * c * d + 6 * d * d)
    # q and k halved between a key head's two value heads, v, g, beta, o;
    # backward the same with dO, and dq, dk, dv, dg, dbeta out
    forward = 2 * 2 * d / n + 2 * d + 8 + 2 * d
    assert gdn.bytes_per_step(config, traffic) == positions_heads * (
        forward + forward + 2 * d + 2 * 2 * d / n + 2 * d + 8)
    assert got["gdn_core_roofline_pct"] == pytest.approx(100 * max(
        gdn.flops_per_step(config, traffic) / 1e12,
        gdn.bytes_per_step(config, traffic) / 1e11) / 30e-3)
    # one full layer, 8 heads over 2 of 32 channels, half of 80 x 80
    gqa = catalog.module("flops", "gqa256_flash")
    assert gqa.flops_per_step(config, traffic) \
        == 1 * 8 * 2 * (80 * 80 // 2) * 6 * 2 * 32
    assert gqa.bytes_per_step(config, traffic) \
        == 1 * (6 * 8 + 6 * 2) * 2 * 80 * 32 * 2
    assert got["gqa256_flash_roofline_pct"] == pytest.approx(100 * max(
        gqa.flops_per_step(config, traffic) / 1e12,
        gqa.bytes_per_step(config, traffic) / 1e11) / 4e-3)
    # a step that runs no flash call: nothing
    reduced["kernel_ns"] = {}
    assert catalog.module("layer_metrics", "gqa256_flash_roofline_pct") \
        .metric(facts) is None
    # a trace without the delta rule's scope: nothing
    del scope_ns["gdn_core"]
    assert catalog.module("layer_metrics", "gdn_core_ms").metric(facts) \
        is None
    assert catalog.module("layer_metrics", "gdn_core_roofline_pct") \
        .metric(facts) is None
    # a program without the scopes (the parent's): nothing, and no raise
    facts["scope_profile"] = None
    facts["job"] = types.SimpleNamespace(step_fn=lambda *a: None)
    assert all(catalog.module("layer_metrics", name).metric(facts) is None
               for name in NEW)


def test_flops_count_what_the_equations_say():
    """One layer of each kind by hand at the cell's sizes, the probe's rows
    once it has run, and the kernels' counts beside the step's."""
    catalog = Catalog()
    _, config, traffic = catalog.cell(REAL)
    flops = catalog.module("flops", "qwen3_next")
    at_par = flops.flops_per_token(config, traffic)
    h = 2048
    # a Gated DeltaNet layer's matmuls a token: [q | k | v | z], [b | a], out
    gdn_layer = 2 * h * (2048 + 2048 + 4096 + 4096) + 2 * h * 64 \
        + 2 * 4096 * h
    # the attention layer: queries and gates, keys, values, out, and the
    # causal half of 16 heads' scores and context at 256
    attn_layer = 2 * h * (2 * 4096 + 2 * 512) + 2 * 4096 * h \
        + 16 * (16384 // 2) * 4 * 256
    # an expert layer at par: the router, 10 x 32 / 512 experts of three
    # matmuls, the shared expert and its scalar gate
    expert = 3 * 2 * h * 512
    moe_layer = 2 * h * 512 + 10 * 32 / 512 * expert + expert + 2 * h
    head = 2 * h * 19072
    gdn = catalog.module("flops", "gdn_core")
    scan = gdn.flops_per_step(config, traffic) / 16384
    assert at_par == pytest.approx(
        3 * (3 * gdn_layer + attn_layer + 4 * moe_layer + head) + scan,
        rel=1e-12)
    assert 1.5e9 < at_par < 1.7e9
    assert 0.13 < 3 * head / at_par < 0.17
    probed = dict(config, probe={"held_rows": [20480, 10240, 5120, 5120],
                                 "tokens": 16384})
    assert flops.flops_per_token(probed, traffic) - at_par \
        == pytest.approx(3 * 4 * (40960 / 4 / 16384 - 0.625) * expert,
                         abs=1.0)
    # the delta rule at d = 128, n = 2 and the op's chunk: by hand
    from paddle_tpu.ops import kda
    c = kda.CHUNK_HEAD
    assert scan == 3 * 3 * 32 * ((3 + 1) * c * 128 + 6 * 128 * 128)
    assert gdn.bytes_per_step(config, traffic) \
        == 3 * 16384 * 32 * (776 + 1552)
    gqa = catalog.module("flops", "gqa256_flash")
    assert gqa.flops_per_step(config, traffic) \
        == 16 * (16384 ** 2 // 2) * 6 * 2 * 256
    assert 0.23 < gqa.flops_per_step(config, traffic) / 16384 / at_par < 0.28
    assert gqa.bytes_per_step(config, traffic) \
        == (6 * 16 + 6 * 2) * 16384 * 256 * 2


PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 10,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False}


def test_configuration_keeps_every_published_width():
    """Every key of the catalog row's config is in the file with its value,
    but the three the cut changes, which ``reduced`` lists."""
    catalog = Catalog()
    entry = {c["name"]: c
             for c in catalog.spec["configs"]}["qwen3_next_80b_a3b"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/Qwen/Qwen3-Next-80B-"
                               "A3B-Instruct/blob/main/config.json")
    assert "one of 16 chips" in entry["why"]
    _, config, _ = catalog.cell(REAL)
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 19072)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert config["router_width"] == 512 and config["experts_held"] == [0, 32]
    assert config["router_aux_loss_coef"] == 0.001
    assert "one of 16 chips that share each layer" in config["deployment"]
    assert len(config["reduced"]) == 3
    for assumed in ("A_log and dt_bias", "linear_conv_kernel_dim",
                    "output norm", "router_aux_loss_coef", "initialisation",
                    "vocab_size"):
        assert assumed in config["assumed"]
    assert len(config["departures"]) == 4
    assert set(config["scopes"]) == {
        "gdn_core", "gdn_gate", "short_conv", "rope", "attn_gate",
        "moe_router", "moe_dispatch", "moe_experts", "moe_shared"}
    for part in ("dtype", "attention", "experts", "recomputation",
                 "precision"):
        assert config["program"][part]
    # and the program's configuration of it is the published model's cut
    from paddle_tpu.models import qwen3_next
    cfg = catalog.module("runners", "train_qwen3_next").model_config(config)
    assert cfg == qwen3_next.qwen3_next_80b_a3b(
        num_layers=4, vocab_size=19072, experts_held=(0, 32))


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


def test_reference_comparison_fails_below_the_configuration_s_precision(
        catalog, job, config):
    """The controls of ``reference/qwen3_next.py`` through the harness's own
    ``compare`` at the committed limits: the program agrees; what every part
    hands on in 4 stored bits fails by the outputs, several times the
    program's reading; bfloat16's 7 bits there pass; a router that chooses
    by logits of 2 stored bits fails the routing check (7 bits do on the
    cell's 16 384 tokens and logits of 2 to 3; 160 tokens and logits of a
    tenth are too few and too small to meet a close pair); a loss
    in 4 bits fails by the loss."""
    import jax
    reference = catalog.module("reference", config["reference"])
    params, _ = job.init_fn(jax.random.PRNGKey(0))
    sample = job.sample(0)
    loss, outputs = job.probe(params, job.place(sample))
    assert job.routing_counts.shape == (4, 16)
    assert (job.routing_counts.sum(axis=1) == 4 * 2 * 80).all()
    assert (job.held_rows == job.routing_counts[:, 4:8].sum(axis=1)).all()
    assert config["probe"]["tokens"] == 160
    assert outputs.shape == sample["program_stream"].shape == (10, 2, 80, 64)
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
                                        router_bits=2)
    ok, errors = run.compare(routed, want, reference.TOLERANCE)
    assert not ok and np.isnan(errors["outputs"])
    ok, errors = run.compare((round_mantissa(loss, 4), outputs), want,
                             reference.TOLERANCE)
    assert not ok and errors["loss"] > reference.TOLERANCE["loss"]
