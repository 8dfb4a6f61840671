"""``chipbench/scope_profile.py``: device time by the program's own names. On a
plane built by hand, where every number can be worked out on paper; on a small
trace recorded on a v5e from the tree that brought the scopes
(``bert_toy.mlm_toy.scopes``: ``record_fixture`` as it is, the file copied
under this name); on the two older traces, which have no scope; and through
the harness on the CPU, where the nine metrics find nothing to read, and with
the recorded trace in the place of the CPU's, where they all do."""

import json

import pytest

from chipbench import run, scope_profile as sp, trace_reduce as tr, xplane
from chipbench.catalog import ROOT, Catalog

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
TRACES = FIXTURES / "traces"
NINE = ["fwd_ms", "bwd_ms", "optimizer_step_ms", "attention_core_ms",
        "layer_norm_ms", "flash_roofline_pct", "scope_unattributed_pct",
        "host_place_ms", "host_enqueue_ms"]


def test_a_name_stack_comes_apart_into_direction_and_scope():
    assert sp.elements("jit(step)/transpose(jvp(attention))/attention_core/"
                       "bnqk,bknd->bqnd/dot_general:") == \
        ["step", "attention", "attention_core", "bnqk,bknd->bqnd",
         "dot_general"]
    assert sp.elements("jit(step)/jvp()/add") == ["step", "add"]
    for tf_op, want in {
            "jit(step)/jvp(attention)/attention_core/flash_fwd/pallas_call:":
                ("forward", "attention_core"),
            "jit(step)/transpose(jvp(attention))/attention_core/mul:":
                ("backward", "attention_core"),
            "jit(step)/transpose(jvp(attention))/transpose:":
                ("backward", "attention"),
            "jit(step)/jvp(embed)/layer_norm/layer_norm_fwd/pallas_call:":
                ("forward", "layer_norm"),
            "jit(step)/jvp(attention)/transpose:": ("forward", "attention"),
            "jit(step)/jvp()/add:": ("forward", None),
            "jit(step)/optimizer/fused_adam/pallas_call:":
                ("optimizer", "optimizer"),
            "jit(step)/reshape:": ("other", None),
            "": ("other", None), None: ("other", None)}.items():
        assert sp.classify(tf_op) == want, tf_op


def ev(name, start, end, tf_op=None, **stats):
    if tf_op is not None:
        stats["tf_op"] = tf_op
    return tr.Event(name, start, end, stats)


MOSAIC = ('%{0}.3 = (f32[8,128]{{1,0:T(8,128)}}) custom-call(f32[8,128]{{1,0}} '
          '%p), custom_call_target="tpu_custom_call"')


def hand_made(scoped=True):
    """Two steps of 100 ns, 20 apart. In each: a fusion of the backward pass
    under attention_core (0-40); the Mosaic call flash_fwd of the forward pass
    (40-50); a copy with no tf_op (50-60); the Mosaic call fused_adam under
    optimizer (60-70); a while of the forward pass with no scope (70-90)
    around a fusion under ffn (75-85); nothing from 90 on, and at 90 the host
    is inside trainer/place, inside step_call."""
    def name(stack):
        return stack if scoped else None

    ops, modules, host = [], [], []
    for lo in (1000, 1120):
        modules.append(ev("jit_step(7)", lo, lo + 100))
        ops += [
            ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", lo,
               lo + 40, name("jit(step)/transpose(jvp(attention))/"
                             "attention_core/mul:"),
               hlo_category="loop fusion"),
            ev(MOSAIC.format("flash_fwd"), lo + 40, lo + 50,
               name("jit(step)/jvp(attention)/attention_core/flash_fwd/"
                    "pallas_call:"), hlo_category="custom-call"),
            ev("%copy.4 = f32[8]{0} copy(f32[8]{0} %q)", lo + 50, lo + 60,
               hlo_category="data formatting"),
            ev(MOSAIC.format("fused_adam"), lo + 60, lo + 70,
               name("jit(step)/optimizer/fused_adam/pallas_call:"),
               hlo_category="custom-call"),
            ev("%while.2 = (f32[8]{0}) while((f32[8]{0}) %t)", lo + 70,
               lo + 90, name("jit(step)/jvp()/while:"),
               hlo_category="while"),
            ev("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %r)", lo + 75, lo + 85,
               name("jit(step)/jvp(ffn)/dot_general:"),
               hlo_category="convolution fusion"),
        ]
        host += [ev("step_call", lo + 80, lo + 99),
                 ev("trainer/place", lo + 82, lo + 95),
                 ev("trainer/enqueue", lo + 95, lo + 98),
                 ev("fetch_loss", lo + 99, lo + 119)]
    modules.append(ev("jit_convert(3)", 900, 905))      # not the step
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": host + [ev("other", 0, 5000)]}}


def test_reduction_of_the_hand_made_plane():
    got = sp.reduce_planes(hand_made())
    assert got["steps"] == 2 and got["devices"] == 1
    assert got["busy_ns"] == 90 and got["window_ns"] == 110
    assert got["direction_ns"] == {"forward": 30, "backward": 40,
                                   "optimizer": 10, "other": 10}
    assert sum(got["direction_ns"].values()) == got["busy_ns"]
    assert got["scope_ns"]["attention_core"] == {
        "forward": 10, "backward": 40, "total": 50}
    assert got["scope_ns"]["ffn"] == {"forward": 10, "backward": 0,
                                      "total": 10}
    assert got["scope_ns"]["optimizer"]["total"] == 10
    assert got["scope_ns"]["attention"]["total"] == 0     # innermost counts
    # the copy and the while's own time: no scope, whatever the direction
    assert got["unscoped_ns"] == 20
    assert dict(map(tuple, got["unscoped_ops"])) == {"copy": 10, "while": 10}
    assert got["kernel_ns"] == {"flash_fwd": 10, "fused_adam": 10}
    assert got["category_ns"]["custom-call"] == 20
    assert got["host_span_ms"]["trainer/place"] == [13e-6, 13e-6]
    assert got["host_span_ms"]["trainer/enqueue"] == [3e-6, 3e-6]
    # idle from 90 of the first step to the start of the second (30) and the
    # last 10 of the second: both gaps began inside trainer/place, which is
    # inside step_call, and a gap goes to the innermost span open at its start
    assert got["idle_gap_ns"] == {"trainer/place": 20}
    assert any("trainer/place" in line for line in sp.table(got))


def test_a_trace_without_scopes_says_so():
    """No device plane: nothing. Device operations with no scope among them
    (the two older traces): ``scoped_events`` is 0 and all time unscoped."""
    assert sp.reduce_planes({"/host:CPU": {"python3": []}}) is None
    got = sp.reduce_planes(hand_made(scoped=False))
    assert got["scoped_events"] == 0 and got["unscoped_ns"] == got["busy_ns"]
    assert got["direction_ns"]["other"] == got["busy_ns"]
    for old in ("bert_toy.mlm_toy", "bert_toy.mlm_toy_dp4"):
        got = sp.reduce_planes(xplane.load(TRACES / f"{old}.xplane.pb.gz"))
        assert got["scoped_events"] == 0
        assert got["unscoped_ns"] == pytest.approx(got["busy_ns"], rel=0.02)
        # forward and backward still come apart: jvp( ) is jax's, not ours
        assert got["direction_ns"]["backward"] > 0


def test_reduction_of_the_trace_recorded_with_scopes():
    """Three steps of the toy BERT on one v5e chip, from the tree that brought
    the scopes: the directions close on the busy time the harness's own
    reduction finds, every scope of the vocabulary has time, the Mosaic calls
    go by their kernels' names and both program spans are on the host."""
    path = TRACES / "bert_toy.mlm_toy.scopes.xplane.pb.gz"
    got = sp.reduce_planes(xplane.load(path))
    harness = tr.reduce_file(path)
    assert got["steps"] == harness["steps"] == 3
    assert got["busy_ns"] * 3 == pytest.approx(harness["busy_ns"])
    assert sum(got["direction_ns"].values()) == pytest.approx(got["busy_ns"],
                                                              rel=0.02)
    for scope in sp.SCOPES:
        assert got["scope_ns"][scope]["total"] > 0, scope
    for scope in sp.SCOPES[:-1]:
        assert got["scope_ns"][scope]["forward"] > 0, scope
        assert got["scope_ns"][scope]["backward"] > 0, scope
    assert got["scope_ns"]["optimizer"]["total"] == \
        pytest.approx(got["direction_ns"]["optimizer"])
    assert set(got["kernel_ns"]) == {"fused_adam", "layer_norm_fwd"}
    assert sum(got["kernel_ns"].values()) * 3 == \
        pytest.approx(harness["mosaic_ns"])
    assert 0 < got["unscoped_ns"] < 0.25 * got["busy_ns"]
    assert len(got["host_span_ms"]["trainer/place"]) == 3
    assert len(got["host_span_ms"]["trainer/enqueue"]) == 3
    labels = [n for n, _ in harness["top_ops"]]
    assert "fused_adam (mosaic)" in labels       # one kernel a Mosaic row


def test_a_configuration_names_further_scopes():
    """``bert_toy_scoped`` lists two names that are on the recorded trace's
    stacks inside ``optimizer`` and ``layer_norm``. Each takes the time of the
    operations it is innermost on out of the base scope around it, the other
    base scopes and the unscoped time stay to the nanosecond, and the whole
    vocabulary still sums with the unscoped time to the busy time."""
    _, config, _ = Catalog(FIXTURES / "benchmark_scopes.json").cell(
        "bert_toy_scoped.mlm_toy")
    assert config["scopes"] == ["fused_adam", "layer_norm_fwd"]
    assert sp.vocabulary(config["scopes"]) == sp.SCOPES + ("fused_adam",
                                                           "layer_norm_fwd")
    assert sp.vocabulary(["ffn", "router", "router"]) == sp.SCOPES + (
        "router",)
    planes = xplane.load(TRACES / "bert_toy.mlm_toy.scopes.xplane.pb.gz")
    base = sp.reduce_planes(planes)
    got = sp.reduce_planes(planes, scopes=config["scopes"])
    assert list(base["scope_ns"]) == list(sp.SCOPES)
    assert list(got["scope_ns"]) == list(sp.SCOPES) + config["scopes"]

    def total(reduced, scope):
        return reduced["scope_ns"][scope]["total"]

    # every operation under fused_adam is the kernel or the reshapes beside it
    assert total(got, "fused_adam") >= got["kernel_ns"]["fused_adam"] > 0
    assert total(got, "layer_norm_fwd") >= got["kernel_ns"]["layer_norm_fwd"]
    assert total(got, "optimizer") + total(got, "fused_adam") == \
        pytest.approx(total(base, "optimizer"))
    assert total(got, "layer_norm") + total(got, "layer_norm_fwd") == \
        pytest.approx(total(base, "layer_norm"))
    assert got["scope_ns"]["layer_norm_fwd"]["forward"] == \
        total(got, "layer_norm_fwd")              # the backward is plain jnp
    for scope in ("embed", "attention", "attention_core", "ffn", "loss"):
        assert got["scope_ns"][scope] == base["scope_ns"][scope]
    assert got["unscoped_ns"] == base["unscoped_ns"]
    assert got["direction_ns"] == base["direction_ns"]
    for reduced in (base, got):
        assert sum(d["total"] for d in reduced["scope_ns"].values()) \
            + reduced["unscoped_ns"] == pytest.approx(reduced["busy_ns"],
                                                      rel=0.02)
    # a name no stack carries reads 0, and takes nothing
    none = sp.reduce_planes(planes, scopes=["router"])
    assert total(none, "router") == 0
    assert total(none, "optimizer") == total(base, "optimizer")


def test_flash_operations_from_shapes():
    """BERT-base at batch 8 x 4096: 12 layers x 6 matmuls x 2 x 8 x 12 x
    4096^2 x 64 = 1.484e13 operations a step, 75.3 ms at 197 TFLOP/s, as
    before the function knew ``head_dim`` and ``attention``. A configuration
    that states its head size is counted by it, and a causal one by half."""
    catalog = Catalog()
    _, config, traffic = catalog.cell("bert_base.mlm_s4096")
    flops_per_step = catalog.module("flops", "flash").flops_per_step
    flops = flops_per_step(config, traffic)
    assert "head_dim" not in config and "attention" not in config
    assert flops == 12 * 6 * 2 * 8 * 12 * 4096 ** 2 * 64 == 14843406974976
    assert flops / 197e12 == pytest.approx(0.0753, rel=1e-2)
    assert flops_per_step(dict(config, attention="causal"), traffic) \
        == flops // 2
    assert flops_per_step(dict(config, head_dim=128), traffic) == 2 * flops
    # one layer of 16 heads of 128, causal, batch 2 x 4096
    decoder = {"num_hidden_layers": 1, "num_attention_heads": 16,
               "hidden_size": 2048, "head_dim": 128, "attention": "causal"}
    assert flops_per_step(decoder, {"batch": 2, "seq_len": 4096}) \
        == 6 * 2 * 2 * 16 * (4096 ** 2 // 2) * 128


@pytest.fixture(scope="module")
def scopes_catalog():
    return Catalog(FIXTURES / "benchmark_scopes.json")


def facts_of(catalog, reduced, **more):
    cell, config, traffic = catalog.cell("bert_toy.mlm_toy")
    return dict({"scope_profile": reduced, "cell": cell, "config": config,
                 "traffic": traffic, "catalog": catalog,
                 "peak": {"bf16_flops_per_s": 197e12}}, **more)


def test_the_nine_metrics_read_a_reduction(scopes_catalog):
    reduced = sp.reduce_planes(hand_made())
    facts = facts_of(scopes_catalog, reduced)
    got = {name: scopes_catalog.module("layer_metrics", name).metric(facts)
           for name in NINE}
    assert got["fwd_ms"] == 30e-6 and got["bwd_ms"] == 40e-6
    assert got["optimizer_step_ms"] == 10e-6
    assert got["attention_core_ms"] == 50e-6
    assert got["layer_norm_ms"] == 0
    assert got["scope_unattributed_pct"] == pytest.approx(100 * 20 / 90)
    assert got["host_place_ms"] == 13e-6
    assert got["host_enqueue_ms"] == 3e-6
    flops = scopes_catalog.module("flops", "flash").flops_per_step(
        facts["config"], facts["traffic"])
    assert got["flash_roofline_pct"] == pytest.approx(
        100 * (flops / 197e12) / 10e-9)
    # the backward call counts under either of its names, old and coming
    for name in ("flash_bwd_dkv", "flash_bwd"):
        reduced["kernel_ns"][name] = 30
        assert scopes_catalog.module(
            "layer_metrics", "flash_roofline_pct").metric(facts) == \
            pytest.approx(100 * (flops / 197e12) / 40e-9)
        del reduced["kernel_ns"][name]
    # a step without flash calls, a program without the spans: nothing to read
    reduced["kernel_ns"].pop("flash_fwd")
    reduced["host_span_ms"] = {"trainer/place": []}
    for name in ("flash_roofline_pct", "host_place_ms", "host_enqueue_ms"):
        assert scopes_catalog.module("layer_metrics", name).metric(
            facts) is None


def test_no_scope_means_none_for_every_metric_and_a_line_in_the_log(
        scopes_catalog, capsys):
    """An executable without scopes (the older recorded trace stands in for
    the run's): every metric returns None, never 0, and the log says why."""
    facts = facts_of(scopes_catalog, None, planes=xplane.load(
        TRACES / "bert_toy.mlm_toy.xplane.pb.gz"))
    del facts["scope_profile"]
    facts["peak"]["device_planes"] = "/device:TPU:"
    for name in NINE:
        assert scopes_catalog.module("layer_metrics", name).metric(
            facts) is None
    out = capsys.readouterr().out
    assert out.count(sp.NO_SCOPE) == 1           # reduced once, kept in facts
    assert facts["scope_profile"] is None


def traced_line(capsys, catalog, workload, **peak):
    run.main(["--workload", workload, "--seed", "2147483900", "--seconds",
              "0.5", "--trace", "1"], catalog=catalog,
             peaks={"cpu": dict({"bf16_flops_per_s": 1e12}, **peak)})
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_rehearsal_of_the_traced_run_with_the_nine_metrics(scopes_catalog,
                                                           capsys):
    """The whole traced run on the CPU, whose trace has no device plane: each
    of the nine returns None and is left out, and the last line keeps its
    keys."""
    line, out = traced_line(capsys, scopes_catalog, "bert_toy.mlm_toy")
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True
    assert not set(line["metrics"]) & set(NINE)
    assert {"mfu_pct", "step_hbm_gib"} <= set(line["metrics"])
    assert "no device plane in the trace" in out
    assert "second trace" not in out
    listed = [m["name"] for m in scopes_catalog.metrics(
        "per_layer", "bert_toy.mlm_toy")]
    assert listed[-9:] == NINE


def test_rehearsal_with_a_device_trace_reads_a_configuration_s_scopes(
        scopes_catalog, capsys, monkeypatch):
    """The planes of the trace recorded with scopes stand in for the CPU
    run's: every per-layer metric of the cell is on the line, all from the
    one parse, and the fixture's reader finds the time of ``fused_adam``,
    which only this cell's configuration names, out of ``optimizer``."""
    recorded = xplane.load(TRACES / "bert_toy.mlm_toy.scopes.xplane.pb.gz")
    loads = []
    monkeypatch.setattr(run.xplane, "load",
                        lambda path: loads.append(path) or recorded)
    line, out = traced_line(capsys, scopes_catalog, "bert_toy_scoped.mlm_toy",
                            device_planes="/device:TPU:")
    assert len(loads) == 1
    want = sp.reduce_planes(recorded, scopes=["fused_adam", "layer_norm_fwd"])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(metrics) == {m["name"] for m in scopes_catalog.metrics(
        "per_layer", "bert_toy_scoped.mlm_toy")}
    assert metrics["fused_adam_ms"] == \
        want["scope_ns"]["fused_adam"]["total"] / 1e6 > 0
    assert metrics["optimizer_step_ms"] == \
        want["scope_ns"]["optimizer"]["total"] / 1e6
    assert metrics["optimizer_step_ms"] + metrics["fused_adam_ms"] == \
        pytest.approx(sp.reduce_planes(recorded)["scope_ns"]["optimizer"]
                      ["total"] / 1e6)
    assert metrics["host_place_ms"] > 0 and metrics["host_enqueue_ms"] > 0
    assert metrics["pallas_time_pct"] == pytest.approx(
        100 * sum(want["kernel_ns"].values()) / want["busy_ns"])
    assert "[scopes] kernel fused_adam" in out
    # the base cell reads the same trace by the base vocabulary
    line, _ = traced_line(capsys, scopes_catalog, "bert_toy.mlm_toy",
                          device_planes="/device:TPU:")
    assert "fused_adam_ms" not in line["metrics"]
    assert line["metrics"]["optimizer_step_ms"]["value"] == pytest.approx(
        metrics["optimizer_step_ms"] + metrics["fused_adam_ms"])
