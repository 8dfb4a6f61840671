"""``chipbench/moe_stages.py`` and its nine readers: the expert layer's device
time by the stage scopes ``paddle_tpu/parallel/moe.py`` nests inside
``moe_router`` and ``moe_dispatch``, and the passes a step ran over held
rows. On a plane built by hand, where every number can be worked out on
paper, in ``test_scope_profile.py``'s manner; no trace of the real cells is
recorded here (``PERF.md`` has their tables, from the chip)."""

import re
import types

import numpy as np
import pytest

from chipbench import moe_stages, scope_profile as sp, trace_reduce as tr
from chipbench import xplane
from chipbench.catalog import ROOT, Catalog

SEVEN_CELLS = [
    "olmoe_1b_7b.lm_s4096", "kimi_linear_48b_a3b.lm_s8192",
    "laguna_xs2.lm_s16384", "qwen3_next_80b_a3b.lm_s16384",
    "lfm2_24b_a2b.lm_b4_s8192", "kanana_2_30b_a3b.lm_s16384",
    "nemotron_3_super_120b_a12b.lm_s8192"]
STAGE_METRICS = {
    "moe_router_logits_ms": "router_logits",
    "moe_router_scores_ms": "router_scores",
    "moe_router_select_ms": "router_select",
    "moe_router_stats_ms": "router_stats",
    "moe_dispatch_order_ms": "dispatch_order",
    "moe_dispatch_gather_ms": "dispatch_gather",
    "moe_dispatch_combine_ms": "dispatch_combine"}
EIGHT = [*STAGE_METRICS, "moe_stage_unnamed_ms"]
CELL_SCOPES = ["moe_router", "moe_dispatch", "moe_experts"]
MOSAIC = ('%moe_combine.3 = (f32[8,128]{1,0:T(8,128)}) custom-call('
          'f32[8,128]{1,0} %p), custom_call_target="tpu_custom_call"')
_STAGE = re.compile("/(?:" + "|".join(moe_stages.STAGES) + ")(?=/)")


def hand_made(stages=True):
    """Two steps of 200 ns. In each, forward: the router's product (0-20),
    its scores (20-25), a sort under its selection (25-45), its counts
    (45-50), a cast under ``moe_router`` and no stage (50-53); a while of
    ``ffn`` (60-120) around a pass's order (62-70), its gather (70-90), its
    experts (90-100) and the Mosaic call that sums the rows back (100-115).
    Backward: the product's gradient (120-140), the rows' gather (140-150),
    the scores' gradient under the combine (150-160). Attention from 160 to
    180. With ``stages`` false the same operations as a program without the
    stage scopes names them."""
    fwd, bwd = "jit(step)/jvp(ffn)", "jit(step)/transpose(jvp(ffn))"
    body = f"{fwd}/while/body/moe_dispatch"

    def ev(name, start, end, tf_op):
        if not stages:
            tf_op = _STAGE.sub("", tf_op)
        return tr.Event(name, start, end, {"tf_op": tf_op})

    ops, modules = [], []
    for lo in (1000, 1300):
        modules.append(tr.Event("jit_step(7)", lo, lo + 200, {}))
        ops += [
            ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", lo, lo + 20,
               f"{fwd}/moe_router/router_logits/dot_general:"),
            ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", lo + 20, lo + 25,
               f"{fwd}/moe_router/router_scores/logistic:"),
            ev("%sort.3 = (f32[8]{0}) sort(f32[8]{0} %p)", lo + 25, lo + 45,
               f"{fwd}/moe_router/router_select/top_k:"),
            ev("%fusion.4 = s32[8]{0} fusion(s32[8]{0} %p)", lo + 45, lo + 50,
               f"{fwd}/moe_router/router_stats/scatter-add:"),
            ev("%convert.5 = f32[8]{0} convert(bf16[8]{0} %p)", lo + 50,
               lo + 53, f"{fwd}/moe_router/convert_element_type:"),
            ev("%while.6 = (f32[8]{0}) while((f32[8]{0}) %t)", lo + 60,
               lo + 120, f"{fwd}/while:"),
            ev("%fusion.7 = s32[8]{0} fusion(s32[8]{0} %p)", lo + 62, lo + 70,
               f"{body}/dispatch_order/dynamic_slice:"),
            ev("%gather.8 = f32[8]{0} gather(f32[8]{0} %p)", lo + 70, lo + 90,
               f"{body}/dispatch_gather/jit(_take)/gather:"),
            ev("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p)", lo + 90,
               lo + 100, f"{fwd}/while/body/moe_experts/dot_general:"),
            ev(MOSAIC, lo + 100, lo + 115,
               f"{body}/dispatch_combine/moe_combine/pallas_call:"),
            ev("%fusion.10 = f32[8]{0} fusion(f32[8]{0} %p)", lo + 120,
               lo + 140, f"{bwd}/moe_router/router_logits/dot_general:"),
            ev("%gather.11 = f32[8]{0} gather(f32[8]{0} %p)", lo + 140,
               lo + 150, f"{bwd}/while/body/moe_dispatch/dispatch_gather/"
               f"jit(_take)/gather:"),
            ev("%scatter.12 = f32[8]{0} scatter(f32[8]{0} %p)", lo + 150,
               lo + 160, f"{bwd}/while/body/moe_dispatch/dispatch_combine/"
               f"scatter-add:"),
            ev("%fusion.13 = f32[8]{0} fusion(f32[8]{0} %p)", lo + 160,
               lo + 180, "jit(step)/jvp(attention)/dot_general:"),
        ]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": []}}


def facts_of(planes, **more):
    return dict({"planes": planes, "config": {"scopes": CELL_SCOPES},
                 "peak": {"device_planes": "/device:TPU:"},
                 "cell": {"name": "hand_made"}, "catalog": Catalog()}, **more)


def test_the_stages_and_the_rest_sum_to_the_two_scopes():
    got = moe_stages.reduce_planes(hand_made(), scopes=CELL_SCOPES)
    assert got["devices"] == 1 and got["stage_events"] == 2 * 10
    assert {s: d["total"] for s, d in got["stage_ns"].items()} == {
        "router_logits": 40, "router_scores": 5, "router_select": 20,
        "router_stats": 5, "dispatch_order": 8, "dispatch_gather": 30,
        "dispatch_combine": 25}
    assert got["stage_ns"]["router_logits"] == {
        "forward": 20, "backward": 20, "total": 40}
    assert got["stage_ns"]["dispatch_combine"] == {
        "forward": 15, "backward": 10, "total": 25}
    assert got["unnamed_ns"] == 3
    assert got["ops"]["router_select"] == [["sort", 20]]
    assert got["ops"]["dispatch_combine"] == [["moe_combine (mosaic)", 15],
                                              ["scatter", 10]]
    assert got["ops"]["moe_router"] == [["convert", 3]]
    whole = sp.reduce_planes(hand_made(), scopes=CELL_SCOPES)["scope_ns"]
    assert whole["moe_router"]["total"] == 73
    assert whole["moe_dispatch"]["total"] == 63
    assert sum(d["total"] for d in got["stage_ns"].values()) \
        + got["unnamed_ns"] == 73 + 63
    # the cell need not list the two scopes for the stages to be read
    assert moe_stages.reduce_planes(hand_made())["stage_ns"] == \
        got["stage_ns"]
    lines = moe_stages.table(got, 136)
    assert any(line.startswith("router_select") and "sort 0.000" in line
               for line in lines)
    assert "sum to 0.000 ms; moe_router + moe_dispatch" in lines[-1]


def test_the_cell_s_own_reduction_does_not_see_the_stage_names():
    """No configuration lists a stage, so the reduction every accepted
    metric reads is the same with the stage names on the stacks and off."""
    named = sp.reduce_planes(hand_made(), scopes=CELL_SCOPES)
    plain = sp.reduce_planes(hand_made(stages=False), scopes=CELL_SCOPES)
    assert named == plain
    assert named["scope_ns"]["ffn"]["total"] == 7      # the while's own time
    assert named["scope_ns"]["moe_experts"]["total"] == 10


def test_a_trace_without_stage_names_reads_as_none_in_every_reader(capsys):
    facts = facts_of(hand_made(stages=False))
    catalog = facts["catalog"]
    for name in EIGHT:
        assert catalog.module("layer_metrics", name).metric(facts) is None
    assert facts["moe_stages"] is None
    assert capsys.readouterr().out.count(moe_stages.NO_STAGE) == 1
    assert moe_stages.reduce_planes({"/host:CPU": {"python3": []}}) is None
    # the traces recorded on a v5e, one chip and four, from before the stages
    for recorded, devices in (("bert_toy.mlm_toy.scopes", 1),
                              ("bert_toy.mlm_toy_dp4", 4)):
        got = moe_stages.reduce_planes(xplane.load(
            ROOT / "chipbench" / "tests" / "fixtures" / "traces"
            / f"{recorded}.xplane.pb.gz"))
        assert got["devices"] == devices and got["stage_events"] == 0
        assert got["unnamed_ns"] == 0


def test_the_readers_read_one_reduction(capsys, tmp_path, monkeypatch):
    from chipbench import run
    monkeypatch.setattr(run, "OUT_DIR", tmp_path)
    facts = facts_of(hand_made())
    catalog = facts["catalog"]
    got = {name: catalog.module("layer_metrics", name).metric(facts)
           for name in EIGHT}
    assert got == {
        "moe_router_logits_ms": 40e-6, "moe_router_scores_ms": 5e-6,
        "moe_router_select_ms": 20e-6, "moe_router_stats_ms": 5e-6,
        "moe_dispatch_order_ms": 8e-6, "moe_dispatch_gather_ms": 30e-6,
        "moe_dispatch_combine_ms": 25e-6, "moe_stage_unnamed_ms": 3e-6}
    routing = catalog.module("layer_metrics", "moe_routing_ms").metric(facts)
    assert sum(got.values()) == pytest.approx(routing) == pytest.approx(
        136e-6)
    out = capsys.readouterr().out
    assert out.count("reduced by the expert layer's stages") == 1
    assert (tmp_path / "hand_made.moe_stages.json").is_file()


def job_with(aux):
    step_fn = types.SimpleNamespace()
    if aux is not None:
        step_fn.aux = aux
    return types.SimpleNamespace(step_fn=step_fn)


def test_held_passes_from_a_step_s_counts():
    """Three expert layers of a router 256 wide, 8 a token on 16 384 tokens,
    experts 0 to 31 held (a pass is 32 768 rows): a layer at par (16 384
    rows held: one pass), one with 26% of the assignments (34 080: two) and
    one that holds nothing this step (none)."""
    reader = Catalog().module("layer_metrics", "moe_held_passes")
    counts = np.zeros((3, 256), np.int64)
    counts[0, :32] = 512
    counts[0, 32:] = (131072 - 16384) // 224
    counts[1, :32] = 1065
    counts[1, 32] = 131072 - 32 * 1065
    counts[2, 100] = 131072
    assert counts.sum(axis=1).tolist() == [131072] * 3
    held = {"experts_held": [0, 32]}
    assert reader.metric({"job": job_with([counts]), "config": held}) == 3.0
    # a decoder with an MTP module hands out the two cross-entropies behind
    assert reader.metric({"job": job_with([counts[:1], np.zeros(2)]),
                          "config": held}) == 1.0
    assert reader.metric({"job": job_with([counts]), "config": {}}) is None
    assert reader.metric({"job": job_with(None), "config": held}) is None
    assert reader.metric({"job": job_with([]), "config": held}) is None


def test_the_nine_entries_list_the_expert_cells_and_nothing_else():
    spec = Catalog().spec
    entries = {m["name"]: m for m in spec["per_layer"]}
    assert list(entries)[-9:] == [*EIGHT, "moe_held_passes"]
    for name in EIGHT:
        assert entries[name]["workloads"] == SEVEN_CELLS
        assert entries[name]["source"] == "device_trace"
    assert entries["moe_held_passes"]["workloads"] == SEVEN_CELLS[1:]
    assert entries["moe_held_passes"]["source"] == "program_counter"
    for cell in spec["workloads"]:
        config = Catalog().cell(cell["name"])[1]
        assert ("experts_held" in config) == (cell["name"] in SEVEN_CELLS[1:])
        assert not set(moe_stages.STAGES) & set(config.get("scopes", ()))
    assert set(STAGE_METRICS.values()) == set(moe_stages.STAGES)
