"""The reduction from a profiler trace to facts: on a trace built by hand,
where every number can be worked out on paper, and on two small traces
recorded on a v5e (``fixtures/traces``, see ``record_fixture.py``)."""

import pytest

from chipbench import trace_reduce as tr
from chipbench.catalog import ROOT

TRACES = ROOT / "chipbench" / "tests" / "fixtures" / "traces"


def ev(name, start, end, **stats):
    return tr.Event(name, start, end, stats)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr.total([[0, 3], [5, 8]]) == 6
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tr.subtract([[0, 4]], []) == [[0, 4]]


def test_self_time_takes_nested_events_out_of_their_parent():
    events = [ev("while", 0, 100), ev("fusion.1", 10, 40),
              ev("fusion.2", 50, 70), ev("after", 100, 120)]
    own = tr.self_times(events)
    assert [own[i] for i in range(4)] == [50, 30, 20, 20]


def hand_made():
    """Two steps of 100 ns with a gap of 20 between them. In each step: a
    fusion (0-40), a Mosaic call (40-50), an all-reduce-start (50-52) and
    -done (78-80) with the transfer (50-80) on the async line, of which a
    second fusion hides 60-70; nothing runs from 80 on. Names are whole HLO
    instructions, as the v5e's runtime writes them."""
    ops, modules, transfers = [], [], []
    for lo in (1000, 1120):
        modules.append(ev("jit_step(7)", lo, lo + 100))
        ops += [
            ev("%fusion.1 = f32[8]{0:T(8)} fusion(f32[8]{0} %p), kind=kLoop",
               lo, lo + 40),
            ev('%step.3 = (f32[8,128]{1,0:T(8,128)}) custom-call(f32[8,128]'
               '{1,0} %p), custom_call_target="tpu_custom_call"',
               lo + 40, lo + 50),
            ev("%all-reduce-start.9 = f32[8]{0} all-reduce-start(f32[8]{0} "
               "%g), replica_groups={{0,1,2,3}}", lo + 50, lo + 52),
            ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q)", lo + 60,
               lo + 70),
            ev("%all-reduce-done.9 = f32[8]{0} all-reduce-done(f32[8]{0} "
               "%all-reduce-start.9)", lo + 78, lo + 80),
        ]
        transfers.append(ev("%all-reduce-start.9 = f32[8]{0} "
                            "all-reduce-start(f32[8]{0} %g)", lo + 50,
                            lo + 80))
    modules.append(ev("jit_convert(3)", 900, 905))      # not the step
    host = [ev("step_call", 1075, 1085), ev("fetch_loss", 1085, 1180),
            ev("next_batch", 1070, 1074), ev("other", 0, 5000)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules,
                              "Async XLA Ops": transfers},
            "/host:CPU": {"python3": host}}


def test_reduction_of_a_hand_made_trace():
    out = tr.reduce_planes(hand_made())
    dev = out["devices"]["/device:TPU:0"]
    assert dev["step_module"] == "jit_step(7)" and dev["steps"] == 2
    assert out["window_ns"] == 220                  # 1000 .. 1220
    assert out["busy_ns"] == 2 * (40 + 10 + 2 + 10 + 2)
    assert out["mosaic_ns"] == 20
    assert out["collective_ns"] == 60
    assert out["collective_exposed_ns"] == 40       # 2 x (30 - 10 hidden)
    assert dict(out["top_ops"])["fusion"] == 100
    assert dict(out["top_ops"])["step (mosaic)"] == 20
    # idle inside the window that the operations' line leaves: 52-60 and
    # 70-78 fall into no span or fetch_loss; 1080-1120 began in step_call
    gaps = dict(out["idle_gaps"])
    assert gaps["step_call"] == 40
    assert sum(gaps.values()) == 220 - out["busy_ns"]
    assert out["host_spans"] == 3


def test_a_trace_without_a_device_plane_reduces_to_no_devices():
    planes = hand_made()
    del planes["/device:TPU:0"]
    assert tr.reduce_planes(planes) == {"devices": {}, "host_spans": 3}


def test_names_as_the_runtime_writes_them():
    text = ("%transpose_jvp___.26 = (bf16[8,12,4096,64]{3,2,1,0:T(8,128)"
            "(2,1)}, f32[8,12,4096,1]{3,2,1,0:T(8,128)}) custom-call(bf16[8,"
            "12,4096,64]{3,2,1,0:T(8,128)(2,1)} %bitcast.2609), "
            'custom_call_target="tpu_custom_call"')
    assert tr.parse(text) == ("transpose_jvp___", "custom-call")
    assert tr.is_mosaic(text) and not tr.is_collective(text)
    assert tr.label(text) == "transpose_jvp___ (mosaic)"
    assert tr.parse("%copy.7 = f32[8]{0:T(8)S(1)} copy(f32[8]{0} %x)") == (
        "copy", "copy")
    assert tr.label("%copy.7 = f32[8]{0:T(8)S(1)} copy(f32[8]{0} %x)") \
        == "copy"
    assert tr.is_collective("%all-reduce.4 = f32[8]{0} all-reduce(f32[8]{0} "
                            "%x), to_apply=%add")
    assert tr.is_collective("all-gather-start.1")         # a plain name
    assert tr.is_collective("%ar.1 = f32[8]{0} collective-permute-done(%y)")
    assert not tr.is_collective("%fusion.12 = f32[8]{0} fusion(%all-reduce.4)")
    assert not tr.is_mosaic("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %x)")


def test_one_chip_trace_recorded_on_a_v5e():
    """Three steps of the toy BERT on one chip (record_fixture.py): Pallas
    layer norm and Adam are in it, no collective, and the toy leaves the chip
    idle most of the time, waiting for the host."""
    out = tr.reduce_file(TRACES / "bert_toy.mlm_toy.xplane.pb.gz")
    assert list(out["devices"]) == ["/device:TPU:0"]
    assert out["steps"] == 3 and out["host_spans"] == 8
    assert 0 < out["busy_ns"] < out["window_ns"]
    assert out["busy_ns"] == 271702 and out["window_ns"] == 5870201
    assert out["mosaic_ns"] == 28948
    assert out["collective_ns"] == 0 and out["collective_exposed_ns"] == 0
    labels = [name for name, _ in out["top_ops"]]
    assert "step (mosaic)" in labels                    # the per-leaf Adam
    gaps = dict(out["idle_gaps"])
    assert sum(gaps.values()) == out["window_ns"] - out["busy_ns"]
    assert set(gaps) <= set(tr.HOST_SPANS) | {"between_spans"}


def test_four_chip_trace_recorded_on_a_v5e():
    """Two steps of the toy BERT on the data=4 mesh (record_fixture.py):
    four device planes, reference bodies only, and gradient all-reduces that
    this runtime writes as synchronous ``all-reduce`` operations on the
    operations' line, so nothing overlaps them and all their time is
    exposed."""
    out = tr.reduce_file(TRACES / "bert_toy.mlm_toy_dp4.xplane.pb.gz")
    assert sorted(out["devices"]) == [f"/device:TPU:{i}" for i in range(4)]
    assert out["steps"] == 2
    first = out["devices"]["/device:TPU:0"]
    assert first["ops"] == 1100
    assert (first["window_ns"], first["busy_ns"]) == (5609878, 184991)
    assert (first["collective_ns"], first["collective_exposed_ns"]) == (
        83098, 83098)
    assert dict(first["top_ops"])["all-reduce"] == 83098
    # the cell's numbers are the mean over its devices
    assert out["busy_ns"] == pytest.approx(182466.75)
    assert out["collective_exposed_ns"] == pytest.approx(81195.25)
    assert out["mosaic_ns"] == 0
    for dev in out["devices"].values():
        assert 0 < dev["collective_exposed_ns"] <= dev["collective_ns"] \
            < dev["busy_ns"] < dev["window_ns"]
