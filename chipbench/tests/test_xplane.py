"""``chipbench/xplane.py``, the plain reader of ``.xplane.pb`` files, on the
traces recorded on a v5e: the same events as ``trace_reduce.load`` reads
through ``jax.profiler.ProfileData``, plus the stats of the events'
metadata, which is where the compiler's account of an operation lives."""

import gzip

import pytest

from chipbench import trace_reduce as tr
from chipbench import xplane
from chipbench.catalog import ROOT

TRACES = ROOT / "chipbench" / "tests" / "fixtures" / "traces"
RECORDED = ["bert_toy.mlm_toy.xplane.pb.gz", "bert_toy.mlm_toy_dp4.xplane.pb.gz",
            "bert_toy.mlm_toy.scopes.xplane.pb.gz"]


def test_varints_fixed_fields_and_slices():
    # field 1 varint 300; field 2 bytes "ab"; field 3 fixed64; field 4 int64 -1
    data = (b"\x08\xac\x02" + b"\x12\x02ab" + b"\x19" + bytes(range(8))
            + b"\x20" + b"\xff" * 9 + b"\x01")
    got = list(xplane.fields(memoryview(data), 0, len(data)))
    assert got[0] == (1, xplane.VARINT, 300)
    assert got[1] == (2, xplane.BYTES, (5, 7))
    assert xplane.text(memoryview(data), got[1][2]) == "ab"
    assert got[2] == (3, xplane.FIXED64, 8)
    assert xplane.signed(got[3][2]) == -1
    with pytest.raises(ValueError):
        list(xplane.fields(memoryview(b"\x0b"), 0, 1))     # a group


@pytest.mark.parametrize("name", RECORDED)
def test_same_events_as_profile_data(name):
    """Every plane, line and event, with the same name and times."""
    mine, theirs = xplane.load(TRACES / name), tr.load(TRACES / name)
    assert set(mine) == set(theirs)
    events = duration = 0
    for plane in theirs:
        assert set(mine[plane]) == set(theirs[plane])
        for line, want in theirs[plane].items():
            got = mine[plane][line]
            assert [(e.name, e.start, e.end) for e in got] == \
                [(e.name, e.start, e.end) for e in want], (plane, line)
            events += len(got)
            duration += sum(e.end - e.start for e in got)
    assert events > 1000 and duration > 0


@pytest.mark.parametrize("name", RECORDED)
def test_device_operations_carry_the_metadata_stats(name):
    """``tf_op`` on four fifths of device time (the rest of these toy steps
    is ``copy-done``, waiting for copies the compiler made);
    ``hlo_category``, ``flops`` and ``bytes_accessed`` on every operation."""
    planes = xplane.load(TRACES / name)
    devices = [p for p in planes if p.startswith(tr.DEVICE_PLANES)]
    assert devices
    for plane in devices:
        ops = planes[plane][tr.OPS_LINE]
        total = sum(e.end - e.start for e in ops)
        named = sum(e.end - e.start for e in ops if e.stats.get("tf_op"))
        assert named > 0.79 * total
        for e in ops:
            assert {"hlo_category", "flops", "bytes_accessed"} <= set(e.stats)
        assert any(e.stats["tf_op"].startswith("jit(step)/transpose(jvp(")
                   for e in ops if "tf_op" in e.stats)
    spans = [e.name for e in planes[tr.HOST_PLANE]["python3"]]
    assert "step_call" in spans and "fetch_loss" in spans


@pytest.mark.parametrize("name", RECORDED[:2])
def test_agrees_with_the_generated_proto_module(name):
    """Against tensorflow's ``xplane_pb2``, where it can be imported: every
    event's metadata id resolves to the same name and the same stats."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    with gzip.open(TRACES / name, "rb") as f:
        space = xplane_pb2.XSpace.FromString(f.read())
    mine = xplane.load(TRACES / name)

    def value(stat, names):
        which = stat.WhichOneof("value")
        if which == "ref_value":
            return names[stat.ref_value]
        return getattr(stat, which)

    checked = 0
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            got = mine[plane.name][line.name or line.display_name]
            if len(got) != len(line.events):
                continue          # two lines of one name were merged
            for event, have in zip(line.events, got):
                meta = plane.event_metadata[event.metadata_id]
                want = {names[s.metadata_id]: value(s, names)
                        for s in list(meta.stats) + list(event.stats)}
                assert have.name == meta.name
                assert have.stats == want
                checked += 1
    assert checked > 1000
