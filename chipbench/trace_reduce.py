"""From a profiler trace (``.xplane.pb``) to the few facts the per-layer
metrics read.

    python -m chipbench.trace_reduce <file.xplane.pb>      # the reduction
    python -m chipbench.trace_reduce --inventory <file>    # what is in it

What a TPU trace holds (looked at by hand, PR 22; PERF.md section 3 has the
account): one plane ``/device:TPU:<n>`` per chip, whose line ``XLA Modules``
has one event per executed program and whose line ``XLA Ops`` has one event
per HLO operation, nested where an operation contains others; one plane
``/host:CPU`` whose lines are host threads, the spans that
``jax.profiler.TraceAnnotation`` writes among them. All on one clock, in
nanoseconds.

The reduction, per device plane, over the window from the start of the first
to the end of the last execution of the step program (the module with most
time in the trace):

- busy: the union of the intervals in which an operation runs;
- idle gaps: the complement, each labelled by the ``chipbench`` host span
  open when it began;
- Mosaic: the self time of custom calls into Mosaic (Pallas kernels);
- collectives: the time in all-reduce, all-gather, reduce-scatter,
  collective-permute and all-to-all operations, and the part of it during
  which no other operation runs on the device (exposed).
"""

import argparse
import bisect
import collections
import functools
import gzip
import json
import re
import sys

DEVICE_PLANES = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
#: the host spans chipbench/run.py writes around its calls into the program
HOST_SPANS = ("next_batch", "step_call", "fetch_loss")

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)(-start|-done)?$")
_OPCODE = re.compile(r"(?:^|[ )])([a-z][a-z0-9\-]*)\(")
_NUMBER = re.compile(r"\.\d+$")


@functools.lru_cache(maxsize=None)     # a step's instructions repeat
def parse(text):
    """(stem, opcode) of an ``XLA Ops`` event name. On this runtime the name
    is the whole HLO instruction, ``%fusion.12 = f32[8]{0} fusion(...), ...``:
    the stem is the instruction's name without its number, the opcode the
    first lower-case word before a parenthesis after the shape. A plain name
    (``fusion.12``) has no opcode."""
    head, eq, rest = text.partition(" = ")
    stem = _NUMBER.sub("", head.lstrip("%"))
    found = _OPCODE.search(rest) if eq else None
    return stem, found.group(1) if found else ""


def is_collective(text):
    stem, opcode = parse(text)
    return bool(_COLLECTIVE.match(opcode) or _COLLECTIVE.match(stem))


def is_mosaic(text):
    """A custom call into Mosaic: how a ``pallas_call`` reaches the chip."""
    return "tpu_custom_call" in text


def label(text):
    """The name ``breakdown`` prints: stem and what kind of operation."""
    stem, opcode = parse(text)
    kind = "mosaic" if is_mosaic(text) else opcode
    return f"{stem} ({kind})" if kind and kind != stem else stem


Event = collections.namedtuple("Event", "name start end stats")


def load(path, stats=False):
    """{plane name: {line name: [Event]}} of an ``.xplane.pb[.gz]`` file;
    the events' stats only where asked for (the inventory reads them)."""
    import jax
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(str(path))
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                Event(e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns),
                      dict(e.stats) if stats else {})
                for e in line.events)
    return planes


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, holes):
    """The parts of merged ``intervals`` that no merged ``holes`` cover."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(holes) and holes[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(holes) and holes[k][0] < e:
            if holes[k][0] > at:
                out.append([at, holes[k][0]])
            at = max(at, holes[k][1])
            k += 1
        if at < e:
            out.append([at, e])
    return out


def clip(events, lo, hi):
    return [Event(e.name, max(e.start, lo), min(e.end, hi), e.stats)
            for e in events if e.end > lo and e.start < hi]


def self_times(events):
    """{event index: nanoseconds not covered by an event nested in it}."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    own = {i: events[i].end - events[i].start for i in order}
    stack = []
    for i in order:
        while stack and events[stack[-1]].end <= events[i].start:
            stack.pop()
        if stack and events[i].end <= events[stack[-1]].end:
            own[stack[-1]] -= events[i].end - events[i].start
        stack.append(i)
    return own


def step_module(modules):
    """The name of the program with most device time: the train step."""
    by_name = collections.Counter()
    for e in modules:
        by_name[e.name] += e.end - e.start
    return by_name.most_common(1)[0][0] if by_name else None


def host_spans(planes):
    """The chipbench host spans of the trace, [(name, start, end)] sorted."""
    spans = [(e.name, e.start, e.end)
             for events in planes.get(HOST_PLANE, {}).values()
             for e in events if e.name in HOST_SPANS]
    return sorted(spans, key=lambda s: s[1])


def label_gap(start, spans):
    """The innermost chipbench span open at ``start``, or 'between_spans'."""
    open_ = [s for s in spans if s[1] <= start < s[2]]
    return max(open_, key=lambda s: s[1])[0] if open_ else "between_spans"


def reduce_device(lines, spans):
    """The facts of one device plane; None where no step ran on it."""
    modules = lines.get(MODULES_LINE, [])
    name = step_module(modules)
    steps = [e for e in modules if e.name == name]
    if not steps or not lines.get(OPS_LINE):
        return None
    lo, hi = min(e.start for e in steps), max(e.end for e in steps)
    ops = clip(lines[OPS_LINE], lo, hi)
    own = self_times(ops)
    busy = union((e.start, e.end) for e in ops)
    # a collective may show as one operation, or as a -start and a -done on
    # the operations' line with the transfer between them on the async line
    collective = union(
        (e.start, e.end)
        for e in ops + clip(lines.get(ASYNC_LINE, []), lo, hi)
        if is_collective(e.name))
    # what can hide a collective: any other operation, unless it merely
    # wraps one (a call or a fusion around an async pair)
    starts = [s for s, _ in collective]

    def wraps_collective(e):
        i = bisect.bisect_left(starts, e.start)
        return i < len(collective) and collective[i][1] <= e.end

    other = union((e.start, e.end) for e in ops
                  if not is_collective(e.name) and not wraps_collective(e))
    by_label = collections.Counter()
    mosaic = 0
    for i, e in enumerate(ops):
        by_label[label(e.name)] += own[i]
        if is_mosaic(e.name):
            mosaic += own[i]
    gaps = collections.Counter()
    for s, e in subtract([[lo, hi]], busy):
        gaps[label_gap(s, spans)] += e - s
    return {
        "step_module": name, "steps": len(steps), "ops": len(ops),
        "window_ns": hi - lo, "busy_ns": total(busy),
        "mosaic_ns": mosaic, "collective_ns": total(collective),
        "collective_exposed_ns": total(subtract(collective, other)),
        "top_ops": by_label.most_common(10),
        "idle_gaps": gaps.most_common(10),
    }


def reduce_planes(planes, device_planes=DEVICE_PLANES):
    """The reduction of a loaded trace; ``devices`` is empty where the trace
    has no device plane (a CPU trace)."""
    spans = host_spans(planes)
    devices = {}
    for name in sorted(planes):
        if name.startswith(device_planes):
            facts = reduce_device(planes[name], spans)
            if facts is not None:
                devices[name] = facts
    out = {"devices": devices, "host_spans": len(spans)}
    if devices:
        n = len(devices)
        for key in ("window_ns", "busy_ns", "mosaic_ns", "collective_ns",
                    "collective_exposed_ns"):
            out[key] = sum(d[key] for d in devices.values()) / n
        out["steps"] = min(d["steps"] for d in devices.values())
        first = devices[min(devices)]
        out["top_ops"] = first["top_ops"]
        out["idle_gaps"] = first["idle_gaps"]
    return out


def reduce_file(path, device_planes=DEVICE_PLANES):
    return reduce_planes(load(path), device_planes)


def inventory(planes, top=25):
    """What a trace holds, for reading by hand: per plane and line the
    number of events, the names with most time, and one event's stats."""
    out = {}
    for pname, lines in planes.items():
        for lname, events in lines.items():
            by_name = collections.Counter()
            for e in events:
                by_name[e.name] += e.end - e.start
            sample = {}
            for e in events:
                sample.setdefault(e.name, {k: str(v)[:200]
                                           for k, v in e.stats.items()})
            out[f"{pname} | {lname}"] = {
                "events": len(events),
                "top": [[n, ns, sample[n]]
                        for n, ns in by_name.most_common(top)],
            }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("file")
    ap.add_argument("--inventory", action="store_true")
    args = ap.parse_args(argv)
    planes = load(args.file, stats=args.inventory)
    json.dump(inventory(planes) if args.inventory else reduce_planes(planes),
              sys.stdout, indent=1)
    print()


if __name__ == "__main__":
    main()
