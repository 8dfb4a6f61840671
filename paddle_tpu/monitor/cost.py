"""XLA cost analytics: per-compiled-segment FLOPs/bytes and MFU.

The reference's profiler answers "where did the time go"; this module
answers "how much of the hardware did we use". Sources:

- ``analyze_lowered(lowered)`` reads jax's
  ``lowered.cost_analysis()`` (XLA's HLO cost model — analytical
  FLOPs/bytes, not measured) for each device segment the executor
  compiles; the executor records them here (``record_segment``) both at
  AOT-compile time (``Executor.prepare``) and lazily on a compiled
  step's first real call.
- ``flops_per_step()`` sums the most recently recorded compiled step's
  segments (older compiled steps — other feed signatures, pre-retrace
  shapes — are superseded, not accumulated: summing two compiles of the
  same program would double-count).
- ``estimate_comm(compiled.as_text())`` estimates cross-device
  collective bytes from the post-SPMD optimized HLO (collectives are
  inserted at COMPILE time, so the pre-partition lowering can't see
  them); the executor records it at AOT-compile time
  (``record_segment_comm`` → ``segment_comm_bytes`` gauge,
  ``comm_bytes_per_step()``), and ``bench.py shard`` reports it per
  mesh topology.
- ``estimate_mfu()`` divides achieved FLOP/s (flops_per_step over the
  ``executor_step_ms`` histogram's mean) by ``peak_flops()``.

``peak_flops()`` looks the device up in ``PEAK_FLOPS``, one table keyed
by jax's ``device_kind``. A device that is not in the table (the CPU
included) has no peak: ``peak_flops()`` raises and ``estimate_mfu()``
is None. jax is only imported inside functions: this module loads under
the stdlib-only launcher.
"""

import os
import re
import threading

from paddle_tpu.monitor.registry import counter, gauge, histogram

__all__ = [
    "analyze_lowered", "estimate_comm", "record_segment",
    "record_segment_comm", "segments", "flops_per_step",
    "bytes_per_step", "comm_bytes_per_step", "estimate_mfu",
    "peak_flops", "record_pass", "pass_evidence", "reset",
    "PEAK_FLOPS", "UnknownDevicePeak",
]

#: peak bf16 matmul FLOP/s of ONE chip, keyed by jax's ``device_kind``.
#: The only peaks table in the repo (bench.py reads it too).
PEAK_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


class UnknownDevicePeak(LookupError):
    """The device has no entry in ``PEAK_FLOPS``, so no utilization can
    be stated for it."""

_lock = threading.Lock()
_segments = {}                  # group -> {index: {"flops","bytes"}}
_latest_group = None

_g_flops = gauge(
    "segment_flops",
    "Analytical FLOPs per execution of each compiled device segment "
    "(XLA cost model via lowered.cost_analysis)", labels=("segment",))
_g_bytes = gauge(
    "segment_bytes",
    "Analytical bytes accessed per execution of each compiled device "
    "segment", labels=("segment",))
_g_peak = gauge(
    "device_peak_flops",
    "Peak bf16 FLOP/s of one device of this rank's device_kind "
    "(monitor/cost.PEAK_FLOPS); absent for a device with no entry")
_g_comm = gauge(
    "segment_comm_bytes",
    "Estimated cross-device collective bytes per execution of each "
    "compiled device segment (result-buffer bytes of the collective "
    "ops in the post-SPMD optimized HLO)", labels=("segment",))

# program-level pass pipeline evidence (static/opt_passes.py): one
# record_pass call per pass application at step-compile / export time
_c_pass_runs = counter(
    "program_pass_runs_total",
    "Applications of each program-level optimization pass "
    "(static/opt_passes.py; one per pass per step compile/export)",
    labels=("pass",))
_c_pass_removed = counter(
    "program_pass_ops_removed_total",
    "Program ops removed (folded, fused away, or dead-eliminated) by "
    "each optimization pass, summed over applications",
    labels=("pass",))
_h_pass_ms = histogram(
    "program_pass_ms",
    "Wall ms per optimization-pass application (program-level pass "
    "pipeline ahead of segment compilation)")
_g_pass_flops_delta = gauge(
    "program_pass_flops_delta",
    "Predicted analytical-FLOPs change of the last application of each "
    "optimization pass (post minus pre lowering cost_analysis, "
    "negative = cheaper; FLAGS_pass_cost_evidence probe)",
    labels=("pass",))
_g_pass_bytes_delta = gauge(
    "program_pass_bytes_delta",
    "Predicted bytes-accessed change of the last application of each "
    "optimization pass (post minus pre lowering cost_analysis, "
    "negative = cheaper; FLAGS_pass_cost_evidence probe)",
    labels=("pass",))

_pass_totals = {}               # pass name -> {"runs", "ops_removed"}

# collective instructions in XLA's post-SPMD optimized HLO text; the
# result type precedes the op name ("%x = f32[4,8]{1,0} all-reduce(…"
# or a tuple "(f32[128]{0}, f32[64]{0})" for fused buckets). Async
# split pairs count on -done ONLY: a -start op's result tuple bundles
# operands + results (+ scheduling context), so counting it would
# tally ~2x the result bytes on backends that lower collectives
# asynchronously (TPU) while synchronous lowerings (CPU) count 1x —
# the -done result is exactly the collective result on every backend.
_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}


def _type_bytes(type_str):
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        size = _DTYPE_BYTES.get(dt)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def estimate_comm(hlo_text):
    """{'comm_bytes': float, 'collectives': {op: count}} from a
    compiled executable's optimized HLO text (``compiled.as_text()``),
    or None when the text carries no parseable module. The estimate is
    the sum of collective RESULT-buffer bytes per execution — a
    lower-bound proxy for wire traffic (a ring all-reduce moves
    ~2(n-1)/n of it per hop), comparable across topologies AND
    backends because the convention is fixed: async-lowered pairs
    (TPU) count their -done result, never the -start tuple (operands +
    results + context, which would double-count). Collectives are
    inserted by SPMD partitioning at COMPILE time, so this must read
    the compiled text, not the pre-partition lowering."""
    if not hlo_text:
        return None
    comm = 0.0
    counts = {}
    for type_str, op, suffix in _COLL_RE.findall(hlo_text):
        if suffix == "-start":
            continue
        counts[op] = counts.get(op, 0) + 1
        comm += _type_bytes(type_str)
    return {"comm_bytes": comm, "collectives": counts}


def analyze_lowered(lowered):
    """{'flops': float, 'bytes': float} from a ``jax.stages.Lowered``
    (or compiled) object, or None when the backend offers no cost
    model. Handles both the dict and the [dict] return shapes jax has
    used across versions."""
    try:
        ca = lowered.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    return {"flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes": float(ca.get("bytes accessed", 0.0) or 0.0)}


def record_segment(group, index, analysis):
    """Record one device segment's cost under ``group`` (an identity
    for the compiled step, e.g. ``id(step)``); the latest group becomes
    the per-step total ``flops_per_step`` reports. The gauges mirror
    ONLY the latest group: when a new compiled step starts recording,
    the superseded step's series are dropped — otherwise a retrace from
    2 segments down to 1 would leave a stale ``segment="1"`` series
    inflating every consumer that sums the gauge (the launcher's MFU
    status line does)."""
    global _latest_group
    if not analysis:
        return
    with _lock:
        if group != _latest_group:
            _g_flops.clear()
            _g_bytes.clear()
            _g_comm.clear()
        # merge, don't replace: comm bytes for the same segment may
        # already have been recorded (record_segment_comm)
        _segments.setdefault(group, {}).setdefault(
            int(index), {}).update(analysis)
        _latest_group = group
    _g_flops.set(analysis["flops"], segment=str(index))
    _g_bytes.set(analysis["bytes"], segment=str(index))
    # the launcher's MFU line divides by this rank-published peak: the
    # launcher itself never initialises a backend to ask for the device
    peak = PEAK_FLOPS.get(_device_kind())
    if peak is not None:
        _g_peak.set(peak)


def record_segment_comm(group, index, comm):
    """Record one device segment's estimated collective bytes (the
    ``estimate_comm`` result) under ``group`` — the executor calls this
    at AOT-compile time (``Executor.prepare``), when the compiled
    executable's HLO text is in hand; bench modes call it for their own
    jitted steps. Same latest-group gauge semantics as
    ``record_segment``."""
    global _latest_group
    if not comm:
        return
    with _lock:
        if group != _latest_group:
            _g_flops.clear()
            _g_bytes.clear()
            _g_comm.clear()
        entry = _segments.setdefault(group, {}).setdefault(int(index), {})
        entry["comm_bytes"] = float(comm.get("comm_bytes", 0.0))
        entry["collectives"] = dict(comm.get("collectives", {}))
        _latest_group = group
    _g_comm.set(float(comm.get("comm_bytes", 0.0)), segment=str(index))


def segments(group=None):
    """{segment index: {"flops","bytes"}} for ``group`` (default: the
    most recently recorded compiled step)."""
    with _lock:
        g = _latest_group if group is None else group
        return {i: dict(a) for i, a in _segments.get(g, {}).items()}


def _total(key):
    with _lock:
        segs = _segments.get(_latest_group, {})
        return sum(a.get(key, 0.0) for a in segs.values())


def flops_per_step():
    return _total("flops")


def bytes_per_step():
    return _total("bytes")


def comm_bytes_per_step():
    return _total("comm_bytes")


def record_pass(name, ops_removed=0, ms=0.0, flops_delta=None,
                bytes_delta=None):
    """Publish one optimization-pass application (opt_passes drivers
    call this): bumps the program_pass_* metrics and folds into the
    in-process evidence table ``pass_evidence`` reports (the
    ``bench.py passes`` per-pass JSON). ``flops_delta``/``bytes_delta``
    (FLAGS_pass_cost_evidence) are the pass's predicted analytical cost
    change — signed, so they publish as gauges and accumulate in the
    evidence table."""
    name = str(name)
    _c_pass_runs.inc(**{"pass": name})
    if ops_removed:
        _c_pass_removed.inc(float(ops_removed), **{"pass": name})
    _h_pass_ms.observe(float(ms))
    if flops_delta is not None:
        _g_pass_flops_delta.set(float(flops_delta), **{"pass": name})
    if bytes_delta is not None:
        _g_pass_bytes_delta.set(float(bytes_delta), **{"pass": name})
    with _lock:
        t = _pass_totals.setdefault(name,
                                    {"runs": 0, "ops_removed": 0})
        t["runs"] += 1
        t["ops_removed"] += int(ops_removed)
        if flops_delta is not None:
            t["flops_delta"] = t.get("flops_delta", 0.0) \
                + float(flops_delta)
        if bytes_delta is not None:
            t["bytes_delta"] = t.get("bytes_delta", 0.0) \
                + float(bytes_delta)


def pass_evidence():
    """{pass name: {"runs", "ops_removed"[, "flops_delta",
    "bytes_delta"]}} accumulated since process start (or the last
    ``reset``)."""
    with _lock:
        return {k: dict(v) for k, v in _pass_totals.items()}


def _device_kind():
    import jax
    return jax.devices()[0].device_kind


def peak_flops(device_kind=None):
    """Peak bf16 FLOP/s of one device of ``device_kind`` (default: this
    process's first device). Raises ``UnknownDevicePeak`` for a device
    that is not in ``PEAK_FLOPS`` — there is no default peak."""
    kind = _device_kind() if device_kind is None else device_kind
    if kind not in PEAK_FLOPS:
        raise UnknownDevicePeak(
            f"no peak FLOP/s on record for device_kind {kind!r} "
            f"(known: {sorted(PEAK_FLOPS)}); add it to "
            f"monitor/cost.PEAK_FLOPS with its source")
    return PEAK_FLOPS[kind]


def estimate_mfu(ms_per_step=None):
    """Model FLOPs utilization in [0, 1], or None when either side of
    the ratio is missing — including the peak, on a device that is not
    in ``PEAK_FLOPS``. ``ms_per_step`` defaults to the mean of the
    ``executor_step_ms`` histogram (wall time around dispatch — on a
    host-overhead-bound model this UNDERSTATES device utilization;
    see docs/OBSERVABILITY.md)."""
    flops = flops_per_step()
    peak = PEAK_FLOPS.get(_device_kind())
    if not flops or peak is None:
        return None
    if ms_per_step is None:
        from paddle_tpu.monitor.registry import REGISTRY
        h = REGISTRY.get("executor_step_ms")
        if h is None or h.count() == 0:
            return None
        ms_per_step = h.sum() / h.count()
    if ms_per_step <= 0:
        return None
    return flops / (ms_per_step / 1e3) / peak


def reset():
    """Forget recorded segments and their gauge series (tests)."""
    global _latest_group
    with _lock:
        _segments.clear()
        _latest_group = None
        _pass_totals.clear()
    _g_flops.clear()
    _g_bytes.clear()
    _g_comm.clear()
