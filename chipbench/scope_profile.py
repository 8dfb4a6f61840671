"""Device time of a train step by the names the program gives its own parts.

    python tools/trace_summary.py <trace dir or .xplane.pb[.gz]>   # prints it

The program (``paddle_tpu``) wraps its parts in ``jax.named_scope``: ``embed``,
``attention``, ``attention_core``, ``ffn``, ``layer_norm``, ``loss`` in the
models, ``optimizer`` in ``Optimizer.apply_gradients``. It names its Pallas
kernels (``flash_fwd``, ``flash_bwd_dkv``, ``layer_norm_fwd``,
``fused_adam``, ...) and writes two host spans a step,
``trainer/place`` and ``trainer/enqueue``. In a profile every device operation
then carries jax's name stack as the stat ``tf_op`` of its event's metadata,

    jit(step)/jvp(attention)/attention_core/flash_fwd/pallas_call
    jit(step)/transpose(jvp(attention))/attention_core/mul
    jit(step)/optimizer/fused_adam/pallas_call

where jax wraps only the outermost scope in ``jvp( )`` (forward pass) and
``transpose(jvp( ))`` (backward pass). ``reduce_planes`` turns that into
nanoseconds a step, per device plane over the window of whole executions of
the step program, from each operation's self time (``trace_reduce.self_times``):

- ``direction_ns``: a partition of busy time into ``forward`` (``jvp(`` on the
  path and no ``transpose(``), ``backward`` (``transpose(``), ``optimizer``
  (neither, scope ``optimizer``) and ``other`` (neither and no ``optimizer``:
  the compiler's own copies, which have no ``tf_op``, among them);
- ``scope_ns``: by the innermost scope of the vocabulary on the path, forward,
  backward and total, and ``unscoped_ns`` for operations with none;
- ``kernel_ns``: by kernel name, for Mosaic calls;
- ``category_ns``: by the compiler's ``hlo_category``.

A fusion is one event and carries one ``tf_op``, its root instruction's: all of
its time counts for that scope, also where XLA fused a neighbour's elementwise
tail into it. Host side: the durations of the two program spans, and each idle
gap of the device put down to the innermost span open on the host when it
began, the harness's (``next_batch``, ``step_call``, ``fetch_loss``) or the
program's. All averaged over the device planes.

The vocabulary is ``SCOPES`` plus what the cell's configuration file lists
under ``"scopes"``: names its program nests inside the base scopes (a router,
its experts, a kernel's name: whatever is on the stacks). A listed name takes
the time of the operations it is innermost on out of the base scope around it,
so the scopes still sum with ``unscoped_ns`` to the busy time.

``profile(facts)`` is what the per-layer metrics call: it reduces the one
trace of the traced run, which the harness parsed once and keeps in
``facts["planes"]`` until the last reader has returned, and keeps the result
in ``facts``. No reader takes a trace of its own. Where not one device
operation carries a scope of the vocabulary (a program from before the
scopes, or an executable loaded from a compile cache that an unscoped program
filled) it returns ``None`` and says so in the log, so that no scoped metric
reads 0 in silence.
"""

import collections
import functools
import json
import re
import statistics
import time

from chipbench import trace_reduce as tr

SCOPES = ("embed", "attention", "attention_core", "ffn", "layer_norm",
          "loss", "optimizer")
PROGRAM_SPANS = ("trainer/place", "trainer/enqueue")
NO_SCOPE = "no scope in the trace: stale executable or scopes removed"

_WRAPPED = re.compile(r"^[A-Za-z_][A-Za-z0-9_.]*\((.*)\)$")


def elements(tf_op):
    """The scopes and the primitive of a ``tf_op`` path, each out of the
    transformations around it: ``transpose(jvp(attention))`` is
    ``attention``, ``jvp()`` is dropped."""
    out = []
    for part in tf_op.rsplit(":", 1)[0].split("/"):   # "<path>:<op type>"
        while True:
            inner = _WRAPPED.match(part)
            if not inner:
                break
            part = inner.group(1)
        if part:
            out.append(part)
    return out


def vocabulary(scopes):
    """``SCOPES`` and then a configuration's own names, each once."""
    return tuple(dict.fromkeys((*SCOPES, *scopes)))


@functools.lru_cache(maxsize=None)     # a step's operations repeat
def classify(tf_op, scopes=SCOPES):
    """(direction, innermost of ``scopes`` on the stack or None)."""
    if not tf_op:
        return "other", None
    names = elements(tf_op)
    scope = next((n for n in reversed(names) if n in scopes), None)
    if "transpose(" in tf_op:
        return "backward", scope
    if "jvp(" in tf_op:
        return "forward", scope
    return ("optimizer" if "optimizer" in names else "other"), scope


def host_spans(planes):
    """[(name, start, end)] of the harness's and the program's host spans."""
    wanted = tr.HOST_SPANS + PROGRAM_SPANS
    spans = [(e.name, e.start, e.end)
             for events in planes.get(tr.HOST_PLANE, {}).values()
             for e in events if e.name in wanted]
    return sorted(spans, key=lambda s: s[1])


def reduce_device(lines, spans, scopes):
    """The sums of one device plane, a step; None where no step ran on it."""
    modules = lines.get(tr.MODULES_LINE, [])
    name = tr.step_module(modules)
    steps = [e for e in modules if e.name == name]
    if not steps or not lines.get(tr.OPS_LINE):
        return None
    lo, hi = min(e.start for e in steps), max(e.end for e in steps)
    ops = tr.clip(lines[tr.OPS_LINE], lo, hi)
    own = tr.self_times(ops)
    direction = dict.fromkeys(("forward", "backward", "optimizer", "other"), 0)
    scope_ns = {s: {"forward": 0, "backward": 0, "total": 0}
                for s in scopes}
    kernels, categories = collections.Counter(), collections.Counter()
    unscoped_ops = collections.Counter()
    unscoped = scoped_events = 0
    for i, e in enumerate(ops):
        ns = own[i]
        way, scope = classify(e.stats.get("tf_op"), scopes)
        direction[way] += ns
        if scope is None:
            unscoped += ns
            unscoped_ops[tr.label(e.name)] += ns
        else:
            scoped_events += 1
            scope_ns[scope]["total"] += ns
            if way in scope_ns[scope]:
                scope_ns[scope][way] += ns
        if tr.is_mosaic(e.name):
            kernels[tr.parse(e.name)[0]] += ns
        categories[e.stats.get("hlo_category") or "none"] += ns
    busy = tr.union((e.start, e.end) for e in ops)
    gaps = collections.Counter()
    for s, e in tr.subtract([[lo, hi]], busy):
        gaps[tr.label_gap(s, spans)] += e - s
    n = len(steps)
    return {
        "step_module": name, "steps": n, "scoped_events": scoped_events,
        "busy_ns": tr.total(busy) / n, "window_ns": (hi - lo) / n,
        "direction_ns": {k: v / n for k, v in direction.items()},
        "scope_ns": {s: {k: v / n for k, v in d.items()}
                     for s, d in scope_ns.items()},
        "unscoped_ns": unscoped / n,
        "kernel_ns": {k: v / n for k, v in kernels.items()},
        "category_ns": {k: v / n for k, v in categories.items()},
        "unscoped_ops": [[k, v / n] for k, v in unscoped_ops.most_common(12)],
        "idle_gap_ns": {k: v / n for k, v in gaps.items()},
    }


def mean_of(dicts):
    """The key-wise mean of dicts of numbers (nested dicts too); a key that
    a dict lacks counts as 0 there."""
    keys = list(dict.fromkeys(k for d in dicts for k in d))
    if any(isinstance(d.get(k), dict) for d in dicts for k in keys):
        return {k: mean_of([d.get(k, {}) for d in dicts]) for k in keys}
    return {k: sum(d.get(k, 0) for d in dicts) / len(dicts) for k in keys}


def reduce_planes(planes, device_planes=tr.DEVICE_PLANES, scopes=()):
    """The reduction of a trace read by ``xplane.load``, averaged over its
    device planes; None where it has no device plane with a step on it.
    ``scopes`` are a configuration's names beside ``SCOPES``.
    ``scoped_events`` counts the device operations that carry a scope of
    the vocabulary: where it is 0 the by-scope numbers say nothing."""
    spans = host_spans(planes)
    scopes = vocabulary(scopes)
    devices = [d for d in (reduce_device(planes[name], spans, scopes)
                           for name in sorted(planes)
                           if name.startswith(device_planes))
               if d is not None]
    if not devices:
        return None
    out = {"devices": len(devices),
           "steps": min(d["steps"] for d in devices),
           "scoped_events": sum(d["scoped_events"] for d in devices),
           "step_module": devices[0]["step_module"],
           "unscoped_ops": devices[0]["unscoped_ops"]}
    for key in ("busy_ns", "window_ns", "unscoped_ns"):
        out[key] = sum(d[key] for d in devices) / len(devices)
    for key in ("direction_ns", "scope_ns", "kernel_ns", "category_ns",
                "idle_gap_ns"):
        out[key] = mean_of([d[key] for d in devices])
    out["host_span_ms"] = {
        name: sorted((e - s) / 1e6 for n, s, e in spans if n == name)
        for name in PROGRAM_SPANS}
    return out


def table(reduced):
    """The reduction as lines for a log."""
    busy = reduced["busy_ns"]
    lines = [f"{reduced['steps']} steps of {reduced['step_module']} on "
             f"{reduced['devices']} device plane(s); busy "
             f"{busy / 1e6:.3f} ms a step",
             f"{'':22}{'forward':>10}{'backward':>10}{'total ms':>10}"
             f"{'% busy':>8}"]

    def row(name, fwd, bwd, total):
        cells = "".join(f"{v / 1e6:10.3f}" if v is not None else f"{'':10}"
                        for v in (fwd, bwd, total))
        return f"{name:22}{cells}{100 * total / busy:8.2f}"

    for name, d in reduced["scope_ns"].items():
        lines.append(row(name, d["forward"], d["backward"], d["total"]))
    lines.append(row("unscoped", None, None, reduced["unscoped_ns"]))
    for name, ns in reduced["direction_ns"].items():
        lines.append(row(f"[{name}]", None, None, ns))
    parts = sum(reduced["direction_ns"].values())
    lines.append(f"the four directions sum to {parts / 1e6:.3f} ms, "
                 f"{100 * parts / busy:.2f}% of busy")
    for name, ns in sorted(reduced["kernel_ns"].items(), key=lambda kv: -kv[1]):
        lines.append(row(f"kernel {name}", None, None, ns))
    for name, values in reduced["host_span_ms"].items():
        if values:
            lines.append(f"host {name}: median "
                         f"{statistics.median(values):.3f} ms over "
                         f"{len(values)}")
    gaps = ", ".join(f"{k} {v / 1e3:.1f} us" for k, v in
                     sorted(reduced["idle_gap_ns"].items(),
                            key=lambda kv: -kv[1]))
    lines.append(f"idle a step, by the host span open: {gaps or 'none'}")
    return lines


def profile(facts):
    """The reduction of the traced run's one trace (``facts["planes"]``), by
    the vocabulary of the cell's configuration, or None (see the module
    docstring). Made once and kept in ``facts``."""
    if "scope_profile" in facts:
        return facts["scope_profile"]
    from chipbench import run

    t0 = time.perf_counter()
    reduced = reduce_planes(facts["planes"],
                            facts["peak"].get("device_planes", "/device:"),
                            facts["config"].get("scopes", ()))
    print(f"[scopes] the run's one trace reduced by scope in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    if reduced is None:
        print("[scopes] no device plane in the trace", flush=True)
    elif not reduced["scoped_events"]:
        print(f"[scopes] {NO_SCOPE}", flush=True)
        reduced = None
    else:
        run.OUT_DIR.mkdir(parents=True, exist_ok=True)
        (run.OUT_DIR / f"{facts['cell']['name']}.scopes.json").write_text(
            json.dumps(reduced, indent=1))
        for line in table(reduced):
            print(f"[scopes] {line}", flush=True)
    facts["scope_profile"] = reduced
    return reduced


def ms(facts, *keys):
    """Milliseconds a step at ``profile(facts)[keys...]``, or None."""
    value = profile(facts)
    for key in keys:
        if value is None:
            return None
        value = value.get(key)
    return None if value is None else value / 1e6


def span_ms(facts, name):
    """Median host milliseconds of the program's span ``name`` in the traced
    steps; None where the program writes no such span."""
    reduced = profile(facts)
    if reduced is None or not reduced["host_span_ms"].get(name):
        return None
    return statistics.median(reduced["host_span_ms"][name])

