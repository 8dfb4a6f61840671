"""Device time of the expert layer by the stages the program names in it.

``paddle_tpu/parallel/moe.py`` nests seven ``jax.named_scope``s inside its
scopes ``moe_router`` and ``moe_dispatch`` (PR 51), so that every operation
under either is under exactly one of them, forward and backward:

    jit(step)/jvp(ffn)/moe_router/router_select/top_k
    jit(step)/transpose(jvp(ffn))/while/body/moe_dispatch/dispatch_gather/...

``router_logits`` (the float32 product and its two gradient products),
``router_scores`` (softmax or sigmoid, renormalisation, scale),
``router_select`` (bias, ``top_k``, the chosen scores taken; backward the
scatter of their gradient), ``router_stats`` (counts, balance and z terms);
``dispatch_order`` (keys, sorts, pad, and what each pass over held rows works
on), ``dispatch_gather`` (the rows in expert order), ``dispatch_combine``
(the rows back in token order, summed by their scores; the scores' gradient).

No configuration file lists these names, so ``scope_profile``'s reduction of
a cell, and every metric that reads it, sees ``moe_router`` and
``moe_dispatch`` whole, as before. ``profile(facts)`` here reads the same
planes once more with the stages on the vocabulary, in ``scope_profile``'s
manner (the step program's whole executions, each operation's self time to
the innermost listed name on its stack, ``scope_profile.classify``), and keeps
a step's nanoseconds by stage, what is left to the two scopes themselves
(``unnamed_ns``: an operation no stage covers) and each stage's largest
operations by XLA's name. A fusion is one event with its root's stack: a
stage takes the whole of a fusion whose root it holds, a neighbour's tail
fused into it too, which is what the operations' names are printed for.
Where not one operation carries a stage (a program from before the stages,
or an executable from a cache that such a program filled) it returns
``None`` and says so, and the eight readers return ``None``.
"""

import collections
import json
import time

from chipbench import scope_profile, trace_reduce as tr

STAGES = ("router_logits", "router_scores", "router_select", "router_stats",
          "dispatch_order", "dispatch_gather", "dispatch_combine")
WHOLE = ("moe_router", "moe_dispatch")     # their own time: under no stage
NO_STAGE = ("no stage scope in the trace: a program from before "
            "parallel/moe.py named its stages, or a stale executable")
LARGEST = 5                                # operations listed a stage


def reduce_device(lines, vocabulary):
    """A step's nanoseconds on one device plane by stage and direction, the
    two whole scopes' own among them, with each name's largest operations;
    None where no step ran on the plane."""
    modules = lines.get(tr.MODULES_LINE, [])
    name = tr.step_module(modules)
    steps = [e for e in modules if e.name == name]
    if not steps or not lines.get(tr.OPS_LINE):
        return None
    ops = tr.clip(lines[tr.OPS_LINE], min(e.start for e in steps),
                  max(e.end for e in steps))
    own = tr.self_times(ops)
    ns = {s: {"forward": 0, "backward": 0, "total": 0}
          for s in STAGES + WHOLE}
    by_op = {s: collections.Counter() for s in ns}
    events = 0
    for i, e in enumerate(ops):
        way, scope = scope_profile.classify(e.stats.get("tf_op"), vocabulary)
        if scope not in ns:
            continue
        events += scope in STAGES
        ns[scope]["total"] += own[i]
        if way in ns[scope]:
            ns[scope][way] += own[i]
        by_op[scope][tr.label(e.name)] += own[i]
    n = len(steps)
    return {"stage_events": events,
            "ns": {s: {k: v / n for k, v in d.items()}
                   for s, d in ns.items()},
            "ops": {s: [[label, v / n]
                        for label, v in by_op[s].most_common(LARGEST)]
                    for s in ns}}


def reduce_planes(planes, device_planes=tr.DEVICE_PLANES, scopes=()):
    """The stage reduction of a trace read by ``xplane.load``, averaged over
    its device planes (the operations' names are the first plane's); None
    where it has no device plane with a step on it. ``scopes`` are the
    cell's names beside ``scope_profile.SCOPES``: the vocabulary is those
    and the stages, so a stage takes exactly what the cell's reduction gives
    ``moe_router`` and ``moe_dispatch``."""
    vocabulary = scope_profile.vocabulary((*scopes, *WHOLE, *STAGES))
    devices = [d for d in (reduce_device(planes[name], vocabulary)
                           for name in sorted(planes)
                           if name.startswith(device_planes))
               if d is not None]
    if not devices:
        return None
    ns = scope_profile.mean_of([d["ns"] for d in devices])
    return {"devices": len(devices),
            "stage_events": sum(d["stage_events"] for d in devices),
            "stage_ns": {s: ns[s] for s in STAGES},
            "unnamed_ns": sum(ns[s]["total"] for s in WHOLE),
            "ops": devices[0]["ops"]}


def table(reduced, whole_ns=None):
    """The reduction as lines for a log; ``whole_ns`` is what the cell's own
    reduction gives ``moe_router`` and ``moe_dispatch`` together."""
    lines = [f"{'':18}{'forward':>10}{'backward':>10}{'total ms':>10}"
             f"  largest operations, ms a step"]
    for stage, d in reduced["stage_ns"].items():
        ops = ", ".join(f"{label} {ns / 1e6:.3f}"
                        for label, ns in reduced["ops"][stage])
        lines.append(f"{stage:18}{d['forward'] / 1e6:10.3f}"
                     f"{d['backward'] / 1e6:10.3f}{d['total'] / 1e6:10.3f}"
                     f"  {ops}")
    ops = ", ".join(f"{label} {ns / 1e6:.3f}" for scope in WHOLE
                    for label, ns in reduced["ops"][scope])
    lines.append(f"{'under no stage':18}{'':20}"
                 f"{reduced['unnamed_ns'] / 1e6:10.3f}  {ops}")
    named = sum(d["total"] for d in reduced["stage_ns"].values()) \
        + reduced["unnamed_ns"]
    lines.append(f"the seven and what is under none sum to "
                 f"{named / 1e6:.3f} ms"
                 + ("" if whole_ns is None else
                    f"; moe_router + moe_dispatch of the cell's reduction "
                    f"{whole_ns / 1e6:.3f} ms"))
    return lines


def profile(facts):
    """The stage reduction of the traced run's one trace
    (``facts["planes"]``), or None (see the module docstring). Made once and
    kept in ``facts``; compiles, launches and allocates nothing."""
    if "moe_stages" in facts:
        return facts["moe_stages"]
    from chipbench import run

    t0 = time.perf_counter()
    reduced = reduce_planes(facts["planes"],
                            facts["peak"].get("device_planes", "/device:"),
                            facts["config"].get("scopes", ()))
    print(f"[stages] the run's one trace reduced by the expert layer's "
          f"stages in {time.perf_counter() - t0:.2f} s", flush=True)
    if reduced is None:
        print("[stages] no device plane in the trace", flush=True)
    elif not reduced["stage_events"]:
        print(f"[stages] {NO_STAGE}", flush=True)
        reduced = None
    else:
        run.OUT_DIR.mkdir(parents=True, exist_ok=True)
        (run.OUT_DIR / f"{facts['cell']['name']}.moe_stages.json"
         ).write_text(json.dumps(reduced, indent=1))
        whole = [scope_profile.ms(facts, "scope_ns", scope, "total")
                 for scope in WHOLE]
        for line in table(reduced, None if None in whole
                          else 1e6 * sum(whole)):
            print(f"[stages] {line}", flush=True)
    facts["moe_stages"] = reduced
    return reduced


def ms(facts, stage):
    """Milliseconds a step under ``stage``, forward and backward together,
    or None where the trace names no stage."""
    reduced = profile(facts)
    return None if reduced is None else \
        reduced["stage_ns"][stage]["total"] / 1e6


def unnamed_ms(facts):
    """Milliseconds a step whose innermost name is ``moe_router`` or
    ``moe_dispatch`` itself once the stages are on the vocabulary."""
    reduced = profile(facts)
    return None if reduced is None else reduced["unnamed_ns"] / 1e6
