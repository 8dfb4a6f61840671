"""Run one cell of ``BENCHMARK.json`` once: load, warm up, measure, print.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell. The last line of standard output is one JSON object
with the keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced); the lines before it are a log for the
reader. Exits non-zero, with no result, on a device that is not in
``peaks.json`` (the CPU included) or with fewer chips than the cell asks for.
No environment variable chooses a path here: what the program's own ``auto``
selections run is what is measured. README.md beside this file has the rest.
"""

import time

T0 = time.perf_counter()      # set-up is counted from here, before any import

import argparse                # noqa: E402
import contextlib              # noqa: E402
import json                    # noqa: E402
import math                    # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

from chipbench import trace_reduce, xplane              # noqa: E402
from chipbench.aot import step_bytes                    # noqa: E402
from chipbench.catalog import ROOT, Catalog             # noqa: E402

WARMUP_STEPS = 3
TRACED_STEPS = 10
OUT_DIR = ROOT / "chiprun_out" / "chipbench"


def log(message):
    print(f"[{time.perf_counter() - T0:7.2f}s] {message}", flush=True)


class Spans:
    """Host spans around the calls into the program: kept in memory for the
    metrics, and written into the profiler's trace while one is taken."""

    def __init__(self):
        self.records = []         # (name, start_ns, end_ns), perf_counter

    @contextlib.contextmanager
    def span(self, name):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter_ns()))

    def durations_ms(self, name):
        return [(e - s) / 1e6 for n, s, e in self.records if n == name]


class CompileCounter:
    """Counts the compile requests jax makes (served from the cache or not)."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.count += 1


def drive(job, state, pool, spans, seconds=None, steps=None):
    """The measured loop: a new host batch every step through the trainer's
    own step_fn, one step kept in flight. The loss of step i-1 is fetched
    after step i is enqueued, and the return of each fetch is that step's
    completion time. Stops after ``seconds`` or ``steps``; the step still in
    flight is completed after the loop and is not among the completions.

    Returns (state, completion times [s], losses, attempted, failed).
    """
    params, opt_state = state
    done, losses, attempted, failed = [], [], 0, 0
    in_flight = None
    start = time.perf_counter()
    while True:
        with spans.span("next_batch"):
            batch = pool[attempted % len(pool)]
        try:
            with spans.span("step_call"):
                loss, params, opt_state = job.step_fn(params, opt_state,
                                                      batch)
            attempted += 1
            if in_flight is not None:
                with spans.span("fetch_loss"):
                    losses.append(float(in_flight))
                done.append(time.perf_counter())
            in_flight = loss
        except jax.errors.JaxRuntimeError as e:
            log(f"step {attempted} failed: {str(e)[:400]}")
            attempted, failed, in_flight = attempted + 1, failed + 1, None
            break
        if steps is not None and attempted >= steps:
            break
        if seconds is not None and time.perf_counter() - start >= seconds:
            break
    if in_flight is not None:
        losses.append(float(in_flight))
    failed += sum(not math.isfinite(v) for v in losses)
    return (params, opt_state), done, losses, attempted, failed


#: An interval between completions is odd, and ends a steady stretch, when it
#: is further from the median interval than this share of it, or than this
#: many times the distance between the intervals' quartiles.
ODD_SHARE, ODD_SPREADS = 0.02, 5.0
MAX_LAG = 256                  # steps between the two completions of a pair


def step_seconds(done):
    """The time of one step, from the completion times of a window: the
    median, over all pairs of completions with no odd interval between them,
    of the time between the pair over the steps between it.

    Why not the window's length over its steps: that carries every moment the
    device idled because this process was not run. The driver's hosts share
    their CPU cores, and its first check saw that rate spread by 2.8% over six
    runs of one tree in the cell with the shortest step (PERF.md, PR 22). An
    odd interval is such a stall (the device idled, every later completion is
    late by as much) or one late reading of the clock (the next interval is as
    much shorter); either way no pair reaches across it. Within a steady
    stretch a pair far apart divides the lateness of its two readings by its
    many steps, so the median over pairs is steadier than the median interval
    by an order of magnitude where the clock is read late often. Where fewer
    than half of the intervals are left in stretches, it is the median
    interval.
    """
    done = np.asarray(done, dtype=np.float64)
    intervals = np.diff(done)
    median = float(np.median(intervals))
    q1, q3 = np.percentile(intervals, [25, 75])
    odd = np.abs(intervals - median) > max(ODD_SHARE * median,
                                           ODD_SPREADS * (q3 - q1))
    stretch = np.concatenate([[0], np.cumsum(odd)])
    slopes = []
    for lag in range(1, min(len(done) - 1, MAX_LAG) + 1):
        same = stretch[lag:] == stretch[:-lag]
        slopes.append((done[lag:] - done[:-lag])[same] / lag)
    slopes = np.concatenate(slopes)
    if 2 * np.count_nonzero(~odd) < len(intervals) or slopes.size == 0:
        return median
    return float(np.median(slopes))


def rates(done, tokens_per_step):
    """(``train_tokens_per_s``: a step's tokens over ``step_seconds``; the
    rate over the whole window, stalls included, for the log; the intervals
    between completions). The per-layer metric ``window_stall_pct`` is the
    share between the two rates, so what the first leaves out stays in
    sight."""
    return (tokens_per_step / step_seconds(done),
            (len(done) - 1) * tokens_per_step / (done[-1] - done[0]),
            np.diff(done))


def highest_percentile(n):
    """The highest percentile with at least ten samples beyond it."""
    return 100.0 * (1 - 10 / n) if n >= 20 else None


def compare(program, reference, tolerance):
    """Relative errors (Frobenius, float32) of the program's (loss, outputs)
    against the reference's, and whether each is inside its tolerance."""
    @jax.jit
    def relative_error(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return jnp.linalg.norm((got - want).ravel()) \
            / jnp.maximum(jnp.linalg.norm(want.ravel()), 1e-30)

    errors = {name: float(relative_error(got, want)) for name, got, want
              in zip(("loss", "outputs"), program, reference)}
    ok = all(math.isfinite(errors[k]) and errors[k] <= tolerance[k]
             for k in errors)
    return ok, errors


def check_reference(catalog, config, job, params, seed):
    """The program's loss and final states on a few seeded sequences, through
    the cell's own mesh, against the plain float32 reference."""
    reference = catalog.module("reference", config["reference"])
    sample = job.sample(seed)
    program = job.probe(params, job.place(sample))
    want = reference.loss_and_outputs(params, config, sample)
    ok, errors = compare(program, want, reference.TOLERANCE)
    log(f"reference: loss {float(program[0]):.5f} against "
        f"{float(want[0]):.5f}; relative errors {errors}, tolerance "
        f"{reference.TOLERANCE}: {'agrees' if ok else 'DISAGREES'}")
    return ok


def take_trace(job, state, pool, trace_dir, device_planes):
    """TRACED_STEPS steady steps under the profiler, the one trace of a
    traced run: its planes, parsed once (every reader reads these, none
    takes a trace of its own), and ``trace_reduce``'s reduction of them. The
    caller deletes ``trace_dir`` once the last reader has returned."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # host spans, not every Python call
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        drive(job, state, pool, Spans(), steps=TRACED_STEPS)
    finally:
        jax.profiler.stop_trace()
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{trace_dir}")
    t0 = time.perf_counter()
    planes = xplane.load(files[-1])
    reduced = trace_reduce.reduce_planes(planes, device_planes)
    log(f"trace: {files[-1].stat().st_size} bytes, "
        f"{len(reduced['devices'])} device plane(s), "
        f"{reduced['host_spans']} host spans; read and reduced in "
        f"{time.perf_counter() - t0:.2f} s")
    return planes, reduced


def read_metrics(catalog, workload, facts, compiles):
    """{name: value or None} of the cell's per-layer metrics, each from its
    own reader file. A reader only reads: what the window and the one trace
    left in ``facts``, and what the program can be asked without running
    it. One that compiles a program (``compiles`` counts the requests) is
    refused here, so that a traced run never needs more of the device than
    the step itself."""
    before, t0 = compiles.count, time.perf_counter()
    values = {m["name"]: catalog.module("layer_metrics",
                                        m["name"]).metric(facts)
              for m in catalog.metrics("per_layer", workload)}
    log(f"readers: {len(values)} metrics read in "
        f"{time.perf_counter() - t0:.2f} s, {compiles.count - before} "
        f"compile requests")
    if compiles.count != before:
        raise SystemExit("a per-layer reader compiled a program: a reader "
                         "only reads (chipbench/README.md): no result")
    return values


def run_cell(workload, seed, seconds, trace, catalog=None, peaks=None,
             keep_trace=False):
    """Run one cell and return the result object ``main`` prints.

    ``peaks`` is the table of devices the benchmark may run on (default:
    ``peaks.json``); a device that is not in it is an error, and that is the
    whole device check. A rehearsal on the CPU passes a table that has it.
    """
    from paddle_tpu.core import compile_cache

    cache_dir = compile_cache.enable()    # before the backend starts
    catalog = catalog or Catalog()
    if peaks is None:
        peaks = catalog.json("peaks.json")
    cell, config, traffic = catalog.cell(workload)
    devices = jax.devices()
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} (platform "
                         f"{devices[0].platform}) is not in peaks.json: no "
                         f"result")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{workload} asks for {cell['chips']} chips, jax "
                         f"finds {len(devices)}: no result")
    peak = peaks[kind]
    devices = devices[:cell["chips"]]
    log(f"cell {workload} seed {seed}: {cell['chips']} x {kind}; compile "
        f"cache at {cache_dir}")
    compiles = CompileCounter()

    # ---- set-up: weights, the cell's own shapes, the reference check
    job = catalog.module("runners", config["runner"]).build(
        config, traffic, devices)
    pool = job.pool(seed)
    state = jax.block_until_ready(job.init_fn(jax.random.PRNGKey(seed)))
    log(f"weights and {len(pool)} host batches made from the seed")
    step_args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding),
        (*state, job.place(pool[0])))
    agrees = check_reference(catalog, config, job, state[0], seed)
    spans = Spans()
    state, _, warm_losses, _, warm_failed = drive(
        job, state, pool, spans, steps=WARMUP_STEPS)
    log(f"warm-up: {WARMUP_STEPS} steps, losses "
        f"{' '.join(f'{v:.4f}' for v in warm_losses)}")
    log(f"compile_cache: {compile_cache.stats()} after set-up "
        f"({compiles.count} compile requests)")

    # ---- the window
    spans.records.clear()
    compiles_before = compiles.count
    setup_s = time.perf_counter() - T0
    state, done, losses, attempted, failed = drive(
        job, state, pool, spans, seconds=seconds)
    compiled_in_window = compiles.count - compiles_before
    if len(done) < 2:
        raise SystemExit(f"{len(done)} steps completed in {seconds} s: the "
                         f"window is too short for a rate")
    tokens_per_s, window_rate, intervals = rates(done, job.tokens_per_step)
    pct = highest_percentile(len(intervals))
    log(f"window: {attempted} steps enqueued, {len(done)} completed in it, "
        f"median interval {1e3 * np.median(intervals):.3f} ms"
        + (f", p{pct:.0f} {1e3 * np.percentile(intervals, pct):.3f} ms"
           if pct else "")
        + f", longest {1e3 * intervals.max():.3f} ms"
        f"; loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
        f"{compiled_in_window} compilations inside the window")
    log(f"rate: {tokens_per_s:.1f} tokens/s at {job.tokens_per_step} tokens "
        f"a step over steady stretches ({window_rate:.1f} over the whole "
        f"window, stalls included); set-up {setup_s:.2f} s")
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{workload}.seed{seed}.completions.json").write_text(
        json.dumps([t - done[0] for t in done]))

    # ---- after the window: memory by the compiler's account, the trace
    need = step_bytes(job.jitted.lower(*step_args).compile()
                      .memory_analysis())
    runtime_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices)
    log(f"memory: the compiled step needs {need} bytes a device; the "
        f"runtime's peak_bytes_in_use reads {runtime_peak}")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": jax.device_count(),
              "memory_peak_bytes": int(max(need, runtime_peak))}
    result = {
        "correct": bool(agrees and failed == 0 and warm_failed == 0
                        and compiled_in_window == 0),
        "attempted": attempted, "failed": failed,
        "metrics": {}, "device": device,
    }
    values = {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s}
    group = "end_to_end"
    if trace:
        group = "per_layer"
        trace_dir = OUT_DIR / "trace" / workload
        planes, reduced = take_trace(
            job, state, pool, trace_dir, peak.get("device_planes", "/device:"))
        (OUT_DIR / f"{workload}.reduced.json").write_text(
            json.dumps(reduced, indent=1))
        if reduced["devices"]:
            device["busy_s"] = reduced["busy_ns"] / 1e9
            device["window_s"] = reduced["window_ns"] / 1e9
            result["breakdown"] = {
                "device_ops": [[n, ns / 1e9] for n, ns in reduced["top_ops"]],
                "idle_gaps": [[n, ns / 1e9]
                              for n, ns in reduced["idle_gaps"]],
            }
        elif peak.get("device_planes"):
            raise SystemExit("no operation ran on a device in the traced "
                             "steps: no result")
        values = read_metrics(catalog, workload, {
            "trace": reduced, "planes": planes, "spans": spans, "job": job,
            "config": config, "traffic": traffic, "cell": cell,
            "peak": peak, "catalog": catalog, "step_bytes": need,
            "tokens_per_s": tokens_per_s, "window_tokens_per_s": window_rate,
            "intervals_s": intervals,
        }, compiles)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for m in catalog.metrics(group, workload):
        if values.get(m["name"]) is not None:
            result["metrics"][m["name"]] = {
                "value": float(values[m["name"]]), "unit": m["unit"]}
    return result


def main(argv=None, **kw):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), **kw)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
