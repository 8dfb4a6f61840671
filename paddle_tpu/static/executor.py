"""Executor + Scope.

Parity: python/paddle/fluid/executor.py (Executor:294, run:566, scope
machinery) and C++ framework/executor.cc.

TPU-native redesign: instead of the reference's per-op interpreter hot
loop (ref: executor.cc:417-421 `for op in ctx->ops_: op->Run`), `run()`
traces the whole block once through the functional op registry and caches
a `jax.jit`-compiled step
`(state, feeds, base_key, step_idx) -> (fetches, new_state)` — the
per-step rng key folds from (base_key, step_idx) INSIDE the compiled
program, so dispatch costs no eager device ops.
Persistable vars (parameters, optimizer moments, counters) are the carried
state pytree (donated, so updates are in-place in HBM). The autodiff
pseudo-op (see backward.py) is executed as `jax.value_and_grad` over the
prefix of the block — one fused XLA computation for
forward+backward+update, which is the entire point of the TPU design.

Dispatch hot path: the block compiles once, but the eager Python AROUND
the compiled step must not become the bottleneck either (ROADMAP: "as
fast as the hardware allows" — on a host-overhead-dominated model the
old per-step program rescans and DP re-`device_put`s WERE the step
time). `run()` therefore memoizes a prepared runner per
(program, feed-signature): state-name/host-out scans and signature
sorting happen once, DP-mode state stays resident on the mesh
(no re-put once placed), and `return_numpy=False` returns jax's async
device arrays so steps N+1.. dispatch while step N computes. The
prepared step also AOT warm-starts: `Executor.prepare()` lowers and
compiles eagerly, so with the persistent compilation cache
(core/compile_cache.py) a restarted worker replays the XLA compile from
disk. `FLAGS_executor_fast_path=0` restores the legacy per-step rescans
(the A/B lever bench_dispatch.py measures against).

Training-health hooks (docs/DEBUGGING.md): under `FLAGS_check_nan_inf`
each device segment also returns one fused isfinite-sentinel scalar,
verified before the step's new state reaches the scope — a trip runs
the eager bisecting localizer (monitor/numerics.py) and raises with
the first non-finite tensor/op named. Tensor-watch programs
(monitor/tensorwatch.py) get their `@watch@stats` vector auto-fetched
alongside the user's fetch list, and step wall time feeds the anomaly
detector (monitor/anomaly.py) when it is enabled.
"""

import itertools
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.enforce import EnforceNotMet, enforce
from paddle_tpu.core.flags import define_flag, get_flag
from paddle_tpu.monitor import anomaly as _anomaly
from paddle_tpu.monitor import flight_recorder as _flight
from paddle_tpu.monitor import goodput as _goodput
from paddle_tpu.monitor import tensorwatch as _tensorwatch
from paddle_tpu.monitor import trace as _trace
from paddle_tpu.monitor.numerics import SENTINEL_KEY as _SENTINEL_KEY
from paddle_tpu.monitor.registry import counter as _counter
from paddle_tpu.monitor.registry import gauge as _gauge
from paddle_tpu.monitor.registry import histogram as _histogram
from paddle_tpu.ops.pallas import registry as _pallas_registry
from paddle_tpu.profiler import RecordEvent
from paddle_tpu.static.program import (
    OP_REGISTRY, Parameter, default_main_program, default_startup_program,
)

define_flag("executor_fast_path", True,
            "Memoize a prepared runner per (program, feed-signature) so "
            "the steady-state step skips per-step state rescans and DP "
            "re-device_puts (0 = legacy per-step preparation)")
define_flag("monitor_cost", True,
            "Record per-compiled-segment FLOPs/bytes (XLA cost "
            "analysis) into the metrics registry on first execution "
            "(0 = skip the one-time extra lowering)")
define_flag("apply_ir_passes", True,
            "Run the program-level optimization pass pipeline "
            "(static/opt_passes.py: constant folding, matmul+bias+act "
            "fusion, transpose/reshape cancellation, dead-op "
            "elimination) before compiling each step; "
            "BuildStrategy.apply_ir_passes overrides per program "
            "(0 = bit-identical legacy lowering)")
define_flag("pass_cost_evidence", False,
            "Probe XLA's analytical FLOPs/bytes before the pass "
            "pipeline and after every pass, publishing per-pass "
            "predicted deltas (program_pass_flops_delta/_bytes_delta "
            "gauges + the pass_evidence table). One extra lowering per "
            "pass per compile signature — evidence tooling, off by "
            "default")

# unified telemetry (monitor/registry.py): the hot-loop counters every
# layer above reads — catalogued in docs/OBSERVABILITY.md
_m_steps = _counter("executor_steps_total",
                    "Executor.run calls that dispatched a step")
_m_step_ms = _histogram("executor_step_ms",
                        "Wall ms per Executor.run call (prepare + "
                        "dispatch + fetch)")
_m_fetch_ms = _histogram("executor_fetch_ms",
                         "Wall ms blocked materializing fetches "
                         "(host sync) per Executor.run call")
_m_retraces = _counter("executor_retraces_total",
                       "Device-segment traces performed (mirrors "
                       "Executor.trace_count across all executors)")
_m_q_depth = _gauge("prefetch_queue_depth",
                    "Items currently buffered in the background "
                    "prefetch queue")
_m_q_wait = _counter("prefetch_producer_wait_ms_total",
                     "Wall ms prefetch producers spent handing items "
                     "to the queue (blocked time on a full queue)")
_m_q_items = _counter("prefetch_items_total",
                      "Items produced by background prefetch pipelines")



class Scope:
    """Name → value store (framework/scope.h parity, flattened: XLA owns
    device memory, so a scope is just the host-side name table).

    ``version`` counts NAME-SET changes only (a var created or dropped),
    not value updates — the executor's prepared runners key on it to
    notice a scope gaining vars (lazily created optimizer state, host-op
    outputs) without rescanning the program every step."""

    def __init__(self):
        self._vars = {}
        self._version = 0

    @property
    def version(self):
        return self._version

    def var(self, name):
        if name not in self._vars:
            self._version += 1
        return self._vars.setdefault(name, None)

    def find_var(self, name):
        return self._vars.get(name)

    def set_var(self, name, value):
        if name not in self._vars:
            self._version += 1
        self._vars[name] = value

    def drop_var(self, name):
        if name in self._vars:
            self._version += 1
        self._vars.pop(name, None)

    def names(self):
        return list(self._vars)


_global_scope = Scope()


class _ScopeStack(threading.local):
    """Per-thread scope stack rooted at the shared global scope.

    The stack must be thread-local: concurrent trainer threads (e.g. the
    in-process two-trainer PS tests, the reference's multi-threaded
    device workers) each `with scope_guard(their_scope)` — a shared
    stack would make one thread resolve global_scope() to another
    thread's scope mid-run (observed as "persistable vars not
    initialized" races). The root _global_scope itself stays shared, as
    in the reference (scope.h:45 global scope singleton)."""

    def __init__(self):
        self.stack = [_global_scope]


_scope_tls = _ScopeStack()


def global_scope():
    return _scope_tls.stack[-1]


class scope_guard:
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        _scope_tls.stack.append(self.scope)
        return self.scope

    def __exit__(self, *exc):
        _scope_tls.stack.pop()


def _as_feed_array(v):
    if isinstance(v, (np.ndarray, jnp.ndarray)):
        return jnp.asarray(v)
    return jnp.asarray(np.asarray(v))


class _PrefetchFailure:
    """Carrier for a producer-thread exception: the worker wraps instead
    of enqueueing the bare exception so (a) an Exception legitimately
    yielded as DATA is never mis-raised, and (b) the original traceback
    rides along explicitly and re-raises in the consumer with the
    producer frames intact. ``index`` is the ordinal of the item that
    failed (== items successfully produced before it), so a data-plane
    postmortem can name WHICH batch blew up, not just how."""

    __slots__ = ("exc", "index")

    def __init__(self, exc, index=None):
        self.exc = exc
        self.index = index


def background_prefetch(producer, transform, depth=2):
    """Generic background-thread prefetch pipeline: a worker thread
    pulls items from ``producer`` (an iterable), applies ``transform``,
    and queues up to ``depth`` results ahead of the consumer
    (``depth <= 0`` = unbounded read-ahead). Producer exceptions
    re-raise in the consumer with the producer's traceback; early
    consumer exit (break / .close()) stops and unblocks the worker —
    its puts time-slice against the stop flag, so it can never stay
    parked on a full queue after the consumer is gone. Shared by
    device_prefetch and dataio's FileDataLoader."""
    import queue as _queue
    import threading

    q = _queue.Queue(maxsize=max(int(depth), 0))
    SENTINEL = object()
    stop = threading.Event()

    def put(item, count=True):
        # never block forever: the consumer may have exited (its drain
        # can race with a worker still inside transform), so a plain
        # q.put could park this thread on a full queue for good
        t0 = time.perf_counter()
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
            except _queue.Full:
                continue
            if count:       # data items only, not sentinel/failure
                _m_q_items.inc()
                _m_q_wait.inc((time.perf_counter() - t0) * 1e3)
            _m_q_depth.set(q.qsize())
            return True
        return False

    # pipeline trace: the context is created on the CONSUMER thread
    # and the worker records its per-item spans against it — the
    # explicit cross-thread propagation monitor/trace.py is built on
    # (a postmortem/timeline then shows the producer's work under the
    # pipeline that owns it, not as orphan spans of an anonymous
    # thread)
    tctx = _trace.start_trace("prefetch/pipeline") \
        if _trace._enabled else None

    def worker():
        produced = 0
        try:
            for b in producer:
                if stop.is_set():
                    return
                if tctx is not None:
                    t0 = time.perf_counter()
                    item = transform(b)
                    _trace.record_span(tctx, "prefetch/item", t0,
                                       time.perf_counter(),
                                       attrs={"index": produced})
                else:
                    item = transform(b)
                if not put(item):
                    return
                produced += 1
        except BaseException as e:       # surface in consumer
            # `produced` == the failing item's ordinal: everything
            # before it was delivered downstream intact
            put(_PrefetchFailure(e, index=produced), count=False)
            return
        finally:
            # close the producer HERE, deterministically: a generator
            # holding file handles (dataio's record readers) would
            # otherwise keep them until GC when the consumer abandons
            # the pipeline early
            close = getattr(producer, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass
        put(SENTINEL, count=False)

    t = threading.Thread(target=worker, daemon=True,
                         name="pt-prefetch-worker")
    t.start()
    try:
        while True:
            if _goodput._armed:
                # consumer blocked on an empty queue = the input
                # pipeline couldn't keep up — the goodput ledger's
                # input_wait phase (docs/DEBUGGING.md "Where did my
                # wall-clock go?")
                _t_get = time.perf_counter()
                item = q.get()
                _goodput.attribute(time.perf_counter() - _t_get,
                                   phase="input_wait")
            else:
                item = q.get()
            _m_q_depth.set(q.qsize())
            if item is SENTINEL:
                break
            if isinstance(item, _PrefetchFailure):
                if _flight._enabled:
                    # the postmortem names the batch that failed, not
                    # just the exception: "batch 1337 of the stream"
                    # is what lets an operator replay/inspect the
                    # offending records
                    _flight.RECORDER.note(
                        "error", "prefetch.producer",
                        batch_index=item.index,
                        error=repr(item.exc))
                try:
                    item.exc.prefetch_batch_index = item.index
                except Exception:      # __slots__-restricted exception
                    pass
                raise item.exc.with_traceback(item.exc.__traceback__)
            yield item
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        except _queue.Empty:
            pass
        if tctx is not None:
            _trace.end_trace(tctx)


def device_prefetch(batches, depth=2, put=None):
    """Double-buffered device staging (the role of the reference's
    operators/reader/buffered_reader.cc): a background thread transfers
    upcoming feed batches host->device ``depth`` steps ahead, so the
    H2D hop overlaps the current step's compute instead of serializing
    with it. ``batches`` yields feed dicts (or tuples/arrays); yields
    the same structure with device-resident arrays. ``put`` overrides
    the per-batch placement — pass ``Executor.feed_stage(...)`` to
    stage batches directly onto the shardings the prepared runner
    consumes (DP/mesh feed placement) instead of the default device."""

    def stage(b):
        t0 = time.perf_counter()
        if put is not None:
            out = put(b)
        elif isinstance(b, dict):
            out = {k: _as_feed_array(v) for k, v in b.items()}
        elif isinstance(b, (tuple, list)):
            out = type(b)(_as_feed_array(v) for v in b)
        else:
            out = _as_feed_array(b)
        from paddle_tpu.dataio.dataloader import _m_h2d_ms
        _m_h2d_ms.inc((time.perf_counter() - t0) * 1e3)
        if put is None and _trace._enabled:
            # park the staging interval for the consuming step's trace
            # to adopt as its feed_stage phase (a feed_stage() put
            # notes for itself — see Executor.feed_stage); keyed by
            # the staged arrays' identity so only their consumer
            # adopts it
            _trace.stage_note("executor/feed_stage", t0,
                              time.perf_counter(),
                              key=_stage_key(out))
        return out

    return background_prefetch(batches, stage, depth)


def exec_op(op, env, key):
    """Run one program op through the functional registry: bind inputs
    from env, return {output name: value}. ``key`` is the op's rng key
    (None for ops without `_needs_rng`)."""
    fn = OP_REGISTRY[op.type]
    ins = {slot: [env[n] for n in names]
           for slot, names in op.inputs.items()}
    attrs = dict(op.attrs)
    # pass-pipeline bookkeeping (opt_passes._stamp_rng_indices), not a
    # compute kwarg — consumed by the caller's key derivation
    attrs.pop("_rng_idx", None)
    if attrs.pop("_needs_rng", False):
        attrs["rng"] = key
    outs = fn(ins, attrs)
    bound = {}
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for n, v in zip(names, vals):
            bound[n] = v
    return bound


def _stage_key(batch):
    """ids of the arrays a staged batch carries — the identity a
    stage note is matched to its consuming step by (trace.adopt_stage:
    an interleaved step that did NOT consume these arrays can never
    adopt their staging span)."""
    if isinstance(batch, dict):
        return [id(v) for v in batch.values()]
    if isinstance(batch, (tuple, list)):
        return [id(v) for v in batch]
    return [id(batch)]


_ABSENT = object()

#: PROCESS-GLOBAL per-run flow ids pairing each dispatch RecordEvent
#: with the fetch that materializes it (profiler.export_chrome_trace
#: draws the arrow by THIS id, not FIFO order — async steps emit
#: dispatches with no fetch, which made FIFO pairing hand a later
#: blocking step's fetch to the wrong dispatch). Global, not
#: per-Executor: all executors share one profiler ring, and
#: per-instance counters would collide ids across executors — the
#: same misattribution class the id pairing exists to kill.
_flow_ids = itertools.count(1)


def _spec_of(v):
    """jax.ShapeDtypeStruct for an array-like / (shape, dtype) pair /
    existing spec — the currency of AOT warm-start."""
    if isinstance(v, jax.ShapeDtypeStruct):
        return v
    if isinstance(v, tuple) and len(v) == 2 and not hasattr(v, "dtype"):
        return jax.ShapeDtypeStruct(tuple(v[0]), np.dtype(v[1]))
    return jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype
                                if not hasattr(v, "dtype") else v.dtype)


class _CompiledStep:
    """One compiled (program, signature) step: the block partitioned
    into host/device segments with each device segment jitted. Callable
    as (state, feeds, base_key, step_idx) -> (fetches, new_state)
    (plus the per-segment numerics sentinels when called with
    ``check=True`` — see monitor/numerics.py); also exposes the segment
    structure so `aot_compile` can lower+compile eagerly (warm-starting
    the persistent compilation cache) and the op list so the non-finite
    localizer can replay the step eagerly per-op."""

    __slots__ = ("segs", "seg_fns", "constants", "state_set",
                 "state_names", "fetch_names", "interpret", "ops",
                 "_donate_names", "donated_fetch_idx", "_cost_done",
                 "uid")

    #: process-unique compiled-step ids — the anomaly detector keys
    #: stall baselines on this, and a recycled id() of a GC'd step
    #: would hand a new program a dead program's baseline
    _uid_counter = itertools.count()

    def __init__(self, segs, seg_fns, constants, state_names,
                 fetch_names, interpret, ops):
        self._cost_done = False
        self.uid = next(_CompiledStep._uid_counter)
        self.segs = segs
        self.seg_fns = seg_fns
        self.constants = constants
        self.state_set = set(state_names)
        self.state_names = state_names
        self.fetch_names = fetch_names
        self.interpret = interpret
        self.ops = ops
        # per device segment: the state names it overwrites, frozen at
        # compile so the hot path does set-membership over a LIST of
        # candidates instead of scanning the whole env every step
        self._donate_names = [
            None if fn_w is None
            else [n for n in state_names if n in fn_w[2]]
            for fn_w in seg_fns]
        # fetches that alias DONATED state: the returned array is the
        # same buffer the next step donates, so an async caller
        # (return_numpy=False) must receive a copy or materialize-later
        # hits a deleted buffer
        donated = {n for d in self._donate_names if d for n in d}
        self.donated_fetch_idx = [i for i, n in enumerate(fetch_names)
                                  if n in donated]

    def _split(self, env, donate_names):
        # donate only state this segment overwrites (params, opt
        # slots): feeds/constants may be reused by the caller, and
        # donated pass-through state comes back as deleted buffers
        donated = {}
        for k in donate_names:
            v = env.pop(k, _ABSENT)
            if v is not _ABSENT:
                donated[k] = v
        if self.constants:
            rest = {k: v for k, v in env.items()
                    if k not in self.constants}
        else:
            rest = env
        return donated, rest

    def __call__(self, state, feeds, base_key, step_idx, check=False):
        """``check=True`` (FLAGS_check_nan_inf) runs the CHECKED jit
        variant of each device segment — same program plus one fused
        isfinite-reduction scalar — and donates nothing, so the
        pre-step state stays alive for the localizer's eager replay.
        Returns (fetches, new_state, sentinels) then; the plain
        2-tuple otherwise."""
        env = dict(self.constants) if self.constants else {}
        env.update(state)
        env.update(feeds)
        record_cost = not self._cost_done and \
            bool(get_flag("monitor_cost"))
        sentinels = []
        dev_i = 0
        for (is_host, a, b), fn_w, donate in zip(
                self.segs, self.seg_fns, self._donate_names):
            if is_host:
                env = self.interpret(env, a, b, base_key, step_idx)
            else:
                fn, checked_fn, _writes = fn_w
                use = checked_fn if check else fn
                donated, rest = self._split(env, () if check else donate)
                if record_cost:
                    # BEFORE executing: donation deletes these buffers
                    self._record_cost(dev_i, use, donated, rest,
                                      base_key, step_idx)
                out = use(donated, rest, base_key, step_idx)
                if check:
                    sentinels.append(out.pop(_SENTINEL_KEY))
                env = dict(self.constants) if self.constants else {}
                env.update(out)
                dev_i += 1
        if record_cost:
            # only latch when the probe actually ran: a step executed
            # under FLAGS_monitor_cost=0 can still record cost later
            # when the flag is flipped back on
            self._cost_done = True
        fetches = [env[n] for n in self.fetch_names]
        new_state = {n: env[n] for n in self.state_names}
        if check:
            return fetches, new_state, sentinels
        return fetches, new_state

    def _record_cost(self, dev_i, fn, donated, rest, base_key,
                     step_idx):
        """One-time per segment: read XLA's analytical FLOPs/bytes off
        ``fn.lower(...)`` and publish them as segment_flops/
        segment_bytes gauges — the raw material of the MFU estimate.
        The lowering shares jit's tracing cache, so it IS the first
        call's trace (trace_count moves exactly as without the probe)
        and the immediately following execution reuses it. Never
        fatal."""
        from paddle_tpu.monitor import cost as _cost
        try:
            lowered = fn.lower(donated, rest, base_key, step_idx)
        except Exception:
            return
        _cost.record_segment(id(self), dev_i,
                             _cost.analyze_lowered(lowered))

    def aot_compile(self, state, feeds, base_key, step_idx):
        """Eagerly .lower().compile() device segments with abstract
        inputs (``state``/``feeds`` values may be arrays, ShapeDtype-
        Structs, or (shape, dtype) pairs). With the persistent
        compilation cache enabled this writes the on-disk entries the
        first real step (and every restarted process) then compiles
        from. Host segments cannot run abstractly, so AOT stops at the
        first one; returns (compiled, total_device_segments)."""
        env = {k: _spec_of(v) for k, v in self.constants.items()}
        env.update({k: _spec_of(v) for k, v in state.items()})
        env.update({k: _spec_of(v) for k, v in feeds.items()})
        compiled = 0
        total = sum(1 for is_host, _, _ in self.segs if not is_host)
        record_cost = not self._cost_done and \
            bool(get_flag("monitor_cost"))
        for (is_host, a, b), fn_w, donate in zip(
                self.segs, self.seg_fns, self._donate_names):
            if is_host:
                break
            fn, _checked_fn, _writes = fn_w
            donated, rest = self._split(env, donate)
            lowered = fn.lower(donated, rest, base_key, step_idx)
            exe = lowered.compile()
            if record_cost:
                from paddle_tpu.monitor import cost as _cost
                _cost.record_segment(id(self), compiled,
                                     _cost.analyze_lowered(lowered))
                # collective bytes only exist POST-SPMD-partitioning,
                # i.e. in the compiled executable's optimized HLO —
                # AOT compile is the one place the executor holds it
                try:
                    txt = exe.as_text()
                except Exception:   # backend without HLO text
                    txt = None
                _cost.record_segment_comm(id(self), compiled,
                                          _cost.estimate_comm(txt))
                # memory analysis likewise lives on the COMPILED
                # executable (CompiledMemoryStats) — captured here so
                # the lazy first-call path never compiles twice just
                # to ask a footprint
                from paddle_tpu.monitor import memory as _memory
                _memory.record_segment_memory(
                    id(self), compiled, _memory.analyze_compiled(exe))
            out = jax.eval_shape(fn, donated, rest, base_key, step_idx)
            compiled += 1
            env = {k: _spec_of(v) for k, v in self.constants.items()}
            env.update(out)
        if record_cost and compiled == total:
            self._cost_done = True
        return compiled, total

    def lower_cost(self, state, feeds, base_key, step_idx):
        """Sum XLA's analytical FLOPs/bytes over the device segments by
        lowering them abstractly (no ``.compile()``, no metric
        recording) — the probe behind FLAGS_pass_cost_evidence. Host
        segments stop the walk like ``aot_compile``; returns
        ``{"flops", "bytes"}`` or None when nothing lowered."""
        env = {k: _spec_of(v) for k, v in self.constants.items()}
        env.update({k: _spec_of(v) for k, v in state.items()})
        env.update({k: _spec_of(v) for k, v in feeds.items()})
        from paddle_tpu.monitor import cost as _cost
        flops = bytes_ = 0.0
        lowered_any = False
        for (is_host, a, b), fn_w, donate in zip(
                self.segs, self.seg_fns, self._donate_names):
            if is_host:
                break
            fn, _checked_fn, _writes = fn_w
            donated, rest = self._split(env, donate)
            try:
                lowered = fn.lower(donated, rest, base_key, step_idx)
                est = _cost.analyze_lowered(lowered)
            except Exception:
                est = None
            if est:
                flops += float(est.get("flops") or 0.0)
                bytes_ += float(est.get("bytes") or 0.0)
                lowered_any = True
            out = jax.eval_shape(fn, donated, rest, base_key, step_idx)
            env = {k: _spec_of(v) for k, v in self.constants.items()}
            env.update(out)
        if not lowered_any:
            return None
        return {"flops": flops, "bytes": bytes_}


class _PreparedRunner:
    """Everything `Executor.run` needs per (program, feed-signature)
    that is invariant step to step — the product of the one-time scans
    the legacy path redid every call."""

    __slots__ = ("step", "state_names", "host_outs", "scope_ref",
                 "scope_version", "rep", "ok_shardings", "ndev",
                 "watch_idx", "spec", "targets")

    def __init__(self, step, state_names, host_outs, scope, rep, ndev,
                 watch_idx=None, spec=None, targets=None):
        self.step = step
        self.state_names = state_names
        self.host_outs = host_outs
        self.scope_ref = weakref.ref(scope)
        self.scope_version = scope.version
        self.watch_idx = watch_idx        # auto-appended @watch@stats
        self.rep = rep                    # replicated sharding (DP) or None
        self.spec = spec                  # ShardingSpec (mesh mode) or None
        # per-state-name target NamedSharding from the spec (replicated
        # for names the spec says nothing about) — the residency fast
        # path compares against THESE, so spec-sharded leaves pass
        # through without a per-step re-put just like replicated ones
        self.targets = targets
        # shardings proven equivalent to their name's target, memoized
        # BY IDENTITY with the object held alive: id alone could be
        # recycled by a new, non-equivalent sharding after GC
        self.ok_shardings = {}            # (name, id(s)) -> s
        self.ndev = ndev

    def fresh_for(self, scope):
        return (self.scope_ref() is scope
                and self.scope_version == scope.version)


class Executor:
    """One compiled XLA computation per (program, feed-signature)."""

    def __init__(self, place=None):
        self.place = place
        self._cache = {}                  # full sig -> _CompiledStep
        self._runners = {}                # dispatch sig -> _PreparedRunner
        self._keys = {}
        self._trace_count = 0             # bumps per device-segment trace

    @property
    def trace_count(self):
        """Number of device-segment traces this executor performed —
        steady-state steps with an unchanged feed signature must not
        move it (the executor-caching tests pin exactly that)."""
        return self._trace_count

    @staticmethod
    def _program_read_names(program):
        """Names of all vars the program's ops read, memoized on the
        program keyed by op count (the reader-protocol hot path calls
        run() in a tight loop and ops only ever get appended)."""
        ops = program.global_block().ops
        cached = getattr(program, "_read_names_cache", None)
        if cached is not None and cached[0] == len(ops):
            return cached[1]
        names = {n for op in ops for n in op.input_names()}
        program._read_names_cache = (len(ops), names)
        return names

    def _base_key(self, seed):
        k = self._keys.get(seed)
        if k is None:
            k = self._keys[seed] = jax.random.PRNGKey(seed)
        return k

    @staticmethod
    def _passes_enabled(compiled):
        """Effective apply_ir_passes setting for one run: the wrapped
        program's ``BuildStrategy.apply_ir_passes`` when explicitly
        set, else ``FLAGS_apply_ir_passes`` (on by default). Off means
        the bit-identical legacy lowering — the A/B lever
        ``bench.py passes`` measures against."""
        on = bool(get_flag("apply_ir_passes"))
        if compiled is not None:
            bs = compiled.__dict__.get("_build_strategy")
            knob = getattr(bs, "apply_ir_passes", None) \
                if bs is not None else None
            if knob is not None:
                on = bool(knob)
        return on

    @staticmethod
    def _dispatch_sig(program, spec, feeds, fetch_names, scope,
                      apply_passes):
        """Prepared-runner cache key. The PROGRAM OBJECT itself (not
        id()) rides in the key: the dict entry then keeps it alive, so
        a dead program's id can never be recycled into a silent stale
        hit (dict hashing is identity-based for Program). The SPEC
        object (ShardingSpec of the mesh mode, or None) rides the same
        way — identity-hashed and kept alive by the entry. The scope is
        keyed by id() only — a recycled scope id is caught at use time
        by _PreparedRunner.fresh_for's weakref identity check, NOT by
        this key. ``apply_passes`` rides in the key so flipping the
        pass pipeline mid-process (the bench A/B) can never serve a
        step compiled under the other setting. feeds values may be
        arrays or ShapeDtypeStructs."""
        return (program, program.version, spec,
                tuple(sorted((k, tuple(v.shape), str(v.dtype))
                             for k, v in feeds.items())),
                tuple(fetch_names), id(scope), bool(apply_passes))

    def _store_runner(self, dsig, runner):
        # dead-scope eviction: a scope-per-request caller would
        # otherwise accumulate one unreachable runner per request; the
        # sweep is O(runners) and only runs when the table has grown
        if len(self._runners) > 32:
            self._runners = {k: r for k, r in self._runners.items()
                             if r.scope_ref() is not None}
        self._runners[dsig] = runner

    # -- public API --------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True):
        """Run one step. ``return_numpy=False`` returns jax device
        arrays WITHOUT synchronizing — dispatch is async, so the caller
        can issue steps N+1..N+k while step N is still computing and
        only pay the sync when a value is materialized
        (``np.asarray``). ``return_numpy=True`` keeps the blocking
        fluid-parity contract."""
        program = program or default_main_program()
        # CompiledProgram.with_mesh_sharding / .with_data_parallel:
        # unwrap and remember the ShardingSpec; the same compiled step
        # runs SPMD over the spec's mesh (GSPMD partitions from the
        # spec-derived feed/state shardings plus the
        # with_sharding_constraint pins the compiled segments carry —
        # SURVEY §3.2's path with the multi-device graph pass replaced
        # by the partitioner)
        spec = None
        from paddle_tpu.compiler import CompiledProgram
        if isinstance(program, CompiledProgram):
            spec = program._spec
            apply_passes = self._passes_enabled(program)
            program = program._program
        else:
            apply_passes = self._passes_enabled(None)
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [f if isinstance(f, str) else f.name
                       for f in fetch_list]
        if not feed:
            # non-iterable reader protocol (fluid.layers.py_reader
            # start()/reset()): pull the next batch from started readers
            # attached to this program; they raise EOFException when
            # exhausted (reader op EOF → core.EOFException parity).
            # Only readers whose vars the program actually reads are
            # pulled, and two started readers feeding the same var is an
            # error — a chained reader (open_files → batch) registers
            # both itself and its underlying py_reader, and silently
            # advancing both would skip data (ADVICE r3 #4).
            started = [r for r in getattr(program, "_py_readers", [])
                       if getattr(r, "_started", False)]
            read_names = (self._program_read_names(program)
                          | set(fetch_names) if started else set())
            # validate BEFORE pulling anything: raising mid-loop would
            # have already consumed a batch from an earlier reader
            pull, fed_by = [], {}
            for r in started:
                rnames = {v.name for v in r.vars}
                if read_names and not (rnames & read_names):
                    continue
                for n in rnames:
                    if n in fed_by:
                        raise EnforceNotMet(
                            f"two started readers would both feed var "
                            f"'{n}' — start only the outermost reader "
                            f"of a chain (e.g. the batch reader, not "
                            f"its underlying py_reader)")
                    fed_by[n] = r
                pull.append(r)
            for r in pull:
                feed.update(r._next_feed())
        scope = scope or global_scope()

        # startup-style programs (initializers only, no feeds) run eagerly
        if not feed and self._is_startup_like(program):
            self._run_eager(program, scope)
            return [] if not fetch_names else [
                self._fetch_value(scope, n, return_numpy) for n in fetch_names]

        t_run = time.perf_counter()
        if _goodput._armed:
            # goodput ledger boundary: the gap since the last run's
            # end (minus stalls the seams attributed) was device_idle
            _goodput.on_run_start(t_run)
        tc0 = self._trace_count
        # per-step trace (tail-sampled; monitor/trace.py): opened as
        # this thread's CURRENT trace so an anomaly/non-finite
        # postmortem fired mid-step embeds the phases recorded so far
        tctx = _trace.start_trace("executor/step", current=True) \
            if _trace._enabled else None
        if tctx is not None:
            # the root must start at t_run: the prepare child span is
            # stamped from t_run, and a child beginning before its own
            # root renders mis-nested in the merged timeline
            tctx.t0 = t_run
        try:
            with RecordEvent("executor.run/prepare"):
                feeds = {k: _as_feed_array(v) for k, v in feed.items()}
                dsig = self._dispatch_sig(program, spec, feeds,
                                          fetch_names, scope,
                                          apply_passes)
                fast = bool(get_flag("executor_fast_path"))
                runner = self._runners.get(dsig) if fast else None
                if runner is None or not runner.fresh_for(scope):
                    runner = self._prepare_runner(program, feeds, fetch_names,
                                                  scope, spec, apply_passes)
                    if fast:
                        self._store_runner(dsig, runner)
                state = self._gather_state(runner, scope)
                if state is None:             # scope changed under us
                    runner = self._prepare_runner(program, feeds, fetch_names,
                                                  scope, spec, apply_passes)
                    if fast:
                        self._store_runner(dsig, runner)
                    state = self._gather_state(runner, scope)

                if spec is not None:
                    feeds = spec.shard_feeds(feeds)
                    state = self._ensure_resident(state, runner, fast)
            t_prep = time.perf_counter()
            if tctx is not None:
                _trace.record_span(tctx, "executor/prepare", t_run,
                                   t_prep)
                # adopt the prefetch worker's staging interval for the
                # batch this step consumes: the span ran on the worker
                # thread (its tid says so) but belongs to THIS step's
                # tree. Matched BY ARRAY IDENTITY — only the note whose
                # staged arrays this step actually feeds is adopted, so an
                # interleaved manually-fed step (even one fed device_put
                # jax arrays) can neither steal a pipeline's note nor
                # shift later adoptions off by one.
                if feed:
                    _trace.adopt_stage(
                        tctx, match={id(v) for v in feed.values()})

            # per-step rng: the base key is staged on device once per seed,
            # and the step fold happens INSIDE the jitted program (the old
            # eager PRNGKey+fold_in cost two device round-trips per step)
            base_key = self._base_key(program.random_seed)
            step_idx = np.uint32(scope.find_var("@step@") or 0)
            scope.set_var("@step@", (scope.find_var("@step@") or 0) + 1)
            if tctx is not None:
                tctx.attrs["step"] = int(step_idx)
            check = bool(get_flag("check_nan_inf"))
            fid = next(_flow_ids)
            t_disp = time.perf_counter()
            with RecordEvent("executor.run/dispatch", args={"flow": fid}):
                try:
                    if check:
                        fetches, new_state, sentinels = runner.step(
                            state, feeds, base_key, step_idx, check=True)
                    else:
                        fetches, new_state = runner.step(
                            state, feeds, base_key, step_idx)
                except Exception as e:
                    from paddle_tpu.monitor import memory as _memory
                    if _memory.is_oom_error(e):
                        # typed OOM with attribution: ledger table, top
                        # live buffers, compile-time estimate vs limit,
                        # dumped via anomaly.trip("oom") (which embeds
                        # the in-flight trace). The BaseException
                        # handler below still ends the trace as error.
                        _memory.handle_oom(e, "executor.run/dispatch",
                                           step=int(step_idx))
                    raise
            t_disp_end = time.perf_counter()
            if tctx is not None:
                # recorded BEFORE the sentinel verification so a
                # non-finite trip's postmortem already names the dispatch
                # phase and its duration
                _trace.record_span(tctx, "executor/dispatch", t_disp,
                                   t_disp_end)
            if check:
                # the one deliberate host sync of the checked mode: a
                # scalar per segment, verified BEFORE the new state reaches
                # the scope so a trip leaves the pre-step params intact for
                # inspection. handle_trip localizes + raises.
                for seg_i, s in enumerate(sentinels):
                    if not bool(np.asarray(s)):
                        from paddle_tpu.monitor import numerics as _numerics
                        _numerics.handle_trip(runner.step, state, feeds,
                                              base_key, step_idx, seg_i)
            for n, v in new_state.items():
                scope.set_var(n, v)
            watch_v = None
            if runner.watch_idx is not None:
                # @watch@stats rides last in the fetch list (auto-appended
                # by _prepare_runner) — peel it off before the user sees
                # fetches; published after the step-time observation below
                watch_v = fetches.pop(runner.watch_idx)
            if return_numpy:
                with RecordEvent("executor.run/fetch", args={"flow": fid}):
                    t_fetch = time.perf_counter()
                    fetches = [np.asarray(f) for f in fetches]
                    _m_fetch_ms.observe(
                        (time.perf_counter() - t_fetch) * 1e3)
                if tctx is not None:
                    _trace.record_span(tctx, "executor/fetch", t_fetch,
                                       time.perf_counter())
            elif runner.step.donated_fetch_idx:
                # async contract: a fetched var that is also donated state
                # (e.g. fetch_list=[some_param]) would have its buffer
                # deleted by the NEXT step's donation before the caller
                # materializes it — hand back an (async) device copy
                for i in runner.step.donated_fetch_idx:
                    fetches[i] = jnp.array(fetches[i], copy=True)
            _m_steps.inc()
            step_ms = (time.perf_counter() - t_run) * 1e3
            _m_step_ms.observe(step_ms)
            if _goodput._armed:
                # close the ledger's in-run window: compile vs compute
                # (vs replay) split for this step
                _goodput.on_run_end(t_run, t_prep, t_disp, t_disp_end,
                                    self._trace_count > tc0)
            if watch_v is not None and _tensorwatch._enabled:
                _tensorwatch.on_step(watch_v, int(step_idx),
                                     sync=return_numpy)
            if _anomaly._enabled:
                # keyed by compiled-step identity: train and eval programs
                # through one executor get separate stall baselines
                _anomaly.DETECTOR.observe(step=int(step_idx),
                                          step_ms=step_ms,
                                          step_ms_key=runner.step.uid)
            if _flight._enabled:
                _flight.RECORDER.note("step", "executor.run",
                                      step=int(step_idx))
            if tctx is not None:
                # exemplar BEFORE the tail-sampling verdict (it force-
                # keeps the slowest step's tree), end AFTER the anomaly
                # feed above (a step_stall trip must still find this trace
                # in flight to embed it in its postmortem)
                _trace.record_exemplar("executor_step_ms", step_ms, tctx)
                _trace.end_trace(tctx)
            return fetches
        except BaseException:
            # a step that dies mid-flight (runner.step, a non-finite
            # sentinel trip, fetch) still ends its trace as an error:
            # errors are always kept by tail sampling, and leaving the
            # context in flight would pin _tls.current at a dead step
            # until the next run() on this thread. handle_trip /
            # anomaly postmortems embed the in-flight trace BEFORE
            # raising, so ending it here loses nothing.
            if tctx is not None:
                _trace.end_trace(tctx, error=True)
            raise

    def prepare(self, program=None, feed=None, fetch_list=None,
                scope=None):
        """AOT warm-start (jit .lower().compile() done eagerly): build
        the prepared runner for (program, feed-signature) and compile
        its device segments BEFORE the first step. ``feed`` maps names
        to sample arrays, (shape, dtype) pairs, or jax.ShapeDtypeStructs
        — only shapes/dtypes matter. Requires the startup program to
        have run (state shapes come from the scope).

        With the persistent compilation cache enabled
        (core/compile_cache.py) the compiled
        executables land on disk, so the first real step — and every
        restarted worker process — replays the XLA compile as a disk
        read instead of recompiling. Returns True when every device
        segment was AOT-compiled (programs with host segments warm up
        to the first host boundary only)."""
        program = program or default_main_program()
        sspec = None
        from paddle_tpu.compiler import CompiledProgram
        if isinstance(program, CompiledProgram):
            sspec = program._spec
            apply_passes = self._passes_enabled(program)
            program = program._program
        else:
            apply_passes = self._passes_enabled(None)
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [f if isinstance(f, str) else f.name
                       for f in fetch_list]
        scope = scope or global_scope()
        specs = {k: _spec_of(v if not isinstance(v, (list,))
                             else np.asarray(v))
                 for k, v in feed.items()}
        runner = self._prepare_runner(program, specs, fetch_names, scope,
                                      sspec, apply_passes)
        if bool(get_flag("executor_fast_path")):
            dsig = self._dispatch_sig(program, sspec, specs,
                                      fetch_names, scope, apply_passes)
            self._store_runner(dsig, runner)
        state = {}
        for n in runner.state_names:
            v = scope.find_var(n)
            if v is None:                 # host-written: materializes at
                continue                  # step time, can't be spec'd
            state[n] = v
        try:
            # ledger attribution of scope residency: optimizer slots
            # are named "<param>@<slot>" and internal optimizer state
            # leads with "@" — everything else is a persistable param
            from paddle_tpu.monitor import memory as _memory
            p_bytes = s_bytes = 0
            for n, v in state.items():
                nb = int(getattr(v, "nbytes", 0) or
                         np.asarray(v).nbytes)
                if "@" in n:
                    s_bytes += nb
                else:
                    p_bytes += nb
            _memory.ledger_set("train/params", p_bytes)
            if s_bytes:
                _memory.ledger_set("train/optimizer_slots", s_bytes)
        except Exception:
            pass
        if sspec is not None:
            # abstract inputs carry the SPEC-derived shardings, so the
            # AOT compile partitions exactly like the first real step
            state = {n: jax.ShapeDtypeStruct(
                        np.shape(v), v.dtype,
                        sharding=runner.targets[n])
                     for n, v in state.items()}
            specs = {
                k: jax.ShapeDtypeStruct(
                    s.shape, s.dtype,
                    sharding=sspec.feed_sharding(k, len(s.shape)))
                for k, s in specs.items()}
        base_key = self._base_key(program.random_seed)
        compiled, total = runner.step.aot_compile(
            state, specs, base_key, np.uint32(0))
        return compiled == total

    def feed_stage(self, program=None, feed_names=None):
        """Device-side double-buffer stage: returns ``put(batch)`` for
        a data loader's prefetch worker
        (``FileDataLoader(device_put=put)`` /
        ``device_prefetch(put=put)``) that places each feed batch on
        the EXACT sharding the prepared runner consumes — the
        spec-derived feed shardings for
        ``CompiledProgram.with_mesh_sharding`` / ``with_data_parallel``
        programs, the default device otherwise. The host->device hop
        for batch N+1 then runs in the worker thread while the
        compiled step for batch N computes, and ``run()`` passes the
        already-placed arrays through instead of re-putting them on
        its critical path (``dataio_h2d_overlap_ms`` counts the moved
        milliseconds). ``feed_names`` orders tuple/list batches (dict
        batches carry their own names; a bare-array batch needs
        exactly one name)."""
        program = program or default_main_program()
        spec = None
        from paddle_tpu.compiler import CompiledProgram
        if isinstance(program, CompiledProgram):
            spec = program._spec
        names = list(feed_names) if feed_names is not None else None

        def _staged(base_put):
            # tracing wrapper: the staging runs in a prefetch WORKER
            # thread, so the interval is parked as a stage note the
            # consuming step's trace adopts (monitor/trace.py) — one
            # `_enabled` check per batch when tracing is off
            def staged(batch):
                if not _trace._enabled:
                    return base_put(batch)
                t0 = time.perf_counter()
                out = base_put(batch)
                _trace.stage_note("executor/feed_stage", t0,
                                  time.perf_counter(),
                                  key=_stage_key(out))
                return out
            return staged

        if spec is None:
            return _staged(jax.device_put)

        def place(name, v):
            sh = spec.feed_sharding(name, np.ndim(v))
            s = getattr(v, "sharding", None)
            if s is not None:
                try:
                    if s == sh or s.is_equivalent_to(sh, np.ndim(v)):
                        return v
                except Exception:
                    pass
            return jax.device_put(v, sh)

        def put(batch):
            if isinstance(batch, dict):
                return {k: place(k, v) for k, v in batch.items()}
            if names is None:
                raise EnforceNotMet(
                    "feed_stage(feed_names=...) is required for "
                    "tuple/array batches — the spec's feed shardings "
                    "are name-keyed")
            if isinstance(batch, (tuple, list)):
                if len(batch) != len(names):
                    raise EnforceNotMet(
                        f"feed_stage got a {len(batch)}-field batch "
                        f"for feed_names={names}")
                return type(batch)(place(n, v)
                                   for n, v in zip(names, batch))
            if len(names) != 1:
                raise EnforceNotMet(
                    f"feed_stage got a single-array batch but "
                    f"{len(names)} feed_names — pass the one name "
                    f"this array feeds")
            return place(names[0], batch)

        return _staged(put)

    # -- internals ---------------------------------------------------------
    def _prepare_runner(self, program, feeds, fetch_names, scope, spec,
                        apply_passes=False):
        """The one-time (per feed-signature) preparation the legacy path
        performed every step: state-name/host-out scans, the
        initialization check, and the compiled-step lookup."""
        # pre-create the step counter: creating it AFTER this prepare
        # (on the first run) would bump scope.version and force one
        # spurious re-prepare — and drop the DP residency memo — at
        # step 2
        if scope.find_var("@step@") is None:
            scope.set_var("@step@", 0)
        # tensor-watch programs (minimize() under tensorwatch.enable())
        # carry an @watch@stats var: auto-fetch it so the stats ride the
        # step's existing materialization instead of a second dispatch
        watch_idx = None
        if program.global_block().has_var(_tensorwatch.STATS_VAR) \
                and _tensorwatch.STATS_VAR not in fetch_names:
            fetch_names = list(fetch_names) + [_tensorwatch.STATS_VAR]
            watch_idx = len(fetch_names) - 1
        state_names = self._state_names(program, scope)
        state = {n: scope.find_var(n) for n in state_names}
        # vars a host op (load_combine, ps_recv…) writes are initialized
        # BY the program — they may legitimately start uninitialized
        host_outs = {n for op in program.global_block().ops
                     if op.attrs.get("_host") for n in op.output_names()}
        missing = [n for n, v in state.items()
                   if v is None and n not in host_outs]
        if missing:
            raise EnforceNotMet(
                f"Persistable vars not initialized: {missing[:5]} — run the "
                f"startup program first (exe.run(startup_program))")
        rep = None
        ndev = 0
        targets = None
        if spec is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(spec.mesh, PartitionSpec())
            ndev = spec.mesh.size
            # per-name target shardings (replicated unless the spec
            # says otherwise), validated ONCE against the live state
            # shapes so a bad tiling fails here with the param named,
            # not deep inside the partitioner
            targets = spec.state_shardings(state_names)
            for n, v in state.items():
                if v is not None:
                    jax.tree.map(
                        lambda x, n=n: spec.validate_leaf(n, np.shape(x)),
                        v)
        # program OBJECT in the key (see _dispatch_sig): identity hash
        # plus a live reference — id() alone could be recycled by a new
        # program after GC and silently serve the stale compiled step.
        # The spec rides the same way (identity, kept alive).
        sig = (program, program.version, spec,
               tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in feeds.items())),
               tuple(fetch_names), tuple(sorted(state_names)),
               bool(apply_passes))
        step = self._cache.get(sig)
        if step is None:
            cost_probe = None
            if apply_passes and bool(get_flag("pass_cost_evidence")):
                # FLAGS_pass_cost_evidence: lower each intermediate
                # program of the pass pipeline abstractly and hand XLA's
                # analytical FLOPs/bytes back to opt_passes, which
                # publishes the per-pass predicted delta
                # (program_pass_flops_delta / program_pass_bytes_delta
                # and the pass_evidence table). Needs the live shapes,
                # hence built here rather than in _compile.
                p_state = {n: v for n, v in state.items()
                           if v is not None}
                p_key = self._base_key(program.random_seed)

                def cost_probe(prog, _s=p_state, _f=dict(feeds),
                               _fn=tuple(fetch_names), _spec=spec,
                               _k=p_key):
                    probe_step = self._compile(
                        prog, sorted(_s), sorted(_f), list(_fn), _spec,
                        apply_passes=False)
                    return probe_step.lower_cost(_s, _f, _k,
                                                 np.uint32(0))
            step = self._compile(program, sorted(state_names),
                                 sorted(feeds), fetch_names, spec,
                                 apply_passes=apply_passes,
                                 cost_probe=cost_probe)
            self._cache[sig] = step
        return _PreparedRunner(step, state_names, host_outs, scope, rep,
                               ndev, watch_idx=watch_idx, spec=spec,
                               targets=targets)

    def _gather_state(self, runner, scope):
        """Pull the current state values for a prepared runner. Returns
        None when a state var has vanished from the scope (the caller
        re-prepares, which re-raises the proper diagnostic)."""
        state = {}
        host_outs = runner.host_outs
        for n in runner.state_names:
            v = scope.find_var(n)
            if v is None:
                if n not in host_outs:
                    return None
                continue
            state[n] = v
        return state

    def _ensure_resident(self, state, runner, fast):
        """Persistable state rides on the SAME mesh as the feeds, placed
        per the spec's per-name target sharding (replicated unless the
        spec shards it) — mixing single-device state with mesh-sharded
        feeds in one jit is an error. Fast path: once the step has run,
        its outputs already carry their target shardings (the compiled
        segments pin them with with_sharding_constraint), so re-putting
        every leaf every step (the legacy behavior, one eager dispatch
        per parameter per step) is pure overhead — a leaf whose
        sharding is provably equivalent to ITS name's target passes
        through untouched, spec-sharded leaves exactly like replicated
        ones, and the equivalence check memoizes on the (name, sharding
        object) pair (stable across steps: executables reuse their
        output shardings)."""
        rep = runner.rep
        targets = runner.targets
        ok = runner.ok_shardings
        out = {}
        for n, v in state.items():
            tgt = targets.get(n, rep) if targets is not None else rep

            def place_leaf(x, n=n, tgt=tgt):
                if fast:
                    s = getattr(x, "sharding", None)
                    if s is not None:
                        key = (n, id(s))
                        if ok.get(key) is s:
                            return x
                        try:
                            same = s == tgt or s.is_equivalent_to(
                                tgt, getattr(x, "ndim", 0))
                        except Exception:
                            same = False
                        if same:
                            ok[key] = s
                            return x
                return jax.device_put(x, tgt)

            out[n] = jax.tree.map(place_leaf, v)
        return out

    def train_from_dataset(self, program=None, dataset=None,
                           fetch_list=None, fetch_info=None,
                           print_period=100, scope=None, debug=False):
        """Dataset-driven training loop (executor.py:927 parity, call
        stack SURVEY §3.4): iterate the dataset's batches, feed each into
        the compiled program, print fetches every ``print_period`` steps
        (the FetchConfig/LodTensorPrinter role). The reference's
        per-thread hogwild workers collapse into batched device steps.

        Steps run with ``return_numpy=False`` and fetches only
        materialize (→ host sync) at ``print_period`` boundaries, so up
        to ``print_period`` steps stay in flight on the device queue
        while the host races ahead dispatching — pairing with
        ``device_prefetch``'s H2D double-buffering on the input side."""
        enforce(dataset is not None, "dataset is required")
        fetch_list = fetch_list or []
        fetch_names = [f if isinstance(f, str) else f.name
                       for f in fetch_list]
        enforce(fetch_info is None or len(fetch_info) == len(fetch_names),
                "fetch_info must match fetch_list in length")
        labels = fetch_info or fetch_names
        step = 0
        last = []
        # double-buffered device staging: H2D for batch n+1 overlaps
        # step n's compute (buffered_reader.cc role)
        for batch in device_prefetch(dataset):
            last = self.run(program, feed=batch, fetch_list=fetch_names,
                            scope=scope, return_numpy=False)
            step += 1
            if fetch_names and step % print_period == 0:
                # the ONLY sync point in the steady loop
                last = [np.asarray(v) for v in last]
                msg = ", ".join(f"{l}={np.asarray(v).mean():.6f}"
                                for l, v in zip(labels, last))
                print(f"step {step}: {msg}")
        # materialize the tail so callers keep the numpy contract
        return [np.asarray(v) for v in last]

    def infer_from_dataset(self, program=None, dataset=None,
                           fetch_list=None, fetch_info=None,
                           print_period=100, scope=None, debug=False):
        """executor.py infer_from_dataset parity — same loop; the caller
        passes an inference (for_test) program so no state is updated."""
        return self.train_from_dataset(program, dataset, fetch_list,
                                       fetch_info, print_period, scope,
                                       debug)

    def _is_startup_like(self, program):
        blk = program.global_block()
        return all(op.type != "autodiff" for op in blk.ops) and all(
            not (blk.has_var(n) and blk.var(n).is_data)
            for op in blk.ops for n in op.input_names())

    def _state_names(self, program, scope):
        blk = program.global_block()
        names = [n for n, v in blk.vars.items() if v.persistable]
        # include any extra persistables already living in the scope that
        # ops reference (optimizer state created lazily)
        for op in blk.ops:
            for n in op.input_names() + op.output_names():
                if scope.find_var(n) is not None and n not in names \
                        and not blk.has_var(n):
                    names.append(n)
        return names

    def _run_eager(self, program, scope):
        blk = program.global_block()
        key = self._base_key(program.random_seed)
        env = dict(getattr(program, "_constants", {}))
        env.update({n: scope.find_var(n) for n in scope.names()})
        for i, op in enumerate(blk.ops):
            op_key = (jax.random.fold_in(key, i)
                      if op.attrs.get("_needs_rng") else None)
            env.update(self._exec_op(op, env, op_key))
        for n, v in env.items():
            if v is not None:
                scope.set_var(n, v)

    def _exec_op(self, op, env, key):
        return exec_op(op, env, key)

    def _compile(self, program, state_names, feed_names, fetch_names,
                 spec=None, apply_passes=False, cost_probe=None):
        """Partition the block into maximal device runs, each jitted as
        ONE XLA computation (the whole block, in the common case), with
        host segments (attrs['_host']: RPC send/recv, py_func-style
        callbacks — ops the reference runs like any other in its per-op
        loop, executor.cc:417) executed eagerly between them. The
        PS-mode trainer program [ps_recv | fwd+bwd | ps_send] therefore
        still compiles its whole compute as a single fused program.

        Each op's rng key folds in its index *net of preceding host
        ops*, so a transpiler that brackets a program with host ops
        leaves the original ops' randomness (dropout masks…) unchanged
        — transpiled runs remain bit-comparable to local runs."""
        if apply_passes:
            # program-level pass pipeline (static/opt_passes.py): runs
            # on a CLONE against this step's actual fetch list, so the
            # caller's program object — and the apply_ir_passes=False
            # legacy lowering — stay bit-identical. Per-pass evidence
            # lands in monitor/cost.py (program_pass_* metrics). Rng
            # ops carry _rng_idx stamps, so optimization never shifts
            # a dropout mask.
            from paddle_tpu.static import opt_passes as _opt
            program = _opt.optimize_for_execution(program, fetch_names,
                                                  cost_probe=cost_probe)
        blk = program.global_block()
        ops = list(blk.ops)
        constants = dict(getattr(program, "_constants", {}))
        state_set = set(state_names)

        # ShardingSpec lowering: names the spec annotates (params and
        # their @GRADs) are pinned with with_sharding_constraint inside
        # every jitted segment — the pjit path, so GSPMD partitions the
        # fused step exactly per the program-level annotations instead of
        # guessing from inputs alone. Lookup is memoized per name;
        # names the spec says nothing about are left to the
        # partitioner (the pure-DP default spec pins nothing, keeping
        # that lowering bit-identical to the pre-spec executor).
        c_memo = {}

        def _target(n, state_default=False):
            """Constraint target for name ``n``: the spec's explicit
            entry (params and their @GRADs), or — with
            ``state_default`` — the replicated default for UNSPEC'D
            state names. Segment OUTPUTS pin every state name: left
            free, GSPMD may pick a sharded layout for an unannotated
            param (observed: P('model') chosen for a replicated-target
            leaf), which both breaks the "replicated unless spec'd"
            state contract and defeats the residency fast path into a
            re-put per leaf per step."""
            if spec is None:
                return None
            key = (n, state_default)
            t = c_memo.get(key, _ABSENT)
            if t is _ABSENT:
                t = spec.constraint_for(n)
                if t is None and state_default and n in state_set:
                    t = spec.param_sharding(n)
                c_memo[key] = t
            return t

        def _pin(env, state_default=False):
            if spec is None:
                return env
            for n in list(env):
                t = _target(n, state_default)
                if t is not None:
                    env[n] = jax.lax.with_sharding_constraint(env[n], t)
            return env

        # a host op BEFORE the autodiff marker splits the differentiated
        # prefix across segments, so value_and_grad cannot see through it
        # and upstream params would silently train with zero grads. The
        # one legal shape is a host op whose outputs are exactly autodiff
        # roots (ps_recv delivering params): refuse everything else.
        ad_global = next((i for i, op in enumerate(ops)
                          if op.type == "autodiff"), None)
        if ad_global is not None:
            roots = set(ops[ad_global].attrs["params"])
            for i in range(ad_global):
                op = ops[i]
                outs = set(op.output_names())
                # a no-output host op (save_combine, barriers) still
                # splits the differentiated prefix — refuse it too
                if op.attrs.get("_host") and \
                        (not outs or not outs <= roots):
                    raise EnforceNotMet(
                        f"host op {op.type!r} at position {i} feeds the "
                        f"differentiated forward region — gradients cannot "
                        f"flow through a host boundary, so every parameter "
                        f"upstream of it would silently stop training. "
                        f"Move it after the loss/backward, or use a "
                        f"jax-traceable op instead")

        hosts_before = []              # rng index adjustment
        h = 0
        for op in ops:
            hosts_before.append(h)
            if op.attrs.get("_host"):
                h += 1

        segs = []                      # (is_host, start, end)
        i = 0
        while i < len(ops):
            j = i
            is_host = bool(ops[i].attrs.get("_host"))
            while j < len(ops) and bool(ops[j].attrs.get("_host")) == is_host:
                j += 1
            segs.append((is_host, i, j))
            i = j

        def interpret(env, lo, hi, base_key, step_idx):
            # lazy fold: host segments run eagerly, and most host ops
            # (RPC send/recv, save/load) take no rng — folding
            # unconditionally would cost device round-trips per host op.
            # Inside jitted segments the folds trace into the program.
            key = None
            for k in range(lo, hi):
                if ops[k].attrs.get("_needs_rng"):
                    if key is None:
                        key = jax.random.fold_in(base_key, step_idx)
                    # _rng_idx (stamped by the pass pipeline before any
                    # op moved) pins the fold index an optimized op had
                    # in the ORIGINAL program — masks stay bit-identical
                    # to the unoptimized lowering
                    idx = ops[k].attrs.get("_rng_idx")
                    if idx is None:
                        idx = k - hosts_before[k]
                    op_key = jax.random.fold_in(key, idx)
                else:
                    op_key = None
                env.update(self._exec_op(ops[k], env, op_key))
            return env

        def make_device_fn(lo, hi):
            ad = next((k for k in range(lo, hi)
                       if ops[k].type == "autodiff"), None)
            # only vars this segment WRITES may be donated: a donated
            # input that XLA merely forwards to an output (pass-through
            # state, e.g. a PS-mode trainer's orphaned optimizer step
            # counter) comes back as a deleted buffer and poisons the
            # scope for the next step
            writes = set()
            for k in range(lo, hi):
                writes.update(ops[k].output_names())
            # the sentinel's fixed scan order over everything this
            # segment writes (outputs, grads, optimizer state)
            watch_names = sorted(writes)

            # a spec'd program is partitioned by GSPMD: the kernel
            # registry must hand the traced ops bodies XLA can split
            @_pallas_registry.mesh_scope(spec.mesh if spec is not None
                                         else None)
            def seg_fn(donated, rest, base_key, step_idx, check=False):
                # python executes at trace time only: the counter is the
                # retrace probe the caching tests (and bench_dispatch's
                # sanity check) read
                self._trace_count += 1
                _m_retraces.inc()
                # constants enter via closure -> XLA compile-time consts
                env = dict(constants)
                env.update(rest)
                env.update(donated)
                env = _pin(env)
                if ad is None:
                    env = interpret(env, lo, hi, base_key, step_idx)
                else:
                    adop = ops[ad]
                    loss_name = adop.attrs["loss"]
                    param_names = adop.attrs["params"]
                    base = {k: v for k, v in env.items()
                            if k not in param_names}

                    def fwd(params):
                        e = dict(base)
                        e.update(params)
                        e = interpret(e, lo, ad, base_key, step_idx)
                        return jnp.sum(e[loss_name]), e

                    params = {n: env[n] for n in param_names}
                    (_, env2), grads = jax.value_and_grad(
                        fwd, has_aux=True)(params)
                    env = env2
                    for n in param_names:
                        g = grads[n]
                        t = _target(n + "@GRAD")
                        if t is not None:
                            # pin the gradient to its param's placement
                            # BEFORE the update ops consume it: the
                            # gradient collective then reduces the
                            # shard-local buffers where the sharded
                            # update needs them
                            g = jax.lax.with_sharding_constraint(g, t)
                        env[n + "@GRAD"] = g
                    env = interpret(env, ad + 1, hi, base_key, step_idx)
                res = {k: v for k, v in env.items()
                       if k not in constants}
                res = _pin(res, state_default=True)
                if check:
                    # FLAGS_check_nan_inf: one fused isfinite reduction
                    # over every tensor this segment writes — a single
                    # extra scalar output, no extra dispatch
                    from paddle_tpu.monitor import numerics as _numerics
                    res[_SENTINEL_KEY] = _numerics.sentinel(
                        [env[n] for n in watch_names if n in env])
                return res

            fast = jax.jit(seg_fn, donate_argnums=(0,))
            # checked variant: separate jit (its own trace/compile,
            # first checked step pays it once), NO donation — the
            # localizer replays from the still-live pre-step state
            checked = jax.jit(
                lambda donated, rest, base_key, step_idx: seg_fn(
                    donated, rest, base_key, step_idx, True))
            return fast, checked, writes

        seg_fns = [None if is_host else make_device_fn(a, b)
                   for is_host, a, b in segs]

        return _CompiledStep(segs, seg_fns, constants, state_names,
                             fetch_names, interpret, ops)

    def _fetch_value(self, scope, name, return_numpy):
        v = scope.find_var(name)
        return np.asarray(v) if return_numpy and v is not None else v

    def close(self):
        self._cache.clear()
        self._runners.clear()


class AsyncExecutor:
    """async_executor.h:62 parity (the legacy pre-Trainer thread-pool
    trainer over DataFeed). On TPU the per-thread hogwild loops collapse
    into batched device steps, so this is a thin facade over
    Executor.train_from_dataset — kept because fluid user code
    instantiates fluid.AsyncExecutor(place) and calls run_from_files."""

    def __init__(self, place=None, run_mode=""):
        self._exe = Executor(place)

    def run(self, program, data_feed, filelist, thread_num, fetch,
            mode="", debug=False):
        data_feed.set_filelist(filelist)
        data_feed.set_thread(thread_num)
        return self._exe.train_from_dataset(
            program, data_feed,
            fetch_list=list(fetch) if fetch else None, debug=debug)

    run_from_files = run
