"""Multi-replica dispatch: the execution half of the serving subsystem.

Each :class:`Replica` owns a device, a device-resident copy of the
frozen program's params, and one ahead-of-time compiled XLA executable
per bucket of the ladder — compiled at **warm boot** (pool
construction), before the server accepts traffic, so the first real
request never pays a trace or an XLA compile. When the PR-2 persistent
compilation cache is armed (``JAX_COMPILATION_CACHE_DIR``; see
``core/compile_cache.py``), warm boot itself is a disk read on every
boot after the first.

Replicas are fed from ONE shared batch queue (the scheduler's dispatch
target): a slow replica simply takes fewer batches, it cannot convoy
the others — the reference's multi-stream serving shape
(inference/api: one AnalysisPredictor clone per stream), with streams
replaced by device-pinned executables.

Device pinning uses sharding-annotated avals
(``jax.ShapeDtypeStruct(..., sharding=SingleDeviceSharding(dev))``), so
each replica's executables are compiled FOR its device and feeds are
``device_put`` onto it at dispatch; replicas that share a device (more
replicas than devices) share one executable map and one param copy —
the extra replicas then only add pipelining across the Python/dispatch
gap, which is exactly what they are for on a single-chip host.

**Resilience** (docs/SERVING.md "Resilience"): every replica
heartbeats per dispatch — the ``distributed/health.py`` idiom, with
mtime-touches replaced by in-process stamps (``busy_since``,
``current``) — and a supervisor thread in :class:`ReplicaPool`
watches them. A replica wedged mid-dispatch past ``replica_stall_ms``,
or whose thread died by uncaught exception, is **quarantined**: its
in-flight batch's riders are failed with a typed
:class:`~.resilience.ReplicaLostError` (never a silent hang), the
``serving_replica_state`` gauge tells the truth, and the slot is
**respawned** against the already-compiled executable map after a
capped exponential backoff. ``max_consecutive_stalls`` losses with no
successful batch in between permanently retire the slot and shrink
the pool — loudly. If every slot retires, the supervisor keeps
draining the batch queue and failing riders so no request ever hangs.
"""

import itertools
import queue
import sys
import threading
import time

import numpy as np

from paddle_tpu.core.enforce import enforce
from paddle_tpu.monitor.registry import counter, gauge, histogram
from paddle_tpu.serving.resilience import ReplicaLostError, _log

__all__ = ["Replica", "ReplicaPool"]

_m_replicas = gauge(
    "serving_replicas",
    "Replica workers serving the shared batch queue (supervisor-owned "
    "truth: a dead or quarantined replica leaves this gauge, a "
    "respawned one re-enters)")
_m_exec_ms = histogram(
    "serving_batch_execute_ms",
    "Wall ms a replica spent executing one micro-batch (device_put + "
    "compiled call + host fetch)")
_m_state = gauge(
    "serving_replica_state",
    "Replica count by lifecycle state: up (draining the batch queue), "
    "quarantined (lost mid-dispatch, awaiting respawn backoff), "
    "retired (permanently removed after max_consecutive_stalls)",
    labels=("state",))
_m_respawns = counter(
    "serving_replica_respawns_total",
    "Replica worker threads respawned by the pool supervisor after a "
    "stall or thread death (against the already-compiled executable "
    "map — a respawn never recompiles)")
_m_param_bytes = gauge(
    "serving_param_bytes",
    "Device-resident model-parameter bytes per replica device of the "
    "LIVE pool (weight-quantized serving shrinks this ~4x for int8, "
    "2x for bf16 — docs/SERVING.md \"Quantized serving\")")

#: batch-queue sentinel, one per live replica at shutdown
_STOP = object()

#: monotonic pool tags scoping memory-ledger entities — two pools
#: coexist during a hot swap, so role alone cannot name residency
_POOL_SEQ = itertools.count()

#: replica lifecycle states (the serving_replica_state vocabulary)
_UP, _QUARANTINED, _RETIRED = "up", "quarantined", "retired"


def zero_pool_gauges():
    """Zero every pool gauge — a TRULY closed server has nothing up,
    nothing awaiting respawn, nothing newly retired. Called by a live
    pool's own close AND by the server's close epilogue: during a
    hot-swap rollback racing a close, the pool the server closes may
    already be demoted (role-gated zeroing skips it), so the server
    re-asserts gauge truth itself."""
    _m_replicas.set(0)
    _m_param_bytes.set(0)
    for s in (_UP, _QUARANTINED, _RETIRED):
        _m_state.set(0, state=s)


class Replica:
    """One worker: a device, resident params, per-bucket executables,
    and a thread draining the shared batch queue."""

    def __init__(self, index, device, params, executables, feed_names,
                 batch_queue, pool=None):
        self.index = index
        self.device = device
        self._params = params
        self._executables = executables
        self._feed_names = tuple(feed_names)
        self._q = batch_queue
        #: owning pool (None in direct unit-test construction) — the
        #: failure-attribution home: a batch failed HERE counts
        #: against THIS pool, which is what the hot-swap watchdog
        #: needs (the process-global error counter can't tell a new
        #: version's errors from the old pool's draining stragglers)
        self._pool = pool
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"serving-replica-{index}")
        self.batches_run = 0
        # -- supervisor-visible health stamps (the health.py heartbeat
        # idiom, in-process: the attribute stores below are the mtime
        # touches, written once per dispatch) --
        #: perf_counter at the current batch's pickup, None while idle
        #: — a non-None value older than replica_stall_ms is a wedged
        #: dispatch (the stale_ranks asymmetry: only a replica that
        #: STARTED a batch and stopped progressing is hung; idle is
        #: idle, however long)
        self.busy_since = None
        #: the in-flight micro-batch, so the supervisor can fail its
        #: riders if this thread is lost
        self.current = None
        #: set by the supervisor at quarantine: the thread must stop
        #: taking work the moment it can observe the flag (its slot is
        #: respawned; two drainers would race the queue)
        self._abandoned = False
        #: distinguishes a clean _STOP exit from a death — the
        #: supervisor must not quarantine a replica that shut down
        self._exited_clean = False

    def start(self):
        self._thread.start()
        return self

    def join(self, timeout=None):
        self._thread.join(timeout)

    def is_alive(self):
        return self._thread.is_alive()

    def _loop(self):
        while True:
            mb = self._q.get()
            if self._abandoned:
                # quarantined while blocked in get(): this slot
                # belongs to the respawn now — hand back WHATEVER was
                # grabbed and bow out. The _abandoned check must come
                # before the sentinel check: at close() sentinels are
                # enqueued one per LIVE replica, and an abandoned
                # thread consuming one would leave a live replica
                # blocked in get() forever (close joins it forever)
                self._q.put(mb)
                break
            if mb is _STOP:
                self._exited_clean = True
                break
            t0 = time.perf_counter()
            # heartbeat-per-dispatch: current BEFORE busy_since here,
            # current cleared first in _idle — the supervisor's
            # unlocked read pair (batch, then stamp, both non-None +
            # stale) is sound under those write orders
            self.current = mb
            self.busy_since = t0
            # trace stamps only (dispatch_wait ends / execute starts
            # here; fakes enqueued by tests may lack the slots): the
            # per-request spans assemble from these at tail-sampling
            # keep time, so the serving hot path pays attribute
            # stores, never span construction
            stamped = hasattr(mb, "t_pick")
            if stamped:
                mb.t_pick = t0
                mb.tid_replica = threading.get_ident()
                mb.replica = self.index
            # dispatch-wait deadline stage: riders that expired while
            # the batch sat in the queue get their typed error here,
            # and a batch with NO live rider never consumes a dispatch
            if hasattr(mb, "expire_riders") and \
                    mb.expire_riders(now=t0) == 0:
                self._idle()
                if self._abandoned:
                    break
                continue
            try:
                outs = self.run_batch(mb.bucket, mb.feeds)
            except Exception as e:
                # deliver the failure to the batch's requests and keep
                # serving: one poisoned batch must not kill the replica
                self._note_failure()
                mb.fail(e)
                self._idle()
                if self._abandoned:
                    break
                continue
            if stamped:
                mb.t_exec = time.perf_counter()
            try:
                mb.complete(outs)
            except Exception as e:
                # complete() itself failed (e.g. an executable returned
                # a wrong leading dim): sweep the undelivered requests
                # with the error (first-wins delivery) and keep serving
                self._note_failure()
                mb.fail(e)
                self._idle()
                if self._abandoned:
                    break
                continue
            self.batches_run += 1
            self._idle()
            _m_exec_ms.observe((time.perf_counter() - t0) * 1e3)
            if self._abandoned:
                break

    def _idle(self):
        self.current = None
        self.busy_since = None

    def _note_failure(self):
        if self._pool is not None:
            self._pool._note_batch_failures()

    def run_batch(self, bucket, feeds):
        """Execute one padded batch dict on this replica's executable
        for ``bucket``; returns host arrays in fetch order."""
        import jax
        exe = self._executables.get(bucket)
        enforce(exe is not None,
                f"replica {self.index} has no executable for bucket "
                f"{bucket} (ladder {sorted(self._executables)})")
        fd = tuple(jax.device_put(feeds[n], self.device)
                   for n in self._feed_names)
        try:
            outs = exe(self._params, fd)
            return [np.asarray(o) for o in outs]
        except Exception as e:
            from paddle_tpu.monitor import memory as _memory
            if _memory.is_oom_error(e):
                # typed postmortem instead of a raw RESOURCE_EXHAUSTED
                # traceback; flows through _loop's failure handling to
                # mb.fail, so riders see the attributed error
                _memory.handle_oom(e, f"serving.replica/bucket{bucket}")
            raise


class ReplicaPool:
    """N replicas over the visible devices (round-robin), all draining
    one shared bounded batch queue. Construction IS the warm boot:
    every (device, bucket) executable compiles before this returns.

    ``pure_fn`` is the jittable ``fn(params_tuple, feeds_tuple) ->
    outputs_tuple`` from ``inference._build_pure_fn``; ``params_np``
    the state arrays in its order; ``sample_specs`` {feed name:
    (sample_shape, dtype)} fixing every non-batch dim.

    Resilience knobs (docs/SERVING.md "Resilience"):
    ``replica_stall_ms`` — a dispatch running longer than this is a
    wedge (quarantine + respawn); ``max_consecutive_stalls`` — losses
    with no successful batch in between before the slot permanently
    retires; ``respawn_backoff_ms`` — base of the capped (5s)
    exponential respawn backoff; ``supervise=False`` disables the
    supervisor thread entirely (the pre-resilience pool).

    ``role`` makes two pools coexist for the hot model swap
    (docs/SERVING.md "Hot model swap"): only the ``"live"`` pool
    publishes the ``serving_replicas``/``serving_replica_state``
    gauges — a ``"standby"`` pool warm-boots and drains its own queue
    silently (its supervisor still heals it), and ``promote()``/
    ``demote()`` hand gauge ownership over at cutover. A demoted
    pool's ``close()`` never zeroes the gauges the new live pool now
    owns."""

    def __init__(self, pure_fn, params_np, feed_names, sample_specs,
                 ladder, n_replicas=1, devices=None, queue_depth=None,
                 replica_stall_ms=30_000.0, max_consecutive_stalls=3,
                 respawn_backoff_ms=100.0, supervise=True, role="live"):
        import jax
        from jax.sharding import SingleDeviceSharding

        enforce(n_replicas >= 1, f"n_replicas < 1 ({n_replicas})")
        enforce(replica_stall_ms > 0,
                f"replica_stall_ms must be positive, got "
                f"{replica_stall_ms!r}")
        enforce(max_consecutive_stalls >= 1,
                f"max_consecutive_stalls must be >= 1, got "
                f"{max_consecutive_stalls!r}")
        enforce(respawn_backoff_ms >= 0,
                f"respawn_backoff_ms must be >= 0, got "
                f"{respawn_backoff_ms!r}")
        enforce(role in ("live", "standby"),
                f"role must be 'live' or 'standby', got {role!r}")
        self.role = role
        self._feed_names = tuple(feed_names)
        self.ladder = tuple(ladder)
        devices = list(devices if devices is not None else jax.devices())
        enforce(devices, "no devices visible for serving")
        if queue_depth is None:
            # deep enough that the batcher never stalls behind an idle
            # replica, shallow enough that batches don't age in queue
            queue_depth = max(2 * n_replicas, 2)
        self.batch_queue = queue.Queue(maxsize=queue_depth)
        #: bytes of ONE device's resident param copy — int8/bf16
        #: quantized bundles land here ~4x/2x smaller than fp32, the
        #: replicas-per-device headroom the quantized export buys
        #: (bench.py serving BENCH_SERVING_QUANT A/B reads this)
        self._param_bytes = int(sum(np.asarray(p).nbytes
                                    for p in params_np))
        #: per-bucket CompiledMemoryStats (one device's — buckets
        #: compile identically per device); feeds projected_bytes()
        #: and the memory ledger
        self._bucket_mem = {}
        self._pool_tag = f"pool{next(_POOL_SEQ)}"
        self._ledger_entities = ()
        jitted = jax.jit(pure_fn)
        self._by_device = {}        # device -> (params, {bucket: exe})
        for dev in {devices[i % len(devices)]: None
                    for i in range(n_replicas)}:
            sh = SingleDeviceSharding(dev)
            params = tuple(jax.device_put(np.asarray(p), dev)
                           for p in params_np)
            param_sds = tuple(
                jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=sh)
                for p in params)
            exes = {}
            for bucket in self.ladder:
                feed_sds = tuple(
                    jax.ShapeDtypeStruct((bucket,) + tuple(shape),
                                         np.dtype(dtype), sharding=sh)
                    for shape, dtype in
                    (sample_specs[n] for n in self._feed_names))
                exes[bucket] = jitted.lower(param_sds,
                                            feed_sds).compile()
                if bucket not in self._bucket_mem:
                    try:
                        from paddle_tpu.monitor import memory as _memory
                        self._bucket_mem[bucket] = \
                            _memory.analyze_compiled(exes[bucket])
                    except Exception:
                        self._bucket_mem[bucket] = None
            self._by_device[dev] = (params, exes)
        self._ledger_publish()
        self._stopped = False
        #: True only after a TRUE close finished its final sweep — the
        #: dispatch() post-put sweep keys on it (see dispatch)
        self._closed_done = False
        #: batches this pool delivered as typed FAILURES (replica
        #: execution/complete errors, supervisor-failed in-flight
        #: batches, dead-pool/close sweeps) — per-pool attribution for
        #: the hot-swap watchdog; deadline expiries are load symptoms,
        #: not version faults, and don't count
        self.batch_failures = 0
        self._fail_lock = threading.Lock()
        self._stall_s = replica_stall_ms / 1e3
        self._max_stalls = int(max_consecutive_stalls)
        self._backoff_s = respawn_backoff_ms / 1e3
        self._lock = threading.Lock()
        self._slot_device = [devices[i % len(devices)]
                             for i in range(n_replicas)]
        self._states = [_UP] * n_replicas
        self._stall_counts = [0] * n_replicas
        self._respawn_due = {}          # slot -> monotonic due time
        self._live_at_close = []
        self._stops_pending = 0
        self._drained_dead_pool = False
        self.replicas = []
        for i in range(n_replicas):
            params, exes = self._by_device[self._slot_device[i]]
            self.replicas.append(Replica(
                i, self._slot_device[i], params, exes,
                self._feed_names, self.batch_queue, pool=self))
        for r in self.replicas:
            r.start()
        self._publish_states()
        self._sup_stop = threading.Event()
        self._supervisor = None
        if supervise:
            self._supervisor = threading.Thread(
                target=self._supervise, daemon=True,
                name="serving-supervisor")
            self._supervisor.start()

    # -- supervision -------------------------------------------------------
    def _publish_states(self):
        if self.role != "live":
            # a standby pool coexists with the live one during a hot
            # swap: publishing its counts would overwrite the live
            # pool's gauge truth with the not-yet-serving pool's
            return
        counts = {_UP: 0, _QUARANTINED: 0, _RETIRED: 0}
        for s in self._states:
            counts[s] += 1
        for s, c in counts.items():
            _m_state.set(c, state=s)
        # the supervisor owns gauge truth: serving_replicas is the
        # count actually draining the queue, not the count booted
        _m_replicas.set(counts[_UP])
        _m_param_bytes.set(self._param_bytes)

    def projected_bytes(self):
        """Per-device bytes this pool needs to co-reside: the worst
        bucket's compile-time peak estimate (params ride as arguments,
        so the estimate already covers them + feeds + temps + outputs)
        when the backend reported one, else the raw param bytes — the
        number swap admission projects BEFORE booting a standby."""
        peaks = [m.get("peak_bytes_estimate", 0.0)
                 for m in self._bucket_mem.values() if m]
        return int(max([self._param_bytes] + peaks))

    def _ledger_publish(self):
        """Attribute this pool's device residency in the memory
        ledger: params (summed across the pool's distinct devices) +
        each bucket executable's compile-time peak. Entities are
        scoped by the pool's own tag, NOT the role alone — during a
        hot swap two pools coexist and the ledger must show BOTH
        (that ~2x-param window is exactly what memory-aware admission
        guards). Never fatal — telemetry must not fail a boot or a
        cutover."""
        try:
            from paddle_tpu.monitor import memory as _memory
            self._ledger_drop()
            ndev = max(1, len(self._by_device))
            pre = f"serving/{self._pool_tag}:{self.role}"
            entities = {f"{pre}/params": self._param_bytes * ndev}
            for bucket, m in self._bucket_mem.items():
                if m:
                    entities[f"{pre}/bucket{bucket}"] = \
                        m.get("peak_bytes_estimate", 0.0)
            for e, b in entities.items():
                _memory.ledger_set(e, b)
            self._ledger_entities = tuple(entities)
        except Exception:
            pass

    def _ledger_drop(self):
        try:
            from paddle_tpu.monitor import memory as _memory
            for e in getattr(self, "_ledger_entities", ()):
                _memory.ledger_remove(e)
            self._ledger_entities = ()
        except Exception:
            pass

    def promote(self):
        """Standby -> live at hot-swap cutover: take gauge ownership
        and publish this pool's current states (flip and publish under
        the pool lock — see ``demote`` for why the serialization
        matters)."""
        with self._lock:
            self.role = "live"
            self._publish_states()
            self._ledger_publish()

    def demote(self):
        """Live -> draining-out at hot-swap cutover (or rollback of a
        freshly promoted standby): stop publishing gauges — the other
        pool owns them now — while the replicas keep draining whatever
        batches were already dispatched here. Taken under the pool
        lock so a supervisor mid-``_publish_states`` finishes BEFORE
        the role flips: an unserialized flip would let this pool's
        in-flight publish land after the new owner's, leaving the
        gauges describing the demoted pool until its next (never)
        state change."""
        with self._lock:
            self.role = "standby"
            # its residency is still real until release(): re-attribute
            # under the draining role rather than vanish from the ledger
            self._ledger_publish()

    def release(self):
        """Drop the device-resident param copies and executable maps
        after a TRUE close — the hot swap's ~2x-param-memory window
        ends here, when the drained old pool lets go. A released pool
        cannot respawn; only call once close() returned True."""
        self._ledger_drop()
        self._by_device.clear()
        for r in self.replicas:
            r._params = ()
            r._executables = {}

    def _supervise(self):
        """Detect wedged/dead replicas, quarantine, respawn (capped
        exponential backoff), retire after repeated stalls — and while
        the pool has NO live replica, drain the batch queue and fail
        riders so an accepted request can never hang on a dead pool."""
        poll = max(min(0.05, self._stall_s / 4.0), 0.005)
        while not self._sup_stop.wait(poll):
            now = time.perf_counter()
            mono = time.monotonic()
            to_fail = []            # (micro-batch, error) outside lock
            with self._lock:
                if self._stopped:
                    break
                for i, r in enumerate(self.replicas):
                    st = self._states[i]
                    if st == _QUARANTINED:
                        if mono >= self._respawn_due.get(i,
                                                         float("inf")):
                            self._respawn_locked(i)
                        continue
                    if st != _UP:
                        continue
                    if r.batches_run > 0 and self._stall_counts[i]:
                        # a batch has completed since the last loss:
                        # the stall streak is broken, the slot earned
                        # its consecutive-count back
                        self._stall_counts[i] = 0
                    if not r.is_alive() and not r._exited_clean:
                        to_fail.append(self._lose_locked(
                            i, r, "thread died by uncaught exception"))
                    elif r.busy_since is not None and \
                            now - r.busy_since > self._stall_s:
                        # re-validate before acting: the replica holds
                        # no pool lock, so between the check above and
                        # here it may have FINISHED the judged dispatch
                        # (and even picked a fresh batch). _loop's
                        # write orders (current before busy_since on
                        # pickup; current cleared before busy_since on
                        # idle) make this read pair sound: a fresh or
                        # ended dispatch shows a young/None busy_since
                        # or a None batch, and quarantining then would
                        # fail a HEALTHY batch's riders with spurious
                        # ReplicaLostError
                        mb = r.current
                        t2 = r.busy_since
                        if mb is not None and t2 is not None and \
                                now - t2 > self._stall_s:
                            to_fail.append(self._lose_locked(
                                i, r,
                                f"wedged mid-dispatch (> "
                                f"{self._stall_s * 1e3:.0f}ms)",
                                mb=mb))
                dead_pool = all(s == _RETIRED for s in self._states)
            for mb, exc in to_fail:
                if mb is not None and hasattr(mb, "fail"):
                    self._note_batch_failures()
                    mb.fail(exc)
            if dead_pool:
                self._drain_dead_pool()

    def _lose_locked(self, i, r, cause, mb=None):
        """Quarantine slot ``i`` (or retire it after max consecutive
        stalls); returns (in-flight batch, error) for the caller to
        fail OUTSIDE the pool lock. ``mb`` pins the judged batch for
        the stall path (re-validated by the caller); the dead-thread
        path reads whatever the corpse last held."""
        r._abandoned = True
        if mb is None:
            mb = r.current
        self._stall_counts[i] += 1
        cons = self._stall_counts[i]
        retire = cons >= self._max_stalls
        self._states[i] = _RETIRED if retire else _QUARANTINED
        if retire:
            up = sum(1 for s in self._states if s == _UP)
            _log(f"replica {i} {cause}; PERMANENTLY RETIRED after "
                 f"{cons} consecutive losses with no completed batch "
                 f"— pool shrinks to {up} live replica(s)"
                 + ("" if up else
                    " (ZERO live replicas: queued batches will be "
                    "failed, not hung — restart the server)"))
        else:
            backoff = min(self._backoff_s * (2 ** (cons - 1)), 5.0)
            self._respawn_due[i] = time.monotonic() + backoff
            _log(f"replica {i} {cause}; quarantined "
                 f"(consecutive losses: {cons}/{self._max_stalls}), "
                 f"failing its in-flight batch, respawn in "
                 f"{backoff * 1e3:.0f}ms")
        self._publish_states()
        exc = ReplicaLostError(
            f"serving replica {i} {cause}; its in-flight micro-batch "
            f"was failed by the pool supervisor and the replica was "
            f"{'retired' if retire else 'quarantined for respawn'} — "
            f"the request is safe to retry")
        return mb, exc

    def _note_batch_failures(self, n=1):
        with self._fail_lock:
            self.batch_failures += n

    def _respawn_locked(self, i):
        self._respawn_due.pop(i, None)
        dev = self._slot_device[i]
        params, exes = self._by_device[dev]     # warm: never recompiles
        nr = Replica(i, dev, params, exes, self._feed_names,
                     self.batch_queue, pool=self)
        self.replicas[i] = nr
        self._states[i] = _UP
        nr.start()
        _m_respawns.inc()
        self._publish_states()
        _log(f"replica {i} respawned against the warm executable map")

    def _fail_queued(self, why):
        """Drain the batch queue non-blocking, failing every rider
        with a typed ReplicaLostError — the shared no-hang backstop
        for a dead pool and for shutdown."""
        while True:
            try:
                mb = self.batch_queue.get_nowait()
            except queue.Empty:
                return
            if mb is not _STOP and hasattr(mb, "fail"):
                self._note_batch_failures()
                mb.fail(ReplicaLostError(why))

    def _drain_dead_pool(self):
        """Every slot retired: nothing will ever drain the batch
        queue, so the supervisor does — failing riders typed instead
        of letting accepted requests hang forever."""
        if not self._drained_dead_pool:
            self._drained_dead_pool = True
            _log("serving pool has ZERO live replicas; the supervisor "
                 "is draining the batch queue and failing riders")
        self._fail_queued(
            "serving pool has no live replicas (every slot "
            "permanently retired); the batch was failed without "
            "dispatch — restart the server")

    # -- dispatch ----------------------------------------------------------
    def dispatch(self, micro_batch):
        """The scheduler's dispatch target: blocking put, so a saturated
        pool backpressures the batcher (and through it the bounded
        request queue) instead of queueing unboundedly. The post-put
        sweep closes the hot-swap cutover's one standing race: the
        batcher can load THIS pool's dispatch, be descheduled, and put
        only after a committed swap's background drain fully closed
        the pool — nothing would ever consume that batch, so its
        riders would hang. If the pool is truly stopped, the batch is
        failed typed right here (first-wins delivery makes a double
        sweep harmless); the in-close window is covered by close()'s
        OWN final sweep, which runs after ``_closed_done`` is set."""
        self.batch_queue.put(micro_batch)
        if self._closed_done:
            self._fail_queued(
                "serving pool was already closed when this batch was "
                "dispatched (hot-swap drain completed); the batch was "
                "failed without dispatch — the request is safe to "
                "retry")

    def resident_param_bytes(self):
        """Bytes of one device-resident param copy (every replica
        device holds one) — the quantized-serving A/B's memory
        evidence."""
        return self._param_bytes

    def executables(self, device=None):
        """{bucket: executable} for ``device`` (default: first replica's
        device) — warm-boot introspection for tests and doctors."""
        if device is None:
            device = self.replicas[0].device
        return dict(self._by_device[device][1])

    def _judge_losses_at_close(self):
        """The supervisor is stopped for the whole close phase, so the
        drain carries its own loss handling ("no accepted request ever
        hangs" includes shutdown): a replica wedged past the stall
        threshold is failed+abandoned (never waited on), and one whose
        thread died mid-drain has its in-flight batch failed. Returns
        the replicas still draining."""
        now = time.perf_counter()
        remaining = []
        for r in self._live_at_close:
            if r._abandoned:
                continue
            if not r.is_alive():
                if not r._exited_clean and r.current is not None \
                        and hasattr(r.current, "fail"):
                    self._note_batch_failures()
                    r.current.fail(ReplicaLostError(
                        f"serving replica {r.index} thread died "
                        f"during shutdown with this batch in flight; "
                        f"the batch was failed — the request is safe "
                        f"to retry"))
                continue
            mb, t = r.current, r.busy_since
            if mb is not None and t is not None \
                    and now - t > self._stall_s:
                r._abandoned = True
                if hasattr(mb, "fail"):
                    self._note_batch_failures()
                    mb.fail(ReplicaLostError(
                        f"serving replica {r.index} wedged "
                        f"mid-dispatch during shutdown; its in-flight "
                        f"batch was failed — the request is safe to "
                        f"retry"))
                continue
            remaining.append(r)
        return remaining

    def close(self, timeout=None):
        """Stop every live replica after the in-queue batches drain.
        Returns True when every live replica has exited; with a
        ``timeout``, False means some replica is still finishing (its
        batches will complete — call again). The gauge only zeroes on
        a TRUE stop. Idempotent — sentinels are budgeted once, for the
        replicas LIVE at first close. The drain is a poll loop, not a
        bare join: the supervisor is already stopped, so close itself
        must keep judging losses (a replica that wedges past
        ``replica_stall_ms`` or dies MID-DRAIN gets its riders failed
        and stops gating the close), and sentinels are enqueued
        non-blocking as capacity appears — a blocking put on a queue
        whose only consumers are lost would ignore ``timeout``
        forever."""
        if not self._stopped:
            self._sup_stop.set()
            with self._lock:
                self._stopped = True
                self._live_at_close = [
                    r for i, r in enumerate(self.replicas)
                    if self._states[i] == _UP]
                self._stops_pending = len(self._live_at_close)
            if self._supervisor is not None:
                self._supervisor.join(5)
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        while True:
            remaining = self._judge_losses_at_close()
            while self._stops_pending > 0:
                try:
                    self.batch_queue.put_nowait(_STOP)
                except queue.Full:
                    break
                self._stops_pending -= 1
            if not remaining:
                # no consumer left to need a sentinel: drained (or
                # every drainer lost — the sweep below covers both)
                self._stops_pending = 0
                break
            if deadline is not None and time.monotonic() >= deadline:
                return False
            time.sleep(0.005)
        # true stop: nothing will ever drain the queue again. Set the
        # flag BEFORE the final sweep so a dispatch racing this close
        # either lands before the sweep (swept here) or sees the flag
        # and sweeps itself — either way its riders get a typed error,
        # never silence.
        self._closed_done = True
        self._ledger_drop()
        self._fail_queued(
            "serving pool closed with this batch undispatched (no "
            "live replica remained to run it)")
        if self.role == "live":
            # gauge truth on the way out: a closed pool has nothing
            # up, nothing awaiting respawn, nothing newly retired — a
            # stale {quarantined}=1 on a dead server would read as a
            # respawn that can never come. A DEMOTED pool draining out
            # after a hot-swap cutover skips this: the promoted pool
            # owns the gauges now.
            zero_pool_gauges()
        return True
