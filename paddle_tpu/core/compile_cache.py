"""Persistent XLA compilation cache: where it lives, and whether it hit.

jax keeps compiled programs on disk once ``jax_compilation_cache_dir`` is
set, so a second process (a restarted worker, the next chip call)
compiles by reading a file. The directory is part of every entry's key:
a cache that moves never hits. Hence one rule for where it lives:

- ``JAX_COMPILATION_CACHE_DIR`` set — jax's own handling of that
  variable stands and nothing here sets a directory. Whoever runs the
  program places the cache.
- not set — the entry points that run on the chip (``chip_smoke.py``,
  ``bench.py``, launcher workers) call :func:`enable`, which uses ONE
  fixed path inside the checkout, :data:`DEFAULT_DIR` (git-ignored).
  Never a path built from a temp dir, a pid or the clock.

``import paddle_tpu`` calls :func:`enable` when the variable is set (the
launcher exports it to its workers), so a restarted rank's compiles hit
the previous incarnation's entries. :func:`enable` also zeroes jax's
"only cache slow/large compiles" thresholds, makes the operations' names
and source lines part of the key (so a cached executable never brings
another program's names into a profile; the price is a recompile when
only a line or the call path moved) and registers the listeners behind
:func:`stats`; ``paddle_tpu.profiler`` prints those counters, so a warm
start is verifiable (hits > 0).

**The compile log.** Those listeners keep one bounded record of what jax
did, :class:`CompileLog`: for every trace, lowering and backend compile
(the compile itself, or the read from the cache) a :class:`Record`
``(kind, fun_name, start, end)`` on ``time.perf_counter``'s clock, the
clock of ``profiler.RecordEvent``; a backend record also holds the cache's
answer and the seconds the read took. jax publishes a trace event for every
wrapped ``jax.numpy`` function it traces through (3670 for one BERT-base
step), nested in the event of the jitted function around them, so a record
is folded on arrival: the records of its kind at the log's tail that
started inside it are its children, their tables of (calls, self seconds)
by ``fun_name`` become part of its own, and only the outer interval stays:
about one trace, one lowering and one backend record a program, and one
trace record for every ``jax.numpy`` function traced with no jitted
function around it (under ``jax.eval_shape``: the random bits of every
leaf of a model's initialisation, 4211 records in a BERT-base cell's
set-up; PERF.md, PR 35).
:func:`stats` is that record's count of the cache's answers;
:func:`reduce` gives, for any interval, the seconds tracing, lowering and
in the backend, each as the union of its intervals, the requests, hits and
misses, and the functions by self seconds. Always on once a listener is
registered; no switch. A callback costs about 1.5 microseconds in the
sandbox over the 0.6 jax spends publishing the event (PERF.md, PR 35).
"""

import collections
import os
import threading
import time

from paddle_tpu.monitor.registry import counter as _counter

__all__ = ["enable", "disable", "is_enabled", "cache_dir", "stats",
           "reset_stats", "reduce", "records", "Record", "CompileLog",
           "KINDS", "ENV_VAR", "DEFAULT_DIR"]

#: jax's own variable; read by jax at import, never written here
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache — the cache's home when nobody placed it
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_state = {"enabled": False, "listening": False, "clock_offset": 0.0}

# registry mirrors of the log's three counts, so /metrics and the per-rank
# snapshots carry warm-restart evidence too: CompileLog.answer moves both
_m_counters = {
    "hits": _counter("compile_cache_hits_total",
                     "XLA compiles served from the persistent "
                     "compilation cache (disk)"),
    "misses": _counter("compile_cache_misses_total",
                       "XLA compiles that missed the persistent cache "
                       "and compiled for real"),
    "requests": _counter("compile_cache_requests_total",
                         "Compile requests eligible for the persistent "
                         "cache"),
}

# the last part of a jax monitoring event's name -> what the log calls it
# (the full names are '/jax/compilation_cache/cache_hits',
# '/jax/core/compile/jaxpr_trace_duration' etc.; matched by the last part
# so a jax upgrade that re-roots the namespace keeps counting)
_ANSWERS = {
    "cache_hits": "hits",
    "cache_misses": "misses",
    "compile_requests_use_cache": "requests",
}
_CACHE_SAID = {"requests": "asked", "hits": "hit", "misses": "miss"}
KINDS = ("trace", "lower", "backend")
_SPANS = {
    "jaxpr_trace_duration": "trace",
    "jaxpr_to_mlir_module_duration": "lower",
    "backend_compile_duration": "backend",
}
_RETRIEVAL = "cache_retrieval_time_sec"

#: the log's bounds: records kept a kind (the oldest go first), and names in
#: one record's table (the rest are summed under OTHER_NAMES). 2048 records
#: dropped 2163 of a BERT-base cell's set-up (my chip run, PR 35)
MAX_RECORDS = 16384
MAX_NAMES = 256
OTHER_NAMES = "<other>"

#: One interval of compile activity, ``start`` and ``end`` on
#: ``time.perf_counter``. ``names`` is {fun_name: [calls, self seconds]} of
#: the record and of the records of its kind folded into it. A backend
#: record's ``cache`` is "hit", "miss", "asked" (the cache was asked and
#: reported neither) or None (it was not asked); ``retrieval_s`` the
#: seconds a hit took to read.
Record = collections.namedtuple(
    "Record", "kind fun_name start end names cache retrieval_s")


def _union(intervals):
    """Sorted disjoint [start, end] lists covering ``intervals``."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


class CompileLog:
    """The one record of compile activity (module docstring). Every method
    takes the module's lock: jax calls the listeners from whichever thread
    traces or compiles. The fold takes a record's children from the tail
    by their start alone, so two threads that compile at once may fold
    each other's records; the unions and the counts do not depend on it."""

    def __init__(self):
        self.records = {kind: collections.deque() for kind in KINDS}
        self.counts = dict.fromkeys(_ANSWERS.values(), 0)
        self.dropped = {"records": 0, "names": 0}
        self._asked = threading.local()

    def answer(self, key):
        """The cache was asked ("requests") or answered ("hits", "misses")
        for the compile in flight on this thread."""
        with _lock:
            self.counts[key] += 1
        _m_counters[key].inc()
        self._asked.cache = _CACHE_SAID[key]

    def retrieved(self, seconds):
        self._asked.retrieval_s = seconds

    def add(self, kind, fun_name, start, end):
        cache = retrieval_s = None
        if kind == "backend":
            cache = getattr(self._asked, "cache", None)
            retrieval_s = getattr(self._asked, "retrieval_s", None)
            self._asked.cache = self._asked.retrieval_s = None
        names = {fun_name: [1, end - start]}
        with _lock:
            tail = self.records[kind]
            while tail and tail[-1].start >= start:
                child = tail.pop()
                names[fun_name][1] -= child.end - child.start
                for name, (calls, self_s) in child.names.items():
                    if name not in names and len(names) >= MAX_NAMES:
                        self.dropped["names"] += 1
                        name = OTHER_NAMES
                    row = names.setdefault(name, [0, 0.0])
                    row[0] += calls
                    row[1] += self_s
            tail.append(Record(kind, fun_name, start, end, names, cache,
                               retrieval_s))
            if len(tail) > MAX_RECORDS:
                tail.popleft()
                self.dropped["records"] += 1

    def reduce(self, since=None, until=None, top=5):
        """What the log holds of [since, until), either end open where
        None. Seconds are of each kind's records cut to the interval, as
        the union of what is left (``trace_lower_s``: of both kinds
        together); counts and ``by_self_s`` are of the records that began
        in it. ``by_self_s``: the ``top`` functions by self seconds of
        trace + lowering, [fun_name, calls, self seconds]; ``compiled``:
        the backend records, [fun_name, start, seconds, cache]."""
        lo = float("-inf") if since is None else since
        hi = float("inf") if until is None else until
        with _lock:
            kept = {kind: [r for r in self.records[kind]
                           if r.end > lo and r.start < hi]
                    for kind in KINDS}
            dropped = dict(self.dropped)

        def seconds(*kinds):
            return sum(e - s for s, e in _union(
                (max(r.start, lo), min(r.end, hi))
                for kind in kinds for r in kept[kind]))

        began = {kind: [r for r in kept[kind] if r.start >= lo]
                 for kind in KINDS}
        table = {}
        for kind in ("trace", "lower"):
            for r in began[kind]:
                for name, (calls, self_s) in r.names.items():
                    row = table.setdefault(name, [0, 0.0])
                    row[0] += calls
                    row[1] += self_s
        backend = began["backend"]
        return {
            "trace_s": seconds("trace"), "lower_s": seconds("lower"),
            "trace_lower_s": seconds("trace", "lower"),
            "backend_s": seconds("backend"),
            "retrieval_s": sum(r.retrieval_s or 0.0 for r in backend),
            "programs": len(backend),
            "requests": sum(r.cache is not None for r in backend),
            "hits": sum(r.cache == "hit" for r in backend),
            "misses": sum(r.cache == "miss" for r in backend),
            "by_self_s": [[name, calls, self_s] for name, (calls, self_s)
                          in sorted(table.items(),
                                    key=lambda kv: -kv[1][1])[:top]],
            "compiled": [[r.fun_name, r.start, r.end - r.start, r.cache]
                         for r in backend],
            "dropped": dropped,
        }


_log = CompileLog()


def _on_event(event, **kw):
    key = _ANSWERS.get(event.rsplit("/", 1)[-1])
    if key is not None:
        _log.answer(key)


def _on_duration(event, duration, **kw):
    if event.endswith(_RETRIEVAL):
        _log.retrieved(duration)


def _on_span(event, start, end, fun_name="", **kw):
    kind = _SPANS.get(event.rsplit("/", 1)[-1])
    if kind is not None:
        offset = _state["clock_offset"]
        _log.add(kind, fun_name, start + offset, end + offset)


def _ensure_listener():
    # idempotent: one set of listeners per process
    with _lock:
        if _state["listening"]:
            return
        _state["listening"] = True
        # jax stamps its spans with time.time(); the log is on
        # perf_counter, the clock of profiler.RecordEvent: converted by
        # the distance between the two, read once, here
        _state["clock_offset"] = time.perf_counter() - time.time()
    from jax._src import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_time_span_listener(_on_span)


def enable():
    """Turn the persistent compilation cache on, at the directory the
    module docstring's rule gives, and return that directory.
    Thresholds are zeroed so even sub-second programs cache — the win
    scales with compile time, and a tiny program costs one small file."""
    import jax
    if _mid_process():
        # once per process, not per enable(): retry loops and tests
        # re-enable freely and must not spam the log
        from paddle_tpu.core.enforce import warn_once
        warn_once(
            "compile_cache_mid_process",
            "compilation cache enabled mid-process: computations "
            "compiled before enable() were not cached (jax's one-shot "
            "cache state is reset so later compiles are)")
    if not os.environ.get(ENV_VAR):
        os.makedirs(DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # cache everything: the default 1s/0B floors exist to keep prod
    # caches small, but they would silently exclude the small programs
    # the warm-restart tests (and fast iteration loops) rely on
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # names are part of the key. jax strips debug info (op names, the
    # named scopes among them, source lines) from a module before it
    # hashes it, so a program that differs from a cached one only in its
    # scopes is a hit, and the executable it gets carries the OLD names
    # into every profile (tests/test_step_scopes.py shows it). What this
    # costs: the same program compiles again once a line of a traced
    # function moved, or when it is traced from another call site (the
    # locations hold the call stack). A restarted process takes the same
    # path through the same lines and still hits. What it buys: a
    # profile whose names are the running code's.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    _reset_jax_cache_state()
    _ensure_listener()
    with _lock:
        _state["enabled"] = True
    return cache_dir()


def _mid_process():
    """True when a jax backend already initialized — i.e. something may
    already have compiled, so this enable() is the 'mid-process' path
    whose earlier compiles the cache can never cover."""
    from jax._src import xla_bridge
    return bool(xla_bridge._backends)


def _reset_jax_cache_state():
    # jax initializes its cache object at most ONCE per process, at the
    # first compile — if anything compiled before enable()/disable()
    # flipped the dir, the one-shot init already latched (possibly to
    # "no cache") and the config change would silently do nothing.
    # reset_cache() returns it to pristine so the next compile re-reads
    # the config.
    from jax._src import compilation_cache as _cc
    _cc.reset_cache()


def disable():
    """Stop caching in this process (tests restore state with it)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache_state()
    with _lock:
        _state["enabled"] = False


def is_enabled():
    return _state["enabled"]


def cache_dir():
    """The directory in force (jax's own config value), or None."""
    if not _state["enabled"]:
        return None
    import jax
    return jax.config.jax_compilation_cache_dir


def stats():
    """{'hits', 'misses', 'requests'} since process start (or the last
    reset_stats): the log's count of the cache's answers. Hits mean an
    XLA compile was served from disk — a restarted worker with
    hits > 0 provably skipped recompilation."""
    with _lock:
        return dict(_log.counts)


def reduce(since=None, until=None, top=5):
    """:meth:`CompileLog.reduce` of this process's log."""
    return _log.reduce(since, until, top)


def records(kind):
    """The log's records of one of :data:`KINDS`, oldest first."""
    with _lock:
        return list(_log.records[kind])


def reset_stats():
    """Empty the log, its counts with it (tests start from zero)."""
    global _log
    _log = CompileLog()
