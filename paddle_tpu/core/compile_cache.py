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
only a line or the call path moved) and registers the listener behind :func:`stats`;
``paddle_tpu.profiler`` prints those counters, so a warm start is
verifiable (hits > 0).
"""

import os
import threading

from paddle_tpu.monitor.registry import counter as _counter

__all__ = ["enable", "disable", "is_enabled", "cache_dir", "stats",
           "reset_stats", "ENV_VAR", "DEFAULT_DIR"]

#: jax's own variable; read by jax at import, never written here
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache — the cache's home when nobody placed it
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_state = {"enabled": False, "listening": False}
_counters = {"hits": 0, "misses": 0, "requests": 0}

# registry mirrors of the jax-monitoring-fed counters, so /metrics and
# the per-rank snapshots carry warm-restart evidence too
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

# jax monitoring event suffixes -> our counter keys (the full names are
# '/jax/compilation_cache/cache_hits' etc.; matched by suffix so a jax
# upgrade that re-roots the namespace keeps counting)
_EVENT_MAP = {
    "cache_hits": "hits",
    "cache_misses": "misses",
    "compile_requests_use_cache": "requests",
}


def _on_event(event, **kw):
    key = _EVENT_MAP.get(event.rsplit("/", 1)[-1])
    if key is not None:
        with _lock:
            _counters[key] += 1
        _m_counters[key].inc()


def _ensure_listener():
    # idempotent: one listener per process
    with _lock:
        if _state["listening"]:
            return
        _state["listening"] = True
    from jax._src import monitoring
    monitoring.register_event_listener(_on_event)


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
    reset_stats). Hits mean an XLA compile was served from disk —
    a restarted worker with hits > 0 provably skipped recompilation."""
    with _lock:
        return dict(_counters)


def reset_stats():
    with _lock:
        for k in _counters:
            _counters[k] = 0
