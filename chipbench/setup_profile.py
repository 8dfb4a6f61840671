"""What a run's set-up was made of, from the program's own start-up timeline.

``setup_s`` is one pair of clock readings in ``run.py``. The program keeps
what lies between them (``paddle_tpu.profiler.startup``, since PR 35): when
the process started, the span ``startup/import`` (the package's own import,
first line to last), the spans ``trainer/init``, and the compile log of
``paddle_tpu.core.compile_cache``: one record for every trace, lowering and
backend compile (the compile, or the read from the cache) jax made, each with
its function's name, its start and end on ``time.perf_counter`` and, on a
backend record, the cache's answer.

``profile(facts)`` is what the six ``setup_*`` per-layer metrics call. Set-up
here is the time from the process's start to the window's start, which is the
first start among ``facts["spans"].records`` (the harness clears the warm-up's
spans, so that is the window's first ``next_batch``, on ``perf_counter_ns``).
It is cut into four parts that sum to it, each second counted once and given
to the first of these that holds it:

- ``import_s``: process start to the end of ``startup/import`` (the
  interpreter, ``import jax``, the harness's own imports, then the package);
- ``compile_s``: the union of the backend records after that;
- ``trace_lower_s``: the union of the trace and lowering records, less what
  the two above cover (Python, Mosaic lowering included);
- ``other_s``: the rest: the backend's start, the device running the
  initialisation, the probe, the reference and the warm-up, and the harness's
  own host work.

``programs`` counts the compile requests before the window that asked the
cache, ``cache_misses`` those of them that compiled for real. ``setup_s`` of
the same run is shorter than the four together by the time from the process's
start to ``run.py``'s first line.

Made once a run, kept in ``facts`` and printed as ``[setup]`` lines, among
them the five functions with most self seconds of trace + lowering. Where the
program keeps no such timeline (a tree from before PR 35), where ``/proc`` does
not give the process's start or where the window left no span, it returns
``None`` and so does every reader. It reads and compiles nothing.
"""

from chipbench import trace_reduce as tr

IMPORT_SPAN = "startup/import"
INIT_SPAN = "trainer/init"


def window_start(facts):
    """The window's first span's start, seconds on ``perf_counter``."""
    records = facts["spans"].records
    return min(start for _name, start, _end in records) / 1e9 \
        if records else None


def reduce(timeline, records, until):
    """The account of the module docstring from ``profiler.startup(until)``
    and the compile log's records by kind; None where a part is missing."""
    start = timeline["process_start"]
    spans = {}
    for name, t0, t1 in timeline["spans"]:
        spans.setdefault(name, []).append((t0, t1))
    if start is None or until is None or IMPORT_SPAN not in spans:
        return None
    package_t0, import_end = spans[IMPORT_SPAN][0]

    def before_window(*kinds):
        return tr.union((max(r.start, start), min(r.end, until))
                        for kind in kinds for r in records[kind]
                        if r.start < until and r.end > start)

    taken = [[start, import_end]]
    compiling = tr.subtract(before_window("backend"), taken)
    taken = tr.union(taken + compiling)
    tracing = tr.subtract(before_window("trace", "lower"), taken)
    parts = {"import_s": import_end - start,
             "compile_s": tr.total(compiling),
             "trace_lower_s": tr.total(tracing)}
    parts["other_s"] = until - start - sum(parts.values())
    log = timeline["compile"]
    first = min((r.start for kind in records for r in records[kind]
                 if r.start >= import_end), default=until)
    return {
        **parts, "total_s": until - start,
        "import_to_first_record_s": first - import_end,
        "before_package_s": package_t0 - start,
        "package_s": import_end - package_t0,
        "init_s": [t1 - t0 for t0, t1 in spans.get(INIT_SPAN, ())],
        "programs": log["requests"], "cache_misses": log["misses"],
        "cache_hits": log["hits"], "retrieval_s": log["retrieval_s"],
        "trace_s": log["trace_s"], "lower_s": log["lower_s"],
        "by_self_s": log["by_self_s"], "dropped": log["dropped"],
    }


def table(account, after):
    """The account as lines for a log; ``after``: the compile log's
    reduction from the window's start on."""
    a = account
    lines = [
        f"process start to the window {a['total_s']:.2f} s = import "
        f"{a['import_s']:.2f} (before the package's first line "
        f"{a['before_package_s']:.2f}, the package {a['package_s']:.2f}) + "
        f"trace and lowering {a['trace_lower_s']:.2f} + backend "
        f"{a['compile_s']:.2f} + other {a['other_s']:.2f}",
        f"the log's first record begins {a['import_to_first_record_s']:.2f} "
        f"s after the import's end: the backend's start and the harness's "
        f"work before the first program are in there",
        f"compile log before the window: tracing {a['trace_s']:.2f} s, "
        f"lowering {a['lower_s']:.2f} s; {a['programs']} requests, "
        f"{a['cache_hits']} hits ({a['retrieval_s']:.2f} s reading), "
        f"{a['cache_misses']} misses; dropped {a['dropped']}",
        f"{INIT_SPAN}: " + (" ".join(f"{s:.2f} s" for s in a["init_s"])
                            or "no such span"),
        "most self seconds of trace + lowering: " + ", ".join(
            f"{name} {self_s:.2f} s in {calls}"
            for name, calls, self_s in a["by_self_s"]),
    ]
    lines.append(
        f"from the window's start on: {after['programs']} programs ("
        + (", ".join(f"{name} {cache or 'cache not asked'} {seconds:.2f} s"
                     for name, _t0, seconds, cache in after["compiled"])
           or "none") + ")")
    return lines


def profile(facts):
    """The account of this run's set-up, or None (module docstring)."""
    if "setup_profile" in facts:
        return facts["setup_profile"]
    account = None
    until = window_start(facts)
    try:
        from paddle_tpu import profiler
        from paddle_tpu.core import compile_cache
        timeline = profiler.startup(until)
        records = {kind: compile_cache.records(kind)
                   for kind in compile_cache.KINDS}
    except (ImportError, AttributeError):
        print("[setup] the program keeps no start-up timeline", flush=True)
    else:
        account = reduce(timeline, records, until)
        if account is None:
            print("[setup] no process start, import span or window: no "
                  "account", flush=True)
        else:
            for line in table(account, compile_cache.reduce(since=until)):
                print(f"[setup] {line}", flush=True)
    facts["setup_profile"] = account
    return account


def part(facts, key):
    """``profile(facts)[key]``, or None where there is no account."""
    account = profile(facts)
    return None if account is None else account[key]
