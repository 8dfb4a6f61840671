"""The start-up timeline: the compile log of ``core/compile_cache.py`` and the
spans ``profiler`` keeps with no profile running, on one clock.

jax publishes the start and end of every trace, lowering and backend compile
with the function's name; the log folds a record's children into it on
arrival, so it stays at about one record of each kind a program, and answers
in seconds (unions of intervals), counts and self seconds by function.
``profiler.startup()`` puts it beside the process's start, the package's
``startup/import`` and the trainers' ``trainer/init``. chipbench's
``setup_*`` per-layer metrics read all of it (``chipbench/setup_profile.py``).
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache
from paddle_tpu.models import bert
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "warm_restart_worker.py")
#: jax stamps with time.time(), the log converts once: a reading of one
#: clock against a pair of the other's agrees to well under this
CLOCKS_AGREE_S = 2e-3


@pytest.fixture(autouse=True)
def listening():
    compile_cache._ensure_listener()


def _named(name, body):
    body.__name__ = body.__qualname__ = name
    return body


def _records(kind, fun_name, since):
    return [r for r in compile_cache.records(kind)
            if r.fun_name == fun_name and r.start >= since - CLOCKS_AGREE_S]


def test_a_jitted_function_leaves_one_record_of_each_kind_on_perf_counter():
    x = jnp.ones((4, 4))
    toy = jax.jit(_named("timeline_toy", lambda x: jnp.sin(x) * 2 + 1))
    t0 = time.perf_counter()
    toy(x).block_until_ready()
    t1 = time.perf_counter()
    found = {}
    for kind, name in (("trace", "timeline_toy"),
                       ("lower", "jit(timeline_toy)"),
                       ("backend", "jit(timeline_toy)")):
        record, = _records(kind, name, t0)
        assert record.kind == kind
        assert t0 - CLOCKS_AGREE_S <= record.start <= record.end \
            <= t1 + CLOCKS_AGREE_S
        assert record.names[name][0] == 1
        found[kind] = record
    trace, lower, backend = (found[kind] for kind in compile_cache.KINDS)
    assert trace.end <= lower.start + CLOCKS_AGREE_S
    assert lower.end <= backend.start + CLOCKS_AGREE_S
    # the wrapped jax.numpy functions traced through are in its table, and
    # are no records of their own
    assert {"sin", "multiply", "add"} <= set(trace.names)
    assert not _records("trace", "sin", t0)

    # the same shape again: nothing is traced, lowered or compiled
    before = {kind: len(compile_cache.records(kind))
              for kind in compile_cache.KINDS}
    toy(x).block_until_ready()
    assert before == {kind: len(compile_cache.records(kind))
                      for kind in compile_cache.KINDS}
    account = compile_cache.reduce(since=t1)
    assert account["programs"] == 0 and account["trace_lower_s"] == 0


def test_a_function_traced_inside_another_counts_once_and_self_sums_to_union():
    inner = jax.jit(_named("timeline_inner", lambda x: jnp.tanh(x) @ x))
    outer = jax.jit(_named("timeline_outer",
                           lambda x: inner(x) + inner(x * 2).sum()))
    x = jnp.ones((8, 8))
    t0 = time.perf_counter()
    outer(x).block_until_ready()
    t1 = time.perf_counter()
    record, = _records("trace", "timeline_outer", t0)
    assert not _records("trace", "timeline_inner", t0)
    calls, inner_self = record.names["timeline_inner"]
    assert calls >= 1 and 0 < inner_self < record.end - record.start
    assert 0 < record.names["timeline_outer"][1] < record.end - record.start
    assert sum(self_s for _calls, self_s in record.names.values()) == \
        pytest.approx(record.end - record.start, abs=1e-9)

    account = compile_cache.reduce(since=t0, until=t1, top=1000)
    assert 0 < account["trace_s"] <= t1 - t0
    assert account["trace_lower_s"] <= account["trace_s"] \
        + account["lower_s"] + 1e-9
    began = [r for kind in ("trace", "lower")
             for r in compile_cache.records(kind) if t0 <= r.start < t1]
    assert sum(self_s for _n, _c, self_s in account["by_self_s"]) == \
        pytest.approx(sum(r.end - r.start for r in began), abs=1e-9)
    assert account["trace_s"] + account["lower_s"] == \
        pytest.approx(sum(r.end - r.start for r in began), abs=1e-9)
    assert "timeline_outer" in {name for name, _c, _s in account["by_self_s"]}


def test_reduce_cuts_records_to_the_interval_asked_for():
    log = compile_cache.CompileLog()
    log.add("trace", "f", 10.0, 12.0)
    log.add("lower", "jit(f)", 11.5, 13.0)     # overlaps the trace
    log.answer("requests")
    log.answer("hits")
    log.retrieved(0.25)
    log.add("backend", "jit(f)", 13.0, 14.0)
    log.add("backend", "jit(g)", 20.0, 21.0)   # the cache was not asked
    whole = log.reduce()
    assert whole["trace_s"] == 2.0 and whole["lower_s"] == 1.5
    assert whole["trace_lower_s"] == 3.0       # the union: 10 to 13
    assert whole["backend_s"] == 2.0 and whole["retrieval_s"] == 0.25
    assert (whole["programs"], whole["requests"], whole["hits"],
            whole["misses"]) == (2, 1, 1, 0)
    assert whole["compiled"] == [["jit(f)", 13.0, 1.0, "hit"],
                                 ["jit(g)", 20.0, 1.0, None]]
    assert log.counts == {"hits": 1, "misses": 0, "requests": 1}
    cut = log.reduce(since=11.0, until=13.5)
    assert cut["trace_s"] == 1.0 and cut["lower_s"] == 1.5
    assert cut["trace_lower_s"] == 2.0 and cut["backend_s"] == 0.5
    assert cut["programs"] == 1 and cut["hits"] == 1
    # by function: of the records that began in the interval
    assert cut["by_self_s"] == [["jit(f)", 1, 1.5]]
    assert log.reduce(since=14.0)["compiled"] == [["jit(g)", 20.0, 1.0, None]]


def test_the_log_is_bounded_and_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(compile_cache, "MAX_RECORDS", 4)
    monkeypatch.setattr(compile_cache, "MAX_NAMES", 3)
    log = compile_cache.CompileLog()
    for i in range(10):
        log.add("trace", f"f{i}", float(i), i + 0.5)
    assert [r.fun_name for r in log.records["trace"]] == \
        ["f6", "f7", "f8", "f9"]
    assert log.dropped == {"records": 6, "names": 0}
    # a parent over the four: three names fit its table, the rest are summed
    log.add("trace", "parent", 5.5, 10.0)
    parent, = log.records["trace"]
    assert set(parent.names) == {"parent", "f9", "f8", compile_cache.OTHER_NAMES}
    assert parent.names[compile_cache.OTHER_NAMES][0] == 2
    assert log.dropped["names"] == 2
    assert sum(s for _c, s in parent.names.values()) == pytest.approx(4.5)
    assert log.reduce()["dropped"] == log.dropped


def _run_worker(prefix, cache_dir):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env[compile_cache.ENV_VAR] = str(cache_dir)
    env.pop("PADDLE_RESTART_COUNT", None)
    done = subprocess.run([sys.executable, WORKER, str(prefix), "2"],
                          env=env, capture_output=True, text=True,
                          timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    with open(f"{prefix}.inc0.json") as f:
        return json.load(f)


def test_a_second_process_reads_the_cache_and_stats_agree_with_the_log(
        tmp_path):
    """The worker of the warm-restart test, twice in turn through one cache
    directory: the first process's backend records say miss, the second's
    hit, and in both ``stats()`` is what the log counts."""
    first = _run_worker(tmp_path / "first", tmp_path / "cache")
    second = _run_worker(tmp_path / "second", tmp_path / "cache")
    for report in (first, second):
        log = report["log"]
        assert (log["hits"], log["misses"], log["requests"]) == \
            (report["hits"], report["misses"], report["requests"])
        assert log["programs"] >= log["requests"] > 0
        assert log["dropped"] == {"records": 0, "names": 0}
    assert first["misses"] > 0 and first["hits"] == 0
    assert second["hits"] == first["misses"] and second["misses"] == 0
    assert {c[3] for c in second["log"]["compiled"]} == {"hit"}
    assert second["log"]["retrieval_s"] > 0
    assert {c[0] for c in second["log"]["compiled"]} == \
        {c[0] for c in first["log"]["compiled"]}


def test_process_start_and_the_import_span_are_there_after_import():
    code = ("import json, time; t = time.perf_counter(); import paddle_tpu;"
            " from paddle_tpu import profiler;"
            " print(json.dumps([t, profiler.startup()]))")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=240)
    wall = time.perf_counter() - t0
    assert done.returncode == 0, done.stderr[-2000:]
    first_stamp, timeline = json.loads(done.stdout.strip().splitlines()[-1])
    (name, start, end), = timeline["spans"]
    assert name == "startup/import"
    assert first_stamp <= start < end <= timeline["until"]
    # the child's clock is this process's (CLOCK_MONOTONIC): it started
    # after this test took t0, to /proc's tick
    assert t0 - 0.02 <= timeline["process_start"] < first_stamp
    assert first_stamp - timeline["process_start"] < wall
    assert set(timeline["compile"]) >= {"trace_s", "lower_s", "backend_s",
                                        "requests", "hits", "misses"}


def test_process_start_is_none_where_proc_does_not_say(monkeypatch):
    import builtins

    def no_proc(path, *a, **kw):
        raise FileNotFoundError(path)
    monkeypatch.setattr(builtins, "open", no_proc)
    assert profiler.process_start() is None


def test_trainer_init_is_kept_with_no_profile_running_and_place_is_not():
    cfg = bert.bert_tiny()
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = bert.make_train_step(cfg, pt.optimizer.Adam(1e-3),
                                            mesh)
    profiler.reset_profiler()
    assert not profiler._active["on"]
    t0 = time.perf_counter()
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = bert.synthetic_batch(cfg, 4, 16, max_preds=4)
    loss, params, opt_state = step_fn(params, opt_state, batch)
    float(loss)
    t1 = time.perf_counter()
    names = [name for name, *_ in profiler._events.snapshot()]
    assert names == ["trainer/init"]
    (name, start, end), = profiler.startup()["spans"]
    assert name == "trainer/init" and t0 <= start < end <= t1
    # a reading taken before it began does not hold it
    assert profiler.startup(until=t0)["spans"] == []
    # while profiling the step's spans reach the ring, as before
    profiler.start_profiler()
    try:
        step_fn(params, opt_state, batch)
    finally:
        profiler.stop_profiler()
    names = [name for name, *_ in profiler._events.snapshot()]
    assert names.count("trainer/place") == 1
    assert names.count("trainer/enqueue") == 1
    profiler.reset_profiler()


def test_summary_prints_the_timeline_and_names_what_compiled_last():
    late = jax.jit(_named("timeline_late", lambda x: x * 3 - 1))
    late(jnp.ones(3)).block_until_ready()
    report = profiler.summary()
    assert "start-up: process start " in report
    assert "compile log: tracing " in report
    for word in ("lowering", "backend", "requests", "hits", "misses"):
        assert word in report
    assert "most self seconds of trace + lowering: " in report
    last = [line for line in report.splitlines()
            if line.startswith("  last compiled: ")]
    assert last and "jit(timeline_late) at " in last[0]
