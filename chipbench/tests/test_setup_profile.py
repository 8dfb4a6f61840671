"""``chipbench/setup_profile.py`` and the six ``setup_*`` readers: what a run's
set-up was made of, from the program's start-up timeline. On an account worked
out on paper; on hand-built ``facts`` (a ``Spans`` with one record, the
program's real log of a toy jit) in this process; and through the harness on
the CPU in a process of its own, where the four ``_s`` parts must close on
that run's ``setup_s`` and ``setup_programs`` on the requests it logs."""

import collections
import json
import os
import re
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import run, setup_profile
from chipbench.catalog import ROOT, Catalog
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache

FIXTURES = ROOT / "chipbench" / "tests" / "fixtures"
SIX = ["setup_import_s", "setup_trace_lower_s", "setup_compile_s",
       "setup_programs", "setup_cache_misses", "setup_other_s"]
SECONDS = [name for name in SIX if name.endswith("_s")]


def readers():
    return {name: Catalog().module("layer_metrics", name).metric
            for name in SIX}


def test_the_account_counts_every_second_once_and_sums_to_the_set_up():
    """Process start 100, the package imported 101 to 103, the window at 120.
    A trace that began inside the import, a lowering with a compile nested in
    it, a backend record across the window's start, one after it."""
    R = collections.namedtuple("R", "start end")
    records = {"trace": [R(102.5, 104.0), R(110.0, 111.0)],
               "lower": [R(104.0, 106.0)],
               "backend": [R(105.0, 105.5), R(119.0, 121.0), R(125.0, 126.0)]}
    timeline = {"process_start": 100.0,
                "spans": [["startup/import", 101.0, 103.0],
                          ["trainer/init", 107.0, 109.0]],
                "compile": {"requests": 2, "misses": 1, "hits": 1,
                            "retrieval_s": 0.1, "trace_s": 2.5,
                            "lower_s": 2.0, "by_self_s": [["f", 1, 2.0]],
                            "dropped": {"records": 0, "names": 0}}}
    a = setup_profile.reduce(timeline, records, 120.0)
    assert a["import_s"] == 3.0
    assert a["compile_s"] == 0.5 + 1.0            # cut at the window
    # 103 to 106 less the nested compile, and 110 to 111
    assert a["trace_lower_s"] == 2.5 + 1.0
    assert a["other_s"] == 20.0 - 3.0 - 1.5 - 3.5
    assert a["total_s"] == 20.0
    assert (a["before_package_s"], a["package_s"]) == (1.0, 2.0)
    assert a["init_s"] == [2.0]
    assert a["import_to_first_record_s"] == 1.0       # the lowering at 104
    assert (a["programs"], a["cache_misses"]) == (2, 1)
    lines = setup_profile.table(a, {"programs": 1, "compiled": [
        ["jit(late)", 125.0, 1.0, "miss"]]})
    assert "= import 3.00 (before the package's first line 1.00, the " \
        "package 2.00) + trace and lowering 3.50 + backend 1.50 + other " \
        "12.00" in lines[0]
    assert "trainer/init: 2.00 s" in lines
    assert lines[1].startswith("the log's first record begins 1.00 s after")
    assert lines[-1].endswith("1 programs (jit(late) miss 1.00 s)")
    for missing in ({**timeline, "process_start": None},
                    {**timeline, "spans": timeline["spans"][1:]}):
        assert setup_profile.reduce(missing, records, 120.0) is None
    assert setup_profile.reduce(timeline, records, None) is None


@pytest.fixture
def program_timeline():
    """The listeners on, and the package's import span in the ring (another
    test of the process may have emptied it)."""
    compile_cache._ensure_listener()
    if not any(name == setup_profile.IMPORT_SPAN
               for name, *_ in profiler.startup()["spans"]):
        start = profiler.process_start()
        profiler.record_span(setup_profile.IMPORT_SPAN, start + 0.5,
                             start + 1.0)


def named(name, body):
    body.__name__ = body.__qualname__ = name
    return body


def test_the_readers_read_what_the_log_holds_before_the_window(
        program_timeline, capsys):
    before = jax.jit(named("setup_toy_before", lambda x: jnp.cos(x) + 1))
    before(jnp.ones(5)).block_until_ready()
    spans = run.Spans()
    with spans.span("next_batch"):
        pass
    window = spans.records[0][1] / 1e9
    facts = {"spans": spans}
    first = {name: metric(facts) for name, metric in readers().items()}
    after = jax.jit(named("setup_toy_after", lambda x: jnp.cos(x) * 4))
    after(jnp.ones(5)).block_until_ready()
    with spans.span("step_call"):
        pass
    facts = {"spans": spans}
    values = {name: metric(facts) for name, metric in readers().items()}
    assert values == first                 # what came after is not counted
    assert all(v is not None and v >= 0 for v in values.values())
    assert sum(values[name] for name in SECONDS) == pytest.approx(
        window - profiler.process_start(), abs=1e-6)
    backend = [r for r in compile_cache.records("backend")
               if r.start < window]
    assert values["setup_programs"] == sum(r.cache is not None
                                           for r in backend)
    assert values["setup_cache_misses"] == sum(r.cache == "miss"
                                               for r in backend)
    assert "jit(setup_toy_before)" in {r.fun_name for r in backend}
    assert values["setup_compile_s"] > 0 and values["setup_trace_lower_s"] > 0
    out = capsys.readouterr().out
    # the lines are printed once a facts, by the first reader that asks
    assert len(re.findall(r"^\[setup\] process start to the window", out,
                          re.M)) == 2
    assert re.search(r"^\[setup\] from the window's start on: \d+ programs "
                     r"\(.*jit\(setup_toy_after\)", out, re.M)
    assert "setup_toy_after" not in out.split("from the window's start")[0]


def test_every_reader_returns_none_where_the_program_kept_no_log(
        program_timeline, monkeypatch, capsys):
    spans = run.Spans()
    assert [metric({"spans": spans}) for metric in readers().values()] == \
        [None] * 6                         # no span: no window
    with spans.span("next_batch"):
        pass
    monkeypatch.delattr(profiler, "startup")      # a tree from before PR 35
    assert [metric({"spans": spans}) for metric in readers().values()] == \
        [None] * 6
    assert "[setup] the program keeps no start-up timeline" in \
        capsys.readouterr().out
    monkeypatch.undo()
    monkeypatch.setattr(profiler, "process_start", lambda: None)
    assert [metric({"spans": spans}) for metric in readers().values()] == \
        [None] * 6


def test_the_readers_compile_nothing(program_timeline):
    """``run.read_metrics`` refuses a reader that made jax compile."""
    six = types.SimpleNamespace(
        metrics=lambda group, workload: [{"name": name} for name in SIX],
        module=Catalog().module)
    spans = run.Spans()
    with spans.span("next_batch"):
        pass
    values = run.read_metrics(six, "cell", {"spans": spans},
                              run.CompileCounter())
    assert set(values) == set(SIX)


def test_the_real_benchmark_file_lists_the_six_in_the_seven_cells():
    catalog = Catalog()
    cells = [w["name"] for w in catalog.spec["workloads"]][:7]
    for cell in cells:
        found = {m["name"]: m for m in catalog.metrics("per_layer", cell)}
        for name in SIX:
            assert found[name]["moves"] == "setup_s"
            assert found[name]["workloads"] == cells
            assert callable(catalog.module("layer_metrics", name).metric)
    moved = {m["moves"] for m in catalog.spec["per_layer"]
             if m["name"] not in SIX}
    assert moved == {"train_tokens_per_s"}
    # a cell that does not list itself there (the fixtures') reports none
    fixture = Catalog(FIXTURES / "benchmark.json")
    assert not {m["name"] for m in fixture.metrics(
        "per_layer", "bert_toy.mlm_toy")} & set(SIX)


def test_a_traced_run_closes_on_its_own_setup_s(tmp_path):
    """A fixture cell with the six listed for it, through the harness in a
    process of its own (``T0`` and the process's start are then as close as
    in a real run), cold: the four parts are within half a second of that
    run's ``setup_s``, ``setup_programs`` is the requests the harness logs
    after set-up and every one was compiled."""
    spec = json.loads((FIXTURES / "benchmark.json").read_text())
    spec["per_layer"] += [
        {**m, "workloads": ["bert_toy.mlm_toy"]}
        for m in Catalog().spec["per_layer"] if m["name"] in SIX]
    (tmp_path / "benchmark.json").write_text(json.dumps(spec))
    code = ("import sys; from chipbench import run; "
            "from chipbench.catalog import Catalog; "
            "run.main(sys.argv[2:], catalog=Catalog(sys.argv[1]), "
            "peaks={'cpu': {'bf16_flops_per_s': 1e12}})")
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    env[compile_cache.ENV_VAR] = str(tmp_path / "cache")
    t0 = time.perf_counter()
    done = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "benchmark.json"),
         "--workload", "bert_toy.mlm_toy", "--seed", "2147483900",
         "--seconds", "0.5", "--trace", "1"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=280)
    wall = time.perf_counter() - t0
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(SIX) <= set(values)
    setup_s = float(re.search(r"set-up ([0-9.]+) s", done.stdout).group(1))
    parts = sum(values[name] for name in SECONDS)
    assert 0 <= parts - setup_s < 0.5
    assert parts < wall
    requests = int(re.search(r"'requests': (\d+)\} after set-up",
                             done.stdout).group(1))
    assert values["setup_programs"] == requests > 0
    assert values["setup_cache_misses"] == requests         # a cold cache
    assert values["setup_import_s"] > 1.0                   # jax alone is more
    assert sum(line.startswith("[setup] ") for line in lines) == 6
    assert any("0 programs (none)" in line for line in lines)
