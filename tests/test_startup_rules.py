"""Start-up rules: what the program does before it computes anything.

None of this needs a chip; all of it decides whether a run on one means
what it says.

- ``chip_smoke.py`` and bare ``bench.py`` fail when JAX finds no
  accelerator, and print no result;
- the compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else
  at the one fixed path inside the checkout, and no code sets another
  directory in the first case;
- the launcher parent never holds a backend when it spawns, and hands
  pservers the CPU;
- a Place that names a device the process does not have is an error.

A slow test rehearses every phase of ``chip_smoke.py`` on the CPU at toy
sizes with the Pallas bodies in interpreter mode, so a refactor that
breaks the script is caught before chip time is spent on it.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full["PYTHONPATH"] = REPO + os.pathsep + full.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, env=full, cwd=REPO)


def _json_lines(text):
    return [ln for ln in text.splitlines() if ln.startswith("{")]


# ---------------------------------------------------------------------------
# a measurement path that finds no chip fails
# ---------------------------------------------------------------------------
def test_chip_smoke_without_a_chip_exits_nonzero_and_says_why():
    r = _run([SMOKE])
    assert r.returncode != 0
    assert "no accelerator" in r.stderr and "'cpu'" in r.stderr
    assert "platform=cpu" in r.stdout
    # no result: no phase ran and no JSON summary was printed
    assert "one_chip" not in r.stdout and not _json_lines(r.stdout)


def test_bare_bench_without_a_chip_prints_no_metric():
    r = _run([os.path.join(REPO, "bench.py")])
    assert r.returncode != 0
    assert "needs an accelerator" in r.stderr
    assert "bert_base_pretrain_tokens_per_sec_per_chip" not in (
        r.stdout + r.stderr)
    assert not _json_lines(r.stdout)


def test_a_place_must_name_a_device_the_process_has():
    import paddle_tpu as pt
    assert pt.CPUPlace(0).jax_device().platform == "cpu"
    with pytest.raises(pt.core.EnforceNotMet, match="does not have"):
        pt.TPUPlace(0).jax_device()     # the tests run with no chip
    with pytest.raises(pt.core.EnforceNotMet, match="does not have"):
        pt.CPUPlace(64).jax_device()    # not clamped to the last one


# ---------------------------------------------------------------------------
# the compile cache can be placed from outside, and does not move
# ---------------------------------------------------------------------------
_CACHE_PROBE = """
import json, os
import jax
calls = []
_update = jax.config.update
def spy(name, value):
    calls.append(name)
    return _update(name, value)
jax.config.update = spy
import paddle_tpu
from paddle_tpu.core import compile_cache
where = compile_cache.enable()
jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones((8, 8))).block_until_ready()
print(json.dumps({
    "dir": where, "jax_dir": jax.config.jax_compilation_cache_dir,
    "set_dir": "jax_compilation_cache_dir" in calls,
    "default": compile_cache.DEFAULT_DIR,
    "entries": sorted(os.listdir(where))[:3],
    "stats": compile_cache.stats()}))
"""


def test_cache_goes_where_jax_compilation_cache_dir_says(tmp_path):
    placed = str(tmp_path / "placed")
    runs = []
    for _ in range(2):
        r = _run(["-c", _CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=placed)
        assert r.returncode == 0, r.stderr[-2000:]
        runs.append(json.loads(_json_lines(r.stdout)[-1]))
    cold, warm = runs
    # jax's own handling of the variable stands: nothing set a directory
    assert cold["dir"] == cold["jax_dir"] == placed
    assert not cold["set_dir"] and not warm["set_dir"]
    assert cold["entries"] and cold["stats"]["misses"] > 0
    # same path, next process: the compile is a disk read
    assert warm["stats"]["hits"] > 0
    assert not os.path.exists(os.path.join(REPO, "tests", ".jax_cache"))


def test_cache_unplaced_is_the_fixed_path_in_the_checkout():
    from paddle_tpu.core import compile_cache
    assert compile_cache.ENV_VAR == "JAX_COMPILATION_CACHE_DIR"
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=str(os.path.dirname(REPO)))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(_json_lines(r.stdout)[-1])
    # whatever the working directory: one path, so the key never moves
    assert out["dir"] == out["jax_dir"] == compile_cache.DEFAULT_DIR
    assert out["set_dir"]


def test_launcher_hands_workers_the_placed_or_the_fixed_cache(monkeypatch):
    from paddle_tpu.core import compile_cache
    from paddle_tpu.distributed import launch
    var = compile_cache.ENV_VAR
    monkeypatch.delenv(var, raising=False)
    assert launch._cache_dir_env(None) == {var: compile_cache.DEFAULT_DIR}
    assert launch._cache_dir_env({var: "/somewhere"}) == {}
    monkeypatch.setenv(var, "/placed")
    assert launch._cache_dir_env(None) == {}


# ---------------------------------------------------------------------------
# one process for each chip
# ---------------------------------------------------------------------------
_LAUNCH_PROBE = """
import json, sys
from paddle_tpu.distributed import launch
seen = []
_spawn = launch._spawn
def spy(cmd, env, *a, **kw):
    xb = sys.modules.get("jax._src.xla_bridge")
    seen.append({"backends": sorted(xb._backends) if xb else [],
                 "platforms": env.get("JAX_PLATFORMS"),
                 "role": env.get("TRAINING_ROLE"),
                 "chips": env.get("TPU_VISIBLE_CHIPS")})
    return _spawn(cmd, env, *a, **kw)
launch._spawn = spy
try:
    launch.main(sys.argv[1:])
except SystemExit as e:
    print(json.dumps({"rc": e.code, "spawns": seen}))
"""


def _launch(tmp_path, *flags):
    worker = tmp_path / "w.py"
    worker.write_text("import os; print(os.environ['PADDLE_TRAINER_ID'])\n")
    r = _run(["-c", _LAUNCH_PROBE, *flags, str(worker)])
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(_json_lines(r.stdout)[-1])


@pytest.mark.parametrize("flags,ranks", [((), 1),
                                         (("--nproc_per_node", "2"), 2)])
def test_launcher_parent_holds_no_backend_when_it_spawns(tmp_path, flags,
                                                         ranks):
    out = _launch(tmp_path, *flags)
    assert out["rc"] == 0
    assert len(out["spawns"]) == ranks
    # without --nproc_per_node: ONE rank, and no device count was taken
    assert all(s["backends"] == [] for s in out["spawns"])


def test_launcher_refuses_to_spawn_once_it_holds_an_accelerator(monkeypatch):
    from jax._src import xla_bridge
    from paddle_tpu.distributed import launch
    monkeypatch.setitem(xla_bridge._backends, "tpu", object())
    with pytest.raises(launch.LauncherHoldsDeviceError, match="tpu"):
        launch._spawn([sys.executable, "-c", "pass"], dict(os.environ),
                      "workerlog.0", None)


def test_ranks_get_a_chip_each_or_a_typed_refusal():
    from paddle_tpu.distributed import launch
    chip_host = {"PATH": "/bin"}                 # JAX_PLATFORMS unset
    assert launch._chip_env(0, 1, chip_host) == {}   # one rank: all chips
    assert launch._chip_env(1, 2, {"JAX_PLATFORMS": "cpu"}) == {}
    envs = [launch._chip_env(r, 2, chip_host) for r in range(2)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert envs[0]["TPU_MESH_CONTROLLER_PORT"] \
        != envs[1]["TPU_MESH_CONTROLLER_PORT"]
    with pytest.raises(launch.ChipAssignmentError, match="same chip"):
        launch._chip_env(0, 2, dict(chip_host, TPU_VISIBLE_CHIPS="0,1"))


def test_pservers_start_on_the_cpu(tmp_path):
    out = _launch(tmp_path, "--server_num", "1", "--worker_num", "1")
    roles = {s["role"]: s for s in out["spawns"]}
    assert roles["PSERVER"]["platforms"] == "cpu"
    assert all(s["backends"] == [] for s in out["spawns"])


# ---------------------------------------------------------------------------
# chip_smoke.py, rehearsed
# ---------------------------------------------------------------------------
def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke


def test_chip_smoke_last_line_is_ok_and_device_and_nothing_else(
        monkeypatch, capsys):
    """The driver parses the last stdout line and refuses any key beyond
    "ok" and "device" {"platform", "kind", "count"}. The phases are stubbed:
    this pins what main() prints around them."""
    import types

    smoke = _load_smoke()
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(smoke.jax, "devices", lambda: [chip])
    monkeypatch.setattr(smoke.compile_cache, "enable", lambda: "/placed")
    monkeypatch.setattr(
        smoke.native, "get_lib",
        lambda: types.SimpleNamespace(_name="/x/libpaddle_tpu_native.so"))
    monkeypatch.setattr(smoke, "phase_one_chip",
                        lambda: {"first_loss": 10.0, "bodies": {}})
    monkeypatch.setattr(smoke, "phase_kernels", dict)
    monkeypatch.setattr(smoke, "phase_static_quickstart", dict)

    assert smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    # the per-phase account is on the line before, not in the result
    assert lines[-2].startswith("summary: ")
    summary = json.loads(lines[-2][len("summary: "):])
    assert summary["four_chips"] == "skipped (1 device)"
    assert set(summary["phases"]) == {"one_chip", "kernels",
                                      "static_quickstart"}


@pytest.mark.slow
def test_chip_smoke_rehearsal_on_cpu_at_toy_sizes(monkeypatch, capsys):
    import jax

    from paddle_tpu.ops import pallas as plk

    smoke = _load_smoke()
    for name, value in dict(
            GLOBAL_BATCH=8, SEQ=32, MAX_PREDS=4, STEPS=3, VOCAB=512,
            HIDDEN=64, FFN=128, TABLE_ROWS=1000, SLOTS=5,
            FLASH_IN_BERT=((256, 1),), INTERPRET=True).items():
        monkeypatch.setattr(smoke, name, value)
    monkeypatch.setattr(
        smoke, "bert_cfg",
        lambda **kw: smoke.bert.bert_tiny(
            vocab_size=512, **{"max_seq": 64, **kw}))
    # the CPU reports no memory_stats; the chip does
    monkeypatch.setattr(
        type(jax.devices()[0]), "memory_stats",
        lambda self: {"peak_bytes_in_use": 0}, raising=False)

    with plk.override("on"):    # interpreter bodies, as `auto` on a chip
        one = smoke.phase_one_chip()
        assert set(one["bodies"].values()) == {"pallas_interpret"}
        smoke.phase_kernels()
    smoke.phase_static_quickstart()
    four = smoke.phase_four_chips(one["first_loss"])
    assert set(four) == {"data=4", "data=2,model=2", "zero_and_static_dp"}
    out = capsys.readouterr().out
    assert "four_chips[data=2,model=2]: first-step loss" in out
