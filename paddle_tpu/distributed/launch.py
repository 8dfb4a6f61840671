"""Multi-process launcher: `python -m paddle_tpu.distributed.launch`.

Parity: python/paddle/distributed/launch.py:132,214 — spawn one training
process per rank with the PADDLE_* identity env wired, stream logs,
propagate the first failure. Two modes, like the reference:

- collective (default): N trainer processes; each gets
  PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_CURRENT_ENDPOINT /
  PADDLE_TRAINER_ENDPOINTS. On a TPU pod each process drives its own
  host's chips (JAX runtime discovers topology; the env is identity
  metadata, not comm wiring — no gen_nccl_id exchange needed). A chip
  belongs to one process: the launcher itself never initialises a JAX
  backend (asserted at every spawn), the default is ONE rank per host,
  and with more, rank r is pinned to chip r (`_chip_env`).
- ps (--server_num/--worker_num): pserver processes get
  TRAINING_ROLE=PSERVER + PADDLE_PSERVER_ENDPOINTS; workers get
  TRAINING_ROLE=TRAINER. Matches the reference's test_dist_base.py:429
  env contract, which role_maker.PaddleCloudRoleMaker consumes.

Beyond the reference (elastic supervision — SURVEY §5.3 pairs
re-schedulable pod jobs with `io_checkpoint`'s "checkpoint often,
restart anywhere"): the launcher is a supervisor, not just a spawner.

- `--max_restarts N`: a failed or hung rank triggers a restart with
  exponential backoff. Collective mode restarts the whole *gang*
  (survivors would deadlock in the next collective against a dead
  peer); ps mode restarts individual workers while pservers stay up.
- `--hang_timeout S`: hang watchdog. Children touch per-rank heartbeat
  files (see `health.py`; `auto_checkpoint` does it automatically); a
  rank that beat and then stopped for S seconds is *hung* and its gang
  is killed + restarted. A rank that never beat is only logged as
  *slow* — the watchdog never kills workers that don't opt in.
- `--grace_period S`: SIGTERM to the launcher (the TPU-pod preemption
  signal) is forwarded to children, which get S seconds to flush
  (`CheckpointManager.wait()` drains pending async shards) before
  SIGKILL. The launcher then exits 143 without restarting.
- `--min_ranks / --max_ranks`: topology-elastic gangs. A rank exiting
  with code 31 ("rank departed" — spot reclaim, node repair; see
  SHRINK_RC) shrinks the next incarnation to the surviving world size
  instead of respawning a gang that can never be whole again, and
  late-joining hosts (join-request files under `<log_dir>/elastic/`)
  are admitted at the next restart boundary instead of being turned
  away. Each incarnation's world size rides to workers in
  PADDLE_TRAINERS_NUM, so `CheckpointManager.restore()` re-shards the
  last-good checkpoint onto the new mesh and the data cursor rescales
  (see io_checkpoint / docs/ELASTIC_TRAINING.md). Defaults keep
  today's fixed-gang semantics.
- `--ps_snapshot_secs S` (ps mode): pserver failover. Pservers
  snapshot their hosted state to `<log_dir>/ps_state` every S seconds
  (integrity-manifested, atomically published — see distributed/ps.py
  and docs/ELASTIC_TRAINING.md "Pserver failover"); a pserver that
  dies is respawned at its original endpoint under the --max_restarts
  budget and warm-boots from the last-good snapshot while the
  trainers' clients reconnect; with --hang_timeout the supervisor
  also probes each pserver's request loop (a LIST_VARS ping) so a
  wedged-but-alive server is detected and restarted, not just a dead
  one. Without the flag a pserver death tears the job down (today's
  semantics).

Each child additionally sees PADDLE_RESTART_COUNT (0 on the first
incarnation) and PADDLE_HEARTBEAT_DIR.
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import shutil
import tempfile
import threading
import time

from paddle_tpu.core import compile_cache as _compile_cache
from paddle_tpu.distributed import health
from paddle_tpu.monitor import anomaly as _anomaly
from paddle_tpu.monitor import exporter as _exporter
from paddle_tpu.monitor import flight_recorder as _flight
from paddle_tpu.monitor import goodput as _goodput
from paddle_tpu.monitor import trace as _trace
from paddle_tpu.monitor.registry import REGISTRY as _REGISTRY
from paddle_tpu.monitor.registry import counter as _counter
from paddle_tpu.monitor.registry import gauge as _gauge

__all__ = ["launch_collective", "launch_ps", "find_free_ports",
           "backoff_delay", "probe_port_range", "elastic_join_dir",
           "SHRINK_RC", "MIGRATE_RC"]

PREEMPTED_RC = 143          # 128 + SIGTERM, the conventional code

#: a rank that exits with this code is PERMANENTLY DEPARTING (spot
#: reclaim, node repair — or testing.faults' PT_FAULT_SHRINK_AT_STEP,
#: which must match this value): under elastic flags the supervisor
#: restarts the gang at the reduced world size instead of respawning
#: the dead rank. Any other failure code keeps today's same-size gang
#: restart.
SHRINK_RC = 31

#: launch_ps exits with this code when a fleet-resize migration keeps
#: failing past its retry budget: every attempt rolled back to the old
#: epoch (no state was lost), the fleet still serves at its old size,
#: but the requested resize was ABANDONED — see docs/DEBUGGING.md
#: "my resize failed"
MIGRATE_RC = 41

#: the process exit-code vocabulary (docs/DEBUGGING.md table): naming
#: the cause in the supervisor log turns "code 29" into something an
#: operator can act on without grepping the test harness
EXIT_CODE_LABELS = {
    17: "non-finite trip (NonFiniteError)",
    23: "injected crash (testing.faults)",
    29: "checkpoint-corruption fault (testing.faults)",
    31: "rank departed (elastic shrink; supervisor resumes at the "
        "reduced world size)",
    37: "injected pserver crash (testing.faults; supervisor respawns "
        "it at the same endpoint, warm-booting from the last-good "
        "snapshot)",
    41: "pserver fleet resize abandoned (every migration attempt "
        "aborted + rolled back; the fleet still serves at its old "
        "epoch/size — see DEBUGGING.md 'my resize failed')",
    124: "timeout",
    137: "SIGKILLed (OOM killer or kill -9)",
    139: "segfault",
    143: "preempted (SIGTERM)",
}


def _rc_label(rc):
    # Popen returncodes for signal deaths are NEGATIVE (-9, -11, -15);
    # the operator-facing table speaks shell convention (128+signum)
    label = EXIT_CODE_LABELS.get(128 - rc if rc < 0 else rc)
    return f" [{label}]" if label else ""

#: seconds between job-status log lines / job-level metric snapshots
STATUS_INTERVAL = 15.0

# launcher-side telemetry (the supervisor's own registry; aggregated
# with the per-rank snapshots into <log_dir>/metrics.prom)
_m_restarts = _counter(
    "restarts_total",
    "Restarts: the launcher counts restarts it performed; a rank "
    "reports its own incarnation index")
_m_watchdog = _counter(
    "watchdog_trips_total",
    "Hang-watchdog kills (a rank heartbeat, then went silent past "
    "--hang_timeout)")
_m_stragglers = _counter(
    "straggler_trips_total",
    "Ranks newly flagged as stragglers by the launcher (mean step "
    "time above the skew threshold vs the median rank)")
_m_world = _gauge(
    "elastic_world_size",
    "World size of the current gang incarnation (= --nproc_per_node "
    "until --min_ranks/--max_ranks elasticity moves it: shrinks on "
    "rank departure, grows on admitted join requests)")
_m_ps_migration_aborts = _counter(
    "ps_migration_aborts_total",
    "Fleet-resize migration attempts the coordinator aborted and "
    "rolled back to the old epoch (a crashed/unresponsive server or "
    "a failed shadow verification mid-migration; the attempt is "
    "retried up to the resize retry budget)")
_m_ps_restarts = _counter(
    "ps_restarts_total",
    "Pserver processes the launcher respawned at their original "
    "endpoint after a death or a failed liveness probe (ps mode with "
    "--ps_snapshot_secs; the respawn warm-boots from the last-good "
    "snapshot)")


def _postmortem_env(log_dir):
    """Arm workers' flight recorders: PADDLE_POSTMORTEM_DIR under the
    log dir. A killed/crashed rank dumps its recent spans there (see
    monitor/flight_recorder.py); no log_dir means nowhere durable."""
    if not log_dir:
        return {}
    d = os.path.join(os.path.abspath(log_dir), "postmortem")
    os.makedirs(d, exist_ok=True)
    return {_flight.ENV_DIR: d}


def _trace_env(log_dir):
    """Arm workers' distributed tracing: PADDLE_TRACE_DIR under the
    log dir (per-rank span files land in <log_dir>/traces; see
    monitor/trace.py — tail sampling keeps the hot path cheap, so a
    supervised job traces by default). No log_dir means nowhere
    durable."""
    if not log_dir:
        return {}
    d = os.path.join(os.path.abspath(log_dir), "traces")
    os.makedirs(d, exist_ok=True)
    return {_trace.ENV_DIR: d}


def _goodput_env(log_dir):
    """Arm workers' goodput ledgers: PADDLE_GOODPUT_DIR under the log
    dir (see monitor/goodput.py — the dir also holds the launcher's
    incarnations.jsonl, the replay-watermark source). No log_dir means
    nowhere durable."""
    if not log_dir:
        return {}
    d = os.path.join(os.path.abspath(log_dir), "goodput")
    os.makedirs(d, exist_ok=True)
    return {_goodput.ENV_DIR: d}


def _record_incarnation(gp_dir, hb_dir, attempt, world, t_start,
                        status, rc, departed):
    """Append one gang-incarnation record to
    <gp_dir>/incarnations.jsonl: identity (attempt, world), lifetime,
    how it ended (status + labeled exit code), the replay watermark
    (max goodput_step across rank snapshots — the NEXT incarnation
    reads it to price replayed lost work), and each rank's per-phase
    ledger at death (tools/goodput_report.py's per-incarnation
    waterfall input). Never raises — evidence collection must not mask
    the job's exit path."""
    if not gp_dir:
        return
    try:
        snaps = _exporter.read_rank_snapshots(hb_dir)

        def _gv(samples, name):
            for (n, _pairs), v in samples.items():
                if n == name:
                    return float(v)
            return None

        last = [v for v in (_gv(s, "goodput_step")
                            for _t, s in snaps.values())
                if v is not None]
        restored = [v for v in (_gv(s, "goodput_restored_step")
                                for _t, s in snaps.values())
                    if v is not None]
        rec = {
            "incarnation": int(attempt),
            "world": int(world),
            "start": float(t_start),
            "end": time.time(),
            "status": status,
            "rc": int(rc),
            "rc_label": EXIT_CODE_LABELS.get(
                128 - rc if rc < 0 else rc),
            "departed": sorted(departed or []),
            "last_step": int(max(last)) if last else None,
            # MIN across ranks: the most-behind rank's restore point
            # prices the replayed lost work (a rank that restored
            # further ahead replays less, not more)
            "restored_step": int(min(restored)) if restored else None,
            "ranks": {
                str(r): {
                    "wall_seconds": _gv(s, "goodput_wall_seconds"),
                    "phases": _goodput.phase_seconds_of(s),
                } for r, (_t, s) in snaps.items()},
        }
        _goodput.record_incarnation(gp_dir, rec)
    except Exception as e:
        _log(f"goodput record failed (ignored): "
             f"{type(e).__name__}: {e}")


def _merge_job_trace(log_dir):
    """Clock-align and merge every rank's trace file into ONE
    Perfetto/Chrome JSON at <log_dir>/trace.json — the launcher-side
    close of the tracing loop. Never raises (evidence collection must
    not mask the job's exit code)."""
    if not log_dir:
        return None
    d = os.path.join(os.path.abspath(log_dir), "traces")
    try:
        out = _trace.merge_rank_traces(
            d, os.path.join(os.path.abspath(log_dir), "trace.json"))
    except Exception as e:
        _log(f"trace merge failed (ignored): {type(e).__name__}: {e}")
        return None
    if out:
        _log(f"job trace: {out} (per-rank spans clock-aligned and "
             f"merged; open in Perfetto / chrome://tracing)")
    return out


def _report_postmortems(log_dir, why):
    if not log_dir:
        return
    d = os.path.join(os.path.abspath(log_dir), "postmortem")
    try:
        dumps = sorted(f for f in os.listdir(d) if f.endswith(".json"))
    except OSError:
        return
    if dumps:
        _log(f"postmortem ({why}): {len(dumps)} dump(s) in {d} "
             f"(newest: {dumps[-1]})")


def _status_tick(hb_dir, log_dir, restarts, flagged_stragglers=None):
    """One supervision-loop status beat: log the aggregated job line
    (now carrying a ``health=`` field — anomaly trips + straggler
    skew, see monitor/anomaly.py) and refresh <log_dir>/metrics.prom
    from the rank snapshots. A rank newly entering straggler-hood gets
    its own log line and bumps ``straggler_trips_total``;
    ``flagged_stragglers`` is the PER-LAUNCH already-reported set (a
    module-global here would suppress reporting across sequential
    launches in one supervisor process). Never raises — a telemetry
    hiccup (disk error, a malformed snapshot a dying rank half-wrote)
    must not tear down the supervisor."""
    try:
        snaps = _exporter.read_rank_snapshots(hb_dir)
        # one job_health judgment feeds BOTH the health= field and the
        # straggler bookkeeping: two computations could disagree about
        # who is a straggler within a single tick
        health, stragglers = _anomaly.job_health(snaps)
        line = _exporter.job_status_line(hb_dir, restarts=restarts,
                                         snaps=snaps, health=health,
                                         registry=_REGISTRY)
        if line:
            _log("status " + line)
        if flagged_stragglers is not None:
            new = set(stragglers) - flagged_stragglers
            if new:
                _m_stragglers.inc(len(new))
                _log(f"straggler: rank(s) {sorted(new)} mean step "
                     f"time exceeds the skew threshold vs the median "
                     f"rank (see the health= field / "
                     f"docs/DEBUGGING.md)")
            flagged_stragglers.update(new)
        if log_dir:
            _exporter.write_job_snapshot(
                hb_dir, os.path.join(os.path.abspath(log_dir),
                                     "metrics.prom"),
                registry=_REGISTRY, snaps=snaps)
    except Exception as e:
        _log(f"status tick failed (ignored): {type(e).__name__}: {e}")


def _cache_dir_env(env_extra):
    """Where the workers keep their persistent XLA compilation cache.
    Someone who set JAX_COMPILATION_CACHE_DIR (ambient or via env_extra)
    placed it, and jax's own handling of the variable stands. Otherwise
    the workers get the one fixed path inside the checkout
    (core/compile_cache.py): a directory that moves — a log dir, a temp
    dir — never hits, because the path is part of the key. Restarted
    incarnations and later launches then replay their compiles from
    disk."""
    var = _compile_cache.ENV_VAR
    if os.environ.get(var) or (env_extra and env_extra.get(var)):
        return {}
    return {var: _compile_cache.DEFAULT_DIR}


def find_free_ports(n, host="127.0.0.1"):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def probe_port_range(host, start, n, claim_desc):
    """Bind-check every port in the explicitly claimed range
    [start, start+n) and fail fast naming the full range — an explicit
    --started_port is never probed by find_free_ports, and a silent
    collision with an unrelated service surfaces as an inscrutable
    rendezvous failure much later."""
    busy = []
    for port in range(start, start + n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind((host, port))
        except OSError:
            busy.append(port)
        finally:
            s.close()
    if busy:
        raise RuntimeError(
            f"--started_port {start}: port(s) {busy} in the claimed "
            f"range {start}..{start + n - 1} are already in use; "
            f"{claim_desc}")


def backoff_delay(attempt, base=1.0, cap=30.0):
    """Exponential restart backoff: base * 2**attempt, capped."""
    return min(cap, base * (2.0 ** max(attempt, 0)))


def elastic_join_dir(log_dir):
    """Where late-joining hosts request admission: any file named
    ``join.*`` dropped here is consumed at the next restart boundary
    and grows the gang by one rank (up to --max_ranks). File-based on
    purpose — it crosses the process boundary the same way heartbeats
    and rank snapshots do, needs no rendezvous service, and a
    provisioning script can request a join with ``touch``."""
    if not log_dir:
        return None
    return os.path.join(os.path.abspath(log_dir), "elastic")


def _take_ps_resize_request(dirname):
    """Consume (delete) the oldest pending pserver fleet-resize
    trigger (``ps_grow.*`` / ``ps_shrink.*`` — same file-based
    admission idiom as the collective gang's ``join.*``). Returns
    "grow", "shrink", or None."""
    if not dirname:
        return None
    try:
        names = sorted(os.listdir(dirname))
    except OSError:
        return None
    for n in names:
        if n.startswith("ps_grow.") or n.startswith("ps_shrink."):
            try:
                os.remove(os.path.join(dirname, n))
            except OSError:
                continue
            return "grow" if n.startswith("ps_grow.") else "shrink"
    return None


def _ps_retire_grace():
    """Seconds a shrunk-away pserver keeps serving AFTER the epoch
    commit (PT_PS_RETIRE_GRACE, default 2): in-flight client requests
    land on a live server that answers WRONG_EPOCH with the new map
    instead of a connection refusal."""
    try:
        return max(0.0, float(os.environ.get("PT_PS_RETIRE_GRACE",
                                             "2")))
    except ValueError:
        return 2.0


def _ps_resize_retries():
    """Aborted-migration retry budget before the coordinator abandons
    a resize and exits MIGRATE_RC (PT_PS_RESIZE_RETRIES, default 3)."""
    try:
        return max(1, int(os.environ.get("PT_PS_RESIZE_RETRIES", "3")))
    except ValueError:
        return 3


def _take_join_requests(join_dir, room):
    """Consume (delete) up to ``room`` pending join-request files;
    returns how many were admitted. Requests beyond the room stay
    queued for the next boundary."""
    if not join_dir or room <= 0:
        return 0
    try:
        names = sorted(f for f in os.listdir(join_dir)
                       if f.startswith("join."))
    except OSError:
        return 0
    taken = 0
    for f in names[:room]:
        try:
            os.remove(os.path.join(join_dir, f))
        except OSError:
            continue
        taken += 1
    return taken


class LauncherHoldsDeviceError(RuntimeError):
    """The launcher parent initialised an accelerator backend. That
    claims every local chip, and a chip belongs to one process: the
    ranks about to be spawned would fail or hang waiting for it. (A
    parent that touched only the CPU backend — the tests — holds
    nothing.)"""


class ChipAssignmentError(RuntimeError):
    """The launcher cannot give every rank chips of its own."""


#: libtpu's per-process chip selection; set for a whole gang they would
#: point every rank at the same chips
_LIBTPU_PIN_VARS = ("TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES",
                    "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")


def _chip_env(rank, world, env):
    """Device environment of one trainer rank. A chip belongs to one
    process, so there are two legal layouts on a chip host: ONE rank
    that drives every local chip (the JAX model, this launcher's
    default, what the SPMD code in parallel/ wants), or several ranks
    each pinned to the chip of its rank through libtpu's environment —
    each then sees exactly one device, and is a one-chip world of its
    own (two ranks on a v5e 2x2 host each report one local and one
    global device, PR 21): nothing between such ranks rides ICI, so
    SPMD over several chips wants the one-rank layout. Ranks bound for
    the CPU (JAX_PLATFORMS=cpu) share the host freely and get
    nothing."""
    if world == 1 or env.get("JAX_PLATFORMS") == "cpu":
        return {}
    preset = [k for k in _LIBTPU_PIN_VARS if env.get(k)]
    if preset:
        raise ChipAssignmentError(
            f"{', '.join(preset)} set for a gang of {world} ranks: "
            f"every rank would claim the same chip(s). Unset it (the "
            f"launcher pins rank r to chip r), or launch one rank.")
    port = 8476 + rank
    return {
        "TPU_VISIBLE_CHIPS": str(rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # one libtpu runtime per rank: each needs its own controller port
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
        "TPU_MESH_CONTROLLER_PORT": str(port),
    }


def _spawn(cmd, env, log_prefix, log_dir, append=False):
    xb = sys.modules.get("jax._src.xla_bridge")
    held = sorted(p for p in (xb._backends if xb else ()) if p != "cpu")
    if held:
        raise LauncherHoldsDeviceError(
            f"the launcher process initialised the JAX backend(s) "
            f"{held} before spawning {log_prefix}; it must stay off "
            f"jax.devices()/jit so its children can have the chips")
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        out = open(os.path.join(log_dir, f"{log_prefix}.log"),
                   "ab" if append else "wb")
    else:
        out = None
    return subprocess.Popen(cmd, env=env, stdout=out, stderr=out), out


def _drain(procs, grace_period, sig=signal.SIGTERM):
    """Signal every live proc, give them ``grace_period`` seconds to
    exit, SIGKILL the stragglers; reap everything (no zombies, ports
    released). Returns True if no SIGKILL was needed."""
    procs = [p for p in procs if p.poll() is None]
    for p in procs:
        try:
            p.send_signal(sig)
        except OSError:
            pass
    deadline = time.monotonic() + max(grace_period, 0.0)
    clean = True
    for p in procs:
        try:
            p.wait(timeout=max(deadline - time.monotonic(), 0.0))
        except subprocess.TimeoutExpired:
            clean = False
            p.kill()
            p.wait()
    return clean


def _install_term_handler(term):
    """Route SIGTERM (pod preemption) into ``term``; only possible from
    the main thread (in-process test callers on other threads simply
    don't get preemption forwarding). Returns an undo callable."""
    if threading.current_thread() is not threading.main_thread():
        return lambda: None
    prev = signal.signal(signal.SIGTERM, lambda s, f: term.set())
    return lambda: signal.signal(signal.SIGTERM, prev)


def _log(msg):
    print(f"[launch] {msg}", file=sys.stderr, flush=True)


def _wait_gang(procs, ranks, logs, deadline, hang_timeout, hb_dir, term,
               grace_period, log_dir=None, restarts=0,
               flagged_stragglers=None):
    """Poll one gang incarnation to completion.

    ``procs``: name -> Popen; ``ranks``: name -> heartbeat rank (absent
    = unwatched, e.g. pservers). Returns (status, rc, departed) with
    status one of "ok" | "fail" | "hung" | "timeout" | "preempted";
    ``departed`` is the sorted list of ranks whose process ended with
    SHRINK_RC ("rank departed") — counted over the WHOLE reaped gang
    after teardown, not just the first failure observed, so two hosts
    reclaimed at the same step both register and the elastic
    supervisor shrinks to the true surviving world size. On every
    status but "ok" the whole gang has already been torn down and
    reaped. Every STATUS_INTERVAL the loop logs the aggregated job
    status line and refreshes <log_dir>/metrics.prom from the rank
    snapshots.
    """
    start = time.time()
    warned_slow = False
    next_status = time.monotonic() + STATUS_INTERVAL

    def departed():
        # every proc is reaped by now (_drain or natural exit):
        # Popen.returncode is authoritative
        return sorted(ranks[n] for n, p in procs.items()
                      if n in ranks and p.returncode == SHRINK_RC)

    try:
        alive = dict(procs)
        while alive:
            if time.monotonic() >= next_status:
                next_status = time.monotonic() + STATUS_INTERVAL
                _status_tick(hb_dir, log_dir, restarts,
                             flagged_stragglers)
            if term.is_set():
                _log(f"SIGTERM: forwarding to {sorted(alive)} with "
                     f"{grace_period}s grace for checkpoint flush")
                if not _drain(alive.values(), grace_period):
                    _log("grace period expired; SIGKILLed stragglers")
                return "preempted", PREEMPTED_RC, []
            if deadline is not None and time.monotonic() > deadline:
                _log(f"timeout; killing {sorted(alive)}")
                _drain(alive.values(), grace_period)
                return "timeout", 124, []
            for name, p in list(alive.items()):
                r = p.poll()
                if r is None:
                    continue
                del alive[name]
                if r != 0:
                    _log(f"{name} exited with code {r}{_rc_label(r)}")
                    _drain(alive.values(), grace_period)
                    return "fail", r, departed()
            if hang_timeout is not None and alive:
                watched = {ranks[n] for n in alive if n in ranks}
                stale = [(r, age) for r, age in health.stale_ranks(
                    hb_dir, max(watched, default=-1) + 1, hang_timeout)
                    if r in watched]
                if stale:
                    r0, age = stale[0]
                    _m_watchdog.inc()
                    _log(f"watchdog: rank {r0} hung — last heartbeat "
                         f"{age:.1f}s ago (hang_timeout={hang_timeout}s); "
                         f"killing gang")
                    _drain(alive.values(), grace_period)
                    return "hung", 1, departed()
                if not warned_slow and time.time() - start > hang_timeout:
                    silent = [r for r in health.silent_ranks(
                        hb_dir, max(watched, default=-1) + 1)
                        if r in watched]
                    if silent:
                        _log(f"watchdog: rank(s) {silent} slow — no "
                             f"heartbeat yet {time.time() - start:.1f}s "
                             f"after gang start (not killed: only a rank "
                             f"that beat then stopped counts as hung)")
                    warned_slow = True
            time.sleep(0.2)
        return "ok", 0, []
    except KeyboardInterrupt:
        for p in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGINT)
        raise
    finally:
        for f in logs:
            if f:
                f.close()


def _make_hb_dir(log_dir):
    """(dir, is_tmp): a launcher-owned heartbeat dir. With a log_dir it
    lives there (inspectable, reused); otherwise a tempdir the caller
    must remove when the launch ends."""
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        d = os.path.join(log_dir, "heartbeat")
        os.makedirs(d, exist_ok=True)
        return d, False
    return tempfile.mkdtemp(prefix="pt_heartbeat_"), True


def launch_collective(script_args, nproc, started_port=None, ips="127.0.0.1",
                      log_dir=None, env_extra=None, timeout=None,
                      max_restarts=0, hang_timeout=None, grace_period=10.0,
                      min_ranks=None, max_ranks=None):
    """Supervise a gang of ``nproc`` trainers.

    ``min_ranks``/``max_ranks`` (either one set) make the gang
    ELASTIC instead of gang-fatal at a fixed size: with ``min_ranks``
    set, a rank exiting SHRINK_RC (31 — a spot reclaim / node repair
    saying goodbye) shrinks the next incarnation to the surviving
    world size (down to ``min_ranks``; below it the job gives up as
    before; with only ``max_ranks`` — grow-only elasticity — a
    departure is an ordinary failure and the gang restarts at full
    size), and pending
    join requests (files under ``<log_dir>/elastic/``, see
    ``elastic_join_dir``) are admitted at the next restart boundary up
    to ``max_ranks`` — a late-joining host grows the gang instead of
    being turned away. Restarts still draw from the one
    ``max_restarts`` budget with the same backoff. Each incarnation's
    world size is exported to every worker as PADDLE_TRAINERS_NUM (and
    the ``elastic_world_size`` gauge), which is what lets
    ``CheckpointManager.restore`` notice a topology change and
    re-shard. With neither flag set, behavior is exactly the fixed
    gang of old."""
    host = ips.split(",")[0]
    elastic = min_ranks is not None or max_ranks is not None
    # the bounds are contracts, not hints: silently clamping them
    # would let the gang shrink below (or grow past) what the operator
    # asked for — e.g. a --max_ranks below nproc overridden to nproc
    # would re-grow past the ceiling that was protecting the hosts
    if min_ranks is not None and not 1 <= min_ranks <= nproc:
        raise ValueError(
            f"--min_ranks {min_ranks} must be in [1, nproc={nproc}]")
    if max_ranks is not None and max_ranks < nproc:
        raise ValueError(
            f"--max_ranks {max_ranks} is below the starting world "
            f"size nproc={nproc} — lower --nproc_per_node instead")
    # shrink-on-departure is OPT-IN via --min_ranks: with only
    # --max_ranks (grow-only elasticity) a rank exiting SHRINK_RC is
    # an ordinary failure and the gang restarts at full size — the
    # floor stays nproc, it must not turn departures fatal
    can_shrink = min_ranks is not None
    lo = min_ranks if min_ranks is not None else nproc
    hi = max_ranks if max_ranks is not None else nproc
    # trainer endpoints double as the jax.distributed rendezvous in
    # collective mode (rank 0's is the coordinator, a long-lived bound
    # port) — trainer-to-trainer traffic like global_shuffle's sample
    # exchange gets its own dedicated ports, as launch_ps does. One
    # find_free_ports call for both sets: all 2*hi sockets are bound
    # simultaneously, so the sets are guaranteed disjoint — sized for
    # the LARGEST world this launch may grow to, so an admitted join
    # never scrambles the surviving ranks' endpoints.
    if started_port is None:
        allp = find_free_ports(2 * hi, host)
    else:
        probe_port_range(
            host, started_port, 2 * hi,
            f"collective mode claims 2*max world size = {2 * hi} "
            f"consecutive ports (trainer endpoints, then "
            f"global_shuffle exchange endpoints)")
        allp = list(range(started_port, started_port + 2 * hi))
    hb_dir, hb_tmp = _make_hb_dir(log_dir)
    cache_env = _cache_dir_env(env_extra)
    pm_env = _postmortem_env(log_dir)
    tr_env = _trace_env(log_dir)
    gp_env = _goodput_env(log_dir)
    gp_dir = gp_env.get(_goodput.ENV_DIR)
    join_dir = elastic_join_dir(log_dir) if elastic else None
    if join_dir:
        os.makedirs(join_dir, exist_ok=True)
        _log(f"elastic: world size {nproc} (bounds {lo}..{hi}); join "
             f"requests = files named join.* in {join_dir}, admitted "
             f"at restart boundaries")
    elif elastic and hi > nproc:
        # growth was requested but there is nowhere to drop a join
        # request — say so instead of silently never growing
        _log(f"elastic: --max_ranks {hi} has no effect without "
             f"--log_dir (join requests are files under "
             f"<log_dir>/elastic/); the gang can shrink but not grow")

    def spawn_gang(attempt, world):
        ports, xports = allp[:world], allp[hi:hi + world]
        endpoints = ",".join(f"{host}:{p}" for p in ports)
        exchange_eps = ",".join(f"{host}:{p}" for p in xports)
        procs, ranks, logs = {}, {}, []
        try:
            for rank in range(world):
                env = dict(os.environ, **(env_extra or {}), **cache_env,
                           **pm_env, **tr_env, **gp_env)
                env.update(_chip_env(rank, world, env))
                env.update({
                    "PADDLE_TRAINER_ID": str(rank),
                    "PADDLE_TRAINERS_NUM": str(world),
                    "PADDLE_CURRENT_ENDPOINT": f"{host}:{ports[rank]}",
                    "PADDLE_TRAINER_ENDPOINTS": endpoints,
                    "PADDLE_EXCHANGE_ENDPOINTS": exchange_eps,
                    "TRAINING_ROLE": "TRAINER",
                    "PADDLE_HEARTBEAT_DIR": hb_dir,
                    "PADDLE_RESTART_COUNT": str(attempt),
                    # goodput: startup = spawn stamp to ledger arming
                    _goodput.ENV_SPAWN: repr(time.time()),
                })
                p, f = _spawn([sys.executable, "-u"] + script_args, env,
                              f"workerlog.{rank}", log_dir,
                              append=attempt > 0)
                procs[f"trainer {rank}"] = p
                ranks[f"trainer {rank}"] = rank
                logs.append(f)
        except Exception:
            # a spawn failure mid-gang must not leak the ranks already
            # started (nor their log handles)
            _drain(procs.values(), grace_period)
            for f in logs:
                if f:
                    f.close()
            raise
        return procs, ranks, logs

    deadline = None if timeout is None else time.monotonic() + timeout
    term = threading.Event()
    undo = _install_term_handler(term)
    flagged_stragglers = set()          # per-launch straggler memory
    try:
        attempt = 0
        world = nproc
        gang_end = None
        _goodput.enable()
        while True:
            health.reset(hb_dir, world)
            # a previous larger incarnation's rank files would pollute
            # the aggregated metrics.prom/status line and confuse the
            # watchdog — ranks that no longer exist leave no evidence
            swept = health.sweep_stale_ranks(hb_dir, world)
            if swept:
                _log(f"swept stale rank file(s) of departed ranks: "
                     f"{swept}")
            _m_world.set(world)
            if gang_end is not None:
                # goodput: previous gang's death to this spawn, priced
                # at the NEW world size so launcher seconds and
                # rank-seconds share one denominator
                _goodput.attribute(
                    (time.time() - gang_end) * world,
                    phase="restart_downtime")
            gang_t0 = time.time()
            procs, ranks, logs = spawn_gang(attempt, world)
            status, rc, departed = _wait_gang(
                procs, ranks, logs, deadline, hang_timeout, hb_dir,
                term, grace_period, log_dir=log_dir, restarts=attempt,
                flagged_stragglers=flagged_stragglers)
            _status_tick(hb_dir, log_dir, attempt, flagged_stragglers)
            _record_incarnation(gp_dir, hb_dir, attempt, world,
                                gang_t0, status, rc, departed)
            gang_end = time.time()
            if status in ("ok", "timeout", "preempted"):
                return rc
            # the killed gang's flight-recorder dumps are the evidence
            # the restart would otherwise erase — surface them
            _report_postmortems(log_dir, f"gang {status}")
            if attempt >= max_restarts:
                if max_restarts:
                    _log(f"gang {status} (rc={rc}); restart budget "
                         f"{max_restarts} exhausted, giving up")
                return rc
            new_world = world
            if elastic:
                if departed and can_shrink:
                    # EVERY rank that ended with SHRINK_RC this
                    # incarnation is gone for good — two hosts
                    # reclaimed at the same step both count, whatever
                    # exit code the supervisor happened to see first
                    new_world -= len(departed)
                    _log(f"trainer(s) {departed} departed "
                         f"(rc={SHRINK_RC}"
                         f"{_rc_label(SHRINK_RC)}); gang shrinks "
                         f"{world} -> {new_world}")
                elif departed:
                    _log(f"trainer(s) {departed} departed "
                         f"(rc={SHRINK_RC}) but --min_ranks is not "
                         f"set; restarting at full size")
                joined = _take_join_requests(join_dir, hi - new_world)
                if joined:
                    _log(f"admitting {joined} late-joining rank(s) at "
                         f"this restart boundary: world size "
                         f"{new_world} -> {new_world + joined}")
                    new_world += joined
                if new_world < lo:
                    _log(f"world size {new_world} below --min_ranks "
                         f"{lo}; giving up")
                    return rc
            delay = backoff_delay(attempt)
            attempt += 1
            _m_restarts.inc()
            world = new_world
            # gang restart, not per-rank: surviving ranks would deadlock
            # in their next collective against the dead peer
            _log(f"gang {status} (rc={rc}); restarting gang "
                 f"{attempt}/{max_restarts} at world size {world} "
                 f"after {delay:.1f}s backoff")
            if term.wait(delay):
                return PREEMPTED_RC
            if deadline is not None and time.monotonic() > deadline:
                _log("timeout expired during restart backoff")
                return 124
    finally:
        undo()
        # the merged job timeline is evidence like the postmortems:
        # produced however the job ended (ok, budget-exhausted, killed)
        _merge_job_trace(log_dir)
        if hb_tmp:
            shutil.rmtree(hb_dir, ignore_errors=True)


def ps_probe(ep, timeout=2.0):
    """One supervisor-side pserver liveness probe: a LIST_VARS request
    over a fresh connection; True iff the server produced a well-formed
    reply within ``timeout`` (an ERR reply counts — the server
    ANSWERED). A wedged-but-alive pserver (accepting connections,
    never replying) times out here, which is exactly what
    ``hang_timeout`` cannot see from process liveness alone. The wire
    codec imports lazily (it needs numpy): the collective launcher
    keeps its stdlib-only contract, and a probe that cannot even
    import the codec returns None (probing disabled) rather than
    killing servers it cannot judge."""
    try:
        from paddle_tpu.distributed import wire
    except Exception:
        return None
    host, port = ep.rsplit(":", 1)
    try:
        with socket.create_connection((host, int(port)),
                                      timeout=timeout) as s:
            s.settimeout(timeout)
            wire.send_frame(s, wire.LIST_VARS, ())
            wire.recv_frame(s)
        return True
    except Exception:
        return False


class _PsWatch:
    """Per-pserver liveness bookkeeping for the supervision loop,
    mirroring the trainer watchdog's asymmetry: only a server that
    ANSWERED a probe at least once and then stopped answering for
    longer than the hang timeout is *wedged* (kill + respawn); a
    server that never answered is merely *slow* (long startup — jax
    import alone takes seconds) and is logged, never killed."""

    def __init__(self, n):
        self._last_ok = [None] * n      # monotonic time of last reply
        self._warned_slow = set()

    def observe(self, i, ok, now=None):
        now = time.monotonic() if now is None else now
        if ok:
            self._last_ok[i] = now

    def forget(self, i):
        """A respawned server starts a fresh history (its boot must
        not be judged against the dead incarnation's last answer)."""
        self._last_ok[i] = None
        self._warned_slow.discard(i)

    def wedged(self, hang_timeout, now=None):
        """[(index, seconds-since-last-answer)] past the timeout."""
        now = time.monotonic() if now is None else now
        return [(i, now - t) for i, t in enumerate(self._last_ok)
                if t is not None and now - t > hang_timeout]

    def slow(self, i):
        """True ONCE per server that never answered (for the one-shot
        slow log line)."""
        if self._last_ok[i] is None and i not in self._warned_slow:
            self._warned_slow.add(i)
            return True
        return False


def launch_ps(script_args, server_num, worker_num, started_port=None,
              log_dir=None, env_extra=None, timeout=None, max_restarts=0,
              hang_timeout=None, grace_period=10.0,
              ps_snapshot_secs=None, ps_min_servers=None,
              ps_max_servers=None):
    host = "127.0.0.1"
    if ps_max_servers is not None and ps_max_servers < server_num:
        raise ValueError(f"--ps_max_servers {ps_max_servers} < "
                         f"--server_num {server_num}")
    if ps_min_servers is not None and ps_min_servers > server_num:
        raise ValueError(f"--ps_min_servers {ps_min_servers} > "
                         f"--server_num {server_num}")
    # ports for the whole REACHABLE fleet are claimed up front: a grown
    # server's endpoint must be deterministic before it exists
    hi = max(server_num, ps_max_servers or server_num)
    lo = max(1, ps_min_servers or 1)
    if started_port is None:
        ports = find_free_ports(hi, host)
        wports = find_free_ports(worker_num, host)
    else:
        n = hi + worker_num
        probe_port_range(
            host, started_port, n,
            f"ps mode claims max_servers+worker_num = {n} consecutive "
            f"ports (pserver endpoints, then trainer exchange endpoints)")
        ports = list(range(started_port, started_port + hi))
        wports = list(range(started_port + hi, started_port + n))
    # the gang transpiles against the LAUNCH-time fleet only: ports
    # reserved for --ps_max_servers growth stay out of the endpoint
    # list, and clients discover grown servers via the epoch map
    server_eps = ",".join(f"{host}:{p}" for p in ports[:server_num])
    # trainers also get their own endpoints: trainer-to-trainer traffic
    # (global_shuffle's sample exchange) rides these in PS mode too
    worker_eps = ",".join(f"{host}:{p}" for p in wports)
    hb_dir, hb_tmp = _make_hb_dir(log_dir)
    cache_env = _cache_dir_env(env_extra)
    pm_env = _postmortem_env(log_dir)
    tr_env = _trace_env(log_dir)
    # pserver failover (docs/ELASTIC_TRAINING.md "Pserver failover") is
    # OPT-IN via --ps_snapshot_secs: the snapshot dir under log_dir is
    # what makes a pserver death recoverable — without snapshots a
    # respawned server would serve freshly initialized parameters,
    # silently wrong training, so respawning stays off
    ps_state_dir = None
    if ps_snapshot_secs is not None:
        if ps_snapshot_secs <= 0:
            raise ValueError(
                f"--ps_snapshot_secs must be > 0, got {ps_snapshot_secs}")
        if log_dir:
            ps_state_dir = os.path.join(os.path.abspath(log_dir),
                                        "ps_state")
            os.makedirs(ps_state_dir, exist_ok=True)
            _log(f"pserver failover armed: snapshots every "
                 f"{ps_snapshot_secs:g}s to {ps_state_dir}; a dead "
                 f"pserver respawns at its endpoint and warm-boots "
                 f"from the last-good snapshot"
                 + ("" if max_restarts else
                    " (set --max_restarts to actually respawn)"))
        else:
            _log("--ps_snapshot_secs has no effect without --log_dir "
                 "(snapshots need somewhere durable); pserver "
                 "failover disabled")
    ps_elastic = ps_state_dir is not None and max_restarts > 0
    # fleet elasticity (docs/ELASTIC_TRAINING.md "Resizing the pserver
    # fleet"): grow/shrink requests arrive as ps_grow.*/ps_shrink.*
    # trigger files, and the supervisor coordinates the epoch-fenced
    # two-phase migration. Needs the snapshot dir (shadow staging +
    # fleet_epoch.json live there).
    fleet_elastic = ((ps_min_servers is not None
                      or ps_max_servers is not None)
                     and ps_state_dir is not None)
    resize_dir = None
    if fleet_elastic:
        resize_dir = elastic_join_dir(log_dir)
        os.makedirs(resize_dir, exist_ok=True)
        _log(f"pserver fleet elasticity armed: {lo} <= servers <= "
             f"{hi}; drop ps_grow.*/ps_shrink.* files in {resize_dir} "
             f"to resize (epoch-fenced two-phase migration)")
    elif ps_min_servers is not None or ps_max_servers is not None:
        _log("--ps_min_servers/--ps_max_servers need --ps_snapshot_secs "
             "and --log_dir (migration stages shadows in the snapshot "
             "dir); fleet resizing disabled")

    def spawn_server(i, attempt=0):
        env = dict(os.environ, **(env_extra or {}), **cache_env)
        env.update({
            # a pserver is host-only: it must never take the chip from
            # a trainer
            "JAX_PLATFORMS": "cpu",
            "TRAINING_ROLE": "PSERVER",
            "PADDLE_TRAINER_ID": str(i),
            "PADDLE_TRAINERS_NUM": str(worker_num),
            "PADDLE_PSERVER_ENDPOINTS": server_eps,
            "PADDLE_CURRENT_ENDPOINT": f"{host}:{ports[i]}",
            # run_pserver's exporter hookup: pserver-side metrics land
            # at rank<worker_num + i>.prom (offset past the trainers).
            # A DEDICATED env var, NOT PADDLE_HEARTBEAT_DIR: pservers
            # share the trainer id numbering, and handing them the
            # heartbeat env would make a role-shared script's
            # Heartbeat.from_env()/RankExporter.from_env() (the
            # documented worker hookup) clobber trainer i's files —
            # the pserver's beat could even mask a hung trainer i from
            # the watchdog
            "PT_PS_METRICS_DIR": hb_dir,
            "PADDLE_RESTART_COUNT": str(attempt),
        })
        if ps_state_dir:
            env["PT_PS_SNAPSHOT_DIR"] = ps_state_dir
            env["PT_PS_SNAPSHOT_SECS"] = str(ps_snapshot_secs)
        if fleet_elastic:
            env["PT_PS_ELASTIC"] = "1"
        return _spawn([sys.executable, "-u"] + script_args, env,
                      f"serverlog.{i}", log_dir, append=attempt > 0)

    def spawn_worker(i, attempt):
        env = dict(os.environ, **(env_extra or {}), **cache_env,
                   **pm_env, **tr_env)
        env.update(_chip_env(i, worker_num, env))
        env.update({
            "TRAINING_ROLE": "TRAINER",
            "PADDLE_TRAINER_ID": str(i),
            "PADDLE_TRAINERS_NUM": str(worker_num),
            "PADDLE_PSERVER_ENDPOINTS": server_eps,
            "PADDLE_CURRENT_ENDPOINT": f"{host}:{wports[i]}",
            "PADDLE_TRAINER_ENDPOINTS": worker_eps,
            # only workers heartbeat: pservers share the same
            # PADDLE_TRAINER_ID numbering, and their request loop has no
            # natural beat cadence — the watchdog watches trainers
            "PADDLE_HEARTBEAT_DIR": hb_dir,
            "PADDLE_RESTART_COUNT": str(attempt),
        })
        if fleet_elastic:
            # where a trainer (or an operator) drops resize triggers,
            # and where it can watch fleet_epoch.json for the commit
            env["PT_PS_ELASTIC_DIR"] = resize_dir
            env["PT_PS_STATE_DIR"] = ps_state_dir
        return _spawn([sys.executable, "-u"] + script_args, env,
                      f"workerlog.{i}", log_dir, append=attempt > 0)

    servers, workers, logs = {}, {}, []
    restarts = [0] * worker_num
    server_restarts = [0] * hi
    active = list(range(server_num))    # indices of the serving fleet
    flagged_stragglers = set()          # per-launch straggler memory
    # pserver liveness probe: a wedged-but-alive pserver (process up,
    # request loop stuck) stalls every trainer with nothing else to
    # notice it. Armed only when BOTH the hang watchdog and failover
    # are on: killing a slow-but-recoverable server is only an
    # improvement when a warm-booting respawn follows — without
    # --ps_snapshot_secs a probe kill would turn a survivable stall
    # into job teardown, changing pre-failover --hang_timeout
    # semantics
    ps_watch = (_PsWatch(hi)
                if hang_timeout is not None and server_num
                and ps_elastic else None)
    ps_probe_interval = (max(0.5, min(hang_timeout / 3.0, 5.0))
                         if ps_watch else None)
    # probes run serially inside the ONE supervision loop, and only a
    # WEDGED server pays its full timeout (a healthy one answers in
    # ms, a dead one refuses instantly) — so the per-probe timeout is
    # divided by the server count to bound the worst-case loop stall
    # (all servers wedged) at ~hang_timeout/4 per round, keeping
    # trainer reaping / respawn timers / the global deadline serviced
    ps_probe_timeout = (
        max(0.2, min(2.0, hang_timeout / (4.0 * max(server_num, 1))))
        if ps_watch else None)
    next_ps_probe = (time.monotonic() + ps_probe_interval
                     if ps_watch else None)
    health.reset(hb_dir, worker_num)    # a reused log_dir must not
                                        # vouch for the new run
    deadline = None if timeout is None else time.monotonic() + timeout
    term = threading.Event()
    # handler first, spawning inside the try: a spawn failure mid-gang
    # or a SIGTERM in the spawn window must still drain the children
    # already running
    undo = _install_term_handler(term)
    started = time.time()
    warned_slow = False

    def all_procs():
        return list(servers.values()) + list(workers.values())

    # worker idx -> monotonic respawn time: backoff never blocks the
    # supervision loop (a sleeping supervisor would miss pserver
    # deaths, other workers' faults, preemption, and the global
    # deadline for up to the backoff cap)
    pending_respawn = {}
    # pserver idx -> monotonic respawn time (same non-blocking idiom)
    pending_ps_respawn = {}
    # one in-flight fleet-resize request: {"kind", "attempts", "due"}
    pending_resize = None

    def do_resize(kind):
        """One epoch-fenced migration attempt (grow appends index
        len(active), shrink retires max(active)). Returns None on
        success; on any failure the migration has already rolled back
        to the old epoch and the failure description is returned."""
        from paddle_tpu.distributed import membership
        cur_eps = [f"{host}:{ports[i]}" for i in active]
        if kind == "grow":
            ni = len(active)
            name = f"pserver {ni}"
            if name not in servers or servers[name].poll() is not None:
                p, f = spawn_server(ni, server_restarts[ni])
                servers[name] = p
                logs.append(f)
            new_ep = f"{host}:{ports[ni]}"
            ready_by = time.monotonic() + 20.0
            while True:
                ok = ps_probe(new_ep, timeout=1.0)
                if ok:
                    break
                if ok is None:
                    # no wire codec in the launcher process means the
                    # migration RPCs below cannot run either
                    return ("wire codec unavailable in the launcher "
                            "process; fleet resize needs it")
                if servers[name].poll() is not None:
                    return f"new pserver {ni} died while booting"
                if time.monotonic() > ready_by:
                    return f"new pserver {ni} not serving after 20s"
                time.sleep(0.25)
            new_eps = cur_eps + [new_ep]
        else:
            ni = max(active)
            new_eps = [f"{host}:{ports[i]}" for i in active
                       if i != ni]
        # every participant must be SERVING (not merely alive) before
        # the migration RPCs start: a respawned-but-still-booting
        # server would otherwise burn a whole retry attempt
        ready_by = time.monotonic() + 20.0
        for ep in sorted(set(cur_eps) | set(new_eps)):
            while not ps_probe(ep, timeout=1.0):
                if time.monotonic() > ready_by:
                    return f"pserver {ep} not serving; resize needs " \
                           f"the whole fleet reachable"
                time.sleep(0.25)
        try:
            epoch, rows = membership.run_migration(
                ps_state_dir, cur_eps, new_eps, log=_log)
        except membership.MigrationError as e:
            return str(e)
        if kind == "grow":
            active.append(ni)
        else:
            active.remove(ni)
            # retire grace: clients still routed at the old epoch
            # learn the committed map via WRONG_EPOCH (or the
            # EPOCH_MAP probe once this endpoint refuses) — give the
            # in-flight requests a moment before the refusals start
            time.sleep(_ps_retire_grace())
            p = servers.pop(f"pserver {ni}", None)
            if p is not None:
                _drain([p], grace_period)
            pending_ps_respawn.pop(ni, None)
            if ps_watch:
                ps_watch.forget(ni)
        # the PS analog of the trainer-side sweep_stale_ranks: a
        # retired server's rank<worker_num+i>.hb/.prom files must not
        # linger in the metrics.prom aggregate
        health.sweep_stale_ranks(hb_dir, worker_num + len(active))
        _log(f"pserver fleet resize '{kind}' committed at epoch "
             f"{epoch}: now {len(active)} server(s), {rows} row(s) "
             f"migrated")
        return None

    def fail_server(i, why):
        """Pserver restart policy (only reachable with failover armed):
        respawn pserver i at the SAME endpoint after backoff — the
        respawned process warm-boots from the last-good snapshot and
        the trainers' clients reconnect — until the per-server budget
        is spent; then tear down the whole job (its hosted state is
        gone past recovery)."""
        if server_restarts[i] >= max_restarts:
            _log(f"pserver {i} {why}; restart budget {max_restarts} "
                 f"exhausted, tearing down the job")
            _drain(all_procs(), grace_period)
            return False
        delay = backoff_delay(server_restarts[i])
        server_restarts[i] += 1
        _m_ps_restarts.inc()
        _log(f"pserver {i} {why}; respawning at {host}:{ports[i]} "
             f"{server_restarts[i]}/{max_restarts} after {delay:.1f}s "
             f"backoff (warm boot from {ps_state_dir})")
        pending_ps_respawn[i] = time.monotonic() + delay
        if ps_watch:
            ps_watch.forget(i)
        return True

    def fail_worker(i, why):
        """Individual-worker restart policy: respawn worker i after
        backoff while the pservers (whose hosted state would be lost in
        a gang restart) stay up; give up once the budget is spent."""
        if restarts[i] >= max_restarts:
            if max_restarts:
                _log(f"trainer {i} {why}; restart budget {max_restarts} "
                     f"exhausted, tearing down the job")
            _drain(all_procs(), grace_period)
            return False
        delay = backoff_delay(restarts[i])
        restarts[i] += 1
        _m_restarts.inc()
        _report_postmortems(log_dir, f"trainer {i} {why}")
        _log(f"trainer {i} {why}; restarting worker "
             f"{restarts[i]}/{max_restarts} after {delay:.1f}s backoff "
             f"(pservers stay up)")
        pending_respawn[i] = time.monotonic() + delay
        return True

    try:
        try:
            for i in range(server_num):
                p, f = spawn_server(i)
                servers[f"pserver {i}"] = p
                logs.append(f)
            for i in range(worker_num):
                p, f = spawn_worker(i, 0)
                workers[i] = p
                logs.append(f)
        except Exception:
            _drain(all_procs(), grace_period)
            raise
        rc = 0
        done_workers = set()
        next_status = time.monotonic() + STATUS_INTERVAL
        while servers or (set(workers) - done_workers):
            if time.monotonic() >= next_status:
                next_status = time.monotonic() + STATUS_INTERVAL
                _status_tick(hb_dir, log_dir, sum(restarts),
                             flagged_stragglers)
            if term.is_set():
                live = [n for n, p in servers.items() if p.poll() is None]
                live += [f"trainer {i}" for i, p in workers.items()
                         if p.poll() is None]
                _log(f"SIGTERM: forwarding to {live} with "
                     f"{grace_period}s grace for checkpoint flush")
                if not _drain(all_procs(), grace_period):
                    _log("grace period expired; SIGKILLed stragglers")
                return PREEMPTED_RC
            if deadline is not None and time.monotonic() > deadline:
                _log("timeout; killing survivors")
                _drain(all_procs(), grace_period)
                return 124
            for name, p in list(servers.items()):
                r = p.poll()
                if r is None:
                    continue
                del servers[name]
                if r != 0:
                    _log(f"{name} exited with code {r}{_rc_label(r)}")
                    i = int(name.rsplit(None, 1)[-1])
                    if ps_elastic:
                        if not fail_server(i, f"died (rc={r})"):
                            return r
                        continue
                    # without snapshots a dead pserver loses hosted
                    # state no worker restart can recover — fail fast
                    _drain(all_procs(), grace_period)
                    return r
            for i, due in list(pending_ps_respawn.items()):
                if time.monotonic() < due:
                    continue
                del pending_ps_respawn[i]
                p, f = spawn_server(i, server_restarts[i])
                servers[f"pserver {i}"] = p
                logs.append(f)
            if fleet_elastic and pending_resize is None:
                kind = _take_ps_resize_request(resize_dir)
                if kind == "grow" and len(active) >= hi:
                    _log(f"ignoring pserver grow request: already at "
                         f"--ps_max_servers ({hi})")
                elif kind == "shrink" and len(active) <= lo:
                    _log(f"ignoring pserver shrink request: already "
                         f"at --ps_min_servers ({lo})")
                elif kind:
                    pending_resize = {"kind": kind, "attempts": 0,
                                      "due": time.monotonic()}
                    _log(f"pserver fleet resize requested: {kind} "
                         f"(currently {len(active)} server(s))")
            if (pending_resize is not None
                    and time.monotonic() >= pending_resize["due"]
                    and not pending_ps_respawn
                    and all(p.poll() is None
                            for p in servers.values())):
                err = do_resize(pending_resize["kind"])
                if err is None:
                    pending_resize = None
                else:
                    # every failed attempt already rolled back to the
                    # old epoch — nothing is lost, only not-yet-resized
                    _m_ps_migration_aborts.inc()
                    pending_resize["attempts"] += 1
                    budget = _ps_resize_retries()
                    if pending_resize["attempts"] >= budget:
                        _log(f"pserver fleet resize "
                             f"'{pending_resize['kind']}' ABANDONED "
                             f"after {budget} aborted attempt(s) "
                             f"(last: {err}); tearing down "
                             f"[exit {MIGRATE_RC}]")
                        _drain(all_procs(), grace_period)
                        return MIGRATE_RC
                    delay = backoff_delay(pending_resize["attempts"])
                    _log(f"pserver fleet resize attempt "
                         f"{pending_resize['attempts']}/{budget} "
                         f"aborted + rolled back ({err}); retrying "
                         f"in {delay:.1f}s")
                    pending_resize["due"] = time.monotonic() + delay
            if ps_watch is not None and time.monotonic() >= next_ps_probe:
                next_ps_probe = time.monotonic() + ps_probe_interval
                for i in list(active):
                    p = servers.get(f"pserver {i}")
                    if (p is None or p.poll() is not None
                            or i in pending_ps_respawn):
                        continue
                    ok = ps_probe(f"{host}:{ports[i]}",
                                  timeout=ps_probe_timeout)
                    if ok is None:      # codec unavailable: disabled
                        ps_watch = None
                        _log("pserver liveness probe disabled (wire "
                             "codec unavailable in the launcher "
                             "process)")
                        break
                    ps_watch.observe(i, ok)
                for i, age in (ps_watch.wedged(hang_timeout)
                               if ps_watch else []):
                    p = servers.get(f"pserver {i}")
                    if p is None or p.poll() is not None:
                        continue
                    _m_watchdog.inc()
                    _log(f"watchdog: pserver {i} wedged — answered "
                         f"its liveness probe, then stopped for "
                         f"{age:.1f}s (hang_timeout={hang_timeout}s); "
                         f"killing it")
                    # no grace: a wedged request loop won't act on
                    # SIGTERM; the death is handled next poll
                    # (respawn under the budget, or fail fast)
                    _drain([p], 0.0)
                    ps_watch.forget(i)
                if ps_watch:
                    for i in list(active):
                        p = servers.get(f"pserver {i}")
                        if (p is not None and p.poll() is None
                                and i not in pending_ps_respawn
                                and time.time() - started > hang_timeout
                                and ps_watch.slow(i)):
                            _log(f"watchdog: pserver {i} slow — no "
                                 f"probe reply yet (not killed: only "
                                 f"a server that answered then "
                                 f"stopped counts as wedged)")
            for i, due in list(pending_respawn.items()):
                if time.monotonic() < due:
                    continue
                del pending_respawn[i]
                try:
                    os.remove(health.heartbeat_path(hb_dir, i))
                except OSError:
                    pass
                p, f = spawn_worker(i, restarts[i])
                workers[i] = p
                logs.append(f)
            for i, p in list(workers.items()):
                if i in done_workers or i in pending_respawn:
                    continue
                r = p.poll()
                if r is None:
                    continue
                if r == 0:
                    done_workers.add(i)
                    continue
                _log(f"trainer {i} exited with code {r}{_rc_label(r)}")
                if not fail_worker(i, f"failed (rc={r})"):
                    return r
            if hang_timeout is not None:
                alive_w = [i for i, p in workers.items()
                           if p.poll() is None and i not in done_workers]
                stale = [(r, age) for r, age in health.stale_ranks(
                    hb_dir, worker_num, hang_timeout) if r in alive_w]
                if stale:
                    i, age = stale[0]
                    _m_watchdog.inc()
                    _log(f"watchdog: trainer {i} hung — last heartbeat "
                         f"{age:.1f}s ago (hang_timeout={hang_timeout}s); "
                         f"killing worker")
                    # no grace: a hung worker won't act on SIGTERM, and
                    # waiting would stall the supervision of everyone
                    # else (the invariant pending_respawn preserves)
                    _drain([workers[i]], 0.0)
                    if not fail_worker(i, f"hung ({age:.1f}s without "
                                          f"heartbeat)"):
                        return 1
                elif not warned_slow and time.time() - started > hang_timeout:
                    silent = [r for r in health.silent_ranks(
                        hb_dir, worker_num) if r in alive_w]
                    if silent:
                        _log(f"watchdog: trainer(s) {silent} slow — no "
                             f"heartbeat yet (not killed: only a rank "
                             f"that beat then stopped counts as hung)")
                    warned_slow = True
            time.sleep(0.2)
        _status_tick(hb_dir, log_dir, sum(restarts),
                     flagged_stragglers)
        return rc
    except KeyboardInterrupt:
        for p in all_procs():
            if p.poll() is None:
                p.send_signal(signal.SIGINT)
        raise
    finally:
        undo()
        _merge_job_trace(log_dir)
        if hb_tmp:
            shutil.rmtree(hb_dir, ignore_errors=True)
        for f in logs:
            if f:
                f.close()


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="spawn one training process per rank (launch.py "
                    "parity) with elastic supervision")
    ap.add_argument("--nproc_per_node", type=int, default=1,
                    help="collective mode: trainers on this node. "
                         "Default 1: one process drives every local "
                         "chip (the JAX model). With more, rank r is "
                         "pinned to chip r through libtpu's "
                         "environment and sees that one device; ranks "
                         "on JAX_PLATFORMS=cpu share the host freely.")
    ap.add_argument("--ips", default="127.0.0.1")
    ap.add_argument("--started_port", type=int, default=None,
                    help="first port of the claimed range; collective "
                         "mode claims 2*nproc consecutive ports "
                         "(trainer endpoints, then global_shuffle "
                         "exchange endpoints). The full range is "
                         "bind-probed up front and the launch fails "
                         "fast on any collision.")
    ap.add_argument("--server_num", type=int, default=0,
                    help="ps mode: pserver process count")
    ap.add_argument("--worker_num", type=int, default=0,
                    help="ps mode: trainer process count")
    ap.add_argument("--log_dir", default=None)
    ap.add_argument("--max_restarts", type=int, default=0,
                    help="restart budget for failed/hung ranks with "
                         "exponential backoff: collective mode restarts "
                         "the whole gang, ps mode restarts individual "
                         "workers (per-worker budget) while pservers "
                         "stay up")
    ap.add_argument("--min_ranks", type=int, default=None,
                    help="collective mode: make the gang elastic — a "
                         "rank exiting with code 31 (rank departed: "
                         "spot reclaim / node repair) shrinks the next "
                         "incarnation to the surviving world size, "
                         "down to this floor (below it the job gives "
                         "up). Default: fixed gang (today's "
                         "semantics). Workers see the incarnation's "
                         "world size in PADDLE_TRAINERS_NUM; restore() "
                         "re-shards checkpoints across the change.")
    ap.add_argument("--max_ranks", type=int, default=None,
                    help="collective mode: admit late-joining ranks at "
                         "the next restart boundary, growing the gang "
                         "up to this ceiling — a join is requested by "
                         "dropping a file named join.<anything> in "
                         "<log_dir>/elastic/. Default: fixed gang.")
    ap.add_argument("--ps_snapshot_secs", type=float, default=None,
                    help="ps mode: arm pserver failover — each pserver "
                         "snapshots its hosted state (integrity-"
                         "manifested, atomically published) to "
                         "<log_dir>/ps_state every this many seconds "
                         "on a background thread, a dead pserver is "
                         "respawned at its endpoint under the "
                         "--max_restarts budget and warm-boots from "
                         "the last-good snapshot, and (with "
                         "--hang_timeout) a wedged-but-alive pserver "
                         "is probe-detected and restarted too. "
                         "Default: off (a pserver death tears the job "
                         "down, today's semantics). See "
                         "docs/ELASTIC_TRAINING.md 'Pserver failover'.")
    ap.add_argument("--ps_min_servers", type=int, default=None,
                    help="ps mode: arm pserver fleet elasticity — the "
                         "fleet may shrink down to this floor via "
                         "epoch-fenced live migration (requires "
                         "--ps_snapshot_secs + --log_dir). A shrink is "
                         "requested by dropping a file named "
                         "ps_shrink.<anything> in <log_dir>/elastic/. "
                         "Default: fixed fleet.")
    ap.add_argument("--ps_max_servers", type=int, default=None,
                    help="ps mode: allow the fleet to grow up to this "
                         "ceiling (ports for the whole range are "
                         "claimed up front; a grow is requested via a "
                         "ps_grow.<anything> file in "
                         "<log_dir>/elastic/). Each resize is a "
                         "two-phase migration that rolls back on any "
                         "failure; after PT_PS_RESIZE_RETRIES aborted "
                         "attempts the job exits 41. See "
                         "docs/ELASTIC_TRAINING.md 'Resizing the "
                         "pserver fleet'.")
    ap.add_argument("--hang_timeout", type=float, default=None,
                    help="hang watchdog: kill+restart a gang whose rank "
                         "heartbeat once and then stopped for this many "
                         "seconds (see distributed/health.py; "
                         "auto_checkpoint heartbeats automatically)")
    ap.add_argument("--grace_period", type=float, default=10.0,
                    help="seconds between SIGTERM (forwarded on "
                         "launcher preemption, or sent before any "
                         "teardown) and SIGKILL — the window for "
                         "CheckpointManager.wait() to flush")
    ap.add_argument("--timeout", type=float, default=None,
                    help="global wall-clock budget across all restarts; "
                         "exceeded -> kill everything, exit 124")
    ap.add_argument("training_script")
    ap.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    script = [args.training_script] + args.training_script_args
    if args.server_num or args.worker_num:
        rc = launch_ps(script, args.server_num, max(args.worker_num, 1),
                       args.started_port, args.log_dir,
                       timeout=args.timeout,
                       max_restarts=args.max_restarts,
                       hang_timeout=args.hang_timeout,
                       grace_period=args.grace_period,
                       ps_snapshot_secs=args.ps_snapshot_secs,
                       ps_min_servers=args.ps_min_servers,
                       ps_max_servers=args.ps_max_servers)
    else:
        rc = launch_collective(script, args.nproc_per_node,
                               args.started_port, args.ips,
                               args.log_dir, timeout=args.timeout,
                               max_restarts=args.max_restarts,
                               hang_timeout=args.hang_timeout,
                               grace_period=args.grace_period,
                               min_ranks=args.min_ranks,
                               max_ranks=args.max_ranks)
    sys.exit(rc)


if __name__ == "__main__":
    main()
