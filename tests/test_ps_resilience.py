"""Fault-tolerant parameter servers (docs/ELASTIC_TRAINING.md
"Pserver failover").

Layers: (1) npz integrity-artifact units (io_checkpoint.publish_npz /
verify_npz); (2) the generational pserver snapshot store — save/prune/
restore, quarantine-and-walk-back, slot/round continuity, legacy
artifacts, the background snapshot thread; (3) client failover —
incarnation detection, round resync + staleness accounting, reconnect
budgets; (4) supervisor machinery — liveness probe, wedge bookkeeping,
exit-code labels; (5) fsck's pserver verdicts; (6) two slow e2e runs
through the real launcher proving the headline: a pserver killed
mid-training is respawned, warm-boots from its last-good snapshot
(walking back past a bit-flipped one), the trainers reconnect, and the
job exits 0 with the recovery visible in the exported metrics.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import launch as launch_mod
from paddle_tpu.distributed import ps as ps_mod
from paddle_tpu.distributed.ps import (
    ParameterServer, PSClient, _ps_complete_gens, _ps_dense_path,
    _ps_tag,
)
from paddle_tpu.io_checkpoint import (
    CheckpointCorruptError, publish_npz, verify_npz,
)
from paddle_tpu.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _mk_server(port=0, optimizer=None, sparse=True, n_trainers=1,
               sync=True):
    s = ParameterServer(f"127.0.0.1:{port}", n_trainers, sync)
    s.host_dense("w", np.ones(4, np.float32),
                 optimizer or pt.optimizer.SGDOptimizer(0.5))
    if sparse:
        s.host_sparse("emb", dim=3, seed=0, lr=1.0,
                      optimizer="adagrad")
    return s


# ---------------------------------------------------------------------------
# npz integrity artifacts
# ---------------------------------------------------------------------------
class TestNpzArtifacts:
    def test_roundtrip_with_body(self, tmp_path):
        p = str(tmp_path / "a.npz")
        publish_npz(p, {"w": np.arange(6, dtype=np.float32)},
                    {"kind": "pserver_dense", "gen": 3})
        m, a = verify_npz(p)
        assert m["kind"] == "pserver_dense" and m["gen"] == 3
        np.testing.assert_array_equal(a["w"],
                                      np.arange(6, dtype=np.float32))
        assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]

    def test_truncated_is_corrupt(self, tmp_path):
        p = str(tmp_path / "a.npz")
        publish_npz(p, {"w": np.arange(64, dtype=np.float32)})
        os.truncate(p, os.path.getsize(p) // 2)
        with pytest.raises(CheckpointCorruptError):
            verify_npz(p)

    def test_bitflip_is_corrupt_naming_array(self, tmp_path):
        p = str(tmp_path / "a.npz")
        publish_npz(p, {"w": np.arange(64, dtype=np.float32)})
        faults.corrupt_checkpoint(p, "bitflip")
        with pytest.raises(CheckpointCorruptError):
            verify_npz(p)

    def test_legacy_raw_npz_accepted(self, tmp_path):
        p = str(tmp_path / "a.npz")
        np.savez(p, w=np.ones(3))
        m, a = verify_npz(p)
        assert m is None and list(a) == ["w"]

    def test_empty_array_roundtrip(self, tmp_path):
        # the empty-sparse-table case that broke _crc32's memoryview
        p = str(tmp_path / "a.npz")
        publish_npz(p, {"ids": np.zeros((0,), np.int64),
                        "rows": np.zeros((0, 3), np.float32)})
        _, a = verify_npz(p)
        assert a["rows"].shape == (0, 3)


# ---------------------------------------------------------------------------
# the generational snapshot store
# ---------------------------------------------------------------------------
class TestSnapshotStore:
    def test_generations_accumulate_and_prune(self, tmp_path):
        d = str(tmp_path)
        s = _mk_server(port=7101)
        tag = _ps_tag(s.host, s.port)
        for i in range(3):
            s.dense["w"].push_async(np.ones(4, np.float32))
            s.save(d)
        gens = [g for g, _ in _ps_complete_gens(d, tag)]
        assert gens == [1, 2]          # keep=2: gen 0 pruned
        # no gen-0 leftovers of any kind
        assert not [f for f in os.listdir(d) if ".gen0." in f]

    def test_warm_boot_restores_rounds_and_momentum_slots(
            self, tmp_path):
        d = str(tmp_path)
        opt = pt.optimizer.MomentumOptimizer(0.5, momentum=0.9)
        s = _mk_server(port=7102, optimizer=opt)
        g = np.full(4, 1.0, np.float32)
        for _ in range(3):
            s.dense["w"].push_async(g)
        s.save(d)
        # control: the 4th push on the UNinterrupted server
        s.dense["w"].push_async(g)
        control = np.array(s.dense["w"].value)

        s2 = _mk_server(port=7102,
                        optimizer=pt.optimizer.MomentumOptimizer(
                            0.5, momentum=0.9))
        meta = s2.load(d)
        assert meta is not None and meta["gen"] == 0
        assert s2.dense["w"].round == 3
        assert s2.dense["w"].step_count == 3
        # slot continuity: replaying the lost push lands EXACTLY where
        # the uninterrupted server did — momentum velocity survived
        s2.dense["w"].push_async(g)
        np.testing.assert_allclose(s2.dense["w"].value, control)

    def test_sparse_adagrad_accumulators_survive(self, tmp_path):
        d = str(tmp_path)
        s = _mk_server(port=7103)
        s.sparse["emb"].pull(np.asarray([5], np.int64))
        g = np.full((1, 3), 2.0, np.float32)
        s.sparse["emb"].push([5], g)
        s.save(d)
        s.sparse["emb"].push([5], g)
        control = s.sparse["emb"].pull(np.asarray([5], np.int64))

        s2 = _mk_server(port=7103)
        assert s2.load(d) is not None
        s2.sparse["emb"].push([5], g)
        np.testing.assert_allclose(
            s2.sparse["emb"].pull(np.asarray([5], np.int64)), control)

    def test_torn_newest_gen_walks_back_and_quarantines(
            self, tmp_path, capfd):
        """The satellite regression: a half-written artifact must walk
        the restore back to the previous generation, never crash it."""
        d = str(tmp_path)
        s = _mk_server(port=7104)
        s.dense["w"].push_async(np.ones(4, np.float32))
        s.save(d)
        v_gen0 = np.array(s.dense["w"].value)
        s.dense["w"].push_async(np.ones(4, np.float32))
        s.save(d)
        tag = _ps_tag(s.host, s.port)
        newest = _ps_complete_gens(d, tag)[-1][0]
        path = _ps_dense_path(d, tag, newest)
        os.truncate(path, os.path.getsize(path) // 2)

        s2 = _mk_server(port=7104)
        meta = s2.load(d)
        assert meta is not None and meta["gen"] == 0
        np.testing.assert_allclose(s2.dense["w"].value, v_gen0)
        assert s2.dense["w"].round == 1
        corrupts = [f for f in os.listdir(d) if f.endswith(".corrupt")]
        assert any(f".gen{newest}." in f for f in corrupts)
        err = capfd.readouterr().err
        assert "quarantined corrupt snapshot generation" in err
        assert "restored from last-good snapshot generation 0" in err

    def test_all_gens_corrupt_returns_none(self, tmp_path, capfd):
        d = str(tmp_path)
        s = _mk_server(port=7105)
        s.save(d)
        tag = _ps_tag(s.host, s.port)
        os.truncate(_ps_dense_path(d, tag, 0), 10)
        s2 = _mk_server(port=7105)
        assert s2.load(d) is None
        assert "starting from initial values" in capfd.readouterr().err

    def test_quarantined_gen_number_never_reused(self, tmp_path):
        d = str(tmp_path)
        s = _mk_server(port=7106)
        s.save(d)                       # gen 0
        tag = _ps_tag(s.host, s.port)
        os.truncate(_ps_dense_path(d, tag, 0), 10)
        s2 = _mk_server(port=7106)
        s2.load(d)                      # quarantines gen 0
        s2.save(d)                      # must pick gen 1, not 0
        assert [g for g, _ in _ps_complete_gens(d, tag)] == [1]

    def test_legacy_plain_artifacts_restore(self, tmp_path):
        """Pre-generation layout (raw np.savez, un-suffixed names)
        stays restorable."""
        d = str(tmp_path)
        s = _mk_server(port=7107)
        tag = _ps_tag(s.host, s.port)
        np.savez(os.path.join(d, f"pserver_{tag}.npz"),
                 w=np.full(4, 9.0, np.float32))
        ids = np.asarray([3], np.int64)
        np.savez(os.path.join(d, f"pserver_{tag}_emb.npz"),
                 ids=ids, rows=np.full((1, 3), 2.0, np.float32),
                 accum=np.zeros((1, 3), np.float32))
        meta = s.load(d)
        assert meta == {"gen": None, "legacy": True}
        np.testing.assert_allclose(s.dense["w"].value, 9.0)
        np.testing.assert_allclose(s.sparse["emb"].pull(ids), 2.0)

    def test_truncated_legacy_artifact_quarantined_not_crash(
            self, tmp_path):
        """The satellite's exact wording: a crash mid-save used to
        leave a half-written npz that np.load exploded on — restore
        must quarantine it and proceed, never crash."""
        d = str(tmp_path)
        s = _mk_server(port=7108)
        tag = _ps_tag(s.host, s.port)
        p = os.path.join(d, f"pserver_{tag}.npz")
        np.savez(p, w=np.full(4, 9.0, np.float32))
        os.truncate(p, os.path.getsize(p) // 2)
        meta = s.load(d)                # must NOT raise
        assert meta is None
        assert os.path.exists(p + ".corrupt")
        np.testing.assert_allclose(s.dense["w"].value, 1.0)  # initial

    def test_snapshot_thread_runs_off_request_path(self, tmp_path):
        d = str(tmp_path)
        s = _mk_server(port=7109)
        before = ps_mod._m_snap_saves.value()
        s.start_snapshots(d, interval=0.05)
        s.dense["w"].push_async(np.ones(4, np.float32))
        tag = _ps_tag(s.host, s.port)
        deadline = time.monotonic() + 10
        while (not _ps_complete_gens(d, tag)
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert _ps_complete_gens(d, tag), "no generation published"
        s.stop_snapshots(final_save=True)
        assert s._snap_thread is None
        assert ps_mod._m_snap_saves.value() > before
        # final_save flushed once more after the join
        gens = _ps_complete_gens(d, tag)
        assert gens

    def test_start_snapshots_validates(self, tmp_path):
        s = _mk_server(port=7110)
        with pytest.raises(Exception):
            s.start_snapshots(str(tmp_path), interval=0)

    def test_warm_boot_io_blip_raises_not_rewinds(self, tmp_path,
                                                  monkeypatch):
        """Review pin (blip-is-not-corruption): a persistent I/O error
        listing/reading the snapshot dir must RAISE out of load() —
        silently treating it as 'no generations' would warm-boot
        initial values and discard training."""
        d = str(tmp_path)
        s = _mk_server(port=7111)
        s.save(d)
        real_listdir = os.listdir

        def flaky_listdir(path):
            if str(path) == d:
                raise OSError(5, "Input/output error", path)
            return real_listdir(path)

        monkeypatch.setattr(os, "listdir", flaky_listdir)
        s2 = _mk_server(port=7111)
        with pytest.raises(OSError):
            s2.load(d)
        # and the next save must not guess generation 0 over the blip
        with pytest.raises(OSError):
            s.save(d)

    def test_tmp_sweep_spares_sibling_prefix_tag(self, tmp_path):
        """Review pin: tags sharing a string prefix (ports 1234 vs
        12345) live in ONE shared ps_state dir — server A's sweep must
        not unlink server B's in-flight publish temp."""
        d = str(tmp_path)
        mine = os.path.join(d, ".pserver_127_0_0_1_1234.gen0.npz."
                               "abc.tmp.npz")
        sibling = os.path.join(d, ".pserver_127_0_0_1_12345.gen0.npz."
                                  "abc.tmp.npz")
        sib_table = os.path.join(d, ".pserver_127_0_0_1_12345_emb."
                                    "gen0.npz.abc.tmp.npz")
        for p in (mine, sibling, sib_table):
            open(p, "w").close()
        ps_mod._ps_sweep_tmps(d, "127_0_0_1_1234")
        assert not os.path.exists(mine)
        assert os.path.exists(sibling) and os.path.exists(sib_table)


@pytest.mark.skipif(not __import__("paddle_tpu.native",
                                   fromlist=["available"]).available(),
                    reason="native toolchain unavailable")
class TestNativeTransportSnapshots:
    def test_cross_transport_artifact_contract(self, tmp_path):
        """A snapshot written by the C++ server restores into the
        Python server (and the native round/slot accessors work)."""
        from paddle_tpu.distributed.ps import NativeParameterServer
        d = str(tmp_path)
        port = _free_port()
        opt = pt.optimizer.MomentumOptimizer(0.5, momentum=0.9)
        s = NativeParameterServer(f"127.0.0.1:{port}", 1, True)
        s.host_dense("w", np.ones(4, np.float32), opt)
        s.start()
        c = PSClient([s.endpoint], {"w": s.endpoint})
        g = np.full(4, 1.0, np.float32)
        for _ in range(3):
            c.push_grad("w", g)
        s.save(d)
        c.push_grad("w", g)
        control = np.array(s.dense["w"].value)
        s.stop()
        c.close()

        py = ParameterServer(f"127.0.0.1:{port}", 1, True)
        py.host_dense("w", np.ones(4, np.float32),
                      pt.optimizer.MomentumOptimizer(0.5, momentum=0.9))
        assert py.load(d) is not None
        assert py.dense["w"].round == 3
        py.dense["w"].push_async(g)
        np.testing.assert_allclose(py.dense["w"].value, control)


# ---------------------------------------------------------------------------
# client failover: incarnation detection, round resync, reconnects
# ---------------------------------------------------------------------------
class TestClientFailover:
    def test_restart_detection_resync_and_staleness(self, tmp_path):
        d = str(tmp_path)
        port = _free_port()
        s = _mk_server(port=port, sparse=False).start()
        c = PSClient([s.endpoint], {"w": s.endpoint}, trainer_id=0)
        g = np.full(4, 1.0, np.float32)
        for _ in range(3):
            c.push_grad("w", g)
        s.save(d)
        c.push_grad("w", g)             # round 4, lost with the crash
        control = np.array(s.dense["w"].value)
        s.stop()
        c.close()                       # a real crash severs sockets

        s2 = _mk_server(port=port, sparse=False)
        assert s2.load(d) is not None
        s2.start()
        stale0 = ps_mod._m_stale_rounds.value()
        t0 = time.monotonic()
        got = c.pull_param("w", 4)      # would block 120 s without resync
        assert time.monotonic() - t0 < 30
        assert ps_mod._m_stale_rounds.value() - stale0 == 1
        # replaying the lost round lands exactly on the control value
        c.push_grad("w", g)
        got = c.pull_param("w", 5)      # offset 1 -> effective round 4
        np.testing.assert_allclose(got, control)
        s2.stop()

    def test_refused_budget_bounds_downtime_wait(self, monkeypatch):
        monkeypatch.setenv("PT_PS_RECONNECT_SECS", "0.6")
        port = _free_port()
        c = PSClient([f"127.0.0.1:{port}"],
                     {"w": f"127.0.0.1:{port}"})
        t0 = time.monotonic()
        with pytest.raises(OSError):
            c.pull_param("w", 0)
        dt = time.monotonic() - t0
        assert 0.3 < dt < 10

    def test_reconnect_survives_mid_call_downtime(self, monkeypatch):
        """A call issued while the server is DOWN succeeds once it
        comes back within the budget — the supervised-failover
        window."""
        monkeypatch.setenv("PT_PS_RECONNECT_SECS", "30")
        port = _free_port()
        c = PSClient([f"127.0.0.1:{port}"],
                     {"w": f"127.0.0.1:{port}"})
        srv = {}

        def bring_up():
            time.sleep(0.8)
            srv["s"] = _mk_server(port=port, sparse=False).start()

        th = threading.Thread(target=bring_up)
        th.start()
        rec0 = ps_mod._m_reconnects.value()
        try:
            out = c.pull_param("w", 0)
            np.testing.assert_allclose(out, 1.0)
            assert ps_mod._m_reconnects.value() > rec0
        finally:
            th.join()
            srv["s"].stop()

    def test_low_round_pull_does_not_disarm_resync(self, tmp_path):
        """Review pin: an armed restart-resync must survive pulls that
        don't outrun the reborn server (eval fetch / async
        min_round=0) — popping it there would leave the NEXT training
        pull deadlocking on a round the server will never reach."""
        d = str(tmp_path)
        port = _free_port()
        s = _mk_server(port=port, sparse=False).start()
        c = PSClient([s.endpoint], {"w": s.endpoint}, trainer_id=0)
        g = np.full(4, 1.0, np.float32)
        for _ in range(3):
            c.push_grad("w", g)
        s.save(d)
        c.push_grad("w", g)             # round 4, lost with the crash
        s.stop()
        c.close()
        s2 = _mk_server(port=port, sparse=False)
        assert s2.load(d) is not None
        s2.start()
        stale0 = ps_mod._m_stale_rounds.value()
        c.pull_param("w", 0)            # low-round pull: must NOT
        ep = s2.endpoint                # consume the armed resync
        assert ep in c._stale_pending
        t0 = time.monotonic()
        c.pull_param("w", 4)            # the training pull resyncs
        assert time.monotonic() - t0 < 30
        assert ps_mod._m_stale_rounds.value() - stale0 == 1
        s2.stop()

    def test_server_info_surface(self):
        s = _mk_server(port=0, sparse=False).start()
        try:
            c = PSClient([s.endpoint], {"w": s.endpoint})
            inc, rnd = c.server_info()
            assert inc == s.incarnation and rnd == 0
            c.push_grad("w", np.ones(4, np.float32))
            assert c.server_info()[1] == 1
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# supervisor machinery
# ---------------------------------------------------------------------------
class TestSupervisor:
    def test_exit_code_labels(self):
        assert faults.PS_CRASH_EXIT_CODE == 37
        assert 37 in launch_mod.EXIT_CODE_LABELS
        assert "pserver" in launch_mod.EXIT_CODE_LABELS[37]
        # distinct from every other labeled code
        assert len(set(launch_mod.EXIT_CODE_LABELS)) == \
            len(launch_mod.EXIT_CODE_LABELS)

    def test_probe_live_server_answers(self):
        s = _mk_server(port=0, sparse=False).start()
        try:
            assert launch_mod.ps_probe(s.endpoint, timeout=2.0) is True
        finally:
            s.stop()

    def test_probe_wedged_server_times_out(self):
        """The satellite case: a handler that stops answering — the
        socket ACCEPTS (process alive) but no reply ever comes."""
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        port = lst.getsockname()[1]
        conns = []

        def accept_and_sit():
            try:
                conns.append(lst.accept())
                time.sleep(5)
            except OSError:
                pass

        th = threading.Thread(target=accept_and_sit, daemon=True)
        th.start()
        try:
            t0 = time.monotonic()
            assert launch_mod.ps_probe(f"127.0.0.1:{port}",
                                       timeout=0.5) is False
            assert time.monotonic() - t0 < 3
        finally:
            lst.close()

    def test_probe_dead_endpoint_false(self):
        assert launch_mod.ps_probe(f"127.0.0.1:{_free_port()}",
                                   timeout=0.5) is False

    def test_ps_watch_wedge_asymmetry(self):
        w = launch_mod._PsWatch(2)
        w.observe(0, True, now=100.0)
        # 0 answered then went silent -> wedged; 1 never answered ->
        # slow (logged once), never wedged
        assert w.wedged(2.0, now=103.0) == [(0, 3.0)]
        assert w.slow(1) is True and w.slow(1) is False
        assert [i for i, _ in w.wedged(2.0, now=103.0)] == [0]
        w.forget(0)
        assert w.wedged(2.0, now=103.0) == []

    def test_snapshot_secs_without_log_dir_disables_failover(
            self, tmp_path, capfd):
        """No log_dir = nowhere durable: failover must disable loudly,
        and a pserver death must stay fatal (no silent fresh-state
        respawn)."""
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys\n"
            "sys.exit(37 if os.environ['TRAINING_ROLE'] == 'PSERVER'"
            " else 0)\n")
        rc = launch_mod.launch_ps(
            [str(script)], server_num=1, worker_num=1, timeout=60,
            max_restarts=2, grace_period=1.0, ps_snapshot_secs=0.5)
        assert rc == 37
        err = capfd.readouterr().err
        assert "no effect without --log_dir" in err

    def test_bad_snapshot_secs_rejected(self):
        with pytest.raises(ValueError):
            launch_mod.launch_ps(["x.py"], server_num=1, worker_num=1,
                                 ps_snapshot_secs=0.0)

    def test_dead_pserver_respawned_under_budget(self, tmp_path):
        """Supervisor-level respawn without any training stack: the
        pserver process exits 37 once, the supervisor respawns it at
        the same endpoint with PADDLE_RESTART_COUNT=1, and the job
        completes."""
        out = tmp_path / "out"
        out.mkdir()
        script = tmp_path / "w.py"
        script.write_text(f"""\
import os, sys, time
out = {str(out)!r}
role = os.environ["TRAINING_ROLE"]
if role == "PSERVER":
    attempt = os.environ.get("PADDLE_RESTART_COUNT", "0")
    with open(os.path.join(out, f"ps.a{{attempt}}"), "w") as f:
        f.write(os.environ.get("PT_PS_SNAPSHOT_DIR", ""))
    if attempt == "0":
        sys.exit(37)
    deadline = time.time() + 60
    while time.time() < deadline:
        if os.path.exists(os.path.join(out, "done")):
            sys.exit(0)
        time.sleep(0.05)
    sys.exit(7)
else:
    time.sleep(3)      # outlive the pserver's death + respawn
    open(os.path.join(out, "done"), "w").close()
    sys.exit(0)
""")
        before = launch_mod._m_ps_restarts.value()
        rc = launch_mod.launch_ps(
            [str(script)], server_num=1, worker_num=1,
            log_dir=str(tmp_path / "logs"), timeout=90,
            max_restarts=2, grace_period=2.0, ps_snapshot_secs=0.5)
        assert rc == 0
        assert (out / "ps.a0").exists() and (out / "ps.a1").exists()
        # the snapshot dir env reached both incarnations
        assert "ps_state" in (out / "ps.a1").read_text()
        assert launch_mod._m_ps_restarts.value() > before

    WEDGE_SCRIPT = """\
import os, socket, sys, time
out = sys.argv[1]
role = os.environ["TRAINING_ROLE"]
if role == "PSERVER":
    if os.environ.get("PADDLE_RESTART_COUNT", "0") != "0":
        open(os.path.join(out, "respawned"), "w").close()
        deadline = time.time() + 60
        while time.time() < deadline and not os.path.exists(
                os.path.join(out, "done")):
            time.sleep(0.05)
        sys.exit(0)
    # first incarnation: answer ONE probe properly, then stop
    # answering (close without a reply) — wedged-but-alive
    from paddle_tpu.distributed import wire
    host, port = os.environ["PADDLE_CURRENT_ENDPOINT"].rsplit(":", 1)
    lst = socket.socket()
    lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lst.bind((host, int(port)))
    lst.listen(8)
    lst.settimeout(0.1)
    open(os.path.join(out, "listening"), "w").close()
    answered = False
    deadline = time.time() + 60
    while time.time() < deadline:
        if os.path.exists(os.path.join(out, "done")):
            sys.exit(0)
        try:
            c, _ = lst.accept()
        except socket.timeout:
            continue
        try:
            kind, cid, seq, fields = wire.recv_frame(c)
            if not answered:
                wire.send_frame(c, wire.OK_NAMES, ("", ""), cid, seq)
                answered = True
        except Exception:
            pass
        try:
            c.close()
        except OSError:
            pass
    sys.exit(7)
else:
    # long enough that a wedge-kill -> backoff -> respawn lands while
    # the job is still running (the supervisor rightly skips a pending
    # respawn once every worker is done), counted from when the server
    # listens: how long its imports take beside other test workers is
    # not part of what is tested
    deadline = time.time() + 60
    while time.time() < deadline and not os.path.exists(
            os.path.join(out, "listening")):
        time.sleep(0.05)
    time.sleep(6.0)
    open(os.path.join(out, "done"), "w").close()
    sys.exit(0)
"""

    def _wedge_env(self):
        return {"PYTHONPATH": os.pathsep.join([REPO] + sys.path)}

    def test_wedged_pserver_killed_and_respawned(self, tmp_path,
                                                 capfd):
        """Probe path end to end: a pserver that answered once and
        then stopped is wedged — killed and respawned under the
        failover budget."""
        out = tmp_path / "out"
        out.mkdir()
        script = tmp_path / "w.py"
        script.write_text(self.WEDGE_SCRIPT)
        rc = launch_mod.launch_ps(
            [str(script), str(out)], server_num=1, worker_num=1,
            log_dir=str(tmp_path / "logs"), timeout=90,
            max_restarts=2, grace_period=2.0, hang_timeout=1.0,
            ps_snapshot_secs=0.5, env_extra=self._wedge_env())
        assert rc == 0
        assert (out / "respawned").exists()
        assert "wedged" in capfd.readouterr().err

    def test_probe_disarmed_without_failover(self, tmp_path, capfd):
        """The review pin: --hang_timeout WITHOUT --ps_snapshot_secs
        must keep today's semantics — the probe never kills a wedged
        pserver when no warm-booting respawn would follow (a kill
        would turn a survivable stall into job teardown)."""
        out = tmp_path / "out"
        out.mkdir()
        script = tmp_path / "w.py"
        script.write_text(self.WEDGE_SCRIPT)
        rc = launch_mod.launch_ps(
            [str(script), str(out)], server_num=1, worker_num=1,
            log_dir=str(tmp_path / "logs"), timeout=90,
            max_restarts=2, grace_period=2.0, hang_timeout=1.0,
            env_extra=self._wedge_env())
        assert rc == 0
        assert not (out / "respawned").exists()
        assert "wedged" not in capfd.readouterr().err

    def test_budget_exhaustion_tears_down(self, tmp_path):
        out = tmp_path / "out"
        out.mkdir()
        script = tmp_path / "w.py"
        script.write_text("""\
import os, sys, time
if os.environ["TRAINING_ROLE"] == "PSERVER":
    sys.exit(37)       # every incarnation dies
time.sleep(30)
sys.exit(0)
""")
        rc = launch_mod.launch_ps(
            [str(script)], server_num=1, worker_num=1,
            log_dir=str(tmp_path / "logs"), timeout=90,
            max_restarts=1, grace_period=1.0, ps_snapshot_secs=0.5)
        assert rc == 37


# ---------------------------------------------------------------------------
# fsck: pserver artifacts
# ---------------------------------------------------------------------------
class TestFsckPserver:
    def _make_state(self, d):
        s = _mk_server(port=7201)
        s.dense["w"].push_async(np.ones(4, np.float32))
        s.save(d)
        s.dense["w"].push_async(np.ones(4, np.float32))
        s.save(d)
        return s

    def test_cli_reports_and_quarantines_corrupt_gen(self, tmp_path):
        d = str(tmp_path)
        s = self._make_state(d)
        tag = _ps_tag(s.host, s.port)
        newest = _ps_complete_gens(d, tag)[-1][0]
        faults.corrupt_checkpoint(_ps_dense_path(d, tag, newest),
                                  "bitflip")
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "fsck_checkpoint.py"), d,
             "--quarantine"],
            capture_output=True, text=True)
        assert r.returncode == 1
        assert f"pserver {tag} gen {newest}: corrupt" in r.stdout
        assert f"pserver {tag} gen {newest - 1}: ok" in r.stdout
        assert "quarantined ->" in r.stdout
        corrupts = [f for f in os.listdir(d)
                    if f.endswith(".corrupt")]
        assert corrupts and all(f".gen{newest}." in f
                                for f in corrupts)
        # the healthy generation still restores after the quarantine
        s2 = _mk_server(port=7201)
        meta = s2.load(d)
        assert meta is not None and meta["gen"] == newest - 1

    def test_cli_clean_dir_exits_zero(self, tmp_path):
        d = str(tmp_path)
        self._make_state(d)
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "fsck_checkpoint.py"), d],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "# pserver:" in r.stdout

    def test_unreadable_never_renamed(self, tmp_path, monkeypatch):
        """The transient-I/O-is-not-corruption rule: an OSError that
        persists through retries reports `unreadable` and --quarantine
        must NOT rename the generation."""
        import tools.fsck_checkpoint as fsck
        d = str(tmp_path)
        self._make_state(d)

        def raise_io(path, *a, **k):
            raise OSError(5, "Input/output error", path)

        monkeypatch.setattr("paddle_tpu.io_checkpoint.verify_npz",
                            raise_io)
        gens, _ = fsck.fsck_ps_dir(d)
        assert gens and all(r["status"] == "unreadable" for r in gens)

    def test_corrupt_meta_gen_not_double_reported_as_orphan(
            self, tmp_path):
        """Review pin: a generation whose META is garbage gets ONE
        verdict (corrupt) — its artifacts must not also be listed
        under 'orphan_artifacts: meta never published'."""
        d = str(tmp_path)
        s = self._make_state(d)
        tag = _ps_tag(s.host, s.port)
        g = _ps_complete_gens(d, tag)[-1][0]
        with open(os.path.join(d, f"pserver_{tag}.gen{g}.json"),
                  "w") as f:
            f.write("{not json")
        import tools.fsck_checkpoint as fsck
        gens, extras = fsck.fsck_ps_dir(d)
        rec = [r for r in gens if r["gen"] == g][0]
        assert rec["status"] == "corrupt"
        assert not any(f".gen{g}." in a
                       for a in extras["orphan_artifacts"])

    def test_stop_snapshots_skips_final_flush_when_save_wedged(
            self, tmp_path, capfd, monkeypatch):
        """Review pin: a save wedged in I/O holds the save lock —
        stop_snapshots must skip the final flush loudly instead of
        blocking shutdown on that lock forever."""
        s = _mk_server(port=7112)
        release = threading.Event()

        def wedged_save(self_, dirname):
            release.wait(20)

        monkeypatch.setattr(ParameterServer, "save", wedged_save)
        s.start_snapshots(str(tmp_path), interval=0.01)
        time.sleep(0.1)                 # let a save wedge
        t0 = time.monotonic()
        s.stop_snapshots(final_save=True, timeout=0.3)
        assert time.monotonic() - t0 < 5
        assert "skipping the final flush" in capfd.readouterr().err
        release.set()

    def test_orphan_gen_artifacts_reported(self, tmp_path):
        d = str(tmp_path)
        s = self._make_state(d)
        tag = _ps_tag(s.host, s.port)
        # delete a meta: its artifacts become orphans (invisible to
        # the warm boot)
        gens = _ps_complete_gens(d, tag)
        os.remove(os.path.join(
            d, f"pserver_{tag}.gen{gens[0][0]}.json"))
        import tools.fsck_checkpoint as fsck
        _, extras = fsck.fsck_ps_dir(d)
        assert any(f".gen{gens[0][0]}." in f
                   for f in extras["orphan_artifacts"])


def _gang_logs(tmp_path):
    logs = ""
    logdir = tmp_path / "logs"
    if logdir.exists():
        for p in sorted(logdir.glob("*.log")):
            logs += (f"\n--- {p.name} ---\n"
                     + p.read_text(errors="replace")[-3000:])
    return logs


# ---------------------------------------------------------------------------
# slow e2e: the headline, through the real launcher
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.timeout(600)
class TestPserverFailoverEndToEnd:
    def _launch(self, tmp_path, extra_env):
        from paddle_tpu.distributed.launch import launch_ps
        script = os.path.join(os.path.dirname(__file__),
                              "dist_ps_elastic.py")
        result = str(tmp_path / "losses")
        env = {
            "PT_DIST_RESULT": result,
            "PT_FAULT_ONCE_DIR": str(tmp_path / "faults"),
            "PT_PS_RECONNECT_SECS": "120",
            "PYTHONPATH": os.pathsep.join(
                [os.path.dirname(os.path.dirname(__file__))]
                + sys.path),
        }
        env.update(extra_env)
        rc = launch_ps([script], server_num=2, worker_num=2,
                       log_dir=str(tmp_path / "logs"), timeout=240,
                       max_restarts=2, grace_period=5.0,
                       ps_snapshot_secs=0.2, env_extra=env)
        return rc, result

    def _read_losses(self, result, n=2):
        out = []
        for tid in range(n):
            with open(result + f".{tid}") as f:
                out.append(json.load(f))
        return out

    def test_pserver_crash_respawn_warm_boot_reconnect(self, tmp_path):
        """The acceptance headline: PT_FAULT_PS_CRASH_AT_STEP kills
        one of two pservers mid-training, the supervisor respawns it
        at the same endpoint, the server restores from its last-good
        integrity-verified snapshot, the trainers reconnect without
        manual intervention, the job exits 0, and the recovery is
        visible in the exported metrics."""
        before = launch_mod._m_ps_restarts.value()
        rc, result = self._launch(tmp_path, {
            "PT_FAULT_PS_CRASH_AT_STEP": "12",
            "PT_FAULT_RANK": "1",
            "PT_FAULT_PS_AWAIT_SNAPS": "1",
        })
        assert rc == 0, _gang_logs(tmp_path)
        slog = (tmp_path / "logs" / "serverlog.1.log").read_text(
            errors="replace")
        assert "[faults] injected pserver crash" in slog, slog[-2000:]
        assert "warm boot: restored pserver state generation" in slog, \
            slog[-2000:]
        losses = self._read_losses(result)
        for ls in losses:
            assert len(ls) == 40
            assert ls[-1] < ls[0]      # converged despite the rewind
        assert launch_mod._m_ps_restarts.value() - before >= 1
        # the aggregated job metrics carry the recovery evidence
        from paddle_tpu.monitor import exporter as exp
        _, samples = exp.parse_text(
            (tmp_path / "logs" / "metrics.prom").read_text())

        def total(metric):
            return sum(v for (n, _), v in samples.items()
                       if n == metric)

        assert total("ps_restarts_total") >= 1
        assert total("ps_client_reconnects_total") >= 1
        # the background snapshots on the pservers are visible too
        # (exported at rank<worker_num + i>.prom by run_pserver)
        assert total("ps_snapshot_saves_total") >= 1

    def test_bitflipped_snapshot_quarantined_walks_back(self, tmp_path):
        """The second acceptance e2e: the crash bit-flips the newest
        snapshot generation on its way out — the respawned server must
        quarantine it, walk back to the previous generation, and the
        job still completes."""
        rc, result = self._launch(tmp_path, {
            "PT_FAULT_PS_CRASH_AT_STEP": "12",
            "PT_FAULT_RANK": "1",
            "PT_FAULT_PS_BITFLIP_SNAP": "1",
        })
        assert rc == 0, _gang_logs(tmp_path)
        slog = (tmp_path / "logs" / "serverlog.1.log").read_text(
            errors="replace")
        assert "after bitflipping" in slog, slog[-2000:]
        assert "quarantined corrupt snapshot generation" in slog, \
            slog[-2000:]
        assert "restored from last-good snapshot generation" in slog, \
            slog[-2000:]
        ps_state = tmp_path / "logs" / "ps_state"
        assert any(f.name.endswith(".corrupt")
                   for f in ps_state.iterdir())
        losses = self._read_losses(result)
        for ls in losses:
            assert len(ls) == 40 and ls[-1] < ls[0]
