"""Role-driven PS training script for the pserver-failover e2e tests
(dist_ps_linear.py pattern, paced so the run straddles a mid-training
pserver crash): every process builds the same program, transpiles for
its role, then either serves (with fault hooks + snapshot wiring from
the environment) or trains (with a rank exporter so the client-side
reconnect metrics land in the launcher's aggregated metrics.prom).
Launched by paddle_tpu.distributed.launch in ps mode; NOT collected by
pytest."""

import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import json
import time

import numpy as np

import paddle_tpu as pt
from paddle_tpu.distributed import DistributeTranspiler, run_pserver
from paddle_tpu.distributed.transpiler import _get_client
from paddle_tpu.testing import faults

STEPS = int(os.environ.get("PT_PS_E2E_STEPS", "40"))
STEP_SLEEP = float(os.environ.get("PT_PS_E2E_STEP_SLEEP", "0.05"))
DIM = 4


def build():
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 7
    with pt.static.program_guard(main, startup):
        x = pt.static.data("x", shape=[DIM], dtype="float32")
        y = pt.static.data("y", shape=[1], dtype="float32")
        pred = pt.layers.fc(x, size=1)
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.SGDOptimizer(0.2).minimize(loss)
    return main, startup, loss


def data_batch(step, trainer_id, trainers):
    rng = np.random.RandomState(100 + step)
    w = np.linspace(-0.5, 0.5, DIM)
    x = rng.rand(8, DIM).astype(np.float32)
    y = (x @ w).astype(np.float32)[:, None]
    if trainers > 1:
        x = x[trainer_id::trainers]
        y = y[trainer_id::trainers]
    return {"x": x, "y": y}


def main():
    role = os.environ["TRAINING_ROLE"]
    eps = os.environ["PADDLE_PSERVER_ENDPOINTS"]
    tid = int(os.environ.get("PADDLE_TRAINER_ID", 0))
    tnum = int(os.environ.get("PADDLE_TRAINERS_NUM", 1))

    prog, startup, loss = build()
    t = DistributeTranspiler()
    t.transpile(tid, program=prog, pservers=eps, trainers=tnum,
                sync_mode=True, startup_program=startup)

    if role == "PSERVER":
        # run_pserver wires warm boot + snapshots from PT_PS_SNAPSHOT_*
        # (exported by launch_ps --ps_snapshot_secs); the fault hook
        # arms PT_FAULT_PS_CRASH_AT_STEP for this server's rank
        run_pserver(t.get_pserver_program(
            os.environ["PADDLE_CURRENT_ENDPOINT"]),
            on_server=faults.install_ps_faults)
        return

    # trainer: a rank exporter so ps_client_reconnects_total /
    # ps_stale_rounds_total reach the launcher's metrics.prom
    from paddle_tpu.monitor.exporter import RankExporter
    exporter = RankExporter.from_env(interval=0.5)
    if exporter is not None:
        exporter.start()

    trainer_prog = t.get_trainer_program()
    with pt.static.program_guard(trainer_prog, startup):
        exe = pt.static.Executor(pt.CPUPlace())
        exe.run(startup)
        losses = []
        for s in range(STEPS):
            (lv,) = exe.run(trainer_prog,
                            feed=data_batch(s, tid, tnum),
                            fetch_list=[loss.name])
            losses.append(float(np.asarray(lv)))
            # pacing: the run must still be in flight when the fault
            # kills a pserver and while the supervisor respawns it
            time.sleep(STEP_SLEEP)
    out = os.environ.get("PT_DIST_RESULT")
    if out:
        with open(out + f".{tid}", "w") as f:
            json.dump(losses, f)
    client = _get_client(t.endpoints, t.var_ep, tid)
    client.barrier("done")
    if exporter is not None:
        exporter.stop()
    if tid == 0:
        client.stop_servers()


if __name__ == "__main__":
    main()
