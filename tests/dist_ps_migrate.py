"""Role-driven PS training script for the elastic fleet-resize e2e
drills (dist_ps_elastic.py pattern plus a hook-hosted sparse table):
every process builds the same program, transpiles for its role, then
either serves (sparse table hosted on the FIRST endpoint only, fault
hooks + migration chaos hooks armed from the environment) or trains
(dense steps through the executor, deterministic sparse pulls/pushes
through the shared PSClient). The trainer drops a resize trigger file
into PT_PS_ELASTIC_DIR mid-run per PT_PS_E2E_RESIZE ("grow:K" /
"shrink:K"), waits for the coordinator to commit the new fleet epoch
(fleet_epoch.json under PT_PS_STATE_DIR), then finishes training and
dumps losses + final dense params + the FULL sparse table to
PT_DIST_RESULT.<tid>.npz — the test diffs that dump bit-for-bit
against a fixed-fleet control run of this same script. Launched by
paddle_tpu.distributed.launch in ps mode; NOT collected by pytest."""

import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

import time

import numpy as np

import paddle_tpu as pt
from paddle_tpu.distributed import DistributeTranspiler, run_pserver
from paddle_tpu.distributed import membership
from paddle_tpu.distributed.transpiler import _get_client
from paddle_tpu.testing import faults

STEPS = int(os.environ.get("PT_PS_E2E_STEPS", "30"))
STEP_SLEEP = float(os.environ.get("PT_PS_E2E_STEP_SLEEP", "0.05"))
DIM = 4
EMB_DIM = 3
UNIVERSE = 32          # full sparse id universe, warmed before step 0


def emb_init(rng, dim):
    # value-identical to the default initializer, but an explicit
    # python callable forces the python row store on every server —
    # the native table can't host a custom initializer, and the drill
    # needs both control and resized runs on the same store
    return rng.normal(0, 0.01, dim).astype(np.float32)


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


def sparse_batch(step):
    rng = np.random.RandomState(200 + step)
    ids = np.unique(rng.randint(0, UNIVERSE, size=8).astype(np.int64))
    grads = rng.normal(0, 0.1, (ids.size, EMB_DIM)).astype(np.float32)
    return ids, grads


def resize_spec():
    spec = os.environ.get("PT_PS_E2E_RESIZE", "")
    if not spec:
        return None, -1
    kind, _, at = spec.partition(":")
    return kind, int(at or 3)


def wait_for_epoch(want, timeout=150.0):
    """Block until the coordinator commits fleet epoch >= want: the
    drill must finish its deterministic tail AFTER the resize so the
    final state exercises the migrated placement."""
    state_dir = os.environ["PT_PS_STATE_DIR"]
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        ef = membership.load_epoch_file(state_dir)
        if ef and int(ef.get("epoch", 0)) >= want:
            return
        time.sleep(0.25)
    raise RuntimeError(f"fleet epoch never reached {want} within "
                       f"{timeout}s")


def main():
    role = os.environ["TRAINING_ROLE"]
    eps = os.environ["PADDLE_PSERVER_ENDPOINTS"]
    ep_list = eps.split(",")
    tid = int(os.environ.get("PADDLE_TRAINER_ID", 0))
    tnum = int(os.environ.get("PADDLE_TRAINERS_NUM", 1))

    prog, startup, loss = build()
    t = DistributeTranspiler()
    t.transpile(tid, program=prog, pservers=eps, trainers=tnum,
                sync_mode=True, startup_program=startup)
    # hosting recipes: every dense spec the transpiler placed anywhere
    # plus the hook-hosted sparse table — any server (including one
    # grown AFTER launch) can adopt any unit from these
    recipes = t.pserver_recipes()
    recipes["emb"] = dict(kind="sparse", dim=EMB_DIM,
                          initializer=emb_init, seed=0, lr=0.1,
                          optimizer="sgd")

    if role == "PSERVER":
        me = os.environ["PADDLE_CURRENT_ENDPOINT"]
        # control and resized runs must serve from the SAME transport
        # and row store (the native server can't host the custom
        # initializer, and elastic mode forces python anyway)
        from paddle_tpu.core.flags import set_flags
        set_flags({"ps_transport": "python"})

        def hook(server):
            # the first endpoint hosts the sparse table at epoch 0 —
            # guarded so a warm-booted respawn that already restored
            # (or migrated away) its rows is not clobbered
            if (me == ep_list[0] and hasattr(server, "host_sparse")
                    and "emb" not in getattr(server, "sparse", {})):
                server.host_sparse("emb", dim=EMB_DIM,
                                   initializer=emb_init, seed=0,
                                   lr=0.1, optimizer="sgd")
            faults.install_ps_faults(server)
            faults.install_ps_migrate_faults()

        run_pserver(t.get_pserver_program(me, allow_new=True),
                    on_server=hook, recipes=recipes)
        return

    from paddle_tpu.monitor.exporter import RankExporter
    exporter = RankExporter.from_env(interval=0.5)
    if exporter is not None:
        exporter.start()

    client = _get_client(t.endpoints, dict(t.var_ep,
                                           emb=t.endpoints[0]), tid)
    trainer_prog = t.get_trainer_program()
    with pt.static.program_guard(trainer_prog, startup):
        exe = pt.static.Executor(pt.CPUPlace())
        exe.run(startup)
        # warm the ENTIRE id universe in one pull so every row
        # materializes in the same deterministic rng-draw order in
        # control and resized runs alike; after this no pull ever
        # draws a new row, so placement cannot perturb values
        all_ids = np.arange(UNIVERSE, dtype=np.int64)
        client.pull_sparse("emb", all_ids)
        kind, at = resize_spec()
        losses = []
        for s in range(STEPS):
            (lv,) = exe.run(trainer_prog,
                            feed=data_batch(s, tid, tnum),
                            fetch_list=[loss.name])
            losses.append(float(np.asarray(lv)))
            ids, grads = sparse_batch(s)
            client.pull_sparse("emb", ids)
            client.push_sparse("emb", ids, grads)
            if kind and s == at and tid == 0:
                d = os.environ["PT_PS_ELASTIC_DIR"]
                with open(os.path.join(d, f"ps_{kind}.req"), "w") as f:
                    f.write(f"step {s}\n")
            if kind and s == at:
                # every trainer pauses here until the resize commits:
                # the deterministic tail then runs entirely against
                # the new fleet, and stop_servers cannot race an
                # in-flight migration
                wait_for_epoch(1)
            time.sleep(STEP_SLEEP)
    client.barrier("done")
    emb_final = client.pull_sparse("emb", all_ids)
    dense_final = {n: client.pull_param(n) for n in sorted(t.var_ep)}
    out = os.environ.get("PT_DIST_RESULT")
    if out:
        np.savez(out + f".{tid}.npz",
                 losses=np.asarray(losses, np.float64),
                 emb=emb_final,
                 **{"dense_" + n: v for n, v in dense_final.items()})
    if exporter is not None:
        exporter.stop()
    if tid == 0:
        client.stop_servers()


if __name__ == "__main__":
    main()
