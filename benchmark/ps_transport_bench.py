"""PS transport loopback benchmark (the BASELINE.md "PS transport"
numbers): dense push/pull of a 64 MB fp32 parameter, the native dense
optimize-block kernels, small-request dispatch rates, and multi-client
fan-in — one JSON line each, for BOTH server transports.

Run: python benchmark/ps_transport_bench.py [--size MB] [--reps N]

The dense push measures the full server-side path the reference runs
in C++ (recv -> decode -> optimize block -> reply; ref:
operators/distributed/request_handler_impl.cc). Transports:
  native  — C++ accept loop / codec / dispatch / kernels
            (native/src/ps_server.cc), the SURVEY §5.8 path
  python  — the socketserver fallback in distributed/ps.py (its
            optimizer step still uses the C++ kernels)
BENCH_PS_JNP=1 additionally forces the Python server's jnp step for
the r4-era A/B.
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def main():
    import paddle_tpu as pt
    from paddle_tpu.distributed import ps as psmod
    from paddle_tpu.distributed.launch import find_free_ports
    from paddle_tpu.distributed.ps import (NativeParameterServer,
                                           ParameterServer, PSClient)

    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=64, help="param MB")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--small-reps", type=int, default=2000)
    args = ap.parse_args()
    n = args.size * 1024 * 1024 // 4
    grad = np.ones(n, np.float32)

    if os.environ.get("BENCH_PS_JNP") == "1":
        psmod._DenseVar._native_kind = lambda self: (None, None)

    transports = [("native", NativeParameterServer),
                  ("python", ParameterServer)]
    try:
        from paddle_tpu import native
        if not native.available():
            transports = transports[1:]
    except Exception:
        transports = transports[1:]
    if os.environ.get("BENCH_PS_JNP") == "1":
        transports = [("jnp", ParameterServer)]

    def start_server(cls, optimizer, value):
        port = find_free_ports(1)[0]
        srv = cls(f"127.0.0.1:{port}", num_trainers=1, sync_mode=False)
        srv.host_dense("w", value, optimizer=optimizer)
        srv.start()
        cl = PSClient([srv.endpoint], var_ep={"w": srv.endpoint},
                      trainer_id=0)
        return srv, cl

    # -- dense 64 MB push/pull per transport ------------------------------
    gb = n * 4 / 1e9
    for tname, cls in transports:
        for oname, opt in (("sgd", pt.optimizer.SGDOptimizer(0.01)),
                           ("adam", pt.optimizer.AdamOptimizer(1e-3))):
            srv, c = start_server(cls, opt, np.zeros(n, np.float32))
            c.push_grad("w", grad)       # warmup (lazy slots)
            t0 = time.perf_counter()
            for _ in range(args.reps):
                c.push_grad("w", grad)
            push_dt = (time.perf_counter() - t0) / args.reps
            c.pull_param("w")
            t0 = time.perf_counter()
            for _ in range(args.reps):
                c.pull_param("w")
            pull_dt = (time.perf_counter() - t0) / args.reps
            c.close()
            srv.stop()
            print(json.dumps({
                "metric": f"ps_dense_push_{oname}_{tname}_gbps",
                "value": round(gb / push_dt, 3), "unit": "GB/s",
                "ms_per_req": round(push_dt * 1e3, 1),
                "size_mb": args.size, "cpus": os.cpu_count()}))
            if oname == "sgd":
                print(json.dumps({
                    "metric": f"ps_dense_pull_{tname}_gbps",
                    "value": round(gb / pull_dt, 3), "unit": "GB/s",
                    "ms_per_req": round(pull_dt * 1e3, 1),
                    "size_mb": args.size, "cpus": os.cpu_count()}))

    # -- C-speed client: server-side capacity isolated --------------------
    # The Python client's encode/decode shares the CPU with the server
    # on 1-core hosts and caps the end-to-end number; the C++ bench
    # client (pt_ps_bench_push/pull in ps_server.cc, same wire
    # protocol) reduces the client to memcpy-speed, so these rows
    # approximate what the SERVER can sustain — against both
    # transports.
    try:
        from paddle_tpu import native as _native
        _lib = _native.get_lib() if _native.available() else None
    except Exception:
        _lib = None
    if _lib is not None:
        for tname, cls in transports:
            srv, _c = start_server(cls, pt.optimizer.SGDOptimizer(0.01),
                                   np.zeros(n, np.float32))
            _c.close()
            dt = _lib.pt_ps_bench_push(srv.host.encode(), srv.port,
                                       b"w", n, args.reps)
            dtp = _lib.pt_ps_bench_pull(srv.host.encode(), srv.port,
                                        b"w", args.reps)
            srv.stop()
            if dt > 0:
                print(json.dumps({
                    "metric": f"ps_dense_push_sgd_{tname}_cclient_gbps",
                    "value": round(gb / (dt / args.reps), 3),
                    "unit": "GB/s",
                    "ms_per_req": round(dt / args.reps * 1e3, 1),
                    "size_mb": args.size, "cpus": os.cpu_count()}))
            if dtp > 0:
                print(json.dumps({
                    "metric": f"ps_dense_pull_{tname}_cclient_gbps",
                    "value": round(gb / (dtp / args.reps), 3),
                    "unit": "GB/s",
                    "ms_per_req": round(dtp / args.reps * 1e3, 1),
                    "size_mb": args.size, "cpus": os.cpu_count()}))

    # -- small-request dispatch rate (1 KB pushes) ------------------------
    # Bandwidth hides per-request overhead; 1 KB frames expose the
    # accept/decode/dispatch cost — where retiring the Python loop
    # pays even on a 1-core host.
    small = np.ones(256, np.float32)     # 1 KB
    for tname, cls in transports:
        srv, c = start_server(cls, pt.optimizer.SGDOptimizer(0.01),
                              np.zeros(256, np.float32))
        for _ in range(50):
            c.push_grad("w", small)      # warmup
        t0 = time.perf_counter()
        for _ in range(args.small_reps):
            c.push_grad("w", small)
        dt = time.perf_counter() - t0
        c.close()
        srv.stop()
        print(json.dumps({
            "metric": f"ps_small_push_{tname}_rps",
            "value": round(args.small_reps / dt, 0), "unit": "req/s",
            "us_per_req": round(dt / args.small_reps * 1e6, 1),
            "payload_bytes": 1024, "cpus": os.cpu_count()}))

    # -- 4-client fan-in (sync rounds, 1 MB grads) ------------------------
    # The GIL test: 4 trainers push concurrently; the server must
    # decode+accumulate 4 frames per round. Python's server serializes
    # that work on the GIL; the C++ server's only serialization is the
    # per-var mutex around the accumulate itself.
    nf = 1024 * 256                      # 1 MB
    rounds = 24
    for tname, cls in transports:
        port = find_free_ports(1)[0]
        srv = cls(f"127.0.0.1:{port}", num_trainers=4, sync_mode=True)
        srv.host_dense("w", np.zeros(nf, np.float32),
                       optimizer=pt.optimizer.SGDOptimizer(0.01))
        srv.start()
        gsmall = np.ones(nf, np.float32)
        errs = []

        def trainer(tid, warm):
            try:
                c = PSClient([srv.endpoint], var_ep={"w": srv.endpoint},
                             trainer_id=tid)
                for r in range(warm):
                    c.push_grad("w", gsmall)
                    c.pull_param("w", min_round=r + 1)
                c.close()
            except Exception as e:    # pragma: no cover
                errs.append(e)

        # warmup round
        ths = [threading.Thread(target=trainer, args=(i, 1))
               for i in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        t0 = time.perf_counter()

        def trainer_run(tid):
            try:
                c = PSClient([srv.endpoint], var_ep={"w": srv.endpoint},
                             trainer_id=tid)
                for r in range(rounds):
                    c.push_grad("w", gsmall)
                    c.pull_param("w", min_round=r + 2)
                c.close()
            except Exception as e:    # pragma: no cover
                errs.append(e)

        ths = [threading.Thread(target=trainer_run, args=(i,))
               for i in range(4)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        dt = time.perf_counter() - t0
        srv.stop()
        if errs:
            print(json.dumps({"metric": f"ps_fanin4_{tname}_error",
                              "value": str(errs[0])}))
            continue
        # aggregate: 4 trainers x rounds x (1 MB push + 1 MB pull)
        agg_gb = 4 * rounds * 2 * nf * 4 / 1e9
        print(json.dumps({
            "metric": f"ps_fanin4_{tname}_rounds_per_s",
            "value": round(rounds / dt, 2), "unit": "rounds/s",
            "aggregate_gbps": round(agg_gb / dt, 3),
            "clients": 4, "grad_mb": 1, "cpus": os.cpu_count()}))


if __name__ == "__main__":
    sys.exit(main())
