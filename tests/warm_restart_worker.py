"""Worker for the executor warm-restart end-to-end test.

Trains a small static-graph program under the elastic launcher. The
workers run with JAX_COMPILATION_CACHE_DIR set (by the test, or by the
launcher to the fixed in-checkout path), so jax keeps its persistent
compilation cache there and ``import paddle_tpu`` starts the counters;
``Executor.prepare`` then AOT-compiles the step eagerly. The first
incarnation populates the on-disk cache (misses), crashes via
``testing.faults``; the restarted incarnation compiles the identical
program and must hit the cache instead of redoing XLA.

Writes <out_prefix>.inc<restart_count>.json with the incarnation's
compilation-cache counters, the compile log's account of the same process
(``compile_cache.reduce()``: what ``test_startup_timeline.py`` holds the
counters to), executor trace count, and loss stream.
"""

import json
import os
import sys


def main():
    out_prefix = sys.argv[1]
    steps = int(sys.argv[2])

    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.core import compile_cache
    from paddle_tpu.testing import faults

    pt.enable_static()
    main_p, startup = pt.Program(), pt.Program()
    with pt.static.program_guard(main_p, startup):
        x = pt.static.data("x", shape=[13])
        y = pt.static.data("y", shape=[1])
        pred = pt.layers.fc(x, size=1, param_attr="w", bias_attr="b")
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.SGDOptimizer(0.05).minimize(loss)

    exe = pt.static.Executor()
    exe.run(startup)
    rs = np.random.RandomState(0)
    xb = rs.randn(32, 13).astype(np.float32)
    yb = (xb[:, :1] * 0.7).astype(np.float32)

    # AOT warm-start: with the cache enabled this is where the XLA
    # compile happens — a disk write on the first incarnation, a disk
    # read on every restart
    aot_full = exe.prepare(main_p, feed={"x": xb, "y": yb},
                           fetch_list=[loss])

    inc = int(os.environ.get("PADDLE_RESTART_COUNT", "0"))

    def report(phase, losses):
        # written right after prepare AND at the end: the incarnation
        # that the injected fault kills mid-loop still leaves its
        # post-compile counters behind for the test to read
        stats = compile_cache.stats()
        with open(f"{out_prefix}.inc{inc}.json", "w") as f:
            json.dump({
                "incarnation": inc,
                "phase": phase,
                "cache_dir": compile_cache.cache_dir(),
                "hits": stats["hits"],
                "misses": stats["misses"],
                "requests": stats["requests"],
                "log": compile_cache.reduce(),
                "trace_count": exe.trace_count,
                "aot_full": bool(aot_full),
                "losses": losses,
            }, f)

    report("prepared", [])
    losses = []
    for step in range(steps):
        faults.maybe_fault(step)
        (lv,) = exe.run(main_p, feed={"x": xb, "y": yb},
                        fetch_list=[loss])
        losses.append(float(lv))
    report("done", losses)


if __name__ == "__main__":
    main()
