"""Per-op microbenchmark CLI — the operators/benchmark/op_tester.cc
analog (SURVEY §2.4 benchmark/ row): time a single op's forward (and
optionally fwd+bwd) on the current device, print one JSON line per op.

    python tools/op_tester.py --op matmul flash_attention --repeat 30
    python tools/op_tester.py --list
    python tools/op_tester.py --all --preset tiny     # CI / CPU
    python tools/op_tester.py --op fused_matmul --pallas both

Presets scale shapes: "bench" (TPU-sized) and "tiny" (CPU/CI).
``--pallas on|off|both`` wraps each run in the Pallas kernel registry's
override (ops/pallas/registry.py) so any op routed through the registry
(fused_matmul, embedding_scatter_add, layer_norm, ...) can be
A/B'd from the CLI; "both" prints one JSON line per body.
"""

import argparse
import contextlib
import json
import sys
import time


def _ops(preset):
    import jax
    import jax.numpy as jnp

    import paddle_tpu.layers as L
    from paddle_tpu.ops import pallas as PLK

    big = preset == "bench"
    B = 8 if big else 2
    S = 2048 if big else 64
    H = 768 if big else 16
    V = 32768 if big else 128
    IMG = 112 if big else 16
    C = 128 if big else 4
    key = jax.random.PRNGKey(0)

    def r(*shape, dtype=jnp.bfloat16):
        return jax.random.normal(key, shape, dtype)

    # name -> (fn, args, flops_or_None)
    reg = {
        "matmul": (lambda a, b: a @ b,
                   (r(4 * H, 4 * H), r(4 * H, 4 * H)),
                   2 * (4 * H) ** 3),
        "conv2d": (lambda x, w: jax.lax.conv_general_dilated(
                       x, w, (1, 1), "SAME",
                       dimension_numbers=("NCHW", "OIHW", "NCHW")),
                   (r(B, C, IMG, IMG), r(C, C, 3, 3)),
                   2 * B * C * C * 9 * IMG * IMG),
        "elementwise_add": (lambda a, b: a + b,
                            (r(B, S, H), r(B, S, H)), None),
        "reduce_sum": (lambda x: x.sum(axis=-1), (r(B, S, H),), None),
        "softmax": (lambda x: jax.nn.softmax(x, -1), (r(B, S, S),), None),
        "layer_norm": (lambda x, g, b: PLK.fused_layer_norm(x, g, b),
                       (r(B * S, H, dtype=jnp.float32),
                        jnp.ones((H,)), jnp.zeros((H,))), None),
        "softmax_cross_entropy":
            (lambda x, y: PLK.softmax_cross_entropy(x, y).mean(),
             (r(B * S, V, dtype=jnp.float32),
              jax.random.randint(key, (B * S,), 0, V)), None),
        "flash_attention":
            (lambda q, k, v: PLK.flash_attention(q, k, v),
             (r(B, 12, S, 64), r(B, 12, S, 64), r(B, 12, S, 64)),
             4 * B * 12 * S * S * 64),
        "dense_attention":
            (lambda q, k, v: jax.nn.softmax(
                (q @ k.swapaxes(-1, -2)) * (64 ** -0.5), -1) @ v,
             (r(B, 12, S, 64), r(B, 12, S, 64), r(B, 12, S, 64)),
             4 * B * 12 * S * S * 64),
        "embedding": (lambda ids, w: w[ids],
                      (jax.random.randint(key, (B, S), 0, V),
                       r(V, H, dtype=jnp.float32)), None),
        # registry-routed ops: honor --pallas on|off|both
        "fused_matmul":
            (lambda x, w, b: PLK.dispatch("fused_matmul", x, w,
                                          bias=b, act="relu"),
             (r(4 * H, 4 * H), r(4 * H, 4 * H), r(4 * H)),
             2 * (4 * H) ** 3),
        "embedding_scatter_add":
            (lambda d, ids, u: PLK.dispatch("embedding_scatter_add",
                                            d, ids, u),
             (r(V, H, dtype=jnp.float32),
              jax.random.randint(key, (B * S,), 0, V),
              r(B * S, H, dtype=jnp.float32)), None),
    }
    return reg


def run_op(name, fn, args, flops, repeat, grad=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    # fi = first inexact (differentiable) argument: grad targets it, and
    # the scan below nudges it per-iteration to defeat CSE
    fi = next((i for i, a in enumerate(args)
               if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact)), 0)
    if grad:
        base = jax.grad(lambda *a: jnp.sum(
            jnp.asarray(fn(*a), jnp.float32)), argnums=fi)
    else:
        base = fn

    # Time the op INSIDE one compiled program: a lax.scan applies it n
    # times per dispatch, so per-dispatch latency cannot contaminate the
    # number.
    # The first float arg is nudged by the (traced) iteration index so
    # XLA cannot CSE the iterations into one application; the running
    # sum over output leaves keeps every iteration live.

    def chain(n):
        def body(acc, i):
            a = list(args)
            af = jnp.asarray(a[fi])
            a[fi] = af + (i * jnp.asarray(1e-30, jnp.float32)).astype(
                af.dtype)
            out = base(*a)
            leaf = jnp.asarray(jax.tree.leaves(out)[0])
            return acc + leaf.ravel()[0].astype(jnp.float32), None

        return jax.jit(lambda: jax.lax.scan(
            body, jnp.float32(0.0), jnp.arange(n))[0])

    f1, f2 = chain(repeat), chain(3 * repeat)

    def timed(f):
        t0 = time.perf_counter()
        # host fetch: returns only once the whole scan has executed
        float(np.asarray(f()))
        return time.perf_counter() - t0

    timed(f1)                           # compile + warm both programs
    timed(f2)
    t1 = min(timed(f1) for _ in range(3))
    t2 = min(timed(f2) for _ in range(3))
    # marginal cost of the extra 2n iterations: dispatch/fetch latency
    # cancels; min-of-3 tames jitter
    dt = max((t2 - t1) / (2 * repeat), 1e-9)
    rec = {"op": name, "ms": round(dt * 1e3, 4), "grad": grad}
    if flops:
        rec["tflops"] = round(flops * (3 if grad else 1) / dt / 1e12, 3)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--op", nargs="*", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--repeat", type=int, default=20)
    ap.add_argument("--grad", action="store_true",
                    help="time fwd+bwd instead of fwd")
    ap.add_argument("--preset", choices=("bench", "tiny"), default=None)
    ap.add_argument("--pallas", choices=("on", "off", "both"), default=None,
                    help="force the Pallas kernel registry selection "
                         "around each timed run ('on' uses interpreter "
                         "mode on CPU); 'both' prints one line per body")
    args = ap.parse_args(argv)

    import jax
    preset = args.preset or (
        "bench" if jax.devices()[0].platform != "cpu" else "tiny")
    reg = _ops(preset)
    if args.list:
        print("\n".join(reg))
        return 0
    names = list(reg) if (args.all or not args.op) else args.op
    modes = {"both": ("off", "on")}.get(args.pallas, (args.pallas,))
    for n in names:
        if n not in reg:
            print(json.dumps({"op": n, "error": "unknown op"}))
            continue
        fn, a, flops = reg[n]
        for mode in modes:
            if mode is None:
                ctx = contextlib.nullcontext()
            else:
                from paddle_tpu.ops import pallas as plk
                ctx = plk.override(mode)
            with ctx:
                rec = run_op(n, fn, a, flops, args.repeat, grad=args.grad)
            if mode is not None:
                rec["pallas"] = mode
            print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
