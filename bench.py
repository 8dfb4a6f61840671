"""Benchmark: BERT-base MLM pretraining step throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline = measured MFU / 0.35 (the BASELINE.json north-star MFU).
Metric format follows the reference's examples/sec convention
(ref: benchmark/fluid/fluid_benchmark.py:297-300), as tokens/sec here.
"""

import json
import os
import sys
import time

import numpy as np


class TimedResult:
    """Result of a multi-window timing run. ``dt`` is the BEST window's
    wall seconds (what throughput is computed from); ``window_dts`` are
    all window durations; ``contention_suspected`` is True when the
    window spread stayed above the threshold even after retries."""

    def __init__(self, window_dts, steps, carry, res, contention,
                 decision_spread, sub_steps=1):
        self.window_dts = window_dts
        self.dt = min(window_dts)
        # total training steps per window: timed outer calls x scanned
        # inner steps (steps_per_call), so ms_per_step reconciles with
        # the tokens/sec computed from steps*spc on the same JSON line
        self.steps = steps * sub_steps
        self.carry = carry
        self.res = res
        # the spread the contention decision was made on (best-N
        # windows) — NOT the all-windows spread, which legitimately
        # includes retried-away outliers
        self.spread = decision_spread
        self.contention_suspected = contention

    def ms_per_step(self):
        return [round(d / self.steps * 1e3, 3) for d in self.window_dts]

    def extras(self):
        """Diagnostic fields to merge into the headline JSON line (the
        anti-contention record VERDICT r3 Weak #1 asked for: per-window
        per-step ms + an explicit flag when the spread is anomalous)."""
        out = {"windows_ms_per_step": self.ms_per_step(),
               "window_spread": round(self.spread, 4)}
        if self.contention_suspected:
            out["contention_suspected"] = True
        return out


def _mfu(flops_per_sec, dev, n_devices=1):
    """Utilization against the chips' bf16 peak — the one table,
    monitor/cost.PEAK_FLOPS, keyed by device_kind — or None on a device
    that has no entry there (the CPU): no peak, no MFU."""
    from paddle_tpu.monitor import cost as _cost
    peak = _cost.PEAK_FLOPS.get(dev.device_kind)
    return None if peak is None else flops_per_sec / (peak * n_devices)


def _vs_baseline(mfu):
    """MFU over the reference's 0.35 (BASELINE.json), None without one."""
    return None if mfu is None else round(mfu / 0.35, 4)


def _timed_steps(step_once, carry, steps, settle=3, windows=None,
                 spread_threshold=0.20, max_windows=6, sub_steps=1):
    """Shared timing harness for every bench mode: 1 compile/warmup
    step, ``settle`` steps to fill the dispatch pipeline, then
    ``windows`` (default 3, BENCH_WINDOWS overrides) independent timed
    windows of ``steps`` steps each. The reported time is the BEST
    window — a slow sample means interference (host jitter, a
    contended machine), never a faster program, so min is the
    estimator (same reasoning as the reference's examples/sec loop
    discarding warmup, benchmark/fluid/fluid_benchmark.py:297-300, made
    robust). If the window spread exceeds ``spread_threshold``, extra
    windows run (up to ``max_windows``); if the spread over the best 3
    still exceeds it, the result carries contention_suspected=True.

    The sync is a HOST FETCH of the step's result: it cannot return
    before every queued dispatch it depends on has executed.
    step_once(carry) -> (carry, result). Returns a TimedResult."""
    if windows is None:
        windows = int(os.environ.get("BENCH_WINDOWS", "3"))
    # >=2: a single window can neither measure spread nor flag
    # contention — exactly the silent-3x-low failure this harness exists
    # to prevent (VERDICT r3 Weak #1)
    windows = max(2, windows)
    carry, res = step_once(carry)
    float(np.ravel(np.asarray(res))[0])
    for _ in range(settle):
        carry, res = step_once(carry)
    float(np.ravel(np.asarray(res))[0])

    def one_window():
        nonlocal carry, res
        t0 = time.perf_counter()
        for _ in range(steps):
            carry, res = step_once(carry)
        float(np.ravel(np.asarray(res))[0])
        return time.perf_counter() - t0

    def best_spread(dts):
        # judge the spread on the best `windows` samples: one bad
        # window in a retried run must not flag contention if the
        # retries agree with the fast windows
        best = sorted(dts)[:windows]
        return (max(best) - min(best)) / min(best)

    dts = [one_window() for _ in range(windows)]
    while len(dts) < max_windows and best_spread(dts) > spread_threshold:
        dts.append(one_window())
    spread = best_spread(dts)
    tr = TimedResult(dts, steps, carry, res,
                     contention=spread > spread_threshold,
                     decision_spread=spread, sub_steps=sub_steps)
    # the ad-hoc windows dict also lands in the unified metrics
    # registry, so a bench run's numbers ride the same snapshot pipeline
    # as production telemetry (monitor/exporter.py; BENCH_METRICS_OUT
    # below writes the Prometheus file)
    from paddle_tpu.monitor.registry import histogram
    h = histogram("bench_window_ms_per_step",
                  "Per-step wall ms of each timed bench window")
    for v in tr.ms_per_step():
        h.observe(v)
    return tr


def _abba_overhead(window, pairs, bound=1.05, rounds=3):
    """Shared tracing-on/off A/B protocol (bench serving + dispatch):
    ABBA-ordered window quadruples — both sides of each ratio sit in
    the same slice of a shared host's drifting load — estimated by the
    TRIMMED MEAN of pair ratios (individual pairs are wide on this
    host: ~30% exceed 1.05 even for a true-1.00 effect, so a median
    over a dozen pairs flakes; the mean tightens by CLT and the trim
    guards the one wild pair). When the estimate sits above ``bound``,
    gather ``pairs`` more quadruples (all data kept, never discarded)
    up to ``rounds`` extra times — a true regression stays above the
    bound however many pairs pile on.

    ``window(traced)`` runs one timed window and returns its per-unit
    time. Returns ``(estimate, pair_ratios, on_times, off_times)``."""
    pair_ratios, on_ts, off_ts = [], [], []

    def run_pairs(n):
        for _ in range(n):
            a1 = window(True)
            b1 = window(False)
            b2 = window(False)
            a2 = window(True)
            on_ts.extend((a1, a2))
            off_ts.extend((b1, b2))
            pair_ratios.append((a1 + a2) / (b1 + b2))

    def estimate():
        rs = sorted(pair_ratios)
        if len(rs) >= 6:
            rs = rs[1:-1]
        return float(np.mean(rs))

    run_pairs(pairs)
    for _round in range(rounds):
        if estimate() < bound:
            break
        run_pairs(pairs)
    return estimate(), pair_ratios, on_ts, off_ts


def bench_resnet50():
    """Secondary benchmark (`python bench.py resnet50`): ResNet-50
    images/sec/chip + MFU — BASELINE.json's second headline config."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh, set_mesh

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    # BENCH_RESNET_REMAT=block A/Bs the conv-outputs-only remat
    # experiment (models/resnet.py ResNetConfig.remat; BASELINE.md
    # "ResNet-50 remat experiment")
    rm = os.environ.get("BENCH_RESNET_REMAT", "none")
    assert rm in ("none", "block"), \
        f"BENCH_RESNET_REMAT must be none|block, got {rm!r}"
    cfg = (resnet.resnet50(remat=rm) if on_tpu
           else resnet.resnet_cifar10(depth=8, image_size=16, remat=rm))
    batch = 256 if on_tpu else 8
    steps = 20 if on_tpu else 3
    mesh = set_mesh(make_mesh(MeshConfig(data=1), devices=jax.devices()[:1]))
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    # scanned steps per dispatch (train_from_dataset pattern) amortize
    # the host's per-dispatch gap; the batch is reused per inner step
    # exactly like the reference's --use_fake_data (BENCH_SPC overrides)
    spc = int(os.environ.get("BENCH_SPC", "8" if on_tpu else "1"))
    init_fn, step_fn = resnet.make_train_step(cfg, opt, mesh,
                                              steps_per_call=spc)
    imgs, labels = resnet.synthetic_batch(cfg, batch)
    # pre-stage the batch on device: the measured loop models an input
    # pipeline that overlaps host->device transfer (ref: buffered_reader.cc)
    from jax.sharding import NamedSharding, PartitionSpec as P
    dsh = NamedSharding(mesh, P("data"))
    imgs = jax.device_put(imgs, dsh)
    labels = jax.device_put(labels, dsh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))

    def once(carry):
        params, opt_state = carry
        loss, acc, params, opt_state = step_fn(params, opt_state, imgs,
                                               labels)
        return (params, opt_state), loss

    tr = _timed_steps(once, (params, opt_state), steps, sub_steps=spc)
    loss = tr.res
    img_per_sec = batch * spc * steps / tr.dt
    mfu = _mfu(img_per_sec * resnet.flops_per_image(cfg), dev)
    print(json.dumps({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": _vs_baseline(mfu),
        **tr.extras(),
    }))
    print(f"# device={dev.platform} batch={batch} steps={steps} "
          f"loss={float(loss):.4f} mfu={mfu}", file=sys.stderr)


def bench_inference():
    """`python bench.py inference` — the reference's OWN headline
    benchmark shape: ResNet50/VGG16 imagenet single-image-stream
    inference latency, half precision (bf16 here, fp16 there) vs fp32,
    per batch size (ref: paddle/contrib/float16/float16_benchmark.md;
    tables carried in BASELINE.md). One JSON line per (model, dtype, mb);
    vs_baseline on the summary line = reference V100 fp16 latency /
    ours at the largest common batch."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import resnet, vgg

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    steps = 30 if on_tpu else 3
    # reference table rows: (model tag, cfg factory, batches, V100 fp16
    # latency at largest batch — float16_benchmark.md:23-25,39-44)
    jobs = [
        ("resnet50", lambda dt: resnet.resnet50(dtype=dt),
         resnet, [1, 2, 4, 8, 16, 32, 64, 128] if on_tpu else [1, 2],
         64.52),
        ("vgg16", lambda dt: vgg.vgg16(dtype=dt),
         vgg, [1, 2, 4, 8, 16, 32, 64] if on_tpu else [1, 2], 60.23),
    ]
    summary = {}
    for tag, mk, mod, batches, ref_ms in jobs:
        for dtname, dt in (("bf16", jnp.bfloat16), ("fp32", jnp.float32)):
            cfg = mk(dt)
            if not on_tpu:
                cfg = (resnet.resnet_cifar10(depth=8, image_size=16,
                                             dtype=dt)
                       if mod is resnet else vgg.vgg11(image_size=32,
                                                       dtype=dt))
            params = mod.init_params(jax.random.PRNGKey(0), cfg)
            fwd = jax.jit(
                lambda p, x, cfg=cfg, mod=mod: mod.forward(
                    p, cfg, x, train=False))
            for mb in batches:
                x = jnp.zeros((mb, cfg.image_size, cfg.image_size, 3),
                              jnp.float32)

                def once(carry):
                    out = fwd(params, x)
                    return carry, jax.tree.leaves(out)[0].ravel()[:1]

                tr = _timed_steps(once, None, steps, settle=0)
                ms = tr.dt / steps * 1e3
                line = {
                    "metric": f"{tag}_{dtname}_infer_latency_mb{mb}",
                    "value": round(ms, 3), "unit": "ms"}
                if tr.contention_suspected:
                    line["contention_suspected"] = True
                print(json.dumps(line))
                summary[(tag, dtname, mb)] = (ms, tr.contention_suspected)
    if on_tpu:
        # distinct metric names: the per-batch loop already printed the
        # raw latencies; these summarize vs the reference's V100 fp16
        # numbers at each model's largest common batch (jobs[..].ref_ms)
        for tag, mk, mod, batches, ref_ms in jobs:
            entry = summary.get((tag, "bf16", batches[-1]))
            if entry:
                ours, contended = entry
                line = {
                    "metric": (f"{tag}_bf16_infer_speedup_vs_v100fp16_"
                               f"mb{batches[-1]}"),
                    "value": round(ref_ms / ours, 3), "unit": "x",
                    "vs_baseline": round(ref_ms / ours, 3)}
                if contended:
                    line["contention_suspected"] = True
                print(json.dumps(line))


def bench_int8():
    """`python bench.py int8` — int8 vs bf16 inference latency on the
    chip (VERDICT r4 #2; the reference's int8 story is perf-motivated:
    trt int8 engine + calibrator, inference/tensorrt/engine.h:43,
    trt_int8_calibrator.cc, measured with the float16_benchmark.md
    discipline). Three model shapes at 2-3 batch sizes each:

      mlp        — digits-style fc stack (quantized_mul)
      resnet50   — the three dominant ResNet-50 conv shapes chained
                   (quantized_conv2d)
      bert_layer — one BERT-base encoder layer's matmuls at S=128
                   (quantized_mul for QKV/proj/FFN)

    Each row prints int8 ms, bf16 ms, and speedup; v5e's MXU runs
    s8xs8->s32 at 2x the bf16 rate (394 vs 197 TOPS peak), so a row
    materially above 1.0x means XLA mapped the dot/conv onto int8 MXU
    passes; below 1.0x means the quantize/dequantize elementwise
    traffic dominates at that shape (an honest negative, recorded)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.quantize import (quantize_linear, quantized_conv2d,
                                         quantized_mul)

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    steps = 30 if on_tpu else 3
    rng = np.random.RandomState(0)

    # Each candidate fn(*args, jit_c) -> scalar runs ITERS times
    # inside ONE jitted fori_loop (the scalar carry perturbs the input
    # so iterations cannot be CSE'd): at these shapes a single
    # application is ~0.1 ms of device time, which the per-dispatch
    # floor would swamp along with any int8-vs-bf16 difference.
    # Reported ms is per INNER iteration.
    ITERS = 100 if on_tpu else 2   # CPU smoke: the loop exists to
    # amortize the dispatch; on CPU 100 conv iterations would take
    # minutes and measure nothing

    def timed(fn, *args):
        def looped(*a):
            def body(i, c):
                return fn(*a, c * 1e-12)
            return jax.lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

        jfn = jax.jit(looped)

        def once(carry):
            return carry, jnp.ravel(jfn(*args))[:1]

        tr = _timed_steps(once, None, steps, settle=2)
        return tr.dt / steps / ITERS * 1e3, tr.contention_suspected

    def report(tag, mb, int8_ms, bf16_ms, contended):
        line = {"metric": f"int8_{tag}_mb{mb}_speedup_vs_bf16",
                "value": round(bf16_ms / int8_ms, 3), "unit": "x",
                "int8_ms": round(int8_ms, 3),
                "bf16_ms": round(bf16_ms, 3)}
        if contended:
            line["contention_suspected"] = True
        print(json.dumps(line))

    # -- mlp: 784 -> 512 -> 512 -> 10 (digits-style, scaled up) ----------
    dims = [784, 512, 512, 10]
    ws = [rng.randn(a, b).astype(np.float32) * 0.05
          for a, b in zip(dims, dims[1:])]
    w_scales = [float(np.abs(w).max()) for w in ws]
    wq = [np.asarray(quantize_linear(w, s)) for w, s in zip(ws, w_scales)]
    wb = [jnp.asarray(w, jnp.bfloat16) for w in ws]

    def mlp_int8(x, c):
        h = x + c
        for q, s in zip(wq, w_scales):
            h = jnp.maximum(quantized_mul(h, q, 4.0, s), 0.0)
        return h.sum()

    def mlp_bf16(x, c):
        h = (x + c).astype(jnp.bfloat16)
        for w in wb:
            h = jnp.maximum(h @ w, 0.0)
        return h.sum(dtype=jnp.float32)

    for mb in ([64, 512, 4096] if on_tpu else [8]):
        x = jnp.asarray(rng.rand(mb, dims[0]).astype(np.float32))
        i_ms, c1 = timed(mlp_int8, x)
        b_ms, c2 = timed(mlp_bf16, x)
        report("mlp", mb, i_ms, b_ms, c1 or c2)

    # -- resnet50 conv shapes: the three layer archetypes chained --------
    # (1x1 expand, 3x3 mid-stage, 1x1 reduce — where ResNet-50's conv
    # FLOPs live; chaining keeps intermediate activations on device)
    conv_shapes = [  # (cin, cout, k, hw, stride)
        (256, 64, 1, 56, 1),
        (128, 128, 3, 28, 1),
        (1024, 256, 1, 14, 1),
    ]
    cw = [rng.randn(co, ci, k, k).astype(np.float32) * 0.05
          for ci, co, k, hw, st in conv_shapes]
    cw_scales = [float(np.abs(w).max()) for w in cw]
    cwq = [np.asarray(quantize_linear(w, s))
           for w, s in zip(cw, cw_scales)]
    cwb = [jnp.asarray(w, jnp.bfloat16) for w in cw]

    def convs_int8(*xs_c):
        *xs, c = xs_c
        out = jnp.float32(0.0)
        for x, q, s, (ci, co, k, hw, st) in zip(xs, cwq, cw_scales,
                                                conv_shapes):
            out += quantized_conv2d(x + c, q, 4.0, s, stride=st,
                                    padding=k // 2).sum()
        return out

    def convs_bf16(*xs_c):
        *xs, c = xs_c
        out = jnp.float32(0.0)
        for x, w, (ci, co, k, hw, st) in zip(xs, cwb, conv_shapes):
            dn = jax.lax.conv_dimension_numbers(
                x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
            out += jax.lax.conv_general_dilated(
                (x + c).astype(jnp.bfloat16), w, (st, st),
                [(k // 2, k // 2)] * 2,
                dimension_numbers=dn).sum(dtype=jnp.float32)
        return out

    for mb in ([8, 32, 128] if on_tpu else [2]):
        xs = [jnp.asarray(rng.rand(mb, ci, hw, hw).astype(np.float32))
              for ci, co, k, hw, st in conv_shapes]
        i_ms, c1 = timed(convs_int8, *xs)
        b_ms, c2 = timed(convs_bf16, *xs)
        report("resnet50convs", mb, i_ms, b_ms, c1 or c2)

    # -- bert encoder layer matmuls (h=768, ffn=3072, S=128) -------------
    H, F, S = 768, 3072, 128
    bw = {"qkv": rng.randn(H, 3 * H), "proj": rng.randn(H, H),
          "up": rng.randn(H, F), "down": rng.randn(F, H)}
    bw = {k: (v * 0.02).astype(np.float32) for k, v in bw.items()}
    b_scales = {k: float(np.abs(v).max()) for k, v in bw.items()}
    bq = {k: np.asarray(quantize_linear(v, b_scales[k]))
          for k, v in bw.items()}
    bb = {k: jnp.asarray(v, jnp.bfloat16) for k, v in bw.items()}

    def bert_int8(x, c):
        qkv = quantized_mul(x + c, bq["qkv"], 8.0, b_scales["qkv"],
                            x_num_col_dims=2)
        h = quantized_mul(qkv[..., :H], bq["proj"], 8.0,
                          b_scales["proj"], x_num_col_dims=2)
        u = jnp.maximum(quantized_mul(h, bq["up"], 8.0, b_scales["up"],
                                      x_num_col_dims=2), 0.0)
        return quantized_mul(u, bq["down"], 8.0, b_scales["down"],
                             x_num_col_dims=2).sum()

    def bert_bf16(x, c):
        xb = (x + c).astype(jnp.bfloat16)
        qkv = xb @ bb["qkv"]
        h = qkv[..., :H] @ bb["proj"]
        u = jnp.maximum(h @ bb["up"], 0)
        return (u @ bb["down"]).sum(dtype=jnp.float32)

    for mb in ([8, 32] if on_tpu else [2]):
        x = jnp.asarray(rng.rand(mb, S, H).astype(np.float32))
        i_ms, c1 = timed(bert_int8, x)
        b_ms, c2 = timed(bert_bf16, x)
        report("bert_layer", mb, i_ms, b_ms, c1 or c2)


def _passes_trunk_program(hidden, seq, blocks):
    """Static-graph BERT trunk for `bench.py passes` (the pass pipeline
    operates on Programs; models/bert.py is functional): ``blocks``
    post-LN transformer blocks of fc-projected attention + fc FFN —
    mul+bias(+act) chains (FuseMatmulBiasActPass fodder, the
    reference's fc_fuse_pass shape), the 1/sqrt(d) attention scale
    (scale-chain family) and the k-transpose (transpose/reshape
    family). Returns (main, startup, fetch_name)."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import unique_name

    pt.enable_static()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), unique_name.guard():
        x = pt.static.data("x", [seq, hidden], dtype="float32")
        for _ in range(blocks):
            q = layers.fc(x, hidden, num_flatten_dims=2)
            k = layers.fc(x, hidden, num_flatten_dims=2)
            v = layers.fc(x, hidden, num_flatten_dims=2)
            kt = layers.transpose(k, [0, 2, 1])
            att = layers.matmul(q, kt)
            att = layers.scale(att, scale=1.0 / np.sqrt(hidden))
            att = layers.softmax(att)
            ctx = layers.matmul(att, v)
            o = layers.fc(ctx, hidden, num_flatten_dims=2)
            x = layers.layer_norm(layers.elementwise_add(x, o),
                                  begin_norm_axis=2)
            h = layers.fc(x, 4 * hidden, act="relu",
                          num_flatten_dims=2)
            h = layers.fc(h, hidden, num_flatten_dims=2)
            x = layers.layer_norm(layers.elementwise_add(x, h),
                                  begin_norm_axis=2)
        out = layers.mean(x)
    return main, startup, out.name


def _passes_mlp_program():
    """The serving MLP (same shape as ``_freeze_serving_mlp``) as a
    bare program, for the `bench.py passes` A/B."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import unique_name

    pt.enable_static()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), unique_name.guard():
        x = pt.static.data("x", [256], dtype="float32")
        h = layers.fc(x, 256, act="relu")
        h = layers.fc(h, 256, act="relu")
        out = layers.fc(h, 10)
        out = layers.mean(out)
    return main, startup, out.name


def bench_passes():
    """`python bench.py passes` — the program-level pass pipeline's
    on/off A/B (docs/PERFORMANCE.md "Program pass pipeline"): the SAME
    program runs through the Executor twice, wrapped in
    ``CompiledProgram``s whose ``BuildStrategy.apply_ir_passes`` pins
    the pipeline on vs off (off = the bit-identical legacy lowering),
    over the static BERT trunk and the serving MLP. Windows interleave
    in ABBA quadruples (the shared ``_abba_overhead`` protocol) so both
    sides of each ratio see the same slice of host drift; one JSON line
    per model carries the step-time ratio, the per-pass ops-removed
    evidence (``PipelineReport``; the live compile also lands
    ``program_pass_*`` in the registry snapshot) and an
    ``outputs_match`` fetch-equivalence check. Headline
    ``passes_step_ratio`` is the WORST model ratio — the acceptance
    bar is <= 1.0x (the pipeline must never cost a step). Knobs:
    BENCH_PASSES_STEPS / BENCH_PASSES_PAIRS."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.compiler import BuildStrategy, CompiledProgram
    from paddle_tpu.static import opt_passes

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    steps = int(os.environ.get("BENCH_PASSES_STEPS",
                               "30" if on_tpu else "6"))
    pairs = int(os.environ.get("BENCH_PASSES_PAIRS", "3"))
    rng = np.random.RandomState(0)

    models = []
    main, startup, fetch = _passes_mlp_program()
    models.append(("serving_mlp", main, startup,
                   {"x": rng.rand(8, 256).astype(np.float32)}, fetch))
    h, s, b = (256, 128, 4) if on_tpu else (32, 16, 2)
    main, startup, fetch = _passes_trunk_program(h, s, b)
    models.append(("bert_trunk", main, startup,
                   {"x": rng.rand(8 if on_tpu else 2, s, h)
                    .astype(np.float32)}, fetch))

    worst = None
    for tag, main, startup, feed, fetch in models:
        scope = pt.static.Scope()
        with pt.static.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            bs_on, bs_off = BuildStrategy(), BuildStrategy()
            bs_on.apply_ir_passes = True
            bs_off.apply_ir_passes = False
            prog_on = CompiledProgram(main, build_strategy=bs_on)
            prog_off = CompiledProgram(main, build_strategy=bs_off)

            def run_once(prog, feed=feed, fetch=fetch, exe=exe):
                return np.asarray(
                    exe.run(prog, feed=feed, fetch_list=[fetch])[0])

            # the live compile runs under FLAGS_pass_cost_evidence, so
            # each pass's predicted FLOPs/bytes delta (pre/post HLO
            # cost_analysis) lands in program_pass_*_delta and the
            # pass_evidence table — probing happens at compile time
            # only, the timed windows below never pay it
            from paddle_tpu.core.flags import set_flags
            from paddle_tpu.monitor import cost as _pcost
            ev0 = _pcost.pass_evidence()
            set_flags({"pass_cost_evidence": True})
            try:
                out_on = run_once(prog_on)  # compiles each path once
            finally:
                set_flags({"pass_cost_evidence": False})
            predicted = {
                p: {k: t.get(k, 0.0) - ev0.get(p, {}).get(k, 0.0)
                    for k in ("flops_delta", "bytes_delta")}
                for p, t in _pcost.pass_evidence().items()
                if "flops_delta" in t or "bytes_delta" in t}
            out_off = run_once(prog_off)
            outputs_match = bool(np.allclose(out_on, out_off,
                                             rtol=1e-5, atol=1e-6))

            def window(on, prog_on=prog_on, prog_off=prog_off,
                       run_once=run_once):
                prog = prog_on if on else prog_off
                t0 = time.perf_counter()
                for _ in range(steps):
                    r = run_once(prog)
                float(np.ravel(r)[0])
                return (time.perf_counter() - t0) / steps * 1e3

            window(True), window(False)     # settle both paths
            est, pair_ratios, on_ms, off_ms = _abba_overhead(
                window, pairs, bound=1.0)
        # evidence from a metrics-silent re-run of the pipeline (the
        # live compile above already published program_pass_* to the
        # registry; this report is the per-model JSON the smoke reads)
        _, report = opt_passes.optimize_program(
            main, targets=(fetch,), record=False)
        print(json.dumps({
            "metric": f"passes_step_ratio_{tag}",
            "value": round(est, 4), "unit": "x",
            "on_ms_per_step": round(float(np.median(on_ms)), 3),
            "off_ms_per_step": round(float(np.median(off_ms)), 3),
            "pair_ratios": [round(r, 4) for r in pair_ratios],
            "outputs_match": outputs_match,
            "steps_per_window": steps,
            "pass_cost_deltas": {
                p: {k: round(float(v), 1) for k, v in d.items()}
                for p, d in sorted(predicted.items())},
            **report.as_dict(),
        }))
        if worst is None or est > worst:
            worst = est
    print(json.dumps({
        "metric": "passes_step_ratio",
        "value": round(worst, 4), "unit": "x",
        # bigger-is-better convention: legacy/optimized step speedup
        "vs_baseline": round(1.0 / worst, 4),
    }))


def _freeze_serving_mlp(dirname, quant_dir=None, quant_mode="int8"):
    """The serving-bench model: a dispatch-bound MLP — online serving
    of small models is dominated by per-request dispatch overhead,
    exactly the cost continuous batching amortizes (a compute-bound
    model would measure the chip, not the serving stack). Shared by
    the headline A/B, the chaos bench, and the hot-swap bench (which
    freezes a SECOND copy as the new version). ``quant_dir``
    additionally freezes THE SAME weights there with an
    ``export_aot(quantize=quant_mode)`` sidecar — the quantized side
    of the BENCH_SERVING_QUANT A/B (same-weights is what makes its
    accuracy delta meaningful)."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import unique_name

    pt.enable_static()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), unique_name.guard():
        x = pt.static.data("x", [256], dtype="float32")
        h = layers.fc(x, 256, act="relu")
        h = layers.fc(h, 256, act="relu")
        out = layers.fc(h, 10)
    scope = pt.static.Scope()
    with pt.static.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        pt.io.save_inference_model(dirname, ["x"], [out], exe,
                                   main_program=main)
        if quant_dir is not None:
            from paddle_tpu import inference as inf
            pt.io.save_inference_model(quant_dir, ["x"], [out], exe,
                                       main_program=main)
            inf.export_aot(quant_dir, main, ["x"], [out.name], scope,
                           [{"x": ((1, 256), "float32")}],
                           quantize=quant_mode)
    return dirname


def _bench_serving_swap(d, feed, max_batch, max_wait_ms):
    """The hot-swap half of `bench.py serving`
    (BENCH_SERVING_SWAP=1, docs/SERVING.md "Hot model swap"): ONE
    open-loop Poisson schedule at ~0.5x measured capacity with a
    ``server.swap()`` to a freshly frozen second version fired at the
    schedule midpoint. Every request is accounted (a hang is a bench
    failure); two JSON lines:

    - ``serving_swap_p99_ratio``: p99 latency of requests whose
      [arrival, completion] overlaps the swap window (gate ->
      watchdog-pass) vs the p99 of the rest — the acceptance target
      is <= 1.5x (the swap builds the standby OFF the serving path,
      so overlap requests should barely notice).
    - ``serving_swap_blip_ms``: the longest gap between consecutive
      request completions that overlaps the swap window — the cutover
      stall an operator would see on a completions dashboard.

    Knobs: BENCH_SERVING_SWAP_REQS (default 300),
    BENCH_SERVING_SWAP_WATCHDOG_MS (default 200)."""
    import tempfile
    import threading

    from paddle_tpu.serving import InferenceServer, ServingConfig

    n = int(os.environ.get("BENCH_SERVING_SWAP_REQS", "300"))
    watchdog_ms = float(os.environ.get(
        "BENCH_SERVING_SWAP_WATCHDOG_MS", "200"))
    d2 = _freeze_serving_mlp(tempfile.mkdtemp())

    srv = InferenceServer(d, ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_queue=n + 64, replicas=1))
    t0 = time.perf_counter()
    for _ in range(20):
        srv.infer({"x": feed}, timeout=60)
    cap = 20 / (time.perf_counter() - t0)
    offered = 0.5 * cap
    sched = np.cumsum(np.random.RandomState(17).exponential(
        1.0 / offered, size=n))

    swap_state = {}

    def do_swap():
        t_s = time.perf_counter()
        try:
            swap_state["report"] = srv.swap(d2,
                                            watchdog_ms=watchdog_ms)
        except Exception as e:       # surfaced in the JSON row
            swap_state["error"] = f"{type(e).__name__}: {e}"
        swap_state["t0"] = t_s
        swap_state["t1"] = time.perf_counter()

    pend = [None] * n
    arrived = [0.0] * n
    swap_thread = None
    t_origin = time.perf_counter()
    for i in range(n):
        dly = t_origin + sched[i] - time.perf_counter()
        if dly > 0:
            time.sleep(dly)
        if i == n // 2 and swap_thread is None:
            swap_thread = threading.Thread(target=do_swap,
                                           daemon=True)
            swap_thread.start()
        arrived[i] = t_origin + sched[i]
        pend[i] = srv.submit({"x": feed})
    hangs = 0
    for p in pend:
        try:
            p.result(timeout=120)
        except TimeoutError:
            hangs += 1
        except Exception:
            pass                     # typed errors are accounted below
    if swap_thread is not None:
        swap_thread.join(120)
    srv.close(timeout=60)

    t0s = swap_state.get("t0", float("inf"))
    t1s = swap_state.get("t1", float("-inf"))
    done = [p.t_done for p in pend]
    lat_ms = [(dn - ar) * 1e3 for dn, ar in zip(done, arrived)
              if dn is not None]
    overlap = [(dn - ar) * 1e3 for dn, ar in zip(done, arrived)
               if dn is not None and ar <= t1s and dn >= t0s]
    steady = [(dn - ar) * 1e3 for dn, ar in zip(done, arrived)
              if dn is not None and (ar > t1s or dn < t0s)]
    p99_overlap = (float(np.percentile(overlap, 99))
                   if overlap else None)
    p99_steady = (float(np.percentile(steady, 99))
                  if steady else None)
    ratio = (round(p99_overlap / p99_steady, 3)
             if overlap and steady and p99_steady > 0 else None)
    # the longest completion silence overlapping the swap window: the
    # stall an operator's completions-per-second dashboard would show
    comp = sorted(dn for dn in done if dn is not None)
    blip = 0.0
    for a, b in zip(comp, comp[1:]):
        if b >= t0s and a <= t1s:
            blip = max(blip, (b - a) * 1e3)
    print(json.dumps({
        "metric": "serving_swap_p99_ratio",
        "value": ratio, "unit": "x",
        "p99_overlap_ms": (round(p99_overlap, 2)
                           if p99_overlap is not None else None),
        "p99_steady_ms": (round(p99_steady, 2)
                          if p99_steady is not None else None),
        "n_overlap": len(overlap), "n_steady": len(steady),
        "hangs": hangs,
        "outcome": (swap_state.get("report", {}).get("outcome")
                    if "report" in swap_state
                    else swap_state.get("error", "not-run")),
        "swap_ms": (round((t1s - t0s) * 1e3, 1)
                    if "t0" in swap_state else None),
        "offered_qps": round(offered, 1),
    }))
    print(json.dumps({
        "metric": "serving_swap_blip_ms",
        "value": round(blip, 2), "unit": "ms",
        "swap_window_ms": (round((t1s - t0s) * 1e3, 1)
                           if "t0" in swap_state else None),
        "watchdog_ms": watchdog_ms,
    }))


def _bench_serving_quant(max_batch, max_wait_ms):
    """The quantized-serving half of `bench.py serving`
    (BENCH_SERVING_QUANT=1, docs/SERVING.md "Quantized serving"):
    fp32 vs weight-quantized serving of THE SAME weights under the
    SAME open-loop Poisson schedule. Two ``InferenceServer``s boot
    from two frozen dirs sharing one init (``_freeze_serving_mlp``'s
    quant_dir); the quantized dir carries the
    ``export_aot(quantize=...)`` sidecar the warm boot loads
    transparently. JSON rows: per-system sustained QPS + p50/p99 +
    device-resident param bytes (``ReplicaPool.resident_param_bytes``),
    the QPS ratio (acceptance: >= 1.0x — weight-only PTQ must never
    cost throughput), the resident-bytes ratio (acceptance: <= 0.55x
    for int8) and the fixture accuracy delta (max |quant - fp| over
    the fp output span on a 16-row fixture batch — the documented
    accuracy evidence). Knobs: BENCH_SERVING_QUANT_REQS / _MODE,
    BENCH_SERVING_REPLICAS / _RATE_X."""
    import tempfile

    from paddle_tpu.serving import InferenceServer, ServingConfig

    mode = os.environ.get("BENCH_SERVING_QUANT_MODE", "int8")
    n = int(os.environ.get("BENCH_SERVING_QUANT_REQS", "400"))
    rate_x = float(os.environ.get("BENCH_SERVING_RATE_X", "3.0"))
    replicas = int(os.environ.get("BENCH_SERVING_REPLICAS", "1"))

    d_fp = tempfile.mkdtemp()
    d_q = tempfile.mkdtemp()
    _freeze_serving_mlp(d_fp, quant_dir=d_q, quant_mode=mode)
    rng = np.random.RandomState(0)
    feed = rng.rand(1, 256).astype(np.float32)
    fixture = rng.rand(16, 256).astype(np.float32)

    results = {}
    sched = offered = None
    for tag, d in (("fp", d_fp), ("quant", d_q)):
        srv = InferenceServer(d, ServingConfig(
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            max_queue=n + 64, replicas=replicas))
        # fixture rides in bucket-ladder-sized chunks (a single
        # 16-row request would overflow a small max_batch)
        chunk = max(1, min(max_batch, len(fixture)))
        fix_out = np.vstack([
            np.asarray(srv.infer({"x": fixture[i:i + chunk]},
                                 timeout=120)[0])
            for i in range(0, len(fixture), chunk)])
        t0 = time.perf_counter()
        for _ in range(20):
            srv.infer({"x": feed}, timeout=60)
        svc_s = (time.perf_counter() - t0) / 20
        if sched is None:
            # ONE schedule, derived from the FP service rate, shared
            # by both systems — equal offered load is literal
            offered = rate_x * replicas / svc_s
            sched = np.cumsum(np.random.RandomState(42).exponential(
                1.0 / offered, size=n))
        pend = [None] * n
        arrived = [0.0] * n
        t_origin = time.perf_counter()
        for i in range(n):
            dly = t_origin + sched[i] - time.perf_counter()
            if dly > 0:
                time.sleep(dly)
            arrived[i] = t_origin + sched[i]
            pend[i] = srv.submit({"x": feed})
        for p in pend:
            p.result(timeout=600)
        done = [p.t_done for p in pend]
        lat_ms = np.sort((np.asarray(done) - np.asarray(arrived))
                         * 1e3)
        qps = n / (max(done) - t_origin)
        param_bytes = srv.pool.resident_param_bytes()
        srv.close(timeout=60)
        results[tag] = {"qps": qps, "bytes": param_bytes,
                        "out": fix_out}
        row = {
            "metric": f"serving_{tag}_qps",
            "value": round(qps, 1), "unit": "req/s",
            "offered_qps": round(offered, 1), "n_requests": n,
            "replicas": replicas,
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
            "param_bytes": int(param_bytes),
            "service_ms": round(svc_s * 1e3, 3),
        }
        if tag == "quant":
            row["quantize"] = mode
        print(json.dumps(row))

    span = float(np.max(np.abs(results["fp"]["out"]))) + 1e-9
    delta = float(np.max(np.abs(results["quant"]["out"]
                                - results["fp"]["out"]))) / span
    print(json.dumps({
        "metric": "serving_quant_vs_fp_qps",
        "value": round(results["quant"]["qps"]
                       / results["fp"]["qps"], 3),
        "unit": "x",
        "vs_baseline": round(results["quant"]["qps"]
                             / results["fp"]["qps"], 3),
        "quantize": mode,
    }))
    print(json.dumps({
        "metric": "serving_quant_param_bytes_ratio",
        "value": round(results["quant"]["bytes"]
                       / results["fp"]["bytes"], 4),
        "unit": "x",
        "fp_bytes": int(results["fp"]["bytes"]),
        "quant_bytes": int(results["quant"]["bytes"]),
    }))
    print(json.dumps({
        "metric": "serving_quant_accuracy_delta",
        "value": round(delta, 6), "unit": "rel",
        "fixture_rows": int(fixture.shape[0]),
        "fp_output_span": round(span, 4),
        "quantize": mode,
    }))


def _bench_serving_http(d, feed, max_batch, max_wait_ms, replicas):
    """The front-door half of `bench.py serving`
    (BENCH_SERVING_HTTP=1, docs/SERVING.md "Front door"): ONE
    deterministic open-loop Poisson schedule, run through the wire
    (persistent ``WireClient`` connections against a live
    ``HttpFrontDoor``) and in-process (``srv.submit``), interleaved in
    ABBA quadruples via the shared ``_abba_overhead`` protocol so both
    sides see the same slice of host drift. Emits
    ``serving_http_vs_inproc_p99_ratio`` — the wire path's tail cost
    over the library path (JSON + socket + handler thread per
    request; no bound asserted, the number IS the evidence). Offered
    load is half the measured closed-loop capacity, so both windows
    measure overhead rather than saturation queueing. Knobs:
    BENCH_SERVING_HTTP_REQS (default 80), _PAIRS (default 2), _CONNS
    (default 8 client connections)."""
    import queue as _queue
    import threading

    from paddle_tpu.serving import (
        FrontDoorConfig, HttpFrontDoor, InferenceServer,
        ServingConfig, WireClient,
    )

    n = int(os.environ.get("BENCH_SERVING_HTTP_REQS", "80"))
    pairs = int(os.environ.get("BENCH_SERVING_HTTP_PAIRS", "2"))
    conns = int(os.environ.get("BENCH_SERVING_HTTP_CONNS", "8"))

    srv = InferenceServer(d, ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_queue=4 * n + conns, replicas=replicas))
    door = HttpFrontDoor(srv, FrontDoorConfig()).start()
    try:
        np.asarray(srv.infer({"x": feed}, timeout=120)[0])
        with WireClient("127.0.0.1", door.port) as warm:
            st, _, _ = warm.infer({"x": feed})
            assert st == 200, f"warm wire request failed: {st}"

        t0 = time.perf_counter()
        for _ in range(20):
            srv.infer({"x": feed}, timeout=60)
        cap = 20 / (time.perf_counter() - t0)
        offered = 0.5 * cap
        sched = np.cumsum(np.random.RandomState(42).exponential(
            1.0 / offered, size=n))

        def open_loop(submit):
            t_origin = time.perf_counter()
            for i in range(n):
                dly = t_origin + sched[i] - time.perf_counter()
                if dly > 0:
                    time.sleep(dly)
                submit(i, t_origin + sched[i])
            return t_origin

        def window_inproc():
            pend, arrived = [None] * n, [0.0] * n
            open_loop(lambda i, ta: (
                arrived.__setitem__(i, ta),
                pend.__setitem__(i, srv.submit({"x": feed}))))
            for p in pend:
                p.result(timeout=600)
            lat = [(p.t_done - ta) * 1e3
                   for p, ta in zip(pend, arrived)]
            return float(np.percentile(lat, 99))

        def window_wire():
            work = _queue.Queue()
            lat = [None] * n
            errs = []

            def client_worker():
                c = WireClient("127.0.0.1", door.port)
                try:
                    while True:
                        item = work.get()
                        if item is None:
                            return
                        i, ta = item
                        status, _h, _p = c.infer({"x": feed})
                        if status != 200:
                            errs.append((i, status))
                        lat[i] = (time.perf_counter() - ta) * 1e3
                except Exception as e:          # pragma: no cover
                    errs.append(e)
                finally:
                    c.close()

            threads = [threading.Thread(target=client_worker,
                                        daemon=True)
                       for _ in range(conns)]
            for t in threads:
                t.start()
            open_loop(lambda i, ta: work.put((i, ta)))
            for _ in threads:
                work.put(None)
            for t in threads:
                t.join(600)
            # every request accounted: a silent drop would flatter
            # the wire tail exactly where it hurts
            assert not errs and all(v is not None for v in lat), \
                f"wire window failures: {errs[:3]}"
            return float(np.percentile(lat, 99))

        def window(wire):
            return window_wire() if wire else window_inproc()

        window(True), window(False)             # settle both paths
        est, pair_ratios, wire_p99, inproc_p99 = _abba_overhead(
            window, pairs, bound=float("inf"), rounds=0)
        print(json.dumps({
            "metric": "serving_http_vs_inproc_p99_ratio",
            "value": round(est, 3), "unit": "x",
            "http_p99_ms": round(float(np.median(wire_p99)), 2),
            "inproc_p99_ms": round(float(np.median(inproc_p99)), 2),
            "pair_ratios": [round(r, 3) for r in pair_ratios],
            "n_per_window": n, "client_conns": conns,
            "offered_qps": round(offered, 1),
        }))
    finally:
        door.stop()
        srv.close(timeout=60)


def bench_serving():
    """`python bench.py serving` — OPEN-LOOP serving load (the honest
    way to measure tail latency: arrivals follow a deterministic-seed
    Poisson schedule at a target offered rate, and a request's latency
    is measured from its SCHEDULED arrival — a saturated system cannot
    hide queueing by slowing the load generator, i.e. no coordinated
    omission). Two systems take the SAME arrival schedule:

      baseline — single-request dispatch: ``replicas`` worker threads,
                 each with a ``Predictor.clone()``, draining one queue
                 one request at a time (the pre-serving-subsystem
                 shape);
      server   — ``paddle_tpu.serving.InferenceServer`` with the same
                 replica count: continuous micro-batching over
                 per-bucket AOT executables (docs/SERVING.md).

    The offered rate is ``BENCH_SERVING_RATE_X`` (default 3.0) times
    the measured single-request service rate — deliberately past the
    baseline's capacity, where batching either pays or doesn't. One
    JSON line per system with sustained QPS, offered QPS, p50/p99 ms,
    and (server) the micro-batch fill ratio, plus a ratio line.
    Knobs: BENCH_SERVING_REQS / _REPLICAS / _MAX_BATCH / _RATE_X /
    _MAX_WAIT_MS. The ``serving_*`` registry metrics land in the
    end-of-run snapshot every bench mode emits.

    ``BENCH_SERVING_CHAOS=1`` runs the RESILIENCE bench instead
    (docs/SERVING.md "Resilience"): a 2-replica clean-vs-stall A/B
    emitting ``serving_chaos_p99_ratio`` (p99 of unaffected requests
    with one replica wedged mid-load vs the clean run),
    ``serving_shed_precision`` (fraction of adaptively shed requests
    that DID miss their deadline in the shed-off control pass — same
    schedule, traced keep-all), and ``serving_shed_overhead_ratio``
    (the controller's clean-path open-loop p50 cost via the shared
    ABBA protocol; must stay < 1.05x).

    ``BENCH_SERVING_QUANT=1`` runs the QUANTIZED-SERVING A/B instead
    (docs/SERVING.md "Quantized serving"): fp32 vs int8/bf16
    weight-only serving of the same weights under one open-loop
    schedule — sustained QPS, p99, device-resident param bytes and
    the fixture accuracy delta (``_bench_serving_quant``).

    ``BENCH_SERVING_SWAP=1`` runs the HOT-SWAP bench instead
    (docs/SERVING.md "Hot model swap"): one open-loop schedule with a
    mid-run ``server.swap()`` to a second model version, emitting
    ``serving_swap_p99_ratio`` (p99 of requests whose lifetime
    overlaps the swap window vs steady-state) and
    ``serving_swap_blip_ms`` (the longest completion silence
    overlapping the cutover — the stall an operator would see).

    ``BENCH_SERVING_HTTP=1`` runs the FRONT-DOOR bench instead
    (docs/SERVING.md "Front door"): the same open-loop schedule
    through the wire (``HttpFrontDoor`` + persistent ``WireClient``
    connections) vs in-process ``submit``, ABBA-interleaved, emitting
    ``serving_http_vs_inproc_p99_ratio`` (``_bench_serving_http``)."""
    import queue as _queue
    import tempfile
    import threading

    import jax

    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.monitor.registry import REGISTRY
    from paddle_tpu.serving import InferenceServer, ServingConfig

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    n_reqs = int(os.environ.get("BENCH_SERVING_REQS",
                                "600" if on_tpu else "200"))
    replicas = int(os.environ.get("BENCH_SERVING_REPLICAS", "1"))
    max_batch = int(os.environ.get("BENCH_SERVING_MAX_BATCH", "8"))
    rate_x = float(os.environ.get("BENCH_SERVING_RATE_X", "3.0"))
    max_wait_ms = float(os.environ.get("BENCH_SERVING_MAX_WAIT_MS",
                                       "2.0"))

    # branch BEFORE freezing the shared dir / warm-booting the
    # baseline predictor: the quant A/B freezes its own same-weights
    # pair, and neither chaos nor swap uses the predictor
    if os.environ.get("BENCH_SERVING_QUANT") == "1":
        return _bench_serving_quant(max_batch, max_wait_ms)

    d = _freeze_serving_mlp(tempfile.mkdtemp())
    rng = np.random.RandomState(0)
    feed = rng.rand(1, 256).astype(np.float32)

    if os.environ.get("BENCH_SERVING_CHAOS") == "1":
        return _bench_serving_chaos(d, feed, max_batch, max_wait_ms)
    if os.environ.get("BENCH_SERVING_SWAP") == "1":
        return _bench_serving_swap(d, feed, max_batch, max_wait_ms)
    if os.environ.get("BENCH_SERVING_HTTP") == "1":
        return _bench_serving_http(d, feed, max_batch, max_wait_ms,
                                   replicas)

    base = create_predictor(Config(d))
    np.asarray(base.run({"x": feed})[0])       # compile once, shared

    # single-request service time -> offered rate for BOTH systems
    probes = 30 if not on_tpu else 50
    t0 = time.perf_counter()
    for _ in range(probes):
        base.run({"x": feed})
    svc_s = (time.perf_counter() - t0) / probes
    offered = rate_x * replicas / svc_s
    # ONE deterministic Poisson schedule shared by both systems —
    # "equal offered load" is literal, not statistical
    sched = np.cumsum(np.random.RandomState(42).exponential(
        1.0 / offered, size=n_reqs))

    def open_loop(submit):
        """Fire submit(i, t_arrival_abs) at each scheduled instant;
        returns the schedule origin."""
        t_origin = time.perf_counter()
        for i in range(n_reqs):
            delay = t_origin + sched[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            submit(i, t_origin + sched[i])
        return t_origin

    def line_from(tag, t_origin, done_at, lat_s, extra=None):
        lat_ms = np.sort(np.asarray(lat_s)) * 1e3
        sustained = n_reqs / (max(done_at) - t_origin)
        row = {
            "metric": f"serving_{tag}_qps",
            "value": round(sustained, 1), "unit": "req/s",
            "offered_qps": round(offered, 1),
            "n_requests": n_reqs,
            "replicas": replicas,
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        }
        row.update(extra or {})
        print(json.dumps(row))
        return sustained

    # ---- baseline: single-request Predictor dispatch -----------------
    work = _queue.Queue()
    done_at = [0.0] * n_reqs
    lat = [0.0] * n_reqs
    errs = []

    def worker(c):
        try:
            np.asarray(c.run({"x": feed})[0])  # warm this clone
            while True:
                item = work.get()
                if item is None:
                    return
                i, t_arr = item
                np.asarray(c.run({"x": feed})[0])
                done_at[i] = time.perf_counter()
                lat[i] = done_at[i] - t_arr
        except Exception as e:                  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(base.clone(),),
                                daemon=True) for _ in range(replicas)]
    for t in threads:
        t.start()
    t_origin = open_loop(lambda i, ta: work.put((i, ta)))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join(600)
    if errs or any(t.is_alive() for t in threads):
        print(json.dumps({
            "metric": "serving_baseline_error",
            "value": str(errs[0]) if errs else "worker stalled"}))
        return
    base_qps = line_from("baseline", t_origin, done_at, lat,
                         extra={"service_ms":
                                round(svc_s * 1e3, 3)})

    # ---- server: continuous micro-batching ---------------------------
    fill_m = REGISTRY.get("serving_batch_fill_ratio")
    fill0 = (fill_m.sum(), fill_m.count()) if fill_m else (0.0, 0)
    srv = InferenceServer(d, ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        # the open loop never sheds: a full queue would drop requests
        # and flatter the tail, so admission is sized to the run
        max_queue=n_reqs + replicas, replicas=replicas))
    pend = [None] * n_reqs
    arrived = [0.0] * n_reqs
    t_origin = open_loop(lambda i, ta: (
        arrived.__setitem__(i, ta),
        pend.__setitem__(i, srv.submit({"x": feed}))))
    for p in pend:
        p.result(timeout=600)
    srv.close()
    done_at = [p.t_done for p in pend]
    lat = [p.t_done - ta for p, ta in zip(pend, arrived)]
    fill_m = REGISTRY.get("serving_batch_fill_ratio")
    dsum = fill_m.sum() - fill0[0]
    dcount = fill_m.count() - fill0[1]
    srv_qps = line_from(
        "server", t_origin, done_at, lat,
        extra={"max_batch": max_batch, "max_wait_ms": max_wait_ms,
               "batch_fill_ratio":
               round(dsum / dcount, 4) if dcount else None,
               "micro_batches": dcount})
    print(json.dumps({
        "metric": "serving_server_vs_baseline_qps",
        "value": round(srv_qps / base_qps, 3), "unit": "x",
        "vs_baseline": round(srv_qps / base_qps, 3),
    }))
    print(f"# open-loop serving: offered {offered:.0f} req/s "
          f"(rate_x={rate_x} x measured {1 / svc_s:.0f}/s x "
          f"{replicas} replica(s)), baseline {base_qps:.0f} vs "
          f"server {srv_qps:.0f} sustained", file=sys.stderr)

    # ---- tracing: p99 attribution + on/off overhead ------------------
    # Attribution pass: the SAME open-loop schedule, traced keep-all
    # (monitor/trace.py) — every request's span tree lands in the
    # ring, so the slowest decile's time splits into queue-wait /
    # execute / deliver shares BY MEASUREMENT, not guesswork. The
    # headline A/B above stays untraced; tracing's own cost is the
    # separate interleaved ratio below.
    from paddle_tpu.monitor import trace as mtrace

    mtrace.enable(sample_rate=1.0, capacity=max(8 * n_reqs, 4096))
    srv = InferenceServer(d, ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_queue=n_reqs + replicas, replicas=replicas))
    pend = [None] * n_reqs
    arrived = [0.0] * n_reqs
    t_origin = open_loop(lambda i, ta: (
        arrived.__setitem__(i, ta),
        pend.__setitem__(i, srv.submit({"x": feed}))))
    for p in pend:
        p.result(timeout=600)
    srv.close()
    lat = np.asarray([p.t_done - ta for p, ta in zip(pend, arrived)])
    n_dec = max(1, n_reqs // 10)
    phases = ("queue_wait", "batch_form", "dispatch_wait", "execute",
              "deliver")
    shares = {k: [] for k in phases}
    for i in np.argsort(lat)[::-1][:n_dec]:
        durs = {}
        for s in mtrace.spans(pend[int(i)].trace_id):
            durs[s["name"].split("/", 1)[1]] = \
                durs.get(s["name"].split("/", 1)[1], 0.0) + s["dur"]
        total = durs.get("request", 0.0)
        if total <= 0:
            continue
        for k in phases:
            shares[k].append(durs.get(k, 0.0) / total)
    print(json.dumps({
        "metric": "serving_p99_attribution",
        "value": round(float(np.percentile(lat * 1e3, 99)), 2),
        "unit": "ms", "n_slowest": n_dec,
        **{f"{k}_share":
           (round(float(np.median(v)), 4) if v else None)
           for k, v in shares.items()},
    }))
    mtrace.disable()

    # Overhead pass: tracing-on/off A/B of the p50 request latency
    # under sub-saturation OPEN-LOOP load — the regime serving SLOs
    # are about (the hot-path tracing cost is µs against ms-scale
    # latencies; a throughput-mode µbench of this host's GIL
    # scheduling cannot resolve it honestly). The shared
    # _abba_overhead protocol (ABBA quadruples + trimmed-mean +
    # sequential more-pairs) cancels the host's load drift; the smoke
    # test asserts the estimate < 1.05x.
    pairs = int(os.environ.get("BENCH_SERVING_TRACE_PAIRS", "3"))
    win = int(os.environ.get("BENCH_SERVING_TRACE_WIN", "120"))
    mtrace.enable(sample_rate=0.05, slow_keep=8)    # default policy,
    mtrace.disable()                                # tracer persists
    srv = InferenceServer(d, ServingConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_queue=4 * win, replicas=replicas))
    t0 = time.perf_counter()
    for _ in range(20):
        srv.infer({"x": feed}, timeout=60)
    ab_rate = 0.5 * replicas / ((time.perf_counter() - t0) / 20)
    ab_rng = np.random.RandomState(7)

    def p50_window(traced, n=win):
        if traced:
            mtrace.enable()
        else:
            mtrace.disable()
        sched = np.cumsum(ab_rng.exponential(1.0 / ab_rate, size=n))
        t0 = time.perf_counter()
        pend = []
        for i in range(n):
            dly = t0 + sched[i] - time.perf_counter()
            if dly > 0:
                time.sleep(dly)
            pend.append((srv.submit({"x": feed}), t0 + sched[i]))
        lat_w = []
        for p, ta in pend:
            p.result(timeout=120)
            lat_w.append(p.t_done - ta)
        return float(np.median(lat_w)) * 1e3

    p50_window(True), p50_window(False)             # warm both paths
    est, pair_ratios, on_ms, off_ms = _abba_overhead(p50_window, pairs)
    mtrace.disable()
    print(json.dumps({
        "metric": "serving_trace_overhead_ratio",
        "value": round(est, 4), "unit": "x",
        "traced_p50_ms": round(float(np.median(on_ms)), 4),
        "untraced_p50_ms": round(float(np.median(off_ms)), 4),
        "pair_ratios": [round(r, 4) for r in pair_ratios],
        "window_reqs": win, "offered_fraction_of_capacity": 0.5,
    }))

    # Memory-poller overhead pass (monitor/memory.py): identical
    # open-loop protocol and server, with the live-buffer poller
    # sampling at a deliberately hostile 50 ms interval vs fully off
    # (disable == zero recording — no thread, no gauge writes). The
    # poller aggregates jax.live_arrays on its own daemon thread, so
    # this measures the GIL/allocator shadow it casts over request
    # latency; the smoke test asserts the ABBA estimate < 1.05x.
    from paddle_tpu.monitor import memory as _memory
    mem_pairs = int(os.environ.get("BENCH_SERVING_MEM_PAIRS",
                                   str(pairs)))

    def p50_mem_window(polling, n=win):
        if polling:
            _memory.enable(interval=0.05)
        else:
            _memory.disable()
        sched = np.cumsum(ab_rng.exponential(1.0 / ab_rate, size=n))
        t0 = time.perf_counter()
        pend = []
        for i in range(n):
            dly = t0 + sched[i] - time.perf_counter()
            if dly > 0:
                time.sleep(dly)
            pend.append((srv.submit({"x": feed}), t0 + sched[i]))
        lat_w = []
        for p, ta in pend:
            p.result(timeout=120)
            lat_w.append(p.t_done - ta)
        return float(np.median(lat_w)) * 1e3

    p50_mem_window(True), p50_mem_window(False)     # warm both paths
    est_m, pair_ratios_m, on_m, off_m = _abba_overhead(p50_mem_window,
                                                       mem_pairs)
    _memory.disable()
    print(json.dumps({
        "metric": "memory_overhead_ratio", "path": "serving",
        "value": round(est_m, 4), "unit": "x",
        "polled_p50_ms": round(float(np.median(on_m)), 4),
        "unpolled_p50_ms": round(float(np.median(off_m)), 4),
        "pair_ratios": [round(r, 4) for r in pair_ratios_m],
        "poll_interval_s": 0.05, "window_reqs": win,
        "offered_fraction_of_capacity": 0.5,
    }))

    # Goodput-ledger overhead pass (monitor/goodput.py): identical
    # open-loop protocol and server, ledger armed vs disarmed. Serving
    # is deliberately NOT instrumented by the ledger (it attributes
    # the training loop), so armed-vs-off here proves the ledger's
    # module-global arm check casts no shadow over an unrelated hot
    # path; the smoke test asserts the ABBA estimate < 1.05x.
    from paddle_tpu.monitor import goodput as _goodput
    gp_pairs = int(os.environ.get("BENCH_SERVING_GOODPUT_PAIRS",
                                  str(pairs)))

    def p50_gp_window(armed, n=win):
        if armed:
            _goodput.enable()
        else:
            _goodput.disable()
        sched = np.cumsum(ab_rng.exponential(1.0 / ab_rate, size=n))
        t0 = time.perf_counter()
        pend = []
        for i in range(n):
            dly = t0 + sched[i] - time.perf_counter()
            if dly > 0:
                time.sleep(dly)
            pend.append((srv.submit({"x": feed}), t0 + sched[i]))
        lat_w = []
        for p, ta in pend:
            p.result(timeout=120)
            lat_w.append(p.t_done - ta)
        return float(np.median(lat_w)) * 1e3

    p50_gp_window(True), p50_gp_window(False)       # warm both paths
    est_g, pair_ratios_g, on_g, off_g = _abba_overhead(p50_gp_window,
                                                       gp_pairs)
    _goodput.disable()
    srv.close()
    print(json.dumps({
        "metric": "goodput_overhead_ratio", "path": "serving",
        "value": round(est_g, 4), "unit": "x",
        "armed_p50_ms": round(float(np.median(on_g)), 4),
        "disarmed_p50_ms": round(float(np.median(off_g)), 4),
        "pair_ratios": [round(r, 4) for r in pair_ratios_g],
        "window_reqs": win, "offered_fraction_of_capacity": 0.5,
    }))


def _bench_serving_chaos(d, feed, max_batch, max_wait_ms):
    """The resilience half of `bench.py serving`
    (BENCH_SERVING_CHAOS=1). Three measurements on the 2-replica
    server, each a paired A/B on the same deterministic schedule:

    - ``serving_chaos_p99_ratio``: open-loop load at ~0.5x capacity,
      clean vs one replica wedged mid-load (PT_FAULT_REPLICA_STALL) —
      the ratio of the UNAFFECTED requests' p99; the wedged batch's
      riders resolve as typed errors and are reported, never hidden
      in the percentile.
    - ``serving_shed_precision``: overload (~2.5x capacity) with
      deadlines, shed OFF (traced keep-all — ground truth for who
      missed) vs shed adaptive — precision = shed requests that would
      in fact have missed their deadline.
    - ``serving_shed_overhead_ratio``: the controller's clean-path
      cost, ABBA-interleaved open-loop p50 at ~0.5x capacity with the
      controller swapped in/out (the shared _abba_overhead protocol);
      the smoke test pins < 1.05x.

    Knobs: BENCH_SERVING_CHAOS_REQS / _STALL_MS / _DEADLINE_MS /
    _SHED_PAIRS / _SHED_WIN."""
    from paddle_tpu.monitor import trace as mtrace
    from paddle_tpu.monitor.registry import REGISTRY
    from paddle_tpu.serving import (DeadlineExceededError,
                                    InferenceServer, OverloadedError,
                                    QueueFullError, ReplicaLostError,
                                    ServingConfig, ShedController)
    from paddle_tpu.testing import faults

    n = int(os.environ.get("BENCH_SERVING_CHAOS_REQS", "200"))
    stall_ms = float(os.environ.get("BENCH_SERVING_STALL_MS", "300"))
    replicas = 2

    def boot(**kw):
        kw.setdefault("max_batch", max_batch)
        kw.setdefault("max_wait_ms", max_wait_ms)
        kw.setdefault("max_queue", 4 * n + 64)
        kw.setdefault("replicas", replicas)
        kw.setdefault("replica_stall_ms", stall_ms)
        kw.setdefault("respawn_backoff_ms", 20.0)
        return InferenceServer(d, ServingConfig(**kw))

    def open_loop(srv, sched_arr, deadline_ms=None, timeout=120):
        """Submit on the schedule; returns per-request (ok_latency_s
        | exception-class-name | 'hang')."""
        pend = [None] * len(sched_arr)
        t0 = time.perf_counter()
        for i, t_arr in enumerate(sched_arr):
            dly = t0 + t_arr - time.perf_counter()
            if dly > 0:
                time.sleep(dly)
            try:
                pend[i] = (srv.submit({"x": feed},
                                      deadline_ms=deadline_ms),
                           t0 + t_arr)
            except (OverloadedError, DeadlineExceededError,
                    QueueFullError) as e:
                pend[i] = (e, None)
        out = []
        for p, t_arr in pend:
            if not hasattr(p, "result"):
                out.append(type(p).__name__)
                continue
            try:
                p.result(timeout=timeout)
                out.append(p.t_done - t_arr)
            except TimeoutError:
                out.append("hang")
            except Exception as e:
                out.append(type(e).__name__)
        return out

    def warm(srv, rounds=3):
        # sequential singles warm the 1-bucket; concurrent bursts
        # coalesce into the larger buckets so EVERY executable has
        # run before a timed pass (first executions pay one-time
        # transfer/donation setup that would otherwise land in
        # whichever pass ran first)
        for _ in range(6):
            srv.infer({"x": feed}, timeout=60)
        for _ in range(rounds):
            for p in [srv.submit({"x": feed}) for _ in range(16)]:
                p.result(timeout=60)

    # -- capacity probe on a clean warm server -------------------------
    srv = boot()
    warm(srv)
    t0 = time.perf_counter()
    for _ in range(30):
        srv.infer({"x": feed}, timeout=60)
    svc_s = (time.perf_counter() - t0) / 30
    half_rate = 0.5 * replicas / svc_s

    # -- chaos A/B: clean pass, then one replica wedged mid-load -------
    sched = np.cumsum(np.random.RandomState(42).exponential(
        1.0 / half_rate, size=n))
    clean = open_loop(srv, sched)
    srv.close(timeout=120)
    clean_ok = [x for x in clean if isinstance(x, float)]
    p99_clean = float(np.percentile(np.asarray(clean_ok) * 1e3, 99))

    resp_m = REGISTRY.get("serving_replica_respawns_total")
    resp0 = resp_m.value() if resp_m else 0.0
    srv = boot()
    warm(srv)       # same warm-up as the clean pass, pre-arm
    os.environ["PT_FAULT_REPLICA_STALL"] = "8"
    os.environ["PT_FAULT_REPLICA"] = "1"
    os.environ["PT_FAULT_STALL_SECS"] = "120"
    faults._serving_fired.discard("replica_stall")
    uninstall = faults.install_serving_faults()
    try:
        chaos = open_loop(srv, sched)
    finally:
        uninstall()
        for k in ("PT_FAULT_REPLICA_STALL", "PT_FAULT_REPLICA",
                  "PT_FAULT_STALL_SECS"):
            os.environ.pop(k, None)
    # the respawn lands after quarantine + backoff — give the
    # supervisor a bounded moment (BEFORE close stops it) so the row
    # reports the heal
    lost_any = any(x == "ReplicaLostError" for x in chaos)
    heal_by = time.monotonic() + (10 if lost_any else 0)
    while time.monotonic() < heal_by:
        if resp_m is not None and resp_m.value() > resp0:
            break
        time.sleep(0.02)
    srv.close(timeout=120)
    chaos_ok = [x for x in chaos if isinstance(x, float)]
    hangs = sum(1 for x in chaos if x == "hang")
    lost = sum(1 for x in chaos if x == "ReplicaLostError")
    p99_chaos = float(np.percentile(np.asarray(chaos_ok) * 1e3, 99))
    print(json.dumps({
        "metric": "serving_chaos_p99_ratio",
        "value": round(p99_chaos / p99_clean, 3), "unit": "x",
        "clean_p99_ms": round(p99_clean, 2),
        "chaos_p99_ok_ms": round(p99_chaos, 2),
        "n_requests": n, "replicas": replicas,
        "stall_ms": stall_ms,
        "lost_requests": lost, "hangs": hangs,
        "respawns": round((resp_m.value() if resp_m else 0.0)
                          - resp0, 0),
    }))

    # -- shed precision: overload with deadlines, off vs adaptive ------
    # the shed passes serve single-request buckets (max_batch=1):
    # continuous batching multiplies capacity severalfold, so a
    # deterministic sustained overload of a batching ladder would
    # need tens of thousands of requests to hold queue pressure for
    # long enough to observe the controller — with batch=1 the same
    # 2.5x overload holds for the whole pass and the admission
    # mechanism (what this row measures) is identical
    deadline_ms = float(os.environ.get("BENCH_SERVING_DEADLINE_MS")
                        or max(6 * svc_s * 1e3, 20.0))
    n_ov = max(4 * n, 800)
    # true single-bucket capacity, closed loop: the open-loop probe's
    # svc_s includes max_wait_ms batching slack, and an "overload"
    # derived from it can sit at the capacity knife-edge where queue
    # wait never grows and nothing sheds
    srv = boot(max_batch=1, max_queue=n_ov + 64)
    burst = [srv.submit({"x": feed}) for _ in range(200)]
    tb = time.perf_counter()
    for p in burst:
        p.result(timeout=120)
    rate1 = 200 / (time.perf_counter() - tb)
    srv.close(timeout=120)
    over_rate = 2.5 * rate1
    sched_ov = np.cumsum(np.random.RandomState(7).exponential(
        1.0 / over_rate, size=n_ov))
    # ground truth: shed OFF on the same schedule — who actually
    # missed. BOTH passes run keep-all traced (the evidence trail for
    # per-request postmortems) so tracing's cost cancels out of the
    # A/B instead of loading only the control side; try/finally so an
    # exception can't leave process-global tracing enabled
    mtrace.enable(sample_rate=1.0, capacity=max(8 * n_ov, 4096))
    try:
        srv = boot(default_deadline_ms=deadline_ms, max_batch=1,
                   max_queue=n_ov + 64)
        control = open_loop(srv, sched_ov)
        srv.close(timeout=120)
        missed = {i for i, x in enumerate(control)
                  if x == "DeadlineExceededError"}
        # adaptive pass on the SAME schedule
        srv = boot(default_deadline_ms=deadline_ms,
                   shed_mode="adaptive", max_batch=1,
                   max_queue=n_ov + 64)
        adaptive = open_loop(srv, sched_ov)
        srv.close(timeout=120)
    finally:
        mtrace.disable()
    shed = {i for i, x in enumerate(adaptive)
            if x == "OverloadedError"}
    precision = (round(len(shed & missed) / len(shed), 4)
                 if shed else None)
    print(json.dumps({
        "metric": "serving_shed_precision",
        "value": precision, "unit": "fraction",
        "n_shed": len(shed), "n_missed_control": len(missed),
        "deadline_ms": round(deadline_ms, 2),
        "overload_x": 2.5, "n_requests": n_ov, "max_batch": 1,
    }))

    # -- shed controller overhead on the clean path (ABBA p50) ---------
    pairs = int(os.environ.get("BENCH_SERVING_SHED_PAIRS", "3"))
    win = int(os.environ.get("BENCH_SERVING_SHED_WIN", "100"))
    srv = boot(default_deadline_ms=10_000.0)
    ctrl = ShedController(deadline_ms=10_000.0)
    ab_rng = np.random.RandomState(11)

    def p50_window(shed_on, n_w=win):
        # swapping the controller in/out of the live scheduler is the
        # honest A/B: admission checks `self._shed is not None`
        srv.scheduler._shed = ctrl if shed_on else None
        sched_w = np.cumsum(ab_rng.exponential(1.0 / half_rate,
                                               size=n_w))
        lat = open_loop(srv, sched_w, timeout=120)
        return float(np.median([x for x in lat
                                if isinstance(x, float)])) * 1e3

    p50_window(True), p50_window(False)         # warm both paths
    est, pair_ratios, on_ms, off_ms = _abba_overhead(p50_window, pairs)
    srv.scheduler._shed = None
    srv.close(timeout=120)
    print(json.dumps({
        "metric": "serving_shed_overhead_ratio",
        "value": round(est, 4), "unit": "x",
        "shed_on_p50_ms": round(float(np.median(on_ms)), 4),
        "shed_off_p50_ms": round(float(np.median(off_ms)), 4),
        "pair_ratios": [round(r, 4) for r in pair_ratios],
        "window_reqs": win, "offered_fraction_of_capacity": 0.5,
    }))


def bench_longcontext():
    """`python bench.py longcontext` — BERT-base training throughput at
    long sequence lengths on the Pallas flash-attention kernels (the
    numbers BASELINE.md's long-context claims cite). One JSON line per
    length; vs_baseline = speedup over XLA dense attention at the same
    length (both measured here)."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh, set_mesh

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    mesh = set_mesh(make_mesh(MeshConfig(data=1),
                              devices=jax.devices()[:1]))
    configs = ([(2048, 8), (4096, 4)] if on_tpu else [(128, 2)])
    steps = 10 if on_tpu else 2

    def run(seq, batch, impl):
        # each impl at its best memory-feasible config: flash fits
        # without remat (O(block.S) attention memory); dense needs remat
        # at these lengths (the O(S^2) scores blow HBM otherwise)
        remat = impl == "dense"
        cfg = (bert.bert_base(max_seq=seq, attention_impl=impl,
                              remat=remat) if on_tpu
               else bert.bert_tiny(max_seq=seq, attention_impl=impl))
        opt = pt.optimizer.Adam(learning_rate=1e-4)
        # spc=4 stays the long-context default: the r3 A/B measured
        # 2048-flash 89.3k at spc=8 vs 91.2k at spc=4 (4096: 65.6k vs
        # 64.9k — a wash), so the bigger scan hurts at the larger
        # activation footprint. BENCH_SPC overrides.
        spc = int(os.environ.get("BENCH_SPC", "4" if on_tpu else "1"))
        init_fn, step_fn = bert.make_train_step(cfg, opt, mesh,
                                                steps_per_call=spc)
        data = bert.synthetic_batch(cfg, batch_size=batch, seq_len=seq)
        params, opt_state = init_fn(jax.random.PRNGKey(0))

        def once(carry):
            params, opt_state = carry
            loss, params, opt_state = step_fn(params, opt_state, data)
            return (params, opt_state), loss

        tr = _timed_steps(once, (params, opt_state), steps, settle=2,
                          sub_steps=spc)
        return batch * seq * steps * spc / tr.dt, tr

    for seq, batch in configs:
        tps_flash, tr_flash = run(seq, batch, "flash")
        tps_dense, tr_dense = run(seq, batch, "dense")
        line = {
            "metric": f"bert_base_seq{seq}_flash_tokens_per_sec",
            "value": round(tps_flash, 2), "unit": "tokens/sec",
            "vs_baseline": round(tps_flash / tps_dense, 4),
            **tr_flash.extras()}
        if tr_dense.contention_suspected:
            # the denominator of vs_baseline was contended: the speedup
            # claim is suspect even if the flash windows were quiet
            line["contention_suspected"] = True
            line["dense_baseline_contended"] = True
        print(json.dumps(line))


def bench_nmt():
    """`python bench.py nmt`: Transformer-big WMT shape (bs=32, s=256)
    train tokens/sec + MFU, plus beam-search decode latency (the
    reference's stress test, operators/beam_search_op.cc)."""
    import functools

    import jax

    import paddle_tpu as pt
    from paddle_tpu.models import transformer as T
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh, set_mesh

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    mesh = set_mesh(make_mesh(MeshConfig(data=1),
                              devices=jax.devices()[:1]))
    bs, s = (32, 256) if on_tpu else (2, 16)
    cfg = (T.transformer_big(max_seq=s) if on_tpu
           else T.transformer_tiny(max_seq=s))
    opt = pt.optimizer.Adam(1e-4)
    init_fn, step_fn = T.make_train_step(cfg, opt, mesh)
    batch = T.synthetic_batch(cfg, bs, src_len=s, tgt_len=s)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    steps = 20 if on_tpu else 2

    def once(carry):
        params, opt_state = carry
        loss, params, opt_state = step_fn(params, opt_state, batch)
        return (params, opt_state), loss

    tr = _timed_steps(once, (params, opt_state), steps)
    params, _ = tr.carry
    tok_s = bs * s * steps / tr.dt
    mfu = _mfu(T.flops_per_step(cfg, bs, s, s) * steps / tr.dt, dev)
    print(json.dumps({
        "metric": "transformer_big_train_target_tokens_per_sec_per_chip",
        "value": round(tok_s, 1), "unit": "tokens/sec",
        "vs_baseline": _vs_baseline(mfu),
        **tr.extras()}))

    # beam-search decode latency
    max_len = 64 if on_tpu else 8
    bsd = jax.jit(functools.partial(T.beam_search_decode, cfg=cfg,
                                    beam_size=4, max_len=max_len))

    def decode_once(carry):
        out = bsd(params, src_ids=batch["src_ids"],
                  src_mask=batch["src_mask"])
        return carry, jax.tree.leaves(out)[0]

    reps = 5 if on_tpu else 1
    tr = _timed_steps(decode_once, None, reps, settle=1)
    line = {
        "metric": "transformer_big_beam4_decode_latency_ms",
        "value": round(tr.dt / reps * 1e3, 1), "unit": "ms",
        "decode_tokens_per_sec": round(bs * max_len * reps / tr.dt, 1)}
    if tr.contention_suspected:
        line["contention_suspected"] = True
    print(json.dumps(line))


def bench_numerics():
    """`python bench.py numerics` — step-time overhead of the
    FLAGS_check_nan_inf in-graph sentinels (monitor/numerics.py),
    measured the bench_dispatch way: check-on and check-off windows
    INTERLEAVE (adjacent windows see the same ambient host load on a
    shared box), and the headline is the median of per-pair on/off
    ratios, which a load drift cannot bias. The model is the
    deep-and-narrow dispatch-bound stack — the worst case for the
    sentinel, whose reduction cost is trivial but whose per-step
    scalar sync and no-donation policy hit exactly the host-bound
    regime. Prints one JSON line; windows also land in the registry
    snapshot every bench mode emits."""
    import time as _time

    import paddle_tpu as pt
    from paddle_tpu.static.executor import Scope, scope_guard

    steps = int(os.environ.get("BENCH_NUMERICS_STEPS", "150"))
    # mode-specific knob: BENCH_WINDOWS means "timed windows" in every
    # other mode, and silently reading it as PAIRS here would double
    # this mode's runtime under the shared CI knob
    pairs = max(2, int(os.environ.get("BENCH_NUMERICS_PAIRS", "5")))
    DEPTH, HIDDEN, BATCH = 24, 16, 16

    pt.enable_static()
    rs = np.random.RandomState(0)
    xb = rs.randn(BATCH, HIDDEN).astype(np.float32)
    yb = rs.randn(BATCH, 1).astype(np.float32)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.static.data("x", shape=[HIDDEN])
        y = pt.static.data("y", shape=[1])
        h = x
        for i in range(DEPTH):
            h = pt.layers.fc(h, size=HIDDEN, param_attr=f"w{i}",
                             bias_attr=f"b{i}", act="relu")
        pred = pt.layers.fc(h, size=1, param_attr="w_out",
                            bias_attr="b_out")
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.Momentum(0.02, momentum=0.9).minimize(loss)
    scope = Scope()

    def window(check, n):
        pt.set_flags({"check_nan_inf": check})
        try:
            with scope_guard(scope):
                t0 = _time.perf_counter()
                for _ in range(n):
                    exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss])
                return _time.perf_counter() - t0
        finally:
            pt.set_flags({"check_nan_inf": False})

    with scope_guard(scope):
        exe = pt.static.Executor()
        exe.run(startup)
    window(False, 4)            # compile + warm both variants: the
    window(True, 4)             # checked jit is its own trace/compile
    on_ms, off_ms, ratios = [], [], []
    from paddle_tpu.monitor.registry import histogram
    h_win = histogram("bench_window_ms_per_step",
                      "Per-step wall ms of each timed bench window")
    for w in range(pairs):
        first_on = w % 2 == 0   # alternate order within each pair
        a = window(first_on, steps)
        b = window(not first_on, steps)
        on, off = (a, b) if first_on else (b, a)
        on_ms.append(on / steps * 1e3)
        off_ms.append(off / steps * 1e3)
        ratios.append(on / off)
        h_win.observe(on / steps * 1e3)
        h_win.observe(off / steps * 1e3)
    med = float(np.median(ratios))
    print(json.dumps({
        "metric": "numerics_check_overhead_ratio",
        "value": round(med, 4), "unit": "x",
        "check_on_ms_per_step": round(float(np.median(on_ms)), 4),
        "check_off_ms_per_step": round(float(np.median(off_ms)), 4),
        "pair_ratios": [round(r, 4) for r in ratios],
    }))
    print(f"# numerics sentinel overhead: median pair ratio "
          f"{med:.4f}x over {pairs} interleaved pairs x {steps} steps",
          file=sys.stderr)


def bench_ckpt():
    """`python bench.py ckpt` — checkpoint durability-path timings:
    save (serialize + CRC + fsync + atomic publish) and restore with
    digest verification ON vs OFF, so the integrity overhead is
    measured, not assumed. Verify-on and verify-off restore windows
    INTERLEAVE (the bench_dispatch discipline: adjacent windows see
    the same ambient disk/host load on a shared box) and the headline
    is the median of per-pair on/off ratios. BENCH_CKPT_MB sets the
    payload size, BENCH_CKPT_PAIRS the pair count. Three JSON lines:
    ckpt_save_ms, ckpt_restore_ms, ckpt_verify_overhead_ratio."""
    import shutil
    import tempfile
    import time as _time

    from paddle_tpu.io_checkpoint import CheckpointManager

    mb = float(os.environ.get("BENCH_CKPT_MB", "64"))
    pairs = max(2, int(os.environ.get("BENCH_CKPT_PAIRS", "5")))
    n_arrays = 16
    per = max(int(mb * 1e6 / 4 / n_arrays), 1)
    rs = np.random.RandomState(0)
    tree = {"params": {f"w{i}": rs.randn(per).astype(np.float32)
                       for i in range(n_arrays)},
            "opt": {f"m{i}": rs.randn(per).astype(np.float32)
                    for i in range(2)}}
    nbytes = sum(a.nbytes for g in tree.values() for a in g.values())
    d = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        mgr = CheckpointManager(d, async_save=False,
                                save_interval_steps=1, keep_max=2)
        mgr.save(0, tree)               # warmup (dir entries, caches)
        save_ms = []
        for i in range(1, pairs + 1):
            t0 = _time.perf_counter()
            mgr.save(i, tree)
            save_ms.append((_time.perf_counter() - t0) * 1e3)
        step = mgr.latest_step()
        mgr.restore(step)               # warmup both restore paths
        mgr.restore(step, verify=False)
        on_ms, off_ms, ratios = [], [], []
        for w in range(pairs):
            first_on = w % 2 == 0       # alternate order within pairs

            def timed(verify):
                t0 = _time.perf_counter()
                mgr.restore(step, verify=verify)
                return (_time.perf_counter() - t0) * 1e3

            a = timed(first_on)
            b = timed(not first_on)
            on, off = (a, b) if first_on else (b, a)
            on_ms.append(on)
            off_ms.append(off)
            ratios.append(on / off)
        mgr.close()
        med = float(np.median(ratios))
        save_med = float(np.median(save_ms))
        print(json.dumps({
            "metric": "ckpt_save_ms", "value": round(save_med, 2),
            "unit": "ms", "payload_mb": round(nbytes / 1e6, 1),
            "save_mb_per_sec": round(nbytes / 1e6 / (save_med / 1e3), 1),
        }))
        print(json.dumps({
            "metric": "ckpt_restore_ms",
            "value": round(float(np.median(on_ms)), 2), "unit": "ms",
            "verify_on_ms": round(float(np.median(on_ms)), 2),
            "verify_off_ms": round(float(np.median(off_ms)), 2),
        }))
        print(json.dumps({
            "metric": "ckpt_verify_overhead_ratio",
            "value": round(med, 4), "unit": "x",
            "pair_ratios": [round(r, 4) for r in ratios],
        }))
        print(f"# checkpoint verify overhead: median pair ratio "
              f"{med:.4f}x over {pairs} interleaved pairs, "
              f"{nbytes / 1e6:.0f} MB payload", file=sys.stderr)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_data():
    """`python bench.py data` — data-plane A/B (ROADMAP item 4): the
    deterministic sharded NATIVE loader vs the Python oracle on the
    STATEFUL (exactly-once) path — the fast path PR 5/6 used to
    surrender — plus a stateless-native reference row and the
    device-side double-buffer on/off A/B.

    Protocol (the bench_dispatch discipline): each comparison runs as
    interleaved pairs — adjacent windows see the same ambient host
    load — and the headline is the median of per-pair ratios, which a
    load drift cannot bias. Every window consumes a fixed batch count
    from a FRESH loader over the same generated dataset (epochs=-1:
    no window ever hits end-of-stream early).

    JSON lines: data_{native_stateful,python_stateful,stateless}
    _records_per_sec, data_native_vs_python_ratio (>= 2x is ROADMAP
    item 4's bar; resume bit-identity is proven separately by the
    tests/test_data_plane.py conformance suite), and
    data_h2d_overlap_ratio (double-buffer OFF step time / ON step
    time; > 1.0 means the prefetch worker's device_put hid transfer
    under compute — expect ~1.0 on CPU, where jnp.asarray of a host
    batch is a no-copy alias; re-A/B on a real chip, where H2D is a
    PCIe/ICI hop: `JAX_PLATFORMS=tpu python bench.py data`).

    Env knobs: BENCH_DATA_FILES/ROWS/BATCH/BATCHES/PAIRS/SHUFFLE."""
    import shutil
    import tempfile
    import time as _time

    from paddle_tpu import native as _native
    from paddle_tpu.dataio.dataloader import FileDataLoader

    if not _native.available():
        raise RuntimeError(
            "bench.py data needs the native library (the A/B's whole "
            "point); the C++ toolchain is missing or the build failed")

    nfiles = int(os.environ.get("BENCH_DATA_FILES", "4"))
    rows = int(os.environ.get("BENCH_DATA_ROWS", "25000"))
    batch = int(os.environ.get("BENCH_DATA_BATCH", "256"))
    batches = int(os.environ.get("BENCH_DATA_BATCHES", "60"))
    pairs = max(2, int(os.environ.get("BENCH_DATA_PAIRS", "3")))
    shuffle = int(os.environ.get("BENCH_DATA_SHUFFLE", "1024"))

    d = tempfile.mkdtemp(prefix="bench_data_")
    try:
        files = []
        for i in range(nfiles):
            p = os.path.join(d, f"part-{i}.txt")
            with open(p, "w") as f:
                for j in range(rows):
                    f.write(f"{(i * rows + j) % 977}.5\n")
            files.append(p)

        def mk_loader(native, stateful=True, device_put=False):
            # minimal real parse (bytes -> number): the mode measures
            # the DATA PLANE; a heavyweight per-record parse_fn would
            # just flatten the A/B toward its own cost
            return FileDataLoader(
                files, float, batch_size=batch,
                nthreads=4, shuffle_buffer=shuffle, seed=7, epochs=-1,
                device_put=device_put, stateful=stateful,
                native=native)

        def window(native, stateful=True):
            """Wall seconds to consume `batches` fresh batches."""
            ld = mk_loader(native, stateful)
            it = iter(ld)
            next(it)                      # spin up worker + warm cache
            t0 = _time.perf_counter()
            for _ in range(batches):
                next(it)
            dt = _time.perf_counter() - t0
            it.close()
            return dt

        window(True)                      # warm the .so + page cache
        window(False)
        recs = batch * batches
        nat_rps, py_rps, ratios = [], [], []
        for w in range(pairs):
            first_nat = w % 2 == 0        # alternate order within pairs
            a = window(first_nat)
            b = window(not first_nat)
            nat, py = (a, b) if first_nat else (b, a)
            nat_rps.append(recs / nat)
            py_rps.append(recs / py)
            ratios.append(py / nat)       # >1: native faster
        stateless = [recs / window(True, stateful=False)
                     for _ in range(2)]
        med = float(np.median(ratios))
        print(json.dumps({
            "metric": "data_native_stateful_records_per_sec",
            "value": round(float(np.median(nat_rps))), "unit": "rec/s",
            "batch": batch, "shuffle_buffer": shuffle,
            "nfiles": nfiles}))
        print(json.dumps({
            "metric": "data_python_stateful_records_per_sec",
            "value": round(float(np.median(py_rps))), "unit": "rec/s"}))
        print(json.dumps({
            "metric": "data_stateless_records_per_sec",
            "value": round(float(np.median(stateless))),
            "unit": "rec/s"}))
        print(json.dumps({
            "metric": "data_native_vs_python_ratio",
            "value": round(med, 4), "unit": "x",
            "pair_ratios": [round(r, 4) for r in ratios]}))
        print(f"# stateful ingest: native {med:.2f}x the Python "
              f"oracle over {pairs} interleaved pairs x {batches} "
              f"batches of {batch}", file=sys.stderr)

        # ---- device-side double-buffer A/B --------------------------------
        import paddle_tpu as pt
        from paddle_tpu.static.executor import Scope, scope_guard

        steps = min(batches, 40)
        HIDDEN = 128
        pt.enable_static()
        try:
            main, startup = pt.Program(), pt.Program()
            with pt.program_guard(main, startup):
                x = pt.static.data("x", shape=[HIDDEN])
                h = x
                for i in range(4):
                    h = pt.layers.fc(h, size=HIDDEN,
                                     param_attr=f"w{i}",
                                     bias_attr=f"b{i}", act="relu")
                loss = pt.layers.mean(h)
            scope = Scope()
            with scope_guard(scope):
                exe = pt.static.Executor()
                exe.run(startup)

                rs = np.random.RandomState(0)
                feed_rows = rs.randn(batch, HIDDEN).astype(np.float32)

                def feed_loader(put):
                    # per-batch distinct rows (a copy per batch), so
                    # the put stage does real work every step
                    def gen():
                        for i in range(steps + 2):
                            yield feed_rows + np.float32(i)
                    from paddle_tpu.static.executor import \
                        background_prefetch
                    if put is None:
                        return background_prefetch(gen(), lambda b: b,
                                                   2)
                    return background_prefetch(gen(), put, 2)

                put = exe.feed_stage(main, feed_names=["x"])

                def step_window(double_buffer):
                    it = feed_loader(put if double_buffer else None)
                    b0 = next(it)                 # warm the pipeline
                    exe.run(main, feed={"x": b0}, fetch_list=[loss])
                    t0 = _time.perf_counter()
                    out = None
                    for b in it:
                        out = exe.run(main, feed={"x": b},
                                      fetch_list=[loss],
                                      return_numpy=False)
                    float(np.ravel(np.asarray(out[0]))[0])
                    dt = _time.perf_counter() - t0
                    it.close()
                    return dt

                step_window(True)                 # compile + warm both
                step_window(False)
                on_ms, off_ms, h2d_ratios = [], [], []
                for w in range(pairs):
                    first_on = w % 2 == 0
                    a = step_window(first_on)
                    b = step_window(not first_on)
                    on, off = (a, b) if first_on else (b, a)
                    on_ms.append(on / steps * 1e3)
                    off_ms.append(off / steps * 1e3)
                    h2d_ratios.append(off / on)   # >1: overlap won
                med_h = float(np.median(h2d_ratios))
                print(json.dumps({
                    "metric": "data_h2d_overlap_ratio",
                    "value": round(med_h, 4), "unit": "x",
                    "on_ms_per_step":
                        round(float(np.median(on_ms)), 4),
                    "off_ms_per_step":
                        round(float(np.median(off_ms)), 4),
                    "pair_ratios": [round(r, 4) for r in h2d_ratios],
                }))
                print(f"# double buffer: off/on step-time ratio "
                      f"{med_h:.4f}x ({'overlap pays' if med_h > 1.05 else 'within noise on this backend'})",
                      file=sys.stderr)
        finally:
            pt.disable_static()
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_shard():
    """`python bench.py shard` — unified-mesh topology sweep (ROADMAP
    item 2): one transformer trunk trained under the ShardingSpec
    partitioner across mesh topologies — pure-DP (`data=N`),
    model x data (megatron block sharding over "model"), and
    pipe x data (the fused 1F1B scan of parallel/pipeline.py) — on
    whatever devices are visible (the MULTICHIP harness provisions 8).

    Protocol: every topology compiles first, then timed windows
    INTERLEAVE round-robin across topologies (adjacent windows see the
    same ambient host load — the bench_dispatch discipline), and each
    topology reports its BEST window. One JSON line per topology:
    ms/step, MFU (analytic trunk FLOPs / step time / N x chip peak),
    and estimated collective bytes per step from the compiled HLO
    (monitor/cost.estimate_comm — SPMD inserts collectives at compile
    time, so the estimate reads the optimized executable text).

    The pipe topology also A/Bs FLAGS_overlap_grad_reduce (gradient
    all-reduce issued per-bucket inside the backward scan vs one
    epilogue reduction): overlap-on and overlap-off windows interleave
    in pairs and the headline is the median per-pair on/off ratio —
    < 1.0 means the in-scan reduction overlapped with compute.

    Env knobs: BENCH_SHARD_TOPOS (csv of dp,modelxdata,pipexdata),
    BENCH_SHARD_STEPS, BENCH_WINDOWS, BENCH_SHARD_PAIRS,
    BENCH_SHARD_HIDDEN/FFN/SEQ/BATCH/LAYERS/VOCAB/HEADS/MICRO."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.monitor import cost as _cost
    from paddle_tpu.monitor.registry import gauge
    from paddle_tpu.parallel import pipeline as pl
    from paddle_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, PIPE_AXIS, MeshConfig, make_mesh,
    )
    from paddle_tpu.parallel.spec import ShardingSpec

    g_mfu = gauge("shard_topology_mfu",
                  "Model FLOPs utilization measured by bench.py shard "
                  "for each mesh topology (analytic trunk FLOPs / best "
                  "window step time / device count x chip peak)",
                  labels=("topology",))

    devs = jax.devices()
    N = int(os.environ.get("BENCH_SHARD_DEVICES", str(len(devs))))
    devs = devs[:N]
    on_tpu = devs[0].platform != "cpu"

    def knob(name, tpu_default, cpu_default):
        return int(os.environ.get(name, str(tpu_default if on_tpu
                                            else cpu_default)))

    H = knob("BENCH_SHARD_HIDDEN", 1024, 64)
    F = knob("BENCH_SHARD_FFN", 4 * H, 4 * H)
    S = knob("BENCH_SHARD_SEQ", 512, 32)
    B = knob("BENCH_SHARD_BATCH", 4 * N, 2 * N if N > 1 else 8)
    L = knob("BENCH_SHARD_LAYERS", 8, 4)
    V = knob("BENCH_SHARD_VOCAB", 8192, 128)
    NH = knob("BENCH_SHARD_HEADS", 16, 4)
    n_micro = knob("BENCH_SHARD_MICRO", 4, 4)
    steps = knob("BENCH_SHARD_STEPS", 10, 4)
    windows = max(2, int(os.environ.get("BENCH_WINDOWS", "3")))
    pairs = max(2, int(os.environ.get("BENCH_SHARD_PAIRS", "3")))
    lr = 0.05
    assert H % NH == 0, (H, NH)

    # ---- the trunk: pre-LN encoder blocks, shared by every topology --
    def _ln(x, g):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * g).astype(x.dtype)

    def _block_apply(p, x):
        b, s, _ = x.shape
        h = _ln(x, p["ln1"])
        qkv = h @ p["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hd = H // NH

        def heads(t):
            return t.reshape(b, s, NH, hd).transpose(0, 2, 1, 3)
        q, k, v = heads(q), heads(k), heads(v)
        scores = (q @ k.transpose(0, 1, 3, 2)) / np.sqrt(hd)
        a = jax.nn.softmax(scores, axis=-1) @ v
        a = a.transpose(0, 2, 1, 3).reshape(b, s, H)
        x = x + a @ p["wo"]
        h2 = _ln(x, p["ln2"])
        return x + jax.nn.relu(h2 @ p["w1"]) @ p["w2"]

    def _block_params(key):
        ks = jax.random.split(key, 4)

        def init(k, a, b):
            return jax.random.normal(k, (a, b), jnp.float32) * (a ** -0.5)
        return {"wqkv": init(ks[0], H, 3 * H), "wo": init(ks[1], H, H),
                "w1": init(ks[2], H, F), "w2": init(ks[3], F, H),
                "ln1": jnp.ones((H,)), "ln2": jnp.ones((H,))}

    def _xent(logits, labels):
        ls = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(ls, labels[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll)

    def _trunk_flops(layers):
        """Analytic matmul FLOPs per train step (fwd + 2x bwd), the
        fixed-convention MFU numerator comparable across topologies."""
        per_tok = layers * (2 * H * 3 * H + 2 * H * H + 2 * 2 * H * F
                            + 2 * 2 * S * H) + 2 * H * V
        return 3.0 * B * S * per_tok

    rng = np.random.RandomState(0)
    xb_np = rng.randint(0, V, size=(B, S)).astype(np.int32)
    yb_np = rng.randint(0, V, size=(B, S)).astype(np.int32)

    # ---- topology builders: each returns (step_once, carry, meta) ----
    def build_dense(name, cfg):
        """Pure-DP and model x data: stacked blocks scanned in one jit,
        placement from ONE ShardingSpec (megatron rules inert when the
        model axis has extent 1)."""
        mesh = make_mesh(cfg, devices=devs)
        spec = ShardingSpec(mesh, params={
            "emb": P(), "pos": P(), "head": P(),
            "blocks/wqkv": P(None, None, MODEL_AXIS),
            "blocks/w1": P(None, None, MODEL_AXIS),
            "blocks/wo": P(None, MODEL_AXIS, None),
            "blocks/w2": P(None, MODEL_AXIS, None),
        })
        keys = jax.random.split(jax.random.PRNGKey(0), L + 1)
        params = {
            "emb": jax.random.normal(keys[0], (V, H)) * 0.02,
            "pos": jax.random.normal(keys[0], (S, H)) * 0.02,
            "blocks": pl.stack_stage_params(
                [_block_params(k) for k in keys[1:]]),
            "head": jax.random.normal(keys[0], (H, V)) * 0.02,
        }
        params = spec.place_tree(params)

        def loss_fn(p, xt, yt):
            h = p["emb"][xt] + p["pos"][None]

            def f(x, lp):
                return _block_apply(lp, x), None
            h, _ = jax.lax.scan(f, h, p["blocks"])
            return _xent(h @ p["head"], yt)

        def step(p, xt, yt):
            loss, g = jax.value_and_grad(loss_fn)(p, xt, yt)
            return loss, jax.tree.map(lambda w, gw: w - lr * gw, p, g)

        # in/out shardings PINNED to the spec: the params carry is a
        # true fixed point, so (a) the AOT executable below serves the
        # timed loop directly — one compile total, also feeding the
        # comm estimate its optimized-HLO text — and (b) no hidden
        # step-2 recompile when GSPMD would otherwise drift an
        # unpinned output leaf to a sharded layout
        pshard = spec.tree_shardings(params)
        dsh = NamedSharding(mesh, P(DATA_AXIS))
        rep = NamedSharding(mesh, P())
        jit_step = jax.jit(step, donate_argnums=(0,),
                           in_shardings=(pshard, dsh, dsh),
                           out_shardings=(rep, pshard))
        xt = jax.device_put(xb_np, dsh)
        yt = jax.device_put(yb_np, dsh)
        exe, text = _compile_once(jit_step, params, xt, yt)

        def once(carry):
            loss, new_p = exe(carry, xt, yt)
            return new_p, loss

        return once, params, dict(mesh=cfg, layers=L,
                                  comm=_cost.estimate_comm(text))

    def build_pipe(name, cfg, overlap=None):
        """pipe x data: the fused 1F1B scan (one XLA program for the
        whole trunk) with per-bucket in-scan gradient reduction when
        ``overlap`` is on."""
        import paddle_tpu as pt
        mesh = make_mesh(cfg, devices=devs)
        n_stages = dict(mesh.shape)[PIPE_AXIS]
        keys = jax.random.split(jax.random.PRNGKey(0), n_stages + 1)
        params = {
            "embed": {"w": jax.random.normal(keys[0], (V, H)) * 0.02,
                      "pos": jax.random.normal(keys[0], (S, H)) * 0.02},
            "stages": pl.stack_stage_params(
                [_block_params(k) for k in keys[1:]]),
            "head": {"w": jax.random.normal(keys[0], (H, V)) * 0.02},
        }

        def embed_fn(ep, xt):
            return ep["w"][xt] + ep["pos"][None]

        def loss_fn(hp, a, yt):
            return _xent(a @ hp["w"], yt)

        mod = pl.PipelineModule(mesh, embed_fn, _block_apply, loss_fn,
                                n_micro)
        init_fn, step = mod.make_train_step(
            pt.optimizer.SGDOptimizer(learning_rate=lr),
            schedule="1f1b", overlap_grad_reduce=overlap)
        params, opt_state = init_fn(params)
        xt, yt = jnp.asarray(xb_np), jnp.asarray(yb_np)
        # the module's jitted step keeps auto-commit semantics for the
        # timed loop (its out shardings are not caller-pinnable), so
        # the comm estimate pays one extra AOT compile for the HLO
        # text — pipe topologies only
        _, text = _compile_once(step, params, opt_state, xt, yt)

        def once(carry):
            p, o = carry
            loss, p, o = step(p, o, xt, yt)
            return (p, o), loss

        return once, (params, opt_state), dict(
            mesh=cfg, layers=n_stages,
            comm=_cost.estimate_comm(text))

    def _compile_once(jitted, *args):
        """(AOT executable, optimized-HLO text) from one compile."""
        exe = jitted.lower(*args).compile()
        try:
            text = exe.as_text()
        except Exception:       # backend without HLO text
            text = None
        return exe, text

    model = 2 if N % 2 == 0 else 1
    pipe = 4 if N % 4 == 0 else (2 if N % 2 == 0 else 1)
    wanted = os.environ.get("BENCH_SHARD_TOPOS",
                            "dp,modelxdata,pipexdata").split(",")
    topo_defs = {
        "dp": lambda: build_dense("dp", MeshConfig(data=N)),
        "modelxdata": lambda: build_dense(
            "modelxdata", MeshConfig(data=N // model, model=model)),
        "pipexdata": lambda: build_pipe(
            "pipexdata",
            MeshConfig(data=N // pipe, pipe=pipe,
                       axis_order=("data", "pipe", "model", "seq"))),
    }

    def window(once, carry, n):
        t0 = time.perf_counter()
        for _ in range(n):
            carry, res = once(carry)
        float(np.ravel(np.asarray(res))[0])     # host-fetch sync
        return time.perf_counter() - t0, carry

    # compile + settle every topology BEFORE any timing, then
    # interleave windows round-robin
    runners = {}
    for name in wanted:
        name = name.strip()
        if name not in topo_defs:
            continue
        once, carry, meta = topo_defs[name]()
        dt, carry = window(once, carry, 1)      # compile
        dt, carry = window(once, carry, 2)      # settle the pipeline
        runners[name] = [once, carry, meta, []]
    for w in range(windows):
        for name, r in runners.items():
            dt, r[1] = window(r[0], r[1], steps)
            r[3].append(dt)

    for topo_i, (name, (once, carry, meta, dts)) in enumerate(
            runners.items()):
        best = min(dts)
        ms = best / steps * 1e3
        flops = _trunk_flops(meta["layers"])
        mfu = _mfu(flops / (best / steps), devs[0], max(N, 1))
        comm = meta["comm"] or {}
        cfg = meta["mesh"]
        if mfu is not None:
            g_mfu.set(mfu, topology=name)
        if comm:
            # ONE group for the whole sweep, one segment index per
            # topology: a per-topology group would clear the previous
            # topology's gauge series on every record (latest-group
            # semantics), leaving only the last topology in the
            # end-of-run registry snapshot
            _cost.record_segment_comm("bench.shard", topo_i, comm)
        line = {
            "metric": f"shard_{name}_step_ms",
            "value": round(ms, 3), "unit": "ms",
            # None on a device with no peak on record (the CPU)
            "mfu": None if mfu is None else float(f"{mfu:.4g}"),
            "comm_bytes_per_step": comm.get("comm_bytes", 0.0),
            "collectives": comm.get("collectives", {}),
            "tokens_per_sec": round(B * S / (best / steps), 1),
            "layout": {"data": cfg.data, "model": cfg.model,
                       "pipe": cfg.pipe, "n_devices": N},
            "windows_ms_per_step": [round(d / steps * 1e3, 3)
                                    for d in dts],
        }
        spread = (max(dts) - min(dts)) / min(dts) if dts else 0.0
        line["window_spread"] = round(spread, 4)
        if spread > 0.20:
            line["contention_suspected"] = True
        print(json.dumps(line))

    # ---- overlap A/B on the pipe topology (comm-bound config) --------
    from paddle_tpu.parallel.pipeline import _data_reduce_axes
    pmesh_cfg = MeshConfig(data=N // pipe, pipe=pipe,
                           axis_order=("data", "pipe", "model", "seq"))
    pmesh = make_mesh(pmesh_cfg, devices=devs)
    if "pipexdata" in runners and _data_reduce_axes(pmesh):
        on_once, on_carry, on_meta = build_pipe("ov_on", pmesh_cfg,
                                                overlap=True)
        off_once, off_carry, off_meta = build_pipe("ov_off", pmesh_cfg,
                                                   overlap=False)
        onces = {"on": on_once, "off": off_once}
        carries = {"on": on_carry, "off": off_carry}
        for k in ("on", "off"):         # compile + settle
            _, carries[k] = window(onces[k], carries[k], 2)
        on_ms, off_ms, ratios = [], [], []
        for w in range(pairs):
            order = (("on", "off") if w % 2 == 0   # alternate order
                     else ("off", "on"))           # within each pair
            pair = {}
            for k in order:
                pair[k], carries[k] = window(onces[k], carries[k],
                                             steps)
            on_ms.append(pair["on"] / steps * 1e3)
            off_ms.append(pair["off"] / steps * 1e3)
            ratios.append(pair["on"] / pair["off"])
        med = float(np.median(ratios))
        print(json.dumps({
            "metric": "shard_overlap_step_ratio",
            "value": round(med, 4), "unit": "x",
            "overlap_on_ms_per_step": round(float(np.median(on_ms)), 3),
            "overlap_off_ms_per_step": round(float(np.median(off_ms)),
                                             3),
            "pair_ratios": [round(r, 4) for r in ratios],
            "overlap_on_comm_bytes": (on_meta["comm"] or {}).get(
                "comm_bytes", 0.0),
            "overlap_off_comm_bytes": (off_meta["comm"] or {}).get(
                "comm_bytes", 0.0),
            "overlap_on_collectives": (on_meta["comm"] or {}).get(
                "collectives", {}),
            "overlap_off_collectives": (off_meta["comm"] or {}).get(
                "collectives", {}),
        }))
        print(f"# overlap A/B: median pair ratio {med:.4f}x over "
              f"{pairs} interleaved pairs x {steps} steps "
              f"(pipe={pipe}, data={N // pipe})", file=sys.stderr)
    else:
        print("# overlap A/B skipped: pipe topology has no data axis "
              "to reduce over (n_devices too small)", file=sys.stderr)


def _emit_registry_snapshot():
    """End-of-run metrics emission: the registry (bench windows +
    whatever executor/prefetch/checkpoint counters the run touched) as
    Prometheus text — to the BENCH_METRICS_OUT path when set, else a
    compact dump on stderr. Never fatal: a bench must not fail on its
    own telemetry."""
    try:
        from paddle_tpu.monitor import exporter
        out = os.environ.get("BENCH_METRICS_OUT")
        if out:
            exporter.write_snapshot(out)
            print(f"# metrics registry snapshot -> {out}",
                  file=sys.stderr)
        else:
            print("# --- metrics registry snapshot ---",
                  file=sys.stderr)
            print(exporter.render_text(), file=sys.stderr, end="")
    except Exception as e:   # pragma: no cover - telemetry-only path
        print(f"# metrics snapshot failed: {e}", file=sys.stderr)


def _emit_peak_hbm():
    """End-of-run device-memory line, emitted for EVERY mode: one
    final live-buffer sample (monitor/memory.py) folded into the
    high-water mark — the run's peak when the poller was on, its
    end-of-run residency floor otherwise (``sampled`` says which).
    Never fatal: a bench must not fail on its own telemetry."""
    try:
        from paddle_tpu.monitor import memory as _memory
        sampled = _memory.poller_enabled()
        _memory.sample_now()
        print(json.dumps({
            "metric": "peak_hbm_bytes",
            "value": int(_memory.high_water()),
            "unit": "bytes", "sampled_continuously": sampled,
        }))
    except Exception as e:   # pragma: no cover - telemetry-only path
        print(f"# peak_hbm_bytes failed: {e}", file=sys.stderr)


class NoAccelerator(SystemExit):
    """A mode that measures the chip found none: nothing was measured,
    so nothing is printed — no result line, no telemetry."""


def main():
    try:
        return _dispatch_mode()
    finally:
        if not isinstance(sys.exc_info()[1], NoAccelerator):
            _emit_peak_hbm()
            _emit_registry_snapshot()


def _dispatch_mode():
    if len(sys.argv) > 1 and sys.argv[1] == "dispatch":
        # executor host-overhead microbench (small model: the step time
        # IS the dispatch); lives in bench_dispatch.py, reuses this
        # module's _timed_steps harness
        import bench_dispatch
        return bench_dispatch.main()
    if len(sys.argv) > 1 and sys.argv[1] == "resnet50":
        return bench_resnet50()
    if len(sys.argv) > 1 and sys.argv[1] == "nmt":
        return bench_nmt()
    if len(sys.argv) > 1 and sys.argv[1] == "inference":
        return bench_inference()
    if len(sys.argv) > 1 and sys.argv[1] == "longcontext":
        return bench_longcontext()
    if len(sys.argv) > 1 and sys.argv[1] == "int8":
        return bench_int8()
    if len(sys.argv) > 1 and sys.argv[1] == "passes":
        return bench_passes()
    if len(sys.argv) > 1 and sys.argv[1] == "serving":
        return bench_serving()
    if len(sys.argv) > 1 and sys.argv[1] == "numerics":
        return bench_numerics()
    if len(sys.argv) > 1 and sys.argv[1] == "ckpt":
        return bench_ckpt()
    if len(sys.argv) > 1 and sys.argv[1] == "data":
        return bench_data()
    if len(sys.argv) > 1 and sys.argv[1] == "shard":
        return bench_shard()
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh, set_mesh

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        # a measurement path that finds no chip fails; it does not time
        # a toy model on the CPU under the chip metric's name
        raise NoAccelerator(
            "bench.py: the BERT-base pretrain benchmark needs an "
            f"accelerator; jax found platform={dev.platform!r} "
            f"({dev.device_kind}). Nothing measured.")
    # BENCH_ATTN=dense|flash selects the attention path (flash = Pallas
    # blockwise kernel, ops/pallas/flash_attention.py) for A/B runs on the
    # chip
    attn = os.environ.get("BENCH_ATTN", "dense")
    # remat off: BERT-base bs=64 seq=512 activations fit v5e HBM, and
    # skipping the recompute is worth ~+0.06 MFU (measured 0.418 vs 0.362;
    # bs>=96 fails to compile -- OOM -- so bs=64 no-remat is the frontier)
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    # bf16 softmax: r4 on-chip A/B measured 154.2k vs 152.2k tok/s at
    # spc=8 with matching loss curves; BENCH_SOFTMAX=fp32 reverts
    smax = os.environ.get("BENCH_SOFTMAX", "bf16")
    cfg = bert.bert_base(attention_impl=attn, remat=remat,
                         softmax_dtype=smax)
    # batch=64 is the tuned single-chip config (highest measured MFU of
    # {32, 64, 96}); vs_baseline is MFU-based, so it stays comparable
    # across batch choices
    batch, seq = 64, 512
    steps = 20

    # single-chip benchmark: pin a 1-device mesh whatever the host
    mesh = set_mesh(make_mesh(MeshConfig(data=1),
                              devices=jax.devices()[:1]))
    opt = pt.optimizer.Adam(learning_rate=1e-4)
    # 16 scanned steps per dispatch (train_from_dataset pattern):
    # amortizes the per-dispatch gap, same batch per inner step.
    # BENCH_SPC overrides.
    spc = int(os.environ.get("BENCH_SPC", "16"))
    init_fn, step_fn = bert.make_train_step(cfg, opt, mesh,
                                            steps_per_call=spc)
    # gathered MLM head: predict only max_predictions_per_seq positions
    # (80 ~= 0.15*512, BERT pretraining's standard), not all S — the
    # vocab head is 20% of model FLOPs and this is how the objective is
    # defined; MFU accounted at reduced FLOPs
    max_preds = int(os.environ.get("BENCH_MAX_PREDS", "80"))
    data = bert.synthetic_batch(cfg, batch_size=batch, seq_len=seq,
                                max_preds=max_preds)
    params, opt_state = init_fn(jax.random.PRNGKey(0))

    def once(carry):
        params, opt_state = carry
        loss, params, opt_state = step_fn(params, opt_state, data)
        return (params, opt_state), loss

    tr = _timed_steps(once, (params, opt_state), steps, sub_steps=spc)
    loss = tr.res

    tokens = batch * seq * steps * spc
    tok_per_sec = tokens / tr.dt
    flops = bert.flops_per_token(cfg, seq_len=seq, max_preds=max_preds)
    mfu = _mfu(tok_per_sec * flops, dev)
    print(json.dumps({
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 2),
        "unit": "tokens/sec",
        "vs_baseline": _vs_baseline(mfu),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        **tr.extras(),
    }))
    print(f"# device={dev.platform} batch={batch} seq={seq} steps={steps} "
          f"loss={float(loss):.4f} mfu={mfu}", file=sys.stderr)


if __name__ == "__main__":
    main()
