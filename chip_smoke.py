#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the system's main path once, in ONE process (a chip belongs to
one process), through the entry points a user calls:

  one chip          BERT-base at its published width (12 layers, h=768,
                    12 heads, FFN 3072, vocab 30522), bs=64, s=512,
                    gathered MLM head, bf16, a few Adam steps through
                    bert.make_train_step on a one-device mesh
  kernels           every registered Pallas body compiled for the chip
                    (interpret=False), run once at a production shape and
                    compared with its reference body
  static quickstart the README's fluid-style program (embedding, fc,
                    layer_norm, AdamOptimizer.minimize) through
                    pt.static.Executor
  four chips        the one-chip step on MeshConfig(data=4) and
                    MeshConfig(data=2, model=2): shards on four distinct
                    devices, first-step loss equal to the one-chip loss.
                    With fewer than four devices: skipped, and said so.

Depth may be cut in the kernel comparisons; weights are random, from a
seed. Any phase that raises ends the run non-zero. With no accelerator
it exits non-zero and prints no result: it never runs something smaller
instead. The last line of stdout is one JSON object with exactly these
keys, {"ok": true, "device": {"platform", "kind", "count"}}; the line
before it, "summary: {...}", carries the per-phase results.

Times printed here are information for the reader, under no metric name.
"""

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

import paddle_tpu as pt
from paddle_tpu import native, profiler
from paddle_tpu.core import compile_cache
from paddle_tpu.models import bert
from paddle_tpu.monitor.registry import REGISTRY
from paddle_tpu.ops import pallas as plk
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.data_parallel import DataParallelTrainer

GLOBAL_BATCH, SEQ, MAX_PREDS, STEPS = 64, 512, 80, 6
#: kernel-phase sizes, BERT-base's and DeepFM's. Constants, read from no
#: flag and no environment: only tests/test_startup_rules.py, which rehearses
#: this file on the CPU at toy sizes in interpreter mode, rebinds them.
VOCAB, HIDDEN, FFN = 30522, 768, 3072
TABLE_ROWS, TABLE_DIM, SLOTS = 100000, 16, 26
#: (sequence, batch): the cell mlm_s512's one-tile programs, several heads
#: each, and the many-tile programs of 2048 and 4096 positions
FLASH_IN_BERT = ((512, 64), (2048, 2), (4096, 1))
INTERPRET = False
#: |four-chip first-step loss - one-chip first-step loss|. The loss is
#: ~10.3 (ln 30522) through bf16 activations (eps 2^-8); different bodies
#: and reduction orders may move it a few bf16 ulps of an activation,
#: far less than the 0.5% allowed here
LOSS_PARITY_ATOL = 0.05


def log(msg=""):
    print(msg, flush=True)


def bert_cfg(**kw):
    """The bench.py cell: published width, no remat, bf16 softmax."""
    return bert.bert_base(vocab_size=VOCAB, remat=False,
                          softmax_dtype="bf16", **kw)


def train_steps(step_fn, params, opt_state, batch, steps):
    """(compile_seconds, [losses], [step_seconds]); every step ends in
    block_until_ready. The first call compiles."""
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss, params, opt_state = step_fn(params, opt_state, batch)
        loss.block_until_ready()
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return secs[0], losses, secs[1:]


# ---------------------------------------------------------------------------
def phase_one_chip():
    cfg = bert_cfg()
    mesh = mesh_mod.make_mesh(mesh_mod.MeshConfig(data=1),
                              devices=jax.devices()[:1])
    init_fn, step_fn = bert.make_train_step(cfg, pt.optimizer.Adam(1e-4),
                                            mesh)
    batch = bert.synthetic_batch(cfg, GLOBAL_BATCH, SEQ, seed=0,
                                 max_preds=MAX_PREDS)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding),
        (params, opt_state, step_fn.place(batch)))
    compile_s, losses, secs = train_steps(step_fn, params, opt_state,
                                          batch, STEPS)
    del params, opt_state
    stats = jax.devices()[0].memory_stats()
    # the executable the steps just ran (jit keeps it), by the
    # compiler's own account: the runtime's peak counts live arrays only
    ma = step_fn.jitted.lower(*shapes).compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    log(f"one_chip: bert_base L={cfg.num_layers} h={cfg.hidden} "
        f"bs={GLOBAL_BATCH} s={SEQ} max_preds={MAX_PREDS}")
    log(f"one_chip: first step (compile included) {compile_s:.1f} s; "
        f"steady {1e3 * float(np.median(secs)):.1f} ms/step "
        f"(median of {len(secs)})")
    log(f"one_chip: losses {' '.join(f'{v:.4f}' for v in losses)}")
    log(f"one_chip: peak_bytes_in_use={stats['peak_bytes_in_use']} "
        f"bytes_limit={stats.get('bytes_limit')}")
    log(f"one_chip: compiled step needs {need} bytes (arguments "
        f"{ma.argument_size_in_bytes}, temp {ma.temp_size_in_bytes}, "
        f"donated {ma.alias_size_in_bytes})")
    bodies = {k: plk.selected_body(k) for k in plk.list_kernels()}
    for k, body in bodies.items():
        log(f"one_chip: kernel {k} -> {body}")
    log(f"one_chip: pallas_vmem_budget_rejections_total="
        f"{vmem_rejections()}")
    return {"first_loss": losses[0], "last_loss": losses[-1],
            "compile_s": round(compile_s, 1),
            "peak_bytes_in_use": int(stats["peak_bytes_in_use"]),
            "compiled_step_bytes": int(need),
            "bodies": bodies}


def vmem_rejections():
    c = REGISTRY.get("pallas_vmem_budget_rejections_total")
    return 0 if c is None else int(sum(c.samples().values()))


# ---------------------------------------------------------------------------
def rel_err(got, want):
    """max over leaves of ||got - want|| / ||want|| in float64."""
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        if g.shape != w.shape:
            raise AssertionError(f"shape {g.shape} != {w.shape}")
        if not np.all(np.isfinite(g)):
            raise AssertionError("non-finite kernel output")
        worst = max(worst, float(np.linalg.norm(g - w)
                                 / max(np.linalg.norm(w), 1e-30)))
    return worst


def phase_kernels():
    """Each registered Pallas body, compiled for the chip, against its
    reference body. The reference runs at 'highest' matmul precision: on
    a TPU a default-precision float32 matmul is itself a bf16 one."""
    rs = np.random.RandomState(0)

    def f32(*shape, scale=1.0):
        return jnp.asarray(rs.randn(*shape) * scale, jnp.float32)

    def bf16(*shape):
        return f32(*shape).astype(jnp.bfloat16)

    def unit(*shape):
        t = f32(*shape)
        return (t / jnp.linalg.norm(t, axis=-1, keepdims=True)) \
            .astype(jnp.bfloat16)

    def sum_f32(tree):
        return sum(jnp.sum(leaf.astype(jnp.float32))
                   for leaf in jax.tree.leaves(tree))

    n_vocab, h, ffn = VOCAB, HIDDEN, FFN
    rows, dim = TABLE_ROWS, TABLE_DIM
    n_pred = GLOBAL_BATCH * MAX_PREDS
    ids8k = jnp.asarray(rs.randint(0, rows, 8192), jnp.int32)
    labels = jnp.asarray(rs.randint(0, n_vocab, n_pred), jnp.int32)
    w8 = jnp.asarray(rs.randint(-127, 128, (h, ffn)), jnp.int8)
    # name -> (args, kwargs, differentiate wrt these argnums, tolerance).
    # Tolerances: 1e-5 for float32 elementwise math, 2e-2 where the body
    # or its output is bf16 or rides the MXU (bf16 eps is 2^-8 = 4e-3)
    cases = {
        "fused_matmul": ((f32(4096, h), f32(h, ffn, scale=0.02)),
                         {"bias": f32(ffn), "act": "gelu"}, (0, 1), 2e-2),
        "fused_matmul_int8": ((f32(4096, h), w8, jnp.abs(f32(ffn)) + 0.01),
                              {"bias": f32(ffn), "act": "relu"}, None,
                              2e-2),
        # the DeepFM table; 8192 updates fit the VMEM budget
        "embedding_scatter_add": ((f32(rows, dim), ids8k, f32(8192, dim)),
                                  {}, None, 2e-2),
        "flash_attention": (tuple(bf16(8, 12, 4 * SEQ, 64)
                                  for _ in range(3)),
                            {"bias": jnp.zeros((8, 4 * SEQ), jnp.float32)},
                            (0, 1, 2), 3e-2),
        # the projections' own layout: q, k and v side by side in one
        # [B, S, 3 H D] array, two heads of 64 a lane tile (BERT's call)
        "flash_attention/rows_major": ((bf16(8, 4 * SEQ, 3 * 12 * 64),),
                                       {"bias": jnp.zeros((8, 4 * SEQ),
                                                          jnp.float32),
                                        "num_heads": 12}, (0,), 3e-2),
        # a window of SEQ keys, groups of 8 query heads on one key/value head
        "flash_attention/window": ((bf16(1, 16, 4 * SEQ, 128),
                                    bf16(1, 2, 4 * SEQ, 128),
                                    bf16(1, 2, 4 * SEQ, 128)),
                                   {"causal": True, "window": SEQ},
                                   (0, 1, 2), 3e-2),
        "fused_layer_norm": ((bf16(GLOBAL_BATCH, SEQ, h), f32(h) + 1.0,
                              f32(h)), {}, (0, 1, 2), 2e-2),
        "softmax_cross_entropy": ((f32(n_pred, n_vocab), labels), {},
                                  (0,), 1e-5),
        # uneven groups, empty ones first, last and between; float32,
        # because XLA's own ragged-dot kernel, the reference here, does
        # not compile at 'highest' precision with bf16 operands
        "grouped_matmul": ((f32(4096, h), f32(8, h, ffn, scale=0.02),
                            jnp.asarray([0, 1000, 7, 300, 1500, 0, 1289, 0],
                                        jnp.int32)), {}, (0, 1), 2e-2),
        # a pass of a layer that holds a share of the experts: 2048 rows in
        # expert order into 4096 tokens, four a token at most, the last 548
        # rows of weight 0 (past the rows held); y, rows, tokens, weights
        "moe_combine": ((f32(4096, 1024), bf16(2048, 1024),
                         jnp.asarray(rs.permutation(4 * 4096)[:2048] // 4,
                                     jnp.int32),
                         jnp.abs(f32(2048)) * (jnp.arange(2048) < 1500)),
                        {}, None, 1e-5),
        # two heads of 128, ten grid steps of 128 positions with padding; q, k
        # of unit norm, decays in (-1, 0], beta in (0, 1), as a KDA mixer
        # hands them over; both bodies round the same operands to bf16
        "kda_chunked": ((unit(2, 1200, 2, 128), unit(2, 1200, 2, 128),
                         bf16(2, 1200, 2, 128),
                         -jax.nn.sigmoid(f32(2, 1200, 2, 128)),
                         jax.nn.sigmoid(f32(2, 1200, 2))),
                        {"chunk": 32}, (0, 1, 2, 3, 4), 3e-2),
        # a decay a head and 4 key heads under 8 value heads, as a Gated
        # DeltaNet mixer hands them over: the kernels gdn_fwd / gdn_bwd
        "kda_chunked/head_decay": ((unit(2, 1200, 4, 128),
                                    unit(2, 1200, 4, 128),
                                    bf16(2, 1200, 8, 128),
                                    -jax.nn.sigmoid(f32(2, 1200, 8)),
                                    jax.nn.sigmoid(f32(2, 1200, 8))),
                                   {"chunk": 64}, (0, 1, 2, 3, 4), 3e-2),
        # heads of 256, two lane tiles: 16 query heads on 2 key/value heads
        "flash_attention/d256": ((bf16(1, 16, 4 * SEQ, 256),
                                  bf16(1, 2, 4 * SEQ, 256),
                                  bf16(1, 2, 4 * SEQ, 256)),
                                 {"causal": True}, (0, 1, 2), 3e-2),
        # Gated DeltaNet's [q | k | v] in one array, 4 key heads under 8
        # value heads of 128, 4 taps, 1200 positions (three blocks with
        # padding): q and k normed a head, v not; x, then the taps
        "short_conv_norm": ((bf16(2, 1200, 2048), f32(4, 2048, scale=0.3)),
                            {"head_dim": 128,
                             "parts": ((512, 128 ** -0.5), (512, 1.0),
                                       (1024, None))}, (0, 1), 2e-2),
        # the rule's output, its gate and the gain a channel of the head
        "gated_head_norm": ((bf16(2, 1200, 1024), bf16(2, 1200, 1024),
                             f32(128) + 1.0),
                            {"eps": 1e-6, "act": "silu"}, (0, 1, 2), 2e-2),
        "gated_head_norm/sigmoid": ((bf16(2, 1200, 1024),
                                     bf16(2, 1200, 1024), f32(128) + 1.0),
                                    {"eps": 1e-5, "act": "sigmoid"},
                                    (0, 1, 2), 2e-2),
        # LFM2's [B | C | u] in one array of three ranges of 1024 channels,
        # 3 taps, 1200 positions (five blocks with padding): the array, then
        # the taps
        "gated_short_conv": ((bf16(2, 1200, 3072), f32(3, 1024, scale=0.3)),
                             {}, (0, 1), 2e-2),
        # Mamba-2's scan as a Nemotron-H mixer hands it over: 8 heads of 64
        # under 2 groups of B and C with a state of 128, 1200 positions
        # (five grid steps of two chunks with padding), steps in (0, 0.7)
        # and a log-decay down to -11 a position; x, dt, a, B, C, D
        "ssd": ((bf16(2, 1200, 8, 64), jax.nn.softplus(f32(2, 1200, 8) - 2.0),
                 -jnp.exp(jnp.linspace(0.0, 2.7, 8))
                 * jax.nn.softplus(f32(2, 1200, 8) - 2.0),
                 bf16(2, 1200, 2, 128), bf16(2, 1200, 2, 128), f32(8)),
                {"chunk": 128}, (0, 1, 2, 3, 4, 5), 3e-2),
        # EVA's aggregation: four windows of 2 SEQ positions, chunks of 16,
        # 4 heads of 128; q, k, v, then the chunks' summaries
        "eva_attention": ((bf16(1, 4, 8 * SEQ, 128), bf16(1, 4, 8 * SEQ, 128),
                           bf16(1, 4, 8 * SEQ, 128), bf16(1, 4, SEQ // 2, 128),
                           bf16(1, 4, SEQ // 2, 128)),
                          {"window": 2 * SEQ, "chunk": 16}, (0, 1, 2, 3, 4),
                          3e-2),
        # heads of 64, half a lane tile: 32 query heads on 8 key/value heads
        "flash_attention/d64_gqa": ((bf16(1, 32, 4 * SEQ, 64),
                                     bf16(1, 8, 4 * SEQ, 64),
                                     bf16(1, 8, 4 * SEQ, 64)),
                                    {"causal": True}, (0, 1, 2), 3e-2),
    }
    missing = set(plk.list_kernels()) ^ {c.split("/")[0] for c in cases}
    if missing:
        raise AssertionError(f"kernels without a smoke case (or cases "
                             f"without a kernel): {sorted(missing)}")
    out = {}
    for name, (args, kw, argnums, tol) in cases.items():
        def run(which, _name=name, _kw=kw, _argnums=argnums):
            body = plk.get_body(_name.split("/")[0], which)
            kw2 = dict(_kw, interpret=INTERPRET) if which == "pallas" \
                else _kw

            def value(*a):
                return body(*a, **kw2)
            if _argnums is None:
                return jax.jit(value)
            return jax.jit(lambda *a: (
                value(*a),
                jax.grad(lambda *b: sum_f32(value(*b)), _argnums)(*a)))
        t0 = time.perf_counter()
        got = jax.block_until_ready(run("pallas")(*args))
        t1 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(run("reference")(*args))
        err = rel_err(got, want)
        log(f"kernels: {name} pallas vs reference rel_err={err:.2e} "
            f"(tol {tol:.0e}) compile+run {t1 - t0:.1f} s"
            f"{' fwd+bwd' if argnums is not None else ''}")
        if err > tol:
            raise AssertionError(f"{name}: rel_err {err:.3e} > {tol}")
        out[name] = err
        del got, want
    # past the VMEM budget the scatter-add body hands over to its
    # reference, and says so in a counter: DeepFM's 4096 x 26 ids
    before = vmem_rejections()
    ids = jnp.asarray(rs.randint(0, rows, 4096 * SLOTS), jnp.int32)
    dst, upd = f32(rows, dim), f32(4096 * SLOTS, dim)
    got = jax.jit(lambda *a: plk.dispatch("embedding_scatter_add", *a))(
        dst, ids, upd)
    err = rel_err(got, dst.at[ids].add(upd))
    log(f"kernels: embedding_scatter_add at 4096x{SLOTS} ids: rel_err="
        f"{err:.2e}, budget rejections {before} -> {vmem_rejections()}")
    if err > 1e-5 or vmem_rejections() != before + 1:
        raise AssertionError("scatter-add budget guard did not engage")

    # flash forward+backward INSIDE value_and_grad of the BERT loss, where
    # S=4096 used to pass the scoped-VMEM limit; depth cut to 2 layers,
    # batch sized so the dense reference's [B,N,S,S] scores fit beside it
    for seq, bs in FLASH_IN_BERT:
        cfg = bert_cfg(max_seq=seq, num_layers=2,
                       attention_impl="flash")
        params = jax.jit(functools.partial(bert.init_params, cfg=cfg))(
            jax.random.PRNGKey(1))
        batch = bert.synthetic_batch(cfg, bs, seq, seed=1)

        def grads(mode, _cfg=cfg):
            def fn(p, b):
                with plk.override(mode):
                    return jax.value_and_grad(
                        lambda q: bert.mlm_loss(q, _cfg, b))(p)
            return jax.jit(fn)
        got = jax.block_until_ready(grads("on")(params, batch))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(grads("off")(params, batch))
        err = rel_err(got, want)
        log(f"kernels: flash fwd+bwd in the BERT loss s={seq} bs={bs} "
            f"loss {float(got[0]):.4f} vs {float(want[0]):.4f}, "
            f"grads rel_err={err:.2e} (tol 5e-2)")
        # a whole bf16 network's gradients, two attention bodies apart
        if err > 5e-2:
            raise AssertionError(f"flash in BERT s={seq}: {err:.3e}")
        out[f"flash_in_bert_s{seq}"] = err
        del params, got, want
    # ... and one whole train step at the longest length, bs=4: the
    # context in which the flash backward (one kernel: dQ, dK and dV
    # from it) passed Mosaic's default scoped-VMEM limit (16.4 of 16 MiB)
    # before the flash calls raised their own
    seq = FLASH_IN_BERT[-1][0]
    cfg = bert_cfg(max_seq=seq, num_layers=2, attention_impl="flash")
    init_fn, step_fn = bert.make_train_step(
        cfg, pt.optimizer.Adam(1e-4),
        mesh_mod.make_mesh(mesh_mod.MeshConfig(data=1),
                           devices=jax.devices()[:1]))
    params, opt_state = init_fn(jax.random.PRNGKey(1))
    loss, params, opt_state = step_fn(
        params, opt_state, bert.synthetic_batch(cfg, 4, seq, seed=1))
    log(f"kernels: flash train step s={seq} bs=4 L=2 compiled and ran, "
        f"loss {float(loss):.4f}")
    if not np.isfinite(float(loss)):
        raise AssertionError("flash train step: non-finite loss")
    return out


# ---------------------------------------------------------------------------
def phase_static_quickstart():
    """README "Quickstart": the other execution stack."""
    rs = np.random.RandomState(0)
    id_batch = rs.randint(0, 1000, (64, 8)).astype(np.int64)
    ys = rs.rand(64, 1).astype(np.float32)
    pt.enable_static()
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.static.program_guard(main, startup):
            ids = pt.static.data("ids", shape=[8], dtype="int64")
            y = pt.static.data("y", shape=[1], dtype="float32")
            emb = pt.layers.embedding(ids, size=[1000, 16])
            h = pt.layers.fc(pt.layers.reshape(emb, [-1, 8 * 16]),
                             size=32, act="relu")
            pred = pt.layers.fc(pt.layers.layer_norm(h), size=1)
            loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
            pt.optimizer.AdamOptimizer(1e-2).minimize(loss)
        exe = pt.static.Executor()
        exe.run(startup)
        losses = [float(exe.run(main, feed={"ids": id_batch, "y": ys},
                                fetch_list=[loss])[0])
                  for _ in range(20)]
    finally:
        pt.disable_static()
    log(f"static_quickstart: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
        f"over {len(losses)} Executor.run steps")
    if not (np.isfinite(losses).all() and losses[-1] < losses[0]):
        raise AssertionError(f"static quickstart did not train: {losses}")
    return {"first_loss": losses[0], "last_loss": losses[-1]}


# ---------------------------------------------------------------------------
def phase_four_chips(one_chip_loss):
    devices = jax.devices()[:4]
    out = {}
    for label, mcfg in (("data=4", mesh_mod.MeshConfig(data=4)),
                        ("data=2,model=2",
                         mesh_mod.MeshConfig(data=2, model=2))):
        cfg = bert_cfg()
        mesh = mesh_mod.make_mesh(mcfg, devices=devices)
        n_data, n_model = mesh.shape["data"], mesh.shape["model"]
        init_fn, step_fn = bert.make_train_step(
            cfg, pt.optimizer.Adam(1e-4), mesh)
        batch = bert.synthetic_batch(cfg, GLOBAL_BATCH, SEQ, seed=0,
                                     max_preds=MAX_PREDS)
        params, opt_state = init_fn(jax.random.PRNGKey(0))
        # layout: every leaf on four distinct devices; a model-sharded
        # leaf holds 1/n_model per shard, any other a whole copy
        n_sharded = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                (params, opt_state["slots"])):
            shards = leaf.addressable_shards
            if len({s.device for s in shards}) != 4:
                raise AssertionError(f"{path}: not on 4 devices")
            split = n_model if mesh_mod.MODEL_AXIS in leaf.sharding.spec \
                else 1
            n_sharded += split > 1
            if any(s.data.size * split != leaf.size for s in shards):
                raise AssertionError(
                    f"{path}: shard sizes "
                    f"{[s.data.size for s in shards]} of {leaf.size}, "
                    f"expected 1/{split}")
        if (n_sharded > 0) != (n_model > 1):
            raise AssertionError(f"{n_sharded} model-sharded leaves on "
                                 f"a model={n_model} mesh")
        placed = step_fn.place(batch)
        for name, v in placed.items():
            rows = {s.data.shape[0] for s in v.addressable_shards}
            if len({s.device for s in v.addressable_shards}) != 4 \
                    or rows != {GLOBAL_BATCH // n_data}:
                raise AssertionError(f"batch[{name}] rows per device "
                                     f"{rows}, expected "
                                     f"{GLOBAL_BATCH // n_data}")
        with plk.mesh_scope(mesh):
            bodies = {k: plk.selected_body(k) for k in plk.list_kernels()}
        compile_s, losses, secs = train_steps(step_fn, params, opt_state,
                                              placed, 3)
        diff = abs(losses[0] - one_chip_loss)
        log(f"four_chips[{label}]: params+slots on 4 distinct devices, "
            f"{n_sharded} leaves split 1/{n_model} over model; batch "
            f"{GLOBAL_BATCH // n_data} rows per device")
        log(f"four_chips[{label}]: bodies under the mesh: "
            f"{sorted(set(bodies.values()))}")
        log(f"four_chips[{label}]: first step (compile included) "
            f"{compile_s:.1f} s; steady "
            f"{1e3 * float(np.median(secs)):.1f} ms/step; losses "
            f"{' '.join(f'{v:.4f}' for v in losses)}")
        log(f"four_chips[{label}]: first-step loss {losses[0]:.4f} vs "
            f"one chip {one_chip_loss:.4f}: |diff|={diff:.4f} "
            f"(tol {LOSS_PARITY_ATOL})")
        if diff > LOSS_PARITY_ATOL:
            raise AssertionError(f"{label}: loss parity {diff}")
        out[label] = {"first_loss": losses[0], "loss_diff": diff,
                      "bodies": sorted(set(bodies.values()))}
        del params, opt_state, placed
    out["zero_and_static_dp"] = four_chip_trainers(devices)
    return out


def four_chip_trainers(devices):
    """The two other trainers that reach the optimizer's update:
    DataParallelTrainer(param_sharding="zero") — a shard_map body, where
    Adam updates each chip's shard — and the static executor under
    CompiledProgram.with_data_parallel (GSPMD)."""
    mesh = mesh_mod.make_mesh(mesh_mod.MeshConfig(data=4), devices=devices)
    d = 512

    def loss_fn(params, state, rng, batch):
        hid = jnp.tanh(batch["x"] @ params["w1"])
        return jnp.mean((hid @ params["w2"] - batch["y"]) ** 2), state

    def init(rng, batch):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (d, d)) * 0.05,
                "w2": jax.random.normal(k2, (d, d)) * 0.05}, {}

    rs = np.random.RandomState(0)
    batch = {"x": rs.randn(64, d).astype(np.float32),
             "y": rs.randn(64, d).astype(np.float32)}
    tr = DataParallelTrainer(loss_fn, pt.optimizer.Adam(1e-3), mesh=mesh,
                             param_sharding="zero")
    p, o, st = tr.init(init, jax.random.PRNGKey(0), batch)
    for leaf in jax.tree.leaves(p):
        if leaf.addressable_shards[0].data.size * 4 != leaf.size:
            raise AssertionError("zero: a leaf is not split 1/4")
    zl = []
    for i in range(3):
        loss, p, o, st = tr.step(p, o, st, jax.random.PRNGKey(i), batch)
        zl.append(float(loss))
    if not (np.isfinite(zl).all() and zl[-1] < zl[0]):
        raise AssertionError(f"zero trainer did not train: {zl}")
    log(f"four_chips[zero]: params split 1/4, Adam inside shard_map; "
        f"losses {' '.join(f'{v:.4f}' for v in zl)}")

    pt.enable_static()
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.static.program_guard(main, startup):
            x = pt.static.data("x", shape=[d])
            y = pt.static.data("y", shape=[1])
            pred = pt.layers.fc(pt.layers.fc(x, size=64, act="relu"),
                                size=1)
            loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
            pt.optimizer.AdamOptimizer(1e-3).minimize(loss)
        exe = pt.static.Executor()
        exe.run(startup)
        compiled = pt.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=list(devices))
        yb = batch["x"][:, :1] * 0.5
        sl = [float(exe.run(compiled, feed={"x": batch["x"], "y": yb},
                            fetch_list=[loss])[0]) for _ in range(5)]
    finally:
        pt.disable_static()
    if not (np.isfinite(sl).all() and sl[-1] < sl[0]):
        raise AssertionError(f"with_data_parallel did not train: {sl}")
    log(f"four_chips[with_data_parallel]: Executor step over 4 devices; "
        f"losses {' '.join(f'{v:.4f}' for v in sl)}")
    return {"zero_losses": zl, "static_dp_losses": sl}


# ---------------------------------------------------------------------------
def main():
    # before the backend starts and before anything compiles
    cache_dir = compile_cache.enable()
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    log(f"platform={d0.platform} device_kind={d0.device_kind} "
        f"device_count={len(devs)}")
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:   # noqa: BLE001 - a version string, not a result
        libtpu = "unknown"
    log(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu} python={sys.version.split()[0]}")
    if d0.platform != "tpu":
        print(f"chip_smoke: no accelerator: jax.devices()[0].platform is "
              f"{d0.platform!r}, not 'tpu'. Nothing was run.",
              file=sys.stderr, flush=True)
        return 2
    log(f"compile_cache: dir={cache_dir} "
        f"({compile_cache.ENV_VAR} "
        f"{'set' if cache_dir != compile_cache.DEFAULT_DIR else 'unset'})")
    t0 = time.perf_counter()
    so = native.get_lib()._name
    log(f"native: {so.rsplit('/', 1)[-1]} loaded in "
        f"{time.perf_counter() - t0:.1f} s (g++ build on first use)")

    t_all = time.perf_counter()
    phases = {}
    phases["one_chip"] = phase_one_chip()
    if "pallas_interpret" in phases["one_chip"]["bodies"].values():
        raise AssertionError("interpreter body selected on a chip")
    phases["kernels"] = phase_kernels()
    phases["static_quickstart"] = phase_static_quickstart()
    if len(devs) >= 4:
        phases["four_chips"] = phase_four_chips(
            phases["one_chip"]["first_loss"])
        four = "ok"
    else:
        four = f"skipped ({len(devs)} device)"
        log(f"four_chips: {four}")
    cc = compile_cache.stats()
    log(f"compile_cache: hits={cc['hits']} misses={cc['misses']} "
        f"requests={cc['requests']}")
    log(f"total: {time.perf_counter() - t_all:.1f} s")
    # the start-up timeline (process start, import, the compile log); of
    # the programs compiled, the line names the last three
    timeline = profiler.startup()
    timeline["compile"]["compiled"] = timeline["compile"]["compiled"][-3:]
    log("summary: " + json.dumps({
        "phases": {k: "ok" for k in phases}, "four_chips": four,
        "compile_cache": {"dir": cache_dir, **cc}, "startup": timeline}))
    # the last line carries these two keys and no other
    log(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
