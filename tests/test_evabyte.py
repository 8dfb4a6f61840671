"""The EvaByte decoder on the training path, against the plain reference of
the benchmark.

``chipbench/reference/evabyte.py`` computes EVA attention with a dense
visibility mask over the tokens and the chunk summaries, a block of queries at
a time, and the eight-head loss, in float32 ``jax.numpy``; it shares no code
with ``paddle_tpu`` and reads the program's parameter tree by its key names.
Here, on the CPU at ``evabyte_tiny``'s sizes and seeded random weights: the
op's two bodies (the ``jax.numpy`` one and the Mosaic kernels in interpreter
mode) against a dense-mask formulation written here, forward and all five
gradients; the two identities (a window that reaches the sequence, and chunks
of one position, are plain causal attention); the model's loss, its heads'
cross-entropies, every part of its stream and the gradient of every parameter
leaf against the reference, at a sequence of several windows and at one of
exactly one; the last positions' missing targets a head; the published sizes'
parameter count; the counter the benchmark reads; and what a step traces of
the kernels.
"""

import collections
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import blocks, evabyte
from paddle_tpu.ops import eva
from paddle_tpu.ops import pallas as plk
from paddle_tpu.ops.pallas import eva as eva_kernels
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("chipbench/reference/evabyte.py", "reference_evabyte")

#: the registry's override for each body of the op
BODIES = {"reference": "off", "pallas_interpret": "on"}


def reference_config(cfg):
    """The keys the reference reads of a configuration file."""
    return {"hidden_size": cfg.hidden, "num_attention_heads": cfg.num_heads,
            "window_size": cfg.window, "chunk_size": cfg.chunk,
            "num_pred_heads": cfg.pred_heads, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
            "num_hidden_layers": cfg.num_layers}


def one_device():
    return make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


# ---------------------------------------------------------------------------
# the op: both bodies against a dense mask
# ---------------------------------------------------------------------------
def dense_eva(q, k, v, ksum, vsum, window, chunk):
    """The aggregation with one [S, S + S / chunk] mask, [B, S, H, D]."""
    _, s, _, d = q.shape
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    c = jnp.arange(s // chunk)[None, :]
    seen = jnp.concatenate([(j <= i) & (j // window == i // window),
                            c < (window // chunk) * (i // window)], axis=1)
    scores = jnp.concatenate(
        [jnp.einsum("bqhd,bkhd->bhqk", q, k),
         jnp.einsum("bqhd,bchd->bhqc", q, ksum)], axis=-1) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p[..., :s], v) \
        + jnp.einsum("bhqc,bchd->bqhd", p[..., s:], vsum)


def operands(s, b=1, h=2, d=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, weight = (jax.random.normal(key, (b, s, h, d))
                       for key in keys[:4])
    mu, phi = (0.3 * jax.random.normal(key, (h, d)) for key in keys[4:])
    return (q, k, v, mu, phi), weight


def value_and_grads(attend, args, weight, chunk):
    """(the context, the gradients to q, k, v, mu and phi of its weighted
    sum) where the summaries come from ``eva.eva_summaries``."""
    def loss(q, k, v, mu, phi):
        ksum, vsum = eva.eva_summaries(k, v, mu, phi, chunk)
        out = attend(q, k, v, ksum, vsum)
        return jnp.sum(out * weight), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, (0, 1, 2, 3, 4), has_aux=True))(*args)
    return out, grads


#: (positions, window, chunk): three windows; five; exactly one; a window
#: beyond the sequence
SHAPES = [(192, 64, 8), (320, 64, 8), (64, 64, 8), (128, 256, 8)]


@pytest.mark.parametrize("body", sorted(BODIES))
@pytest.mark.parametrize("s, window, chunk", SHAPES)
def test_a_body_is_the_dense_mask_forward_and_all_five_gradients(
        s, window, chunk, body):
    args, weight = operands(s)
    want, want_grads = value_and_grads(
        lambda *a: dense_eva(*a, window, chunk), args, weight, chunk)
    with plk.override(BODIES[body]):
        got, grads = value_and_grads(
            lambda *a: eva.eva_attention(*a, window, chunk), args, weight,
            chunk)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for name, a, b in zip(("q", "k", "v", "mu", "phi"), grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    if window >= s:       # no summary is visible: none takes a gradient
        assert not np.any(grads[3]) and not np.any(grads[4])


@pytest.mark.parametrize("body", sorted(BODIES))
@pytest.mark.parametrize("window, chunk", [(512, 8), (64, 1), (256, 1)])
def test_the_two_identities_are_plain_causal_attention(window, chunk, body):
    """``window >= S``: no summary is visible; ``chunk = 1``: every summary
    is its token, whatever ``mu`` and ``phi``. Either way the op is
    ``blocks.causal_attention`` over the whole sequence."""
    (q, k, v, mu, phi), _ = operands(256, seed=1)
    ksum, vsum = eva.eva_summaries(k, v, 5.0 * mu, -3.0 * phi, chunk)
    if chunk == 1:
        np.testing.assert_allclose(ksum, k, atol=1e-6)
        np.testing.assert_allclose(vsum, v, atol=1e-6)
    with plk.override(BODIES[body]):
        got = eva.eva_attention(q, k, v, ksum, vsum, window, chunk)
    want = blocks.causal_attention(q, k, v, impl="dense")
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_op_on_a_data_mesh_runs_a_shard_of_the_batch_at_a_time():
    """Under a mesh that splits only the batch the registry runs the Pallas
    body a row shard at a time inside ``shard_map`` (the kernel leads with
    the batch): the values and all five gradients of the one-device call."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    (q, k, v, mu, phi), weight = operands(128, b=2)
    ksum, vsum = eva.eva_summaries(k, v, mu, phi, 8)
    mesh = make_mesh(MeshConfig(data=2), devices=jax.devices()[:2])

    def loss(*operands, mesh=None):
        out = eva.eva_attention(*operands, 64, 8, mesh=mesh)
        return jnp.sum(out * weight), out

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    rows = NamedSharding(mesh, P("data"))
    with plk.override("on"):
        with plk.mesh_scope(mesh):
            assert plk.selected_body("eva_attention", 2) \
                == "pallas_per_shard_interpret"
        (_, want), want_grads = grad(q, k, v, ksum, vsum)
        (_, got), grads = jax.jit(lambda *a: grad(*a, mesh=mesh))(
            *(jax.device_put(t, rows) for t in (q, k, v, ksum, vsum)))
    assert got.sharding.is_equivalent_to(rows, got.ndim)
    np.testing.assert_allclose(got, want, atol=1e-6)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_sequence_that_is_no_whole_chunks_is_refused_by_name():
    (q, k, v, mu, phi), _ = operands(100)
    with pytest.raises(ValueError, match="whole chunks"):
        eva.eva_summaries(k, v, mu, phi, 8)
    ksum, vsum = eva.eva_summaries(k[:, :96], v[:, :96], mu, phi, 8)
    with pytest.raises(ValueError, match="summaries"):
        eva.eva_attention(q, k, v, ksum, vsum, 64, 8)
    with pytest.raises(ValueError, match="whole chunks"):
        eva.eva_attention(q[:, :96], k[:, :96], v[:, :96], ksum, vsum, 60,
                          8)


def test_the_kernels_tile_whole_windows_and_the_counter_counts_their_loops():
    """The Pallas body takes a sequence of whole windows of whole blocks;
    any other shape is the reference body's. The counter is the area of the
    tiles the two loops visit over a causal call's, at the cell's size 80
    token tiles of 512 and 112 summary tiles of 128 keys a head against 528
    tiles of 512."""
    assert eva_kernels._blocks(16384, 2048, 16) == (512, 2048, True)
    assert eva_kernels._blocks(1024, 2048, 16) == (512, 1024, True)
    assert eva_kernels._blocks(80, 32, 8) == (32, 32, False)
    assert eva_kernels._blocks(16384, 2000, 16)[2] is False
    under, earlier = eva_kernels._visits(np.arange(32), 4)
    assert under.tolist() == [0, 1, 2, 3] * 8
    assert earlier.tolist() == sorted(list(range(8)) * 4)
    assert int(np.sum(under + 1)) == 80 and int(np.sum(earlier)) == 112
    assert eva_kernels.eva_tiles_visited_pct(16384, 2048, 16) \
        == pytest.approx(100 * (80 * 512 + 112 * 128) / (528 * 512))
    assert eva_kernels.eva_tiles_visited_pct(16384, 16384, 16) \
        == pytest.approx(100.0)
    assert eva_kernels.eva_tiles_visited_pct(80, 32, 8) is None
    assert "eva_attention" in plk.list_kernels()
    assert len(plk.list_kernels()) == 14
    assert eva_kernels.KEPT == blocks._FLASH_KEPT


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    """(config, parameters off their rest: gains, vectors and matrices
    large enough that every path carries signal, a batch of 2 x 320: five
    windows of 64)."""
    cfg = evabyte.evabyte_tiny(dtype=jnp.float32)
    params = evabyte.init_params(jax.random.PRNGKey(0), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree.map(
        lambda a: 10.0 * a if a.ndim == 2
        else 0.2 * jax.random.normal(next(keys), a.shape), params)
    return cfg, params, evabyte.synthetic_batch(cfg, 2, 320, seed=1)


@pytest.fixture(scope="module")
def wanted(tiny):
    """The one compiled reference of the file: (the heads' cross-entropies,
    the gradient of their mean)."""
    cfg, params, batch = tiny
    config = reference_config(cfg)

    def loss(p):
        heads = reference.head_losses(p, config, batch)
        return jnp.mean(heads), heads

    (_, heads), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        params)
    return heads, grads


@pytest.mark.parametrize("body", sorted(BODIES))
def test_loss_heads_and_every_gradient_match_the_reference(tiny, wanted,
                                                           body):
    cfg, params, batch = tiny
    heads, grads = wanted
    with plk.override(BODIES[body]):
        loss, got = jax.jit(jax.value_and_grad(
            lambda p: evabyte.lm_loss(p, cfg, batch)))(params)
        got_heads = jax.jit(
            lambda p: evabyte.head_losses(p, cfg, batch))(params)
    np.testing.assert_allclose(got_heads, heads, rtol=2e-6)
    assert float(loss) == pytest.approx(float(jnp.mean(heads)), rel=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    want = jax.tree.leaves(grads)
    assert len(flat) == len(want) == 3 + 11 * cfg.num_layers
    for (path, a), b in zip(flat, want):
        assert float(jnp.max(jnp.abs(b))) > 0, path     # every leaf is live
        np.testing.assert_allclose(
            a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))), rtol=1e-4,
            err_msg=jax.tree_util.keystr(path))


def test_every_part_of_the_stream_matches_the_reference(tiny):
    """What every part of the pass hands on over five windows, the logits
    and the loss against the reference's own pass, part by part."""
    cfg, params, batch = tiny
    rows, positions = batch["input_ids"].shape
    parts, aux = jax.jit(evabyte.stages, static_argnums=1)(
        params, cfg, batch["input_ids"])
    assert aux == {} and parts.shape == (2 * cfg.num_layers + 2, rows,
                                         positions, cfg.hidden)
    logits = evabyte.DECODER.logits(params, parts[-1])
    handed = list(np.asarray(parts)) + [np.asarray(logits).reshape(
        rows, positions, cfg.pred_heads, cfg.vocab_size)]
    loss, outputs = reference.loss_and_outputs(
        params, reference_config(cfg), batch)
    got = reference.over_norms(handed, reference.norms(handed))
    np.testing.assert_allclose(got, np.asarray(outputs), atol=2e-5)
    assert float(jax.jit(evabyte.lm_loss, static_argnums=1)(
        params, cfg, batch)) == pytest.approx(float(loss), rel=2e-6)


def test_a_sequence_of_exactly_one_window_sees_no_summary(tiny):
    """64 positions under a window of 64: the heads' cross-entropies are
    those of a window that reaches any sequence (plain causal attention),
    and they do not move with the summary vectors."""
    cfg, params, batch = tiny
    batch = {k: v[:1, :cfg.window] for k, v in batch.items()}
    heads = jax.jit(evabyte.head_losses, static_argnums=1)
    got = heads(params, cfg, batch)
    plain = evabyte.evabyte_tiny(dtype=jnp.float32, window=4096)
    np.testing.assert_allclose(got, heads(params, plain, batch), rtol=1e-6)
    moved = dict(params, layers=[dict(lp, mu=-lp["mu"], phi=2.0 * lp["phi"])
                                 for lp in params["layers"]])
    np.testing.assert_array_equal(np.asarray(heads(moved, cfg, batch)),
                                  np.asarray(got))
    # and one chunk more does see one
    longer = evabyte.synthetic_batch(cfg, 1, cfg.window + cfg.chunk, seed=5)
    assert not np.array_equal(np.asarray(heads(moved, cfg, longer)),
                              np.asarray(heads(params, cfg, longer)))


def test_the_last_positions_have_no_target_for_the_further_heads(tiny):
    """Head i at position t is scored against ``labels[t + i]``; the last i
    positions have none and are left out: each head's cross-entropy by hand
    from the model's own logits, and labels no head may read change
    nothing."""
    cfg, params, batch = tiny
    s = batch["labels"].shape[1]
    hidden = jax.jit(evabyte.forward, static_argnums=1)(
        params, cfg, batch["input_ids"])
    logp = jax.nn.log_softmax(evabyte.DECODER.logits(params, hidden).reshape(
        2, s, cfg.pred_heads, cfg.vocab_size), axis=-1)
    labels = jnp.asarray(batch["labels"])
    by_hand = [-jnp.mean(jnp.take_along_axis(
        logp[:, :s - i, i], labels[:, i:, None], axis=-1))
        for i in range(cfg.pred_heads)]
    head_losses = jax.jit(evabyte.head_losses, static_argnums=1)
    heads = head_losses(params, cfg, batch)
    np.testing.assert_allclose(heads, jnp.stack(by_hand), rtol=1e-6)
    # the first label is head 0's alone (t = 0); a shift that wrapped round
    # would hand it to the last position of every further head
    other = dict(batch, labels=np.asarray(labels).copy())
    other["labels"][:, 0] = (other["labels"][:, 0] + 1) % cfg.vocab_size
    moved = head_losses(params, cfg, other)
    assert float(moved[0]) != float(heads[0])
    np.testing.assert_array_equal(np.asarray(moved[1:]),
                                  np.asarray(heads[1:]))


def test_bfloat16_program_is_within_reach_of_the_reference(tiny, wanted):
    """The program as the cell runs it (bfloat16 layers on a float32 stream,
    the Pallas bodies in interpreter mode) against the float32 reference."""
    _, params, batch = tiny
    with plk.override("on"):
        got = jax.jit(evabyte.head_losses, static_argnums=1)(
            params, evabyte.evabyte_tiny(), batch)
    np.testing.assert_allclose(got, wanted[0], rtol=5e-3)
    assert not np.array_equal(np.asarray(got), np.asarray(wanted[0]))


def test_published_sizes_count_the_parameters_of_the_cut():
    """A layer is 202.39 M parameters; four of them, the embedding, the
    eight heads and the final gain are the cell's 821.4 M, and the 32 of the
    released model 6.49 B. At rest every gain is 1 and the summary vectors
    lie within 1 / sqrt(head_dim)."""
    def count(cfg):
        shapes = jax.eval_shape(
            lambda: evabyte.init_params(jax.random.PRNGKey(0), cfg))
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))

    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202_391_552
    rest = 320 * 4096 + 4096 * 8 * 320 + 4096
    assert count(evabyte.evabyte_6b5(num_layers=4)) == 4 * layer + rest \
        == 821_366_784
    assert count(evabyte.evabyte_6b5()) == 32 * layer + rest
    cfg = evabyte.evabyte_tiny()
    params = evabyte.init_params(jax.random.PRNGKey(0), cfg)
    lp = params["layers"][0]
    assert not np.any(lp["ln1_w"]) and not np.any(params["final_norm_w"])
    for name in ("mu", "phi"):
        assert lp[name].shape == (cfg.num_heads, cfg.head_dim)
        assert float(jnp.abs(lp[name]).max()) <= 1 / math.sqrt(cfg.head_dim)
        assert float(jnp.abs(lp[name]).max()) > 0.5 / math.sqrt(cfg.head_dim)
    assert float(jnp.std(lp["q_w"])) == pytest.approx(cfg.init_std, rel=0.05)
    specs = evabyte.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree.structure(params)


# ---------------------------------------------------------------------------
# the set-up: the kernels are traced for the step, not for every layer
# ---------------------------------------------------------------------------
def test_a_step_traces_the_kernel_bodies_once_each(monkeypatch):
    """Three layers, each mixer recomputed in the backward pass, with the
    Pallas bodies forced: tracing the step enters ``flash_fwd_eva``'s body
    once and ``flash_bwd_eva``'s once (``registry.lowered_once``, as
    ``tests/test_nemotron_h.py`` counts for the state-space scan), and the
    gradient holds each call once a layer: ``blocks.recomputed`` keeps what
    the forward kernel hands the backward one."""
    entered = {}
    for name in ("_fwd_kernel", "_bwd_kernel"):
        def enter(*args, _body=getattr(eva_kernels, name), _name=name, **kw):
            entered[_name] = entered.get(_name, 0) + 1
            return _body(*args, **kw)

        monkeypatch.setattr(eva_kernels, name, enter)
    cfg = evabyte.evabyte_tiny(num_layers=3)
    init_fn, step_fn = evabyte.make_train_step(
        cfg, pt.optimizer.Adam(1e-3), one_device())
    params, opt_state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = jax.eval_shape(step_fn.place,
                           evabyte.synthetic_batch(cfg, 1, 256))
    jax.clear_caches()            # what earlier tests of this process traced
    with plk.override("on"):
        traced = step_fn.jitted.trace(params, opt_state, batch)
    assert entered == {"_fwd_kernel": 1, "_bwd_kernel": 1}, entered
    calls = collections.Counter()

    def count(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] += 1
            for inner in jax.core.jaxprs_in_params(eqn.params):
                count(inner)

    count(traced.jaxpr.jaxpr)
    assert calls["flash_fwd_eva"] == 3 == calls["flash_bwd_eva"], calls
