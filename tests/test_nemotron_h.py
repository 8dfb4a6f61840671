"""The Nemotron-H decoder (Nemotron-3-Super's sizes) on the training path,
against the plain reference of the benchmark.

``chipbench/reference/nemotron_h.py`` runs the state-space layer as the
recurrence itself, one position a step, computes the dense [S, S] scores of an
attention layer, loops over the held experts itself and adds the MTP module's
second loss, in float32 ``jax.numpy``; it shares no code with ``paddle_tpu``
and reads the program's parameter tree by its key names. Here, on the CPU at
``nemotron_h_tiny``'s sizes and seeded random weights: the chunked scan
against the recurrence (values and gradients, at lengths that are and are not
multiples of the chunk), plain ``relu2`` experts against a dense sum over the
held ones (with and without a latent), then the loss of both terms, every part
of the forward pass, the routers' choices and the gradient of every parameter
leaf in float32, the program's bfloat16 within reach of them, the shares of
the heads and of the experts against the uncut layers, the published sizes'
parameter count, the counters the benchmark reads, and the lowered step of a
decoder this PR does not touch.
"""

import collections
import dataclasses
import hashlib
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import blocks, deepseek_v3, lm_trainer, nemotron_h
from paddle_tpu.ops import ssd
from paddle_tpu.parallel import moe

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("chipbench/reference/nemotron_h.py", "reference_nemotron_h")


def reference_config(cfg):
    """The keys the reference reads of a configuration file."""
    first, held = cfg.experts_held or (0, cfg.num_experts)
    return {
        "hidden_size": cfg.hidden, "mamba_num_heads": cfg.mamba_heads,
        "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.mamba_groups,
        "ssm_state_size": cfg.state_size, "conv_kernel": cfg.conv_kernel,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "layer_norm_epsilon": cfg.rms_eps,
        "num_hidden_layers": cfg.num_layers,
        "hybrid_override_pattern": cfg.pattern,
        "mtp_hybrid_override_pattern": cfg.mtp_pattern,
        "num_nextn_predict_layers": 1 if cfg.mtp_pattern else 0,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scale,
        "mtp_loss_weight": cfg.mtp_weight, "experts_held": [first, held]}


def relative_error(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def seeded(cfg, seed=0, rows=2, seq=40):
    """Parameters with gains, the skip and the selection biases away from
    their starts, so that one applied in the wrong place shows."""
    params = nemotron_h.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(100 + seed), 400))

    def stirred(path, a):
        name = path[-1].key
        if name.endswith("_g") or name == "D":
            return a + 0.2 * jax.random.normal(next(keys), a.shape)
        if name == "router_bias":
            return 0.05 * jax.random.normal(next(keys), a.shape)
        return a

    params = jax.tree_util.tree_map_with_path(stirred, params)
    return params, nemotron_h.synthetic_batch(cfg, rows, seq, seed=seed)


def one_device():
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    return make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def tiny():
    """Float32 throughout, a share of the experts held."""
    return nemotron_h.nemotron_h_tiny(dtype=jnp.float32, experts_held=(4, 4))


# ---------------------------------------------------------------------------
# the chunked scan
# ---------------------------------------------------------------------------
def scan_operands(s, b=2, h=4, p=8, g=2, n=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 1.0)
    rate = -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7))
    return (jax.random.normal(k[0], (b, s, h, p)), dt, rate * dt,
            jax.random.normal(k[3], (b, s, g, n)),
            jax.random.normal(k[4], (b, s, g, n)),
            jax.random.normal(k[5], (h,)))


@pytest.mark.parametrize("positions", [64, 70, 16, 5])
def test_the_chunked_scan_is_the_recurrence(positions):
    """Values and the gradient of every operand, in chunks of 16: four whole
    chunks, a length that is no multiple of the chunk (handled: padded with
    positions that change nothing), one chunk, less than one."""
    operands = scan_operands(positions)
    with jax.default_matmul_precision("highest"):
        want = ssd.ssd_recurrent(*operands)
        got = ssd.ssd_chunked(*operands, chunk=16)
        assert got.shape == want.shape == operands[0].shape
        assert relative_error(got, want) < 1e-5
        grads = [jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                          argnums=tuple(range(6)))(*operands)
                 for f in (ssd.ssd_recurrent,
                           lambda *a: ssd.ssd_chunked(*a, chunk=16))]
    for want, got in zip(*grads):
        assert relative_error(got, want) < 2e-5


def test_the_scan_rounds_operands_and_keeps_decay_and_state_in_float32():
    """bfloat16 operands: the result is within bfloat16's reach of the
    float32 recurrence, in the operands' dtype, and a head that forgets fast
    (a log-decay of -30 a step) overflows nothing."""
    x, dt, a, B, C, D = scan_operands(64)
    low = [t.astype(jnp.bfloat16) for t in (x, B, C)]
    got = ssd.ssd_chunked(low[0], dt, a, low[1], low[2], D, chunk=16)
    assert got.dtype == jnp.bfloat16
    want = ssd.ssd_recurrent(low[0], dt, a, low[1], low[2], D)
    assert relative_error(got, want) < 2e-2
    fast = ssd.ssd_chunked(x, dt, jnp.full_like(a, -30.0), B, C, D, chunk=16)
    assert bool(jnp.all(jnp.isfinite(fast)))
    assert relative_error(
        fast, ssd.ssd_recurrent(x, dt, jnp.full_like(a, -30.0), B, C,
                                D)) < 1e-5


def test_groups_that_do_not_divide_the_heads_are_refused_by_name():
    x, dt, a, B, C, D = scan_operands(16, h=4, g=2)
    with pytest.raises(ValueError, match="3 groups of B and C do not divide "
                                         "4 heads"):
        ssd.ssd_chunked(x, dt, a, jnp.zeros((2, 16, 3, 16)),
                        jnp.zeros((2, 16, 3, 16)), D)


def test_the_default_chunk_is_the_published_one():
    assert ssd.CHUNK == 128 == nemotron_h.NemotronHConfig().chunk_size


# ---------------------------------------------------------------------------
# plain experts, a latent
# ---------------------------------------------------------------------------
def expert_layer(latent, tokens=48, d=32, e=8, f=24, lat=16, seed=0):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 9))
    width = lat if latent else d
    lp = {"router_w": jax.random.normal(next(k), (d, e)) * 0.5,
          "router_bias": 0.05 * jax.random.normal(next(k), (e,)),
          "w_up": jax.random.normal(next(k), (e, width, f)) * 0.2,
          "w_down": jax.random.normal(next(k), (e, f, width)) * 0.2,
          "shared_up": jax.random.normal(next(k), (d, 2 * f)) * 0.2,
          "shared_down": jax.random.normal(next(k), (2 * f, d)) * 0.2}
    if latent:
        lp["latent_down"] = jax.random.normal(next(k), (d, lat)) * 0.3
        lp["latent_up"] = jax.random.normal(next(k), (lat, d)) * 0.3
    return lp, jax.random.normal(next(k), (tokens, d))


def dense_sum(lp, x, top_k, scoring, held):
    """``sum_e w_e W2_e relu(W1_e l)^2`` over the held experts (``lp``'s
    stacks are theirs), every token through every one of them, masked by the
    choice; then the latent's way back and the shared expert."""
    _, _, top_p, top_e = moe.route(x, lp["router_w"], top_k, scoring,
                                   lp["router_bias"])
    weight = jnp.zeros((x.shape[0], lp["router_w"].shape[1])) \
        .at[jnp.arange(x.shape[0])[:, None], top_e].set(top_p)
    rows = x @ lp["latent_down"] if "latent_down" in lp else x
    first, n = held
    out = sum(weight[:, first + i, None]
              * (jnp.square(jax.nn.relu(rows @ lp["w_up"][i]))
                 @ lp["w_down"][i]) for i in range(n))
    if "latent_up" in lp:
        out = out @ lp["latent_up"]
    return out + jnp.square(jax.nn.relu(x @ lp["shared_up"])) \
        @ lp["shared_down"]


@pytest.mark.parametrize("held", [None, (2, 4)])
@pytest.mark.parametrize("latent", [False, True])
def test_plain_relu2_experts_are_the_dense_sum_over_the_held_ones(latent,
                                                                 held):
    """Values and gradients of ``dropless_moe_ffn`` without a gate (two
    grouped matmuls), on the hidden or on a latent, holding every expert or
    a share of them."""
    lp, x = expert_layer(latent)
    scoring = moe.Scoring("sigmoid", renormalize=True, scale=5.0)
    first, n = held or (0, 8)
    part = dict(lp, w_up=lp["w_up"][first:first + n],
                w_down=lp["w_down"][first:first + n])

    def program(part, x):
        return moe.dropless_moe_ffn(part, x, 3, scoring=scoring, held=held,
                                    activation="relu2")[0]

    def plain(part, x):
        return dense_sum(part, x, 3, scoring, (first, n))

    with jax.default_matmul_precision("highest"):
        assert relative_error(program(part, x), plain(part, x)) < 1e-5
        got, want = (jax.grad(lambda p, x: jnp.sum(jnp.sin(f(p, x))),
                              argnums=(0, 1))(part, x)
                     for f in (program, plain))
    for name in part:
        if name != "router_bias":                  # outside the gradient
            assert relative_error(got[0][name], want[0][name]) < 1e-4, name
    assert relative_error(got[1], want[1]) < 1e-4


def test_a_gate_is_silu_and_an_unknown_activation_is_refused():
    lp, x = expert_layer(False)
    gated = dict(lp, w_gate=lp["w_up"])
    with pytest.raises(ValueError, match="a gated expert's gate is SiLU"):
        moe.dropless_moe_ffn(gated, x, 3, activation="relu2")
    with pytest.raises(KeyError):
        moe.dropless_moe_ffn(lp, x, 3, activation="gelu")
    assert set(moe.ACTIVATIONS) == {"relu2"}


def test_the_latent_is_what_is_gathered_and_summed_back():
    """The rows the experts see and the float32 sum they return are the
    latent's width, not the hidden size: the jaxpr of the layer holds no
    [rows, hidden] operand of a grouped matmul."""
    lp, x = expert_layer(True)
    seen = []
    real = moe._experts

    def spy(rows, weights, sizes, mesh, activation):
        seen.append((rows.shape, len(weights), activation))
        return real(rows, weights, sizes, mesh, activation)

    moe._experts = spy
    try:
        part = dict(lp, w_up=lp["w_up"][:4], w_down=lp["w_down"][:4])
        moe.dropless_moe_ffn(part, x, 3, held=(0, 4), activation="relu2")
    finally:
        moe._experts = real
    assert seen and all(shape[-1] == 16 and n == 2 and act == "relu2"
                        for shape, n, act in seen)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
def test_the_tiny_preset_has_every_kind_of_layer_and_the_module(tiny):
    assert tiny.pattern == "EMEM*" and tiny.mtp_pattern == "*E"
    assert [tiny.kind(i) for i in range(7)] == list("EMEM**E")
    params = nemotron_h.init_params(jax.random.PRNGKey(0), tiny)
    assert len(params["layers"]) == 5 and len(params["mtp"]["layers"]) == 2
    kinds = {"in_w": "M", "q_w": "*", "router_w": "E"}
    assert [next(kinds[k] for k in lp if k in kinds)
            for lp in params["layers"] + params["mtp"]["layers"]] \
        == list("EMEM**E")
    # every layer is a mixer alone: one norm gain
    assert all(sum(k.startswith("ln") for k in lp) == 1
               for lp in params["layers"])
    assert params["layers"][0]["w_up"].shape == (4, 32, 48)     # held, latent
    assert params["layers"][0]["router_w"].shape == (64, 16)
    assert params["mtp"]["eh_w"].shape == (128, 64)
    with pytest.raises(ValueError, match="M, \\* or E"):
        nemotron_h.nemotron_h_tiny(pattern="MX")
    with pytest.raises(ValueError, match="groups divide"):
        nemotron_h.nemotron_h_tiny(mamba_groups=3)


def test_a_mamba_mixer_starts_as_mamba_2_starts_it(tiny):
    lp = nemotron_h.init_params(jax.random.PRNGKey(3), tiny)["layers"][1]
    step = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert (step >= 1e-4 * 0.999).all() and (step <= 0.1 * 1.001).all()
    rate = np.exp(np.asarray(lp["A_log"]))
    assert (rate >= 1).all() and (rate <= 16).all()
    assert (np.asarray(lp["D"]) == 1).all()
    assert np.abs(np.asarray(lp["conv_w"])).max() <= 0.5
    assert lp["conv_w"].shape == (4, 64 + 2 * 2 * 16)
    assert lp["in_w"].shape == (64, 2 * 64 + 2 * 2 * 16 + 8)


def test_published_sizes_count_the_parameters_of_the_cut():
    """ISSUE 48's arithmetic: a quarter of the heads, 8 of 512 experts, an
    eighth of the vocabulary, one period and the module."""
    cfg = nemotron_h.nemotron_3_super_120b_a12b(
        pattern="EMEMEMEMEM*", vocab_size=16384, mamba_heads=32,
        mamba_groups=2, num_heads=8, num_kv_heads=1, experts_held=(0, 8))
    shapes = jax.eval_shape(
        lambda: nemotron_h.init_params(jax.random.PRNGKey(0), cfg))

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    assert round(count(shapes["layers"][1]) / 1e6, 2) == 27.41     # M
    assert round(count(shapes["layers"][10]) / 1e6, 2) == 9.44     # *
    assert round(count(shapes["layers"][0]) / 1e6, 2) == 98.57     # E
    assert round(count(shapes["mtp"]) / 1e6, 1) == 141.6
    assert count(shapes) == 915_161_056
    whole = nemotron_h.nemotron_3_super_120b_a12b()
    assert whole.num_layers == 88 and whole.pattern.count("M") == 40 \
        and whole.pattern.count("E") == 40 and whole.pattern.count("*") == 8
    assert [i for i in range(78) if whole.pattern[i:i + 11]
            == "EMEMEMEMEM*"] == [26, 37, 48, 59]
    assert whole.inner == 8192 and whole.inner // whole.mamba_groups == 1024
    assert cfg.inner // cfg.mamba_groups == 1024          # whole norm groups
    specs = nemotron_h.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree.structure(shapes)


def parts_of(params, cfg, batch):
    return nemotron_h.stages(params, cfg, batch["input_ids"],
                             next_ids=batch["labels"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_loss_parts_choices_and_every_gradient_match_the_reference(tiny,
                                                                   seed):
    """Float32 program against the reference's own pass: the two-term loss,
    each of the 11 parts ``Decoder.stages`` hands on (the embedding, five
    layers, the final states, the module's merged state, its two layers, its
    final states), the routers' choices, every parameter's gradient."""
    params, batch = seeded(tiny, seed)
    config = reference_config(tiny)
    with jax.default_matmul_precision("highest"):
        loss = nemotron_h.lm_loss(params, tiny, batch)
        parts, aux = parts_of(params, tiny, batch)
        want_loss, want_parts = reference.loss_and_outputs(params, config,
                                                           batch)
        grads = jax.grad(nemotron_h.lm_loss)(params, tiny, batch)
        want_grads = jax.grad(reference.loss)(params, config, batch)
    assert abs(float(loss) - float(want_loss)) < 2e-6 * float(want_loss)
    assert parts.shape == (11, 2, 40, 64) == want_parts.shape
    parts = np.asarray(parts, np.float32)
    norms = np.sqrt(np.sum(np.square(parts), axis=(1, 2, 3), keepdims=True))
    for i, (got, want) in enumerate(zip(parts / norms, want_parts)):
        assert relative_error(got, want) < 2e-5, f"part {i}"
    # the choices, against the reference's own top-k of its own scores
    choice = np.asarray(aux["choice"]).reshape(3, 2, 40, -1)
    held = dict(batch, program_choice=choice)
    again, _ = reference.loss_and_outputs(params, config, held)
    assert float(again) == pytest.approx(float(want_loss), rel=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(got).any(), name     # outside the gradient
        else:
            assert relative_error(got, want) < 2e-4, name


def test_the_two_terms_are_the_loss_and_leave_the_step_as_counters(tiny):
    params, batch = seeded(tiny, 0)
    loss, (counts, terms) = nemotron_h.DECODER._loss_and_counts(
        params, tiny, batch)
    assert counts.shape == (3, 16) and terms.shape == (2,)
    assert float(loss) == pytest.approx(
        float(terms[0] + tiny.mtp_weight * terms[1]), rel=1e-6)
    # without the module: the first term alone, and one result
    bare = dataclasses.replace(tiny, mtp_pattern="")
    plain = {k: v for k, v in params.items() if k != "mtp"}
    alone, only = nemotron_h.DECODER._loss_and_counts(plain, bare, batch)
    assert float(alone) == pytest.approx(float(terms[0]), rel=1e-6)
    assert only.shape == (2, 16)
    assert nemotron_h.stages(plain, bare, batch["input_ids"])[0].shape[0] == 7
    assert float(terms[1]) != float(terms[0])


def test_bfloat16_program_is_within_reach_of_the_reference():
    """The program as the cell runs it (bfloat16 activations) against the
    reference's parts, each on the program's state before it: under the
    committed limits; every part in 4 stored bits is over them."""
    cfg = nemotron_h.nemotron_h_tiny(experts_held=(4, 4))
    params, batch = seeded(cfg, 0, seq=80)
    config = reference_config(cfg)
    parts, aux = parts_of(params, cfg, batch)
    assert parts.dtype == jnp.bfloat16 and parts.shape[0] == 11
    sample = dict(batch, program_stream=np.asarray(parts),
                  program_choice=np.asarray(aux["choice"]).reshape(
                      3, 2, 80, -1))
    parts = np.asarray(parts, np.float32)
    got = parts / np.sqrt(np.sum(np.square(parts), axis=(1, 2, 3),
                                 keepdims=True))
    want_loss, want = reference.loss_and_outputs(params, config, sample)
    sound = relative_error(got, want)
    assert sound < reference.TOLERANCE["outputs"]
    loss = nemotron_h.lm_loss(params, cfg, batch)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) \
        < reference.TOLERANCE["loss"]
    _, low = reference.loss_and_outputs(params, config, sample, state_bits=4)
    assert relative_error(low, want) > reference.TOLERANCE["outputs"]
    assert relative_error(low, want) > 3 * sound
    _, same = reference.loss_and_outputs(params, config, sample,
                                         state_bits=7)
    assert relative_error(same, want) < reference.TOLERANCE["outputs"]


def test_routing_stats_count_over_every_router_the_module_s_last(tiny):
    params, batch = seeded(tiny, 1)
    counts, choice = nemotron_h.routing_stats(params, tiny, batch,
                                              choices=True)
    assert counts.shape == (3, 16) and choice.shape == (3, 80, 4)
    assert (counts.sum(axis=1) == 4 * 80).all()
    _, aux = parts_of(params, tiny, batch)
    assert np.array_equal(counts, np.asarray(aux["counts"]))


def test_train_step_lowers_the_loss_and_moves_every_selection_bias():
    """Through ``Decoder.make_train_step``: the loss falls, the three
    routers' biases move, the module's among them, by the sign rule on
    the counts the step hands out, and the step's aux is the counts then the
    two cross-entropies."""
    cfg = nemotron_h.nemotron_h_tiny(experts_held=(4, 4))
    init_fn, step_fn = nemotron_h.make_train_step(
        cfg, pt.optimizer.Adam(learning_rate=1e-3), one_device())
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = nemotron_h.synthetic_batch(cfg, 2, 40)
    before = [np.asarray(lp["router_bias"]) for lp
              in params["layers"] + params["mtp"]["layers"]
              if "router_bias" in lp]
    losses = []
    for _ in range(4):
        loss, params, opt_state = step_fn(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    counts, terms = (np.asarray(a) for a in step_fn.aux)
    assert counts.shape == (3, 16) and terms.shape == (2,)
    assert losses[-1] == pytest.approx(terms[0] + cfg.mtp_weight * terms[1],
                                       rel=1e-5)
    after = [np.asarray(lp["router_bias"]) for lp
             in params["layers"] + params["mtp"]["layers"]
             if "router_bias" in lp]
    assert len(after) == 3
    for was, now in zip(before, after):
        assert np.abs(now - was).max() == pytest.approx(4 * cfg.bias_rate,
                                                        rel=1e-3)
    # the rule on a step's counts, the module's router by the last row
    moved = lm_trainer.move_biases(cfg, params, counts)
    then = [np.asarray(lp["router_bias"]) for lp
            in moved["layers"] + moved["mtp"]["layers"]
            if "router_bias" in lp]
    for row, now, later in zip(counts, after, then):
        assert np.allclose(later - now,
                           cfg.bias_rate * np.sign(row.mean() - row))


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------
WHOLE = dict(hidden=64, mamba_heads=8, mamba_head_dim=8, mamba_groups=4,
             state_size=16, chunk_size=16, num_heads=8, num_kv_heads=2,
             head_dim=16, num_experts=16, experts_per_token=4,
             latent_size=32, expert_width=48, shared_width=96,
             dtype=jnp.float32)


def columns(ranges):
    return np.concatenate([np.arange(lo, hi) for lo, hi in ranges])


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """What the chips that share a layer compute, each from its own part of
    the parameters through the program's mixer, sums to what the uncut
    reference gives for the whole layer: the four head shares of a Mamba
    layer (2 of 8 heads with 1 of 4 B/C groups: a whole norm group) and of
    an attention layer (2 of 8 query heads with the key/value head they
    read), and the eight expert shares of a LatentMoE layer (2 of 16
    experts) with the shared expert, which every chip computes alike, counted
    once, and the latent's projections on every chip."""
    whole = nemotron_h.nemotron_h_tiny(pattern=kind, mtp_pattern="", **WHOLE)
    lp = nemotron_h.init_params(jax.random.PRNGKey(5), whole)["layers"][0]
    lp = {k: v + 0.2 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
          if k in ("norm_g", "D", "conv_b") else v
          for i, (k, v) in enumerate(sorted(lp.items()))}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
    config = reference_config(whole)
    with jax.default_matmul_precision("highest"):
        if kind == "M":
            want = jnp.stack([reference._mamba(lp, row, config) for row in x])
            share = dataclasses.replace(whole, mamba_heads=2, mamba_groups=1)
            inner, bc = 64, 64

            def part(k):
                heads = (2 * k * 8, 2 * (k + 1) * 8)      # its 16 channels
                group = (k * 16, (k + 1) * 16)
                conv = columns([heads, (inner + group[0], inner + group[1]),
                                (inner + bc + group[0],
                                 inner + bc + group[1])])
                cols = np.concatenate([
                    columns([heads]), inner + conv,
                    2 * inner + 2 * bc + np.arange(2 * k, 2 * k + 2)])
                mine = slice(2 * k, 2 * k + 2)
                return {"in_w": lp["in_w"][:, cols],
                        "conv_w": lp["conv_w"][:, conv],
                        "conv_b": lp["conv_b"][conv],
                        "dt_bias": lp["dt_bias"][mine],
                        "A_log": lp["A_log"][mine], "D": lp["D"][mine],
                        "norm_g": lp["norm_g"][heads[0]:heads[1]],
                        "out_w": lp["out_w"][heads[0]:heads[1]]}

            got = sum(nemotron_h._mamba(part(k), x, share) for k in range(4))
        elif kind == "*":
            want = jnp.stack([reference._attention(lp, row, config)
                              for row in x])
            share = dataclasses.replace(whole, num_heads=2, num_kv_heads=1)

            def part(k):
                q = slice(32 * k, 32 * (k + 1))
                kv = slice(16 * (k // 2), 16 * (k // 2 + 1))
                return {"q_w": lp["q_w"][:, q], "k_w": lp["k_w"][:, kv],
                        "v_w": lp["v_w"][:, kv], "o_w": lp["o_w"][q]}

            got = sum(nemotron_h._attention(part(k), x, share)
                      for k in range(4))
        else:
            want = jnp.stack([reference._experts(lp, row, config)[0]
                              for row in x])

            def part(k, shared):
                out = dict(lp, w_up=lp["w_up"][2 * k:2 * k + 2],
                           w_down=lp["w_down"][2 * k:2 * k + 2])
                if not shared:
                    del out["shared_up"], out["shared_down"]
                return moe.dropless_moe_ffn(
                    out, x, 4, scoring=whole.scoring, held=(2 * k, 2),
                    activation="relu2")[0]

            got = part(0, True) + sum(part(k, False) for k in range(1, 8))
    assert relative_error(got, want) < 1e-5
    # and one share alone is not the layer
    assert relative_error(got - (nemotron_h._mamba(part(0), x, share)
                                 if kind == "M" else
                                 nemotron_h._attention(part(0), x, share)
                                 if kind == "*" else part(0, False)),
                          want) > 1e-4


# ---------------------------------------------------------------------------
# what this PR leaves alone
# ---------------------------------------------------------------------------
#: sha256 of ``lowered.as_text()`` of ``deepseek_v3_tiny``'s train step
#: (experts 4 to 7 held, Adam, batch 2 x 48, one device) on PR 47's tree, the parent of
#: the PR that brought the one-part layers, the plain experts, the latent and
#: the MTP module to ``lm_trainer.py`` and ``parallel/moe.py``: the other
#: decoders reach none of it. A PR that changes Kanana-2's step on purpose
#: recomputes it (the test prints the text's hash); PR 52 did, for the
#: router's selection and counts (``moe._biased_top_k``, ``moe._counts``).
KANANA_TINY_STEP = \
    "fa955133ffabe0d0bcf15166a4549ba903d4fb484a1dc0e3e5ee3fe6962f060c"


def test_a_decoder_without_the_new_mechanisms_lowers_as_before():
    cfg = deepseek_v3.deepseek_v3_tiny(experts_held=(4, 4))
    init_fn, step_fn = deepseek_v3.make_train_step(
        cfg, pt.optimizer.Adam(learning_rate=1e-3), one_device())
    params, opt_state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = deepseek_v3.synthetic_batch(cfg, 2, 48)
    text = step_fn.jitted.lower(params, opt_state,
                                step_fn.place(batch)).as_text()
    # four results: the loss, the parameters, Adam's state, the counts
    assert len(step_fn.jitted.eval_shape(params, opt_state,
                                         step_fn.place(batch))) == 4
    assert hashlib.sha256(text.encode()).hexdigest() == KANANA_TINY_STEP


#: the same of ``kimi_linear_tiny``'s train step (experts 4 to 7 held, Adam,
#: batch 2 x 48, one device) on PR 48's tree, the parent of the PR that gave
#: the state-space scan its Mosaic body: the delta-rule decoders share
#: ``blocks.recomputed``, whose policy gained a name their traces do not
#: hold, and the registry, which gained a kernel they never dispatch; renewed
#: by PR 52 with the router's text, as Kanana-2's.
KIMI_TINY_STEP = \
    "1350dbd9f0a63eb8a6bddf33695dcf54db57cefc371c5731f3de401d14f473d9"


def test_a_delta_rule_decoder_lowers_as_before_the_scan_s_kernels():
    from paddle_tpu.models import kimi_linear
    cfg = kimi_linear.kimi_linear_tiny(experts_held=(4, 4))
    init_fn, step_fn = kimi_linear.make_train_step(
        cfg, pt.optimizer.Adam(learning_rate=1e-3), one_device())
    params, opt_state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = kimi_linear.synthetic_batch(cfg, 2, 48)
    text = step_fn.jitted.lower(params, opt_state,
                                step_fn.place(batch)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == KIMI_TINY_STEP


# ---------------------------------------------------------------------------
# the set-up: the scan's kernels are traced for the step, not for every layer
# ---------------------------------------------------------------------------
def test_a_step_traces_the_scan_s_kernel_bodies_once_each(monkeypatch):
    """Three Mamba layers, each recomputed in the backward pass, with the
    Pallas bodies forced: tracing the step enters ``ssd_fwd``'s body once
    and ``ssd_bwd``'s once. The calls are jitted functions the layers
    share, entered through ``registry.traced_once`` so that the pass and
    the checkpoint's JVP share a trace context (``tests/test_kimi_linear.py``
    keeps the same count for the delta rule): a body traced and lowered to
    Mosaic anew a call site is what PR 49's set-up paid. The gradient holds
    the forward kernel once a layer: ``blocks.recomputed`` keeps what it
    hands the backward one."""
    from paddle_tpu.ops import pallas as plk
    from paddle_tpu.ops.pallas import ssd as ssd_kernels
    entered = {}
    for name in ("_fwd_kernel", "_bwd_kernel"):
        def enter(*args, _body=getattr(ssd_kernels, name), _name=name, **kw):
            entered[_name] = entered.get(_name, 0) + 1
            return _body(*args, **kw)

        monkeypatch.setattr(ssd_kernels, name, enter)
    # heads of 64 under groups with a state of 128: shapes the blocks tile
    cfg = nemotron_h.nemotron_h_tiny(
        pattern="MEMM*", mamba_heads=4, mamba_head_dim=64, mamba_groups=2,
        state_size=128, experts_held=(4, 4))
    init_fn, step_fn = nemotron_h.make_train_step(
        cfg, pt.optimizer.Adam(1e-3), one_device())
    params, opt_state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = jax.eval_shape(step_fn.place,
                           nemotron_h.synthetic_batch(cfg, 1, 64))
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
    assert calls["ssd_fwd"] == 3 == calls["ssd_bwd"], calls


def test_blocks_rms_normalize_takes_a_gain_a_group():
    """The gated norm's view: [.., groups, channels] against [groups,
    channels] gains is the norm a group."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 2, 8))
    gain = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 8))
    got = blocks.rms_normalize(x, gain, 1e-5)
    want = jnp.stack([blocks.rms_normalize(x[:, :, g], gain[g], 1e-5)
                      for g in range(2)], axis=2)
    assert relative_error(got, want) < 1e-6
