"""Laguna on the training path, against the plain reference of the benchmark.

``chipbench/reference/laguna.py`` computes the dense [S, S] scores of every
layer, its own rotary tables and its own loop over the held experts, in
float32 ``jax.numpy``, and shares no code with ``paddle_tpu``; it reads the
program's parameter tree by its key names. Here, on the CPU at
``laguna_tiny``'s sizes and seeded random weights: the two rotary laws, then
loss, every part of the forward pass and the gradient of every parameter
leaf in float32 on two seeds, the program's bfloat16 within reach of them,
the expert layer's share of the experts against the uncut layer, and the
counters the benchmark reads.
"""

import collections
import dataclasses
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import blocks
from paddle_tpu.models import laguna as lg
from paddle_tpu.parallel import moe

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("chipbench/reference/laguna.py", "reference_laguna")


def rope_entry(law):
    entry = {"rope_theta": law.theta,
             "partial_rotary_factor": law.rotary_factor,
             "rope_type": "default"}
    if law.yarn is not None:
        factor, original, fast, slow = law.yarn
        entry.update(rope_type="yarn", factor=factor, beta_fast=fast,
                     beta_slow=slow, attention_factor=law.attention_factor,
                     original_max_position_embeddings=original)
    return entry


def reference_config(cfg):
    """The keys the reference reads of a configuration file."""
    first, held = cfg.experts_held or (0, cfg.num_experts)
    return {
        "head_dim": cfg.head_dim, "num_key_value_heads": cfg.kv_heads,
        "num_attention_heads_per_layer": list(cfg.heads),
        "layer_types": list(cfg.layer_types),
        "sliding_window": cfg.window,
        "rope_parameters": {lg.FULL: rope_entry(cfg.rope_full),
                            lg.SLIDING: rope_entry(cfg.rope_sliding)},
        "mlp_layer_types": ["dense" if i in cfg.dense_layers else "sparse"
                            for i in range(cfg.num_layers)],
        "rms_norm_eps": cfg.rms_eps,
        "num_experts_per_tok": cfg.experts_per_token,
        "moe_routed_scaling_factor": cfg.routed_scale,
        "experts_held": [first, held]}


def relative_error(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def over_norms(parts):
    parts = parts.astype(jnp.float32)
    return parts / jnp.sqrt(jnp.sum(jnp.square(parts), axis=(1, 2, 3),
                                    keepdims=True))


def seeded(cfg, seed=0, rows=2, seq=80):
    """Parameters with gains and the selection bias away from their starts,
    so that a norm or a bias applied in the wrong place shows; 80 positions:
    three windows of 24 and a rest."""
    params = lg.init_params(jax.random.PRNGKey(seed), cfg)

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("_g']") or "router_bias" in name:
            return a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)) \
                .reshape(a.shape)
        return a

    params = jax.tree_util.tree_map_with_path(moved, params)
    return params, lg.synthetic_batch(cfg, rows, seq, seed=seed)


@pytest.fixture(scope="module")
def tiny():
    return lg.laguna_tiny(experts_held=(4, 4), dtype=jnp.float32)


# ---------------------------------------------------------------------------
# rotary positions: the two laws, on part of a head
# ---------------------------------------------------------------------------
def test_the_attention_factor_is_a_tenth_of_ln_64_plus_one():
    assert lg.laguna_xs2().rope_full.attention_factor \
        == pytest.approx(1.4158883083359672, rel=1e-15)
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672,
                                                   rel=1e-15)
    assert reference.attention_factor(64) == pytest.approx(
        1.4158883083359672, rel=1e-15)


def test_yarn_keeps_the_fast_channels_and_divides_the_slow_ones_by_64():
    """The published full-layer law: 32 rotated pairs, the correction range
    of beta_fast 64 and beta_slow 1 at 4096 positions is channels 5 to 16."""
    inv_freq = np.asarray(blocks.yarn_inv_freq(64, 500000.0, 64.0, 4096,
                                               64.0, 1.0))
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    low, high = 5, 16
    np.testing.assert_allclose(inv_freq[:low + 1], plain[:low + 1],
                               rtol=1e-6)
    np.testing.assert_allclose(inv_freq[high:], plain[high:] / 64, rtol=1e-6)
    between = inv_freq[low + 1:high] / plain[low + 1:high]
    assert (np.diff(between) < 0).all() and between[0] < 1 \
        and between[-1] > 1 / 64
    # the reference's own tables are the same law
    rope = rope_entry(lg.laguna_xs2().rope_full)
    cos, sin = reference.rotary_tables(rope, 128, 300)
    got_cos, got_sin = lg.laguna_xs2().rope_full.angles(300, 128)
    assert cos.shape == got_cos.shape == (300, 32)
    np.testing.assert_allclose(np.asarray(got_cos), np.asarray(cos),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_sin), np.asarray(sin),
                               atol=1e-4)
    # position 0 turns nothing: cos is the factor itself
    np.testing.assert_allclose(np.asarray(got_cos[0]), 1.4158883083359672,
                               rtol=1e-6)


def test_partial_rotation_leaves_the_last_channels_untouched():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 128))
    cos, sin = lg.laguna_xs2().rope_full.angles(40, 128)
    turned = blocks.apply_rope(x, cos, sin)
    assert np.array_equal(np.asarray(turned[..., 64:]),
                          np.asarray(x[..., 64:]))
    assert not np.allclose(np.asarray(turned[:, 1:, :, :64]),
                           np.asarray(x[:, 1:, :, :64]))
    # the rotated pairs are (i, i + 32) of the first 64
    a, b = np.asarray(x[..., :32]), np.asarray(x[..., 32:64])
    c, s = np.asarray(cos)[None, :, None], np.asarray(sin)[None, :, None]
    np.testing.assert_allclose(np.asarray(turned[..., :32]), a * c - b * s,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(turned[..., 32:64]),
                               b * c + a * s, rtol=1e-5, atol=1e-6)


def test_the_plain_law_on_a_whole_head_is_what_it_was():
    """``rope_angles`` and ``apply_rope`` as OLMoE calls them."""
    cos, sin = blocks.rope_angles(50, 16, 10000.0)
    inv = 10000.0 ** (-np.arange(0, 16, 2) / 16)
    np.testing.assert_allclose(np.asarray(cos),
                               np.cos(np.arange(50)[:, None] * inv),
                               atol=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 50, 2, 16))
    turned = np.asarray(blocks.apply_rope(x, cos, sin))
    a, b = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    c, s = np.asarray(cos)[None, :, None], np.asarray(sin)[None, :, None]
    np.testing.assert_allclose(turned, np.concatenate(
        [a * c - b * s, b * c + a * s], axis=-1), rtol=1e-5, atol=1e-6)
    assert lg.RotaryLaw().angles(50, 16)[0].shape == (50, 8)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_the_tiny_preset_has_every_kind_of_layer(tiny):
    assert list(tiny.layer_types[:5]) == [lg.FULL] + [lg.SLIDING] * 3 \
        + [lg.FULL]
    params = lg.init_params(jax.random.PRNGKey(0), tiny)
    assert "ffn_gate" in params["layers"][0]
    for layer, lp in enumerate(params["layers"]):
        heads = 12 if layer in (0, 4) else 16
        assert lp["q_w"].shape == (64, heads * 16)
        assert lp["k_w"].shape == lp["v_w"].shape == (64, 2 * 16)
        assert lp["g_w"].shape == (64, heads)        # one gate a head
    for lp in params["layers"][1:]:
        assert lp["router_w"].shape == (64, 16)      # routes over all 16
        assert lp["w_gate"].shape == (4, 64, 32)     # holds 4 of them
        assert lp["shared_down"].shape == (32, 64)
    specs = lg.param_specs(tiny)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(s, type(
                specs["embed"]))))


def test_published_sizes_count_the_parameters_of_the_cut():
    """One of 8 chips: 32 experts a layer, an eighth of the vocabulary, the
    dense layer and the four after it: 691.6 M parameters (ISSUE 33)."""
    cfg = lg.laguna_xs2(num_layers=5, vocab_size=12544, experts_held=(0, 32))
    shapes = jax.eval_shape(lambda: lg.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    per_layer = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(lp))
                 for lp in shapes["layers"]]
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == 691_624_960
    # full + dense, sliding + experts three times, full + experts
    assert per_layer == [79_794_176, 142_217_472, 142_217_472, 142_217_472,
                         133_796_096]
    assert shapes["layers"][1]["g_w"].shape == (2048, 64)
    assert shapes["layers"][4]["q_w"].shape == (2048, 48 * 128)


def test_a_configuration_says_what_it_cannot_be():
    with pytest.raises(ValueError, match="multiple"):
        lg.laguna_tiny(heads=(12, 15, 16, 16, 12))
    with pytest.raises(ValueError, match="fewer entries"):
        lg.laguna_tiny(layer_types=(lg.FULL,) * 3)


@pytest.mark.parametrize("seed", [0, 1])
def test_loss_outputs_and_every_gradient_match_the_reference(tiny, seed):
    params, batch = seeded(tiny, seed)
    config = reference_config(tiny)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: lg.lm_loss(p, tiny, batch))(params)
        parts, aux = lg.stages(params, tiny, batch["input_ids"])
        hidden = lg.forward(params, tiny, batch["input_ids"])
        logits = hidden @ params["head_w"]
    want_loss, want_parts = reference.loss_and_outputs(params, config, batch)
    assert parts.shape == (2 * tiny.num_layers + 2, *batch["input_ids"].shape,
                           tiny.hidden)
    assert relative_error(loss, want_loss) < 1e-5
    # every part of the pass, each over its norm: the embedding, the stream
    # after each mixer and feed-forward, the final normed hidden states
    assert relative_error(over_norms(parts), want_parts) < 1e-4
    assert relative_error(parts[-1], hidden) == 0
    # the logits, from the reference's own final states
    want_hidden = want_parts[-1] * jnp.linalg.norm(parts[-1])
    assert relative_error(logits, want_hidden @ params["head_w"]) < 1e-4
    counts, choice = lg.routing_stats(params, tiny, batch, choices=True)
    assert (np.asarray(aux["counts"]) == counts).all()
    assert (np.asarray(aux["choice"]) == choice).all()
    want = jax.grad(lambda p: reference.loss(p, config, batch))(params)
    assert relative_error(reference.loss(params, config, batch),
                          want_loss) < 1e-6
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:           # outside the gradient, both
            assert not np.asarray(got).any() and not np.asarray(ref).any()
            continue
        assert relative_error(got, ref) < 2e-3, name


def test_bfloat16_program_is_within_reach_of_the_reference(tiny):
    """The program's own dtype, under its own admissible routing, each part
    held to float32 on the program's own state before it. A fault in one
    part (the gate left off the second sliding layer) reads several times
    the program's distance; so do every part's states in 4 stored bits, and
    a router that chooses by bfloat16 scores fails the routing check."""
    cfg = dataclasses.replace(tiny, dtype=jnp.bfloat16)
    params, batch = seeded(cfg, seed=1)
    config = reference_config(cfg)
    parts, aux = lg.stages(params, cfg, batch["input_ids"])

    def sample_of(parts, aux):
        return dict(batch, program_stream=np.asarray(parts),
                    program_choice=np.asarray(aux["choice"]).reshape(
                        4, *batch["input_ids"].shape, -1))

    sample = sample_of(parts, aux)
    want_loss, want_parts = reference.loss_and_outputs(params, config, sample)
    assert np.isfinite(np.asarray(want_parts)).all()     # admissible
    assert relative_error(lg.lm_loss(params, cfg, batch), want_loss) < 2e-3
    sound = relative_error(over_norms(parts), want_parts)
    assert sound < reference.TOLERANCE["outputs"]
    # end to end the same program is several times further from float32
    end_to_end = reference.loss_and_outputs(params, config, batch)[1]
    assert relative_error(over_norms(parts)[-1], end_to_end[-1]) \
        > 1.5 * sound
    # the controls: a precision below the configuration's
    _, low = reference.loss_and_outputs(params, config, sample, state_bits=4)
    assert relative_error(low, want_parts) > reference.TOLERANCE["outputs"]
    _, same = reference.loss_and_outputs(params, config, sample, state_bits=7)
    assert relative_error(same, want_parts) < reference.TOLERANCE["outputs"]
    # (160 tokens a layer here: 5 stored bits show what bfloat16's 7 show
    # on the cell's 16 384, PERF.md section 6, PR 33)
    _, routed = reference.loss_and_outputs(params, config, sample,
                                           router_bits=5)
    assert np.isnan(np.asarray(routed)).all()           # a wrong router
    # a fault: no gate on layer 2's attention output (sigmoid -> 1)
    wrong = jax.tree.map(lambda a: a, params)
    wrong["layers"][2]["g_w"] = jnp.zeros_like(params["layers"][2]["g_w"]) \
        + 100.0 * jnp.sign(jnp.sum(params["layers"][2]["g_w"]))
    faulty, aux = lg.stages(wrong, cfg, batch["input_ids"])
    _, want_parts = reference.loss_and_outputs(params, config,
                                               sample_of(faulty, aux))
    assert relative_error(over_norms(faulty), want_parts) > 3 * sound


def test_routing_stats_count_over_every_expert_of_the_router(tiny):
    params, batch = seeded(tiny)
    counts, choice = lg.routing_stats(params, tiny, batch, choices=True)
    assert counts.shape == (4, 16) and choice.shape == (4, 160, 4)
    assert (counts.sum(axis=1) == 4 * 160).all()
    assert choice.max() > 7                 # experts this chip does not hold
    held = counts[:, 4:8].sum(axis=1)
    assert ((0 < held) & (held < 4 * 160)).all()


def test_train_step_lowers_the_loss_and_moves_the_selection_bias(tiny):
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = lg.make_train_step(tiny, pt.optimizer.Adam(1e-3), mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = lg.synthetic_batch(tiny, 2, 48)
    losses = []
    for _ in range(4):
        before = np.asarray(params["layers"][1]["router_bias"])
        loss, params, opt_state = step_fn(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses
    counts = np.asarray(step_fn.aux[0])              # the last step's load
    assert counts.shape == (4, 16) and (counts.sum(axis=1) == 4 * 96).all()
    moved = np.asarray(params["layers"][1]["router_bias"]) - before
    want = tiny.bias_rate * np.sign(counts[0].mean() - counts[0])
    np.testing.assert_allclose(moved, want, atol=1e-7)
    from paddle_tpu.models import lm_trainer
    assert lg.make_train_step.__func__ is lm_trainer.Decoder.make_train_step


@pytest.mark.parametrize("layers", [2, 5])
def test_a_step_traces_a_flash_call_once_a_layer_type(tiny, layers,
                                                      monkeypatch):
    """The flash calls are jitted functions of their own: a layer type's
    layers share one trace of each, however many they are (PERF.md section 6,
    PR 29: traced anew a layer, a Pallas call costs seconds of set-up)."""
    import importlib
    from paddle_tpu.ops import pallas as plk
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    cfg = dataclasses.replace(tiny, num_layers=layers, window=512)
    traced = []
    kernel = fa._flash_fwd_kernel

    def counted(*a, **kw):
        traced.append(kw.get("window"))
        return kernel(*a, **kw)

    monkeypatch.setattr(fa, "_flash_fwd_kernel", counted)
    fa._flash_fwd.clear_cache()
    params, batch = seeded(cfg, rows=1, seq=1024)
    with plk.override("on"):
        jax.jit(lambda p: lg.lm_loss(p, cfg, batch)).lower(params)
    fa._flash_fwd.clear_cache()
    # 1024 positions and a window of 512, so that `auto` takes the kernels
    # in both layer types (``blocks.FLASH_FROM``): a full layer and, from the
    # second layer on, a sliding one: one trace each whatever the depth
    assert sorted(traced, key=str) == [512, None]


# ---------------------------------------------------------------------------
# recomputation: a mixer keeps its flash call's o and lse, by name
# ---------------------------------------------------------------------------
def equations(jaxpr, found=None):
    """How often each primitive stands in ``jaxpr`` and the jaxprs inside
    it; a ``pallas_call`` under its kernel's name."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        pallas = eqn.primitive.name == "pallas_call"
        found[eqn.params["name"] if pallas else eqn.primitive.name] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            equations(inner, found)
    return found


@pytest.fixture(scope="module")
def both_kinds(tiny):
    """Two full layers and three sliding ones as the cell has them, at 1024
    positions and a window of 512 so that ``auto`` takes the kernels in
    both; every parameter has a gradient."""
    cfg = dataclasses.replace(tiny, num_layers=5, window=512)
    return (cfg,) + seeded(cfg, rows=1, seq=1024)


def plain_checkpoint(monkeypatch):
    """The mixers as they were: everything formed again."""
    monkeypatch.setattr(blocks, "recomputed", jax.checkpoint)


@pytest.mark.parametrize("mixers", ["kept_by_name", "plain_checkpoint"])
def test_a_gradient_holds_a_flash_forward_once_a_layer(both_kinds, mixers,
                                                       monkeypatch):
    """Under ``blocks.recomputed`` the recomputation has no use for the
    forward kernel, whose o and lse are residuals of the checkpoint: the
    gradient's jaxpr holds ``flash_fwd`` once a full layer and
    ``flash_fwd_window`` once a sliding one, where the plain
    ``jax.checkpoint`` held each twice (53.8 + 17.2 ms of the cell's 701.8 ms
    step: PERF.md section 6, PR 42). The backward kernels are once a layer
    either way."""
    from paddle_tpu.ops import pallas as plk
    cfg, params, batch = both_kinds
    if mixers == "plain_checkpoint":
        plain_checkpoint(monkeypatch)
    with plk.override("on"):
        found = equations(jax.make_jaxpr(jax.grad(
            lambda p: lg.lm_loss(p, cfg, batch)))(params).jaxpr)
    forwards = 1 if mixers == "kept_by_name" else 2
    assert cfg.layer_types[:cfg.num_layers].count(lg.FULL) == 2
    assert {k: found[k] for k in ("flash_fwd", "flash_bwd",
                                  "flash_fwd_window", "flash_bwd_window")} \
        == {"flash_fwd": 2 * forwards, "flash_bwd": 2,
            "flash_fwd_window": 3 * forwards, "flash_bwd_window": 3}


def test_keeping_the_flash_outputs_changes_no_bit_of_a_gradient(both_kinds,
                                                                monkeypatch):
    """The kept arrays are the ones the second forward would have made:
    loss and every gradient leaf equal those of the plain ``jax.checkpoint``
    bit for bit (the Pallas bodies in interpreter mode)."""
    from paddle_tpu.ops import pallas as plk
    cfg, params, batch = both_kinds

    def loss_and_grads():
        with plk.override("on"):
            return jax.jit(jax.value_and_grad(
                lambda p: lg.lm_loss(p, cfg, batch)))(params)

    kept = loss_and_grads()
    plain_checkpoint(monkeypatch)
    plain = loss_and_grads()
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), kept, plain)))
    assert float(kept[0]) > 0 and all(
        float(jnp.abs(lp[name]).max()) > 0 for lp in kept[1]["layers"]
        for name in ("q_w", "k_w", "v_w", "g_w", "o_w", "ln1_g"))


def test_without_a_kernel_in_the_trace_the_helper_is_the_plain_checkpoint(
        both_kinds, monkeypatch):
    """The reference bodies (the CPU's selection) name nothing, so the
    policy has nothing to keep: the gradient's jaxpr holds equation for
    equation what the plain ``jax.checkpoint`` gives, and no name."""
    cfg, params, batch = both_kinds

    def found():
        return equations(jax.make_jaxpr(jax.grad(
            lambda p: lg.lm_loss(p, cfg, batch)))(params).jaxpr)

    kept = found()
    plain_checkpoint(monkeypatch)
    assert kept == found()
    assert "name" not in kept and "pallas_call" not in kept
    assert kept["remat2"] == 5 + 1          # the mixers, the dense layer


# ---------------------------------------------------------------------------
# the expert layer's share: eighths, as the cell cuts it
# ---------------------------------------------------------------------------
def expert_layer(seed=0, d=32, f=16, experts=16, tokens=96):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    lp = {"router_w": jax.random.normal(ks[0], (d, experts)),
          "router_bias": 0.3 * jax.random.normal(ks[1], (experts,)),
          "w_gate": 0.3 * jax.random.normal(ks[2], (experts, d, f)),
          "w_up": 0.3 * jax.random.normal(ks[3], (experts, d, f)),
          "w_down": 0.3 * jax.random.normal(ks[4], (experts, f, d)),
          "shared_gate": 0.3 * jax.random.normal(ks[5], (d, f)),
          "shared_up": 0.3 * jax.random.normal(ks[6], (d, f)),
          "shared_down": 0.3 * jax.random.normal(ks[7], (f, d))}
    return lp, jax.random.normal(ks[8], (tokens, d))


SCORING = moe.Scoring("sigmoid", renormalize=True, scale=2.5)


def test_the_shares_of_8_chips_add_up_to_the_uncut_layer():
    """The share test at the cell's cut: the experts over 8 chips (here 16
    experts, 2 a chip, where the cell holds 32 of 256). The routed part each
    share computes, summed over the shares, plus what every chip computes
    alike (the shared expert) counted once, is the uncut reference layer. In
    float32, so the experts chosen are the same everywhere."""
    lp, x = expert_layer(seed=1)
    config = {"num_experts_per_tok": 4, "moe_routed_scaling_factor": 2.5,
              "experts_held": [0, 16]}
    with jax.default_matmul_precision("highest"):
        want, _, used, _ = reference._experts(lp, x, config)
        routed = jnp.zeros_like(x)
        rows = 0
        for chip in range(8):
            first = 2 * chip
            share = {k: v[first:first + 2] if k.startswith("w_") else v
                     for k, v in lp.items() if not k.startswith("shared_")}
            part, aux = moe.dropless_moe_ffn(share, x, 4, scoring=SCORING,
                                             held=(first, 2))
            assert aux["counts"].shape == (16,)     # over all the router's
            rows += int(aux["counts"][first:first + 2].sum())
            routed = routed + part
        shared = reference._gated(x, lp["shared_gate"], lp["shared_up"],
                                  lp["shared_down"])
    assert rows == int(used.sum()) == 4 * 96     # every assignment, once
    assert relative_error(routed + shared, want) < 1e-5
    # and one share with its shared expert is the reference given that share
    share = {k: v[6:8] if k.startswith("w_") else v for k, v in lp.items()}
    with jax.default_matmul_precision("highest"):
        got, _ = moe.dropless_moe_ffn(share, x, 4, scoring=SCORING,
                                      held=(6, 2))
        want, *_ = reference._experts(share, x, dict(config,
                                                     experts_held=[6, 2]))
    assert relative_error(got, want) < 1e-5


def test_a_router_at_par_is_not_on_a_pass_s_edge():
    """12.5% of 8 x 16 384 assignments are 16 384 rows, two
    ``HELD_ROW_TILE`` to the row: the layer takes 32 768 rows a pass there
    (one pass up to a share of 25%), and 8192 where the Kimi cell's 8 of
    256 bring 2048."""
    assert 8 * 16384 * 32 // 256 == 2 * moe.HELD_ROW_TILE
    assert moe._held_row_tile(8 * 16384, 32, 256) == 4 * moe.HELD_ROW_TILE
    assert moe._held_row_tile(8 * 8192, 8, 256) == moe.HELD_ROW_TILE
    assert moe._held_row_tile(4 * 160, 4, 16) == 4 * 160   # a tiny layer
