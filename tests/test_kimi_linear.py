"""Kimi Linear on the training path, against the plain reference of the
benchmark.

``chipbench/reference/kimi_linear.py`` runs the delta rule one position a
step and the softmax attention densely, in float32 ``jax.numpy``, and shares
no code with ``paddle_tpu``; it reads the program's parameter tree by its key
names. Here, on the CPU at ``kimi_linear_tiny``'s sizes and seeded random
weights: loss, final hidden states and the gradient of every parameter leaf
in float32, the program's bfloat16 within reach of them, the expert layer's
scoring rule and its share of the experts against the uncut layer, and the
counter the benchmark reads.
"""

import collections
import dataclasses
import importlib
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import kimi_linear as kl
from paddle_tpu.models import olmoe
from paddle_tpu.parallel import moe

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("chipbench/reference/kimi_linear.py", "reference_kimi")


def reference_config(cfg):
    """The keys the reference reads of a configuration file."""
    first, held = cfg.experts_held or (0, cfg.num_experts)
    return {
        "linear_attn_config": {
            "kda_layers": list(cfg.kda_layers),
            "full_attn_layers": list(cfg.full_attn_layers),
            "num_heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim},
        "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "rms_norm_eps": cfg.rms_eps,
        "first_k_dense_replace": cfg.first_dense,
        "num_experts_per_token": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scale,
        "experts_held": [first, held]}


def relative_error(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def seeded(cfg, seed=0, rows=2, seq=80):
    """Parameters with gains and the selection bias away from their starts,
    so that a norm or a bias applied in the wrong place shows; 80 positions:
    one chunk of 64 and 16 left."""
    params = kl.init_params(jax.random.PRNGKey(seed), cfg)

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("_g']") or "router_bias" in name:
            return a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)) \
                .reshape(a.shape)
        return a

    params = jax.tree_util.tree_map_with_path(moved, params)
    return params, kl.synthetic_batch(cfg, rows, seq, seed=seed)


@pytest.fixture(scope="module")
def tiny():
    return kl.kimi_linear_tiny(experts_held=(4, 4), dtype=jnp.float32)


def test_the_tiny_preset_has_every_kind_of_layer(tiny):
    assert [tiny.mixer(i) for i in range(5)] == ["kda"] * 3 + ["mla", "kda"]
    params = kl.init_params(jax.random.PRNGKey(0), tiny)
    assert "ffn_gate" in params["layers"][0]
    for lp in params["layers"][1:]:
        assert lp["router_w"].shape == (64, 16)      # routes over all 16
        assert lp["w_gate"].shape == (4, 64, 32)     # holds 4 of them
        assert lp["shared_down"].shape == (32, 64)
    assert "kva_w" in params["layers"][3] and "A_log" in params["layers"][4]
    specs = kl.param_specs(tiny)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(s, type(
                specs["embed"]))))


def test_published_sizes_count_the_parameters_of_the_cut():
    """One of 32 chips: 8 experts a layer, an eighth of the vocabulary, the
    dense layer and the four after it: 602.4 M parameters (ISSUE 30)."""
    cfg = kl.kimi_linear_48b_a3b(num_layers=5, vocab_size=20480,
                                 experts_held=(0, 8))
    shapes = jax.eval_shape(lambda: kl.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    per_layer = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(lp))
                 for lp in shapes["layers"]]
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == 602_434_432
    # KDA + dense, KDA + experts twice, MLA + experts, KDA + experts
    assert [round(n / 1e6, 1) for n in per_layer] \
        == [103.2, 103.8, 103.8, 93.4, 103.8]


def test_loss_outputs_and_every_gradient_match_the_reference(tiny):
    params, batch = seeded(tiny)
    config = reference_config(tiny)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: kl.lm_loss(p, tiny, batch))(params)
        parts, aux = kl.stages(params, tiny, batch["input_ids"])
    want_loss, want_parts = reference.loss_and_outputs(params, config, batch)
    assert parts.shape == (2 * tiny.num_layers + 2, *batch["input_ids"].shape,
                           tiny.hidden)
    assert relative_error(loss, want_loss) < 1e-5
    # every part of the pass, each over its norm: the embedding, the stream
    # after each mixer and feed-forward, the final normed hidden states
    assert relative_error(reference.over_norms(parts), want_parts) < 1e-4
    assert relative_error(parts[-1],
                          kl.forward(params, tiny, batch["input_ids"])) == 0
    counts, choice = kl.routing_stats(params, tiny, batch, choices=True)
    assert (np.asarray(aux["counts"]) == counts).all()
    assert (np.asarray(aux["choice"]) == choice).all()
    want = jax.grad(
        lambda p: reference.loss_and_outputs(p, config, batch)[0])(params)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:           # outside the gradient, both
            assert not np.asarray(got).any() and not np.asarray(ref).any()
            continue
        assert relative_error(got, ref) < 2e-3, name


def test_bfloat16_program_is_within_reach_of_the_reference(tiny):
    """The program's own dtype, under its own admissible routing, each part
    held to float32 on the program's own state before it: one part's
    rounding, not ten parts' in a row, is what the comparison reads. A
    fault in one part (a decay half as fast in the second KDA layer) reads
    several times that."""
    cfg = dataclasses.replace(tiny, dtype=jnp.bfloat16)
    params, batch = seeded(cfg, seed=1)
    parts, aux = kl.stages(params, cfg, batch["input_ids"])
    sample = dict(batch, program_choice=np.asarray(aux["choice"]).reshape(
        4, *batch["input_ids"].shape, -1), program_stream=np.asarray(
            parts.astype(jnp.float32)))
    want_loss, want_parts = reference.loss_and_outputs(
        params, reference_config(cfg), sample)
    assert np.isfinite(np.asarray(want_parts)).all()     # admissible
    assert relative_error(kl.lm_loss(params, cfg, batch), want_loss) < 2e-3
    sound = relative_error(reference.over_norms(parts), want_parts)
    assert sound < 1e-2
    # end to end the same program is several times further from float32
    end_to_end = reference.loss_and_outputs(
        params, reference_config(cfg), batch)[1]
    assert relative_error(reference.over_norms(parts)[-1],
                          end_to_end[-1]) > 1.5 * sound
    wrong = jax.tree.map(lambda a: a, params)
    wrong["layers"][1]["A_log"] = params["layers"][1]["A_log"] - np.log(2.0)
    faulty, aux = kl.stages(wrong, cfg, batch["input_ids"])
    sample.update(                  # the choices of the pass that is compared
        program_stream=np.asarray(faulty.astype(jnp.float32)),
        program_choice=np.asarray(aux["choice"]).reshape(
            4, *batch["input_ids"].shape, -1))
    _, want_parts = reference.loss_and_outputs(
        params, reference_config(cfg), sample)
    assert relative_error(reference.over_norms(faulty), want_parts) \
        > 3 * sound


def test_routing_stats_count_over_every_expert_of_the_router(tiny):
    params, batch = seeded(tiny)
    counts, choice = kl.routing_stats(params, tiny, batch, choices=True)
    assert counts.shape == (4, 16) and choice.shape == (4, 160, 4)
    assert (counts.sum(axis=1) == 4 * 160).all()
    assert choice.max() > 7                 # experts this chip does not hold
    held = counts[:, 4:8].sum(axis=1)
    assert ((0 < held) & (held < 4 * 160)).all()


def test_train_step_lowers_the_loss(tiny):
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = kl.make_train_step(tiny, pt.optimizer.Adam(1e-3), mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = kl.synthetic_batch(tiny, 2, 48)
    losses = []
    for _ in range(4):
        loss, params, opt_state = step_fn(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses


def test_olmoe_and_kimi_linear_share_one_train_step():
    from paddle_tpu.models import lm_trainer
    # Kimi Linear's is the skeleton's own method; OLMoE's, which hands no
    # counts out, is one hand-over to the function that method calls
    assert kl.make_train_step.__func__ is lm_trainer.Decoder.make_train_step
    assert olmoe.make_train_step.__code__.co_names[:2] == (
        "lm_trainer", "make_train_step")
    assert callable(lm_trainer.make_train_step)


# ---------------------------------------------------------------------------
# the expert layer: its scoring rule, its share
# ---------------------------------------------------------------------------
def expert_layer(seed=0, d=32, f=16, experts=64, tokens=96):
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


SCORING = moe.Scoring("sigmoid", renormalize=True, scale=2.446)


def test_sigmoid_scoring_ranks_with_the_bias_and_weighs_without_it():
    lp, x = expert_layer()
    with jax.default_matmul_precision("highest"):
        _, scores, top_p, top_e = moe.route(x, lp["router_w"], 4, SCORING,
                                            lp["router_bias"])
    s = np.asarray(jax.nn.sigmoid(
        jnp.dot(x, lp["router_w"], precision="highest")))
    want_e = np.argsort(-(s + np.asarray(lp["router_bias"])), axis=1)[:, :4]
    assert (np.sort(np.asarray(top_e), axis=1) == np.sort(want_e, axis=1)) \
        .all()
    chosen = np.take_along_axis(s, np.asarray(top_e), axis=1)
    np.testing.assert_allclose(
        np.asarray(top_p), 2.446 * chosen / chosen.sum(axis=1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(top_p).sum(axis=1), 2.446,
                               rtol=1e-5)
    # the default is OLMoE's: softmax probabilities as they are
    _, probs, p, e = moe.route(x, lp["router_w"], 4)
    np.testing.assert_allclose(np.asarray(probs).sum(axis=1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(p), np.take_along_axis(np.asarray(probs), np.asarray(e),
                                          axis=1))


def test_the_shares_of_32_chips_add_up_to_the_uncut_layer():
    """The share test: 64 experts over 32 chips, 2 each. The routed part
    each share computes, summed over the shares, plus what every chip
    computes alike (the shared expert) counted once, is the uncut reference
    layer. In float32, so the experts chosen are the same everywhere."""
    lp, x = expert_layer(seed=1)
    config = {"num_experts_per_token": 4, "routed_scaling_factor": 2.446,
              "experts_held": [0, 64]}
    with jax.default_matmul_precision("highest"):
        want, _, used, _ = reference._experts(lp, x, config)
        routed = jnp.zeros_like(x)
        rows = 0
        for first in range(0, 64, 2):
            share = {k: v[first:first + 2] if k.startswith("w_") else v
                     for k, v in lp.items() if not k.startswith("shared_")}
            part, aux = moe.dropless_moe_ffn(share, x, 4, scoring=SCORING,
                                             held=(first, 2))
            assert aux["counts"].shape == (64,)     # over all the router's
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


@pytest.mark.parametrize("body", ["off", "on"], ids=["reference", "pallas"])
def test_a_share_s_gradients_do_not_see_the_rows_it_leaves_out(body):
    """Both bodies of the grouped matmul, a share of 2 of 64: the gradient
    of the inputs and of the held experts against the reference's."""
    from paddle_tpu.ops import pallas as plk
    lp, x = expert_layer(seed=2, tokens=64)
    share = {k: v[10:12] if k.startswith("w_") else v for k, v in lp.items()}
    config = {"num_experts_per_token": 4, "routed_scaling_factor": 2.446,
              "experts_held": [10, 2]}
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def program(p, x):
        with plk.override(body):
            return jnp.sum(moe.dropless_moe_ffn(
                p, x, 4, scoring=SCORING, held=(10, 2))[0] * w)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(program, (0, 1))(share, x)
        want = jax.grad(lambda p, x: jnp.sum(
            reference._experts(p, x, config)[0] * w), (0, 1))(share, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            continue
        assert relative_error(a, b) < 1e-4, name


@pytest.mark.parametrize("tile, passes", [(8192, 1), (256, 4), (96, 11)])
def test_a_router_collapsed_onto_the_held_experts_drops_nothing(
        monkeypatch, tile, passes):
    """The held assignments are worked ``HELD_ROW_TILE`` rows a pass, as
    many passes as they need. With a bias that sends every token to the two
    experts held, 1024 of the 2048 assignments fall here: in one pass, in
    four, or in eleven whose last is part empty and whose rows straddle the
    two experts, the result and every gradient are the reference's."""
    monkeypatch.setattr(moe, "HELD_ROW_TILE", tile)
    lp, x = expert_layer(seed=3, tokens=512)
    lp["router_bias"] = lp["router_bias"].at[20:22].add(10.0)
    share = {k: v[20:22] if k.startswith("w_") else v for k, v in lp.items()}
    config = {"num_experts_per_token": 4, "routed_scaling_factor": 2.446,
              "experts_held": [20, 2]}
    w = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def program(p, x):
        y, aux = moe.dropless_moe_ffn(p, x, 4, scoring=SCORING, held=(20, 2))
        return jnp.sum(y * w), (y, aux)

    with jax.default_matmul_precision("highest"):
        (_, (got, aux)), grads = jax.value_and_grad(
            program, (0, 1), has_aux=True)(share, x)
        want, *_ = reference._experts(share, x, config)
        want_grads = jax.grad(lambda p, x: jnp.sum(
            reference._experts(p, x, config)[0] * w), (0, 1))(share, x)
    rows = int(aux["counts"][20:22].sum())
    assert rows == 2 * 512 and -(-rows // min(tile, 2048)) == passes
    assert relative_error(got, want) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" not in name:
            assert relative_error(a, b) < 1e-4, name


def test_a_share_that_no_token_chose_adds_nothing():
    """No assignment held: no pass runs, the routed part and its gradients
    are zero, and the shared expert is still there."""
    lp, x = expert_layer(seed=4, tokens=64)
    lp["router_bias"] = lp["router_bias"].at[30:32].add(-10.0)
    share = {k: v[30:32] if k.startswith("w_") else v for k, v in lp.items()}

    def program(p):
        y, aux = moe.dropless_moe_ffn(p, x, 4, scoring=SCORING, held=(30, 2))
        return jnp.sum(y), (y, aux)

    (_, (got, aux)), grads = jax.value_and_grad(program, has_aux=True)(share)
    assert int(aux["counts"][30:32].sum()) == 0
    want = reference._gated(x, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    assert relative_error(got, want) < 1e-5
    assert not np.asarray(grads["w_gate"]).any()
    assert np.asarray(grads["shared_up"]).any()


def test_the_train_step_moves_the_selection_bias_against_the_load(tiny):
    """One step: every expert that took more than the mean of the batch's
    assignments has lost ``bias_rate``, every one that took fewer has gained
    it, whatever the optimizer did (the bias has no gradient)."""
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = kl.make_train_step(tiny, pt.optimizer.Adam(1e-3), mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = kl.synthetic_batch(tiny, 2, 48)
    counts = kl.routing_stats(params, tiny, batch)            # [4, 16]
    _, params, _ = step_fn(params, opt_state, batch)
    got = np.stack([np.asarray(lp["router_bias"])
                    for lp in params["layers"][1:]])
    want = tiny.bias_rate * np.sign(counts.mean(axis=1, keepdims=True)
                                    - counts)
    np.testing.assert_allclose(got, want, atol=1e-7)
    assert (got != 0).any()
    np.testing.assert_allclose(
        np.asarray(moe.bias_step(jnp.zeros(3), jnp.asarray([5, 1, 3]), 0.5)),
        [-0.5, 0.5, 0.0])


# ---------------------------------------------------------------------------
# recomputation: a KDA mixer keeps what ``kda_fwd`` hands ``kda_bwd``, by name
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
def lane_tile_heads():
    """Three KDA layers and an MLA one (the dense layer first), heads of 128
    channels so that the delta rule takes its kernels, 256 positions."""
    cfg = kl.kimi_linear_tiny(
        num_layers=4, kda_layers=(1, 2, 3), full_attn_layers=(4,),
        kda_heads=2, kda_head_dim=128, experts_held=(4, 4),
        dtype=jnp.float32)
    return (cfg,) + seeded(cfg, rows=1, seq=256)


def plain_checkpoint(monkeypatch):
    """The mixers as they were: everything formed again."""
    from paddle_tpu.models import blocks
    monkeypatch.setattr(blocks, "recomputed", jax.checkpoint)


@pytest.mark.parametrize("mixers", ["kept_by_name", "plain_checkpoint"])
def test_a_gradient_holds_the_rule_s_forward_once_a_kda_layer(
        lane_tile_heads, mixers, monkeypatch):
    """Under ``blocks.recomputed`` the output and the four arrays ``kda_fwd``
    keeps a unit and head are residuals of the mixer's checkpoint, so the
    recomputation has no use for the kernel: the gradient's jaxpr holds it
    once a KDA layer, where the plain ``jax.checkpoint`` held it twice (20.4
    ms of the cell's 310.0 ms step: PERF.md section 6, PR 42). The passes
    around the rule are formed again either way; every backward kernel is
    once a layer."""
    from paddle_tpu.ops import pallas as plk
    cfg, params, batch = lane_tile_heads
    if mixers == "plain_checkpoint":
        plain_checkpoint(monkeypatch)
    with plk.override("on"):
        found = equations(jax.make_jaxpr(jax.grad(
            lambda p: kl.lm_loss(p, cfg, batch)))(params).jaxpr)
    assert {k: found[k] for k in ("kda_fwd", "kda_bwd", "conv_norm_fwd",
                                  "conv_norm_bwd", "gated_norm_fwd",
                                  "gated_norm_bwd")} \
        == {"kda_fwd": 3 if mixers == "kept_by_name" else 6, "kda_bwd": 3,
            "conv_norm_fwd": 18, "conv_norm_bwd": 9,
            "gated_norm_fwd": 6, "gated_norm_bwd": 3}


def test_keeping_the_rule_s_outputs_changes_no_bit_of_a_gradient(
        lane_tile_heads, monkeypatch):
    """The kept arrays are the ones the second forward would have made:
    loss and every gradient leaf equal those of the plain ``jax.checkpoint``
    bit for bit (the Pallas bodies in interpreter mode)."""
    from paddle_tpu.ops import pallas as plk
    cfg, params, batch = lane_tile_heads

    def loss_and_grads():
        with plk.override("on"):
            return jax.jit(jax.value_and_grad(
                lambda p: kl.lm_loss(p, cfg, batch)))(params)

    kept = loss_and_grads()
    plain_checkpoint(monkeypatch)
    plain = loss_and_grads()
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), kept, plain)))
    assert float(kept[0]) > 0 and all(
        float(jnp.abs(lp[name]).max()) > 0 for lp in kept[1]["layers"][:3]
        for name in ("q_w", "k_w", "v_w", "A_log", "beta_w", "o_w", "ln1_g"))


def test_without_a_kernel_in_the_trace_the_helper_is_the_plain_checkpoint(
        lane_tile_heads, monkeypatch):
    """The reference bodies (the CPU's selection) name nothing, so the
    policy has nothing to keep: the gradient's jaxpr holds equation for
    equation what the plain ``jax.checkpoint`` gives, and no name."""
    cfg, params, batch = lane_tile_heads

    def found():
        return equations(jax.make_jaxpr(jax.grad(
            lambda p: kl.lm_loss(p, cfg, batch)))(params).jaxpr)

    kept = found()
    plain_checkpoint(monkeypatch)
    assert kept == found()
    assert "name" not in kept and "pallas_call" not in kept
    assert kept["remat2"] >= 3       # the KDA mixers (and the scan's own)


# ---------------------------------------------------------------------------
# the set-up: a kernel is traced for the step, not for every layer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kda_layers", [1, 3])
def test_a_step_traces_a_kernel_body_once_however_many_layers_call_it(
        kda_layers, monkeypatch):
    """Every Pallas call is a jitted function of its own that the layers
    share, so tracing a step enters a kernel's body once for each context jax
    traces in (the pass; and the JVP of the mixer's ``jax.checkpoint``, which
    jax traces under a mesh context of its own unless the call goes through
    ``registry.traced_once``, as the delta-rule mixer's do: jax 0.9.0's
    behaviour, which this count guards when jax moves), never once a
    layer: a body traced anew a layer costs every set-up
    its trace and its lowering to Mosaic a layer (PERF.md section 6, PR 29
    and PR 32: 5.2 s in the BERT cells, 6 s in the Kimi Linear cell). Heads
    of 128 channels, so that the delta rule takes its kernels; one dense
    layer and ``kda_layers`` expert layers, three grouped matmuls each."""
    from paddle_tpu.ops import pallas as plk
    from paddle_tpu.ops.pallas import delta_glue as glue_mod
    from paddle_tpu.ops.pallas import kda as kda_mod
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

    # the package's name ``grouped_matmul`` is the function, not the module
    gmm_mod = importlib.import_module("paddle_tpu.ops.pallas.grouped_matmul")
    entered = {}

    def counted(module, name):
        body = getattr(module, name)

        def enter(*args, **kw):
            entered[name] = entered.get(name, 0) + 1
            return body(*args, **kw)

        monkeypatch.setattr(module, name, enter)

    for module, names in ((kda_mod, ("_fwd_kernel", "_bwd_kernel")),
                          (gmm_mod, ("_gmm_kernel", "_tgmm_kernel")),
                          (glue_mod, ("_conv_fwd_kernel", "_conv_bwd_kernel",
                                      "_gate_fwd_kernel",
                                      "_gate_bwd_kernel"))):
        for name in names:
            counted(module, name)
    layers = kda_layers + 1
    cfg = kl.kimi_linear_tiny(
        num_layers=layers, kda_layers=tuple(range(1, layers + 1)),
        full_attn_layers=(), kda_heads=2, kda_head_dim=128,
        experts_held=(4, 4))
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = kl.make_train_step(cfg, pt.optimizer.Adam(1e-3), mesh)
    params, opt_state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = jax.eval_shape(step_fn.place, kl.synthetic_batch(cfg, 1, 256))
    jax.clear_caches()            # what earlier tests of this process traced
    with plk.override("on"):
        step_fn.jitted.trace(params, opt_state, batch)
    # the grouped product at two shapes (gate and up; down), each in the
    # pass, in the experts' own backward (``moe._held_bwd`` makes its rows'
    # products again under ``jax.vjp``) and transposed for the rows'
    # gradient; the weights' gradient at the two shapes. The delta rule's
    # kernels and the passes around it (PR 39: the convolution's kernel for
    # q and k, normed a head, their scale an operand, and for v, plain: two
    # programs each way) are entered once a program:
    # ``registry.traced_once`` gives the pass and the checkpoint's JVP one
    # trace context (the rule's forward was entered twice before)
    assert entered == {"_fwd_kernel": 1, "_bwd_kernel": 1,
                       "_gmm_kernel": 6, "_tgmm_kernel": 2,
                       "_conv_fwd_kernel": 2, "_conv_bwd_kernel": 2,
                       "_gate_fwd_kernel": 1, "_gate_bwd_kernel": 1}, entered
