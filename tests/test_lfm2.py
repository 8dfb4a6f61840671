"""LFM2 on the training path, against the plain reference of the benchmark.

``chipbench/reference/lfm2.py`` computes the convolution as three shifted
products, the dense [S, S] scores of the attention layer, its own rotary
tables and its own loop over the held experts, in float32 ``jax.numpy``, and
shares no code with ``paddle_tpu``; it reads the program's parameter tree by
its key names. Here, on the CPU at ``lfm2_tiny``'s sizes and seeded random
weights: the double-gated convolution in both bodies, then loss, every part
of the forward pass and the gradient of every parameter leaf in float32 on
three seeds, the program's bfloat16 within reach of them, the tied table's
gradient, the expert layer's share of the experts against the uncut layer,
the published sizes' parameter count, and the counters the benchmark reads.
"""

import dataclasses
import importlib
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import lfm2
from paddle_tpu.ops import pallas as plk
from paddle_tpu.parallel import moe

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("chipbench/reference/lfm2.py", "reference_lfm2")


def reference_config(cfg):
    """The keys the reference reads of a configuration file."""
    first, held = cfg.experts_held or (0, cfg.num_experts)
    return {
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.kv_heads,
        "layer_types": list(cfg.layer_types), "first_layer": 0,
        "num_hidden_layers": cfg.num_layers,
        "num_dense_layers": cfg.num_dense_layers,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "norm_eps": cfg.rms_eps,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scale,
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
    so that a norm or a bias applied in the wrong place shows."""
    params = lfm2.init_params(jax.random.PRNGKey(seed), cfg)

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("_g']") or "router_bias" in name:
            return a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)) \
                .reshape(a.shape)
        return a

    params = jax.tree_util.tree_map_with_path(moved, params)
    return params, lfm2.synthetic_batch(cfg, rows, seq, seed=seed)


@pytest.fixture(scope="module")
def tiny():
    return lfm2.lfm2_tiny(experts_held=(4, 4), dtype=jnp.float32)


# ---------------------------------------------------------------------------
# the double-gated short convolution: both bodies against shifted products
# ---------------------------------------------------------------------------
def shifted_products(bcu, taps):
    """``C * sum_j taps_j (B u)_{t - K + 1 + j}``, each tap a shifted copy."""
    k, c = taps.shape
    s = bcu.shape[1]
    gate_b, gate_c, u = bcu[..., :c], bcu[..., c:2 * c], bcu[..., 2 * c:]
    z = gate_b * u
    conv = jnp.zeros_like(z)
    for j in range(k):
        back = k - 1 - j                 # tap j reads the row ``back`` before
        conv = conv + taps[j] * jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :s]
    return gate_c * conv


#: (batch, positions, channels, taps): a length that crosses a block of 256
#: rows and is no multiple of the 64-row chunk; one that does not fill a
#: block; channels a step's 512 lanes do not divide; 4 taps
CONV_CASES = [(2, 300, 256, 3), (1, 40, 128, 3), (2, 600, 1152, 3),
              (1, 128, 128, 4)]


@pytest.mark.parametrize("mode", ["on", "off"], ids=["pallas", "reference"])
@pytest.mark.parametrize("b,s,c,k", CONV_CASES)
def test_gated_short_conv_is_three_shifted_products(b, s, c, k, mode):
    ks = jax.random.split(jax.random.PRNGKey(s), 3)
    bcu = jax.random.normal(ks[0], (b, s, 3 * c))
    taps = jax.random.uniform(ks[1], (k, c), minval=-0.5, maxval=0.5)
    w = jax.random.normal(ks[2], (b, s, c))

    def op(bcu, taps):
        with plk.override(mode):
            return plk.gated_short_conv(bcu, taps)

    with plk.override(mode):
        assert plk.selected_body("gated_short_conv") == (
            "pallas_interpret" if mode == "on" else "reference")
    got = op(bcu, taps)
    assert got.shape == (b, s, c)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(shifted_products(bcu, taps)),
                               atol=2e-5)
    (dx, dw), (want_dx, want_dw) = (
        jax.grad(lambda x, t: jnp.sum(f(x, t) * w), (0, 1))(bcu, taps)
        for f in (op, shifted_products))
    assert dx.shape == bcu.shape and dw.shape == taps.shape
    # d[B | C | u] is one array, its three ranges the three gradients
    for name, cols in zip("BCu", (slice(0, c), slice(c, 2 * c),
                                  slice(2 * c, None))):
        np.testing.assert_allclose(np.asarray(dx[..., cols]),
                                   np.asarray(want_dx[..., cols]),
                                   atol=5e-5, err_msg=f"d{name}")
    assert relative_error(dw, want_dw) < 1e-5


def test_gated_short_conv_sees_nothing_after_a_position():
    """Causal, and K - 1 rows of reach: a change at position 255, the last
    row of a block, moves rows 255 to 257 of the next block's side and
    nothing before."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    bcu = jax.random.normal(ks[0], (1, 512, 3 * 128))
    taps = jax.random.uniform(ks[1], (3, 128), minval=0.1, maxval=0.5)
    with plk.override("on"):
        base = plk.gated_short_conv(bcu, taps)
        moved = plk.gated_short_conv(bcu.at[:, 255, 256:].add(1.0), taps)
    changed = np.flatnonzero(np.abs(np.asarray(moved - base)).max((0, 2)))
    assert changed.tolist() == [255, 256, 257]


def test_gated_short_conv_says_what_it_cannot_take():
    with pytest.raises(ValueError, match="three ranges"):
        plk.gated_short_conv(jnp.ones((1, 16, 256)), jnp.ones((3, 128)))
    # channels that are no whole lane tile: the reference body, also "on"
    from paddle_tpu.ops.pallas import gated_conv
    bcu, taps = jnp.ones((1, 16, 3 * 64)), jnp.ones((3, 64))
    np.testing.assert_allclose(
        np.asarray(gated_conv._gated_short_conv_pallas(bcu, taps, True)),
        np.asarray(gated_conv._gated_short_conv_reference(bcu, taps)))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_the_tiny_preset_is_the_cut_s_five_layers(tiny):
    assert list(tiny.layer_types[:5]) == [lfm2.CONV, lfm2.FULL] \
        + [lfm2.CONV] * 3
    assert tuple(lfm2.lfm2_24b_a2b().layer_types[1:6]) == tiny.layer_types
    params = lfm2.init_params(jax.random.PRNGKey(0), tiny)
    assert "head_w" not in params                      # the head is tied
    first = params["layers"][0]
    assert first["in_w"].shape == (64, 192) and first["conv"].shape == (3, 64)
    assert first["ffn_gate"].shape == (64, 160) and "router_w" not in first
    attn = params["layers"][1]
    assert attn["q_w"].shape == (64, 64) and attn["k_w"].shape == (64, 16)
    assert attn["q_norm_g"].shape == attn["k_norm_g"].shape == (8,)
    for lp in params["layers"][1:]:
        assert lp["router_w"].shape == (64, 16)      # routes over all 16
        assert lp["w_gate"].shape == (4, 64, 32)     # holds 4 of them
        assert "shared_gate" not in lp               # no shared expert
    assert float(jnp.abs(first["conv"]).max()) <= 3 ** -0.5
    specs = lfm2.param_specs(tiny)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(s, type(
                specs["embed"]))))


#: the parts of the cut at the published widths (ISSUE 40's table)
PARTS = {"convolution": 16_783_360, "attention": 10_485_888,
         "dense": 72_351_744, "experts": 75_628_608}


def test_published_sizes_count_the_parameters_of_the_cut():
    """One of 8 chips: 8 of 64 experts a layer, an eighth of the vocabulary
    tied with the head, the published layers 1 to 5: 469.3 M parameters."""
    published = lfm2.lfm2_24b_a2b()
    cfg = lfm2.lfm2_24b_a2b(num_layers=5,
                            layer_types=published.layer_types[1:6],
                            num_dense_layers=1, vocab_size=8192,
                            experts_held=(0, 8))
    shapes = jax.eval_shape(lambda: lfm2.init_params(jax.random.PRNGKey(0),
                                                     cfg))

    def count(tree, names=None):
        return sum(int(np.prod(a.shape)) for k, a in tree.items()
                   if names is None or k in names)

    layers = shapes["layers"]
    assert count(layers[0], ("in_w", "conv", "out_w")) \
        == PARTS["convolution"]
    assert count(layers[1], ("q_w", "k_w", "v_w", "o_w", "q_norm_g",
                             "k_norm_g")) == PARTS["attention"]
    assert count(layers[0], ("ffn_gate", "ffn_up", "ffn_down")) \
        == PARTS["dense"]
    assert count(layers[2], ("router_w", "router_bias", "w_gate", "w_up",
                             "w_down")) == PARTS["experts"]
    assert [round(n / 1e6, 2) for n in PARTS.values()] \
        == [16.78, 10.49, 72.35, 75.63]
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == 469_285_248 and round(total / 1e6, 1) == 469.3
    assert total == 4 * PARTS["convolution"] + PARTS["attention"] \
        + PARTS["dense"] + 4 * PARTS["experts"] + 8192 * 2048 \
        + (2 * 5 + 1) * 2048


def test_a_configuration_says_what_it_cannot_be():
    with pytest.raises(ValueError, match="multiple"):
        lfm2.lfm2_tiny(num_heads=7)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.lfm2_tiny(layer_types=(lfm2.CONV,) * 3)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.lfm2_tiny(layer_types=("conv", "sliding_attention") * 3)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_loss_outputs_and_every_gradient_match_the_reference(tiny, seed):
    params, batch = seeded(tiny, seed)
    config = reference_config(tiny)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: lfm2.lm_loss(p, tiny, batch))(params)
        parts, aux = lfm2.stages(params, tiny, batch["input_ids"])
        hidden = lfm2.forward(params, tiny, batch["input_ids"])
    want_loss, want_parts = reference.loss_and_outputs(params, config, batch)
    assert parts.shape == (2 * tiny.num_layers + 2, *batch["input_ids"].shape,
                           tiny.hidden)
    assert relative_error(loss, want_loss) < 1e-5
    # every stage's output, each over its norm: the embedding, the stream
    # after each operator and feed-forward, the final normed hidden states
    stagewise = [relative_error(a, b)
                 for a, b in zip(over_norms(parts), want_parts)]
    assert max(stagewise) < 1e-5, stagewise
    assert relative_error(parts[-1], hidden) == 0
    counts, choice = lfm2.routing_stats(params, tiny, batch, choices=True)
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
        assert relative_error(got, ref) < 1e-4, name


def test_the_tied_table_s_gradient_is_the_head_s_plus_the_gather_s(tiny):
    """One leaf, two uses: with the head untied by hand (the same values
    under a second name) the table's gradient splits into the two parts,
    and they add up to the tied one."""
    params, batch = seeded(tiny, seed=3)
    with jax.default_matmul_precision("highest"):
        tied = jax.grad(lambda p: lfm2.lm_loss(p, tiny, batch))(
            params)["embed"]

        def untied_loss(table, head):
            hidden = lfm2.forward(dict(params, embed=table), tiny,
                                  batch["input_ids"])
            logits = jnp.einsum("bsh,vh->bsv", hidden, head)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, batch["labels"][..., None], axis=-1))

        gather, head = jax.grad(untied_loss, (0, 1))(params["embed"],
                                                     params["embed"])
    assert relative_error(tied, gather + head) < 1e-5
    # both parts are there: neither is the whole
    assert relative_error(tied, head) > 1e-2
    assert relative_error(tied, gather) > 1e-2
    # rows no id touched have the head's part alone
    unused = np.setdiff1d(np.arange(tiny.vocab_size),
                          np.asarray(batch["input_ids"]).ravel())
    assert unused.size and not np.asarray(gather)[unused].any()


def test_bfloat16_program_is_within_reach_of_the_reference(tiny):
    """The program's own dtype, under its own admissible routing, each part
    held to float32 on the program's own state before it: inside the cell's
    limit. What a precision below the configuration's reads: every part's
    states in 4 stored bits fail by the outputs, bfloat16's 7 pass; a
    convolution summed in 4 bits reads several times the program's distance
    (its own bits on the cell's sizes are PERF.md's, section 6, PR 40); a
    router that chooses by coarse scores fails the routing check; a fault in
    one part (the gate C left off the last convolution) shows."""
    cfg = dataclasses.replace(tiny, dtype=jnp.bfloat16)
    params, batch = seeded(cfg, seed=1)
    # at 64 channels an operator's output is a hundredth of the stream it is
    # added to; at the published 2048 it is the larger of the two (the
    # products' sums grow with the width). Wider projections put the tiny
    # model's convolutions where the cell's are, so that their arithmetic
    # shows in what the parts hand on
    for lp in params["layers"]:
        for name in ("in_w", "out_w"):
            if name in lp:
                lp[name] = lp[name] * 8.0
    config = reference_config(cfg)
    parts, aux = lfm2.stages(params, cfg, batch["input_ids"])

    def sample_of(parts, aux):
        return dict(batch, program_stream=np.asarray(parts),
                    program_choice=np.asarray(aux["choice"]).reshape(
                        4, *batch["input_ids"].shape, -1))

    sample = sample_of(parts, aux)
    want_loss, want_parts = reference.loss_and_outputs(params, config, sample)
    assert np.isfinite(np.asarray(want_parts)).all()     # admissible
    assert relative_error(lfm2.lm_loss(params, cfg, batch), want_loss) < 2e-3
    sound = relative_error(over_norms(parts), want_parts)
    assert sound < reference.TOLERANCE["outputs"]
    _, low = reference.loss_and_outputs(params, config, sample, state_bits=4)
    assert relative_error(low, want_parts) > reference.TOLERANCE["outputs"]
    _, same = reference.loss_and_outputs(params, config, sample, state_bits=7)
    assert relative_error(same, want_parts) < reference.TOLERANCE["outputs"]
    _, conv = reference.loss_and_outputs(params, config, sample, conv_bits=4)
    assert relative_error(conv, want_parts) > 3 * relative_error(same,
                                                                 want_parts)
    _, routed = reference.loss_and_outputs(params, config, sample,
                                           router_bits=4)
    assert np.isnan(np.asarray(routed)).all()           # a wrong router
    # a fault: layer 4's convolution without its gate C (its columns of the
    # projection send C to 1 whatever the input)
    wrong = jax.tree.map(lambda a: a, params)
    in_w = params["layers"][4]["in_w"]
    wrong["layers"][4]["in_w"] = in_w.at[:, 64:128].set(0.0)
    faulty, faulty_aux = lfm2.stages(wrong, cfg, batch["input_ids"])
    _, want_parts = reference.loss_and_outputs(params, config,
                                               sample_of(faulty, faulty_aux))
    assert relative_error(over_norms(faulty), want_parts) > 3 * sound


def test_routing_stats_count_over_every_expert_of_the_router(tiny):
    params, batch = seeded(tiny)
    counts, choice = lfm2.routing_stats(params, tiny, batch, choices=True)
    assert counts.shape == (4, 16) and choice.shape == (4, 160, 4)
    assert (counts.sum(axis=1) == 4 * 160).all()
    assert choice.max() > 7                 # experts this chip does not hold
    held = counts[:, 4:8].sum(axis=1)
    assert ((0 < held) & (held < 4 * 160)).all()


def test_train_step_lowers_the_loss_and_moves_the_selection_bias(tiny):
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = lfm2.make_train_step(tiny, pt.optimizer.Adam(1e-3),
                                            mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = lfm2.synthetic_batch(tiny, 2, 48)
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
    assert lfm2.make_train_step.__func__ \
        is lm_trainer.Decoder.make_train_step


@pytest.mark.parametrize("layers", [5, 9])
def test_a_training_step_enters_each_kernel_body_once(layers, monkeypatch):
    """The whole step, backward included: the convolution's two kernels and
    the flash kernels are jitted functions of their own, entered once an
    operator type however many layers call them with one shape (PERF.md
    section 6, PR 29, 32 and 39: traced anew a layer, a Pallas call costs
    seconds of set-up)."""
    from paddle_tpu.ops.pallas import gated_conv
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    entered = {}

    def counted(module, name):
        body = getattr(module, name)

        def enter(*args, **kw):
            entered[name] = entered.get(name, 0) + 1
            return body(*args, **kw)

        monkeypatch.setattr(module, name, enter)

    for module, names in ((gated_conv, ("_fwd_kernel", "_bwd_kernel")),
                          (fa, ("_flash_fwd_kernel",))):
        for name in names:
            counted(module, name)
    published = lfm2.lfm2_24b_a2b().layer_types
    cfg = lfm2.lfm2_tiny(num_layers=layers,
                         layer_types=published[1:1 + layers], hidden=128,
                         head_dim=16, experts_held=(0, 4))
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = lfm2.make_train_step(cfg, pt.optimizer.Adam(1e-3),
                                            mesh)
    params, opt_state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = jax.eval_shape(step_fn.place,
                           lfm2.synthetic_batch(cfg, 1, 1024))
    jax.clear_caches()            # what earlier tests of this process traced
    with plk.override("on"):
        step_fn.jitted.trace(params, opt_state, batch)
    assert entered == {"_fwd_kernel": 1, "_bwd_kernel": 1,
                       "_flash_fwd_kernel": 1}, entered


# ---------------------------------------------------------------------------
# the expert layer's share: eighths, as the cell cuts it
# ---------------------------------------------------------------------------
def expert_layer(seed=0, d=32, f=16, experts=16, tokens=96):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    lp = {"router_w": jax.random.normal(ks[0], (d, experts)),
          "router_bias": 0.3 * jax.random.normal(ks[1], (experts,)),
          "w_gate": 0.3 * jax.random.normal(ks[2], (experts, d, f)),
          "w_up": 0.3 * jax.random.normal(ks[3], (experts, d, f)),
          "w_down": 0.3 * jax.random.normal(ks[4], (experts, f, d))}
    return lp, jax.random.normal(ks[5], (tokens, d))


SCORING = lfm2.lfm2_24b_a2b().scoring


def test_the_shares_of_8_chips_add_up_to_the_uncut_layer():
    """The share test at the cell's cut: the experts over 8 chips (here 16
    experts, 2 a chip, where the cell holds 8 of 64). Sigmoid scores, the
    bias in the choice and not in the weights, renormalised over the four
    chosen, scaled by 1; no shared expert to count once: the parts the
    shares compute simply add up to the uncut reference layer. In float32,
    so the experts chosen are the same everywhere."""
    assert SCORING == moe.Scoring("sigmoid", renormalize=True, scale=1.0)
    lp, x = expert_layer(seed=1)
    config = {"num_experts_per_tok": 4, "routed_scaling_factor": 1,
              "experts_held": [0, 16]}
    with jax.default_matmul_precision("highest"):
        want, ranked, used, _ = reference._experts(lp, x, config)
        total = jnp.zeros_like(x)
        rows = 0
        for chip in range(8):
            first = 2 * chip
            share = {k: v[first:first + 2] if k.startswith("w_") else v
                     for k, v in lp.items()}
            part, aux = moe.dropless_moe_ffn(share, x, 4, scoring=SCORING,
                                             held=(first, 2))
            assert aux["counts"].shape == (16,)     # over all the router's
            rows += int(aux["counts"][first:first + 2].sum())
            total = total + part
    assert rows == int(used.sum()) == 4 * 96     # every assignment, once
    assert relative_error(total, want) < 1e-5
    # the bias is in the choice: without it other experts are chosen
    _, _, unbiased, _ = reference._experts(
        dict(lp, router_bias=jnp.zeros(16)), x, config)
    assert (np.asarray(unbiased) != np.asarray(used)).any()
    # and not in the weights: a token's weights are its chosen scores over
    # their sum (the scores are ``ranked`` less the bias)
    scores = np.asarray(ranked - lp["router_bias"]) * np.asarray(used)
    weights = scores / scores.sum(-1, keepdims=True)
    by_hand = sum(
        weights[:, e:e + 1] * np.asarray(reference._gated(
            x, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e]))
        for e in range(16))
    assert relative_error(want, by_hand) < 1e-5
    # and one share is the reference given that share
    share = {k: v[6:8] if k.startswith("w_") else v for k, v in lp.items()}
    with jax.default_matmul_precision("highest"):
        got, _ = moe.dropless_moe_ffn(share, x, 4, scoring=SCORING,
                                      held=(6, 2))
        want, *_ = reference._experts(share, x, dict(config,
                                                     experts_held=[6, 2]))
    assert relative_error(got, want) < 1e-5


def test_the_cell_s_expert_layer_is_one_pass_at_par():
    """4 x 8192 tokens, 4 experts a token, 8 of 64 held: 16 384 rows at par,
    2048 an expert; a pass takes 32 768 (twice par in whole tiles of 8192),
    so the layer is one pass up to a share of 25%."""
    assignments = 4 * 8192 * 4
    assert assignments * 8 // 64 == 16384 == 8 * 2048
    assert moe._held_row_tile(assignments, 8, 64) == 4 * moe.HELD_ROW_TILE \
        == 32768
    assert moe._held_row_tile(4 * 160, 4, 16) == 4 * 160    # lfm2_tiny
