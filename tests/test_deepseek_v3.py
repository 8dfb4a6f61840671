"""The DeepSeek-V3-shaped decoder (Kanana-2's sizes) on the training path,
against the plain reference of the benchmark.

``chipbench/reference/deepseek_v3.py`` computes the dense [S, S] scores of
every layer, turns the interleaved pairs directly, and loops over the held
experts itself, in float32 ``jax.numpy``; it shares no code with
``paddle_tpu`` and reads the program's parameter tree by its key names. Here,
on the CPU at ``deepseek_v3_tiny``'s sizes and seeded random weights: the
rotation of the decoupled channels against a hand-written one and against the
other pairing, then loss, every part of the forward pass and the gradient of
every parameter leaf in float32 on three seeds, the program's bfloat16 within
reach of them and the controls beyond it, the expert layer's share of the
experts against the uncut layer, the recomputed mixers against the kept ones,
the published sizes' parameter count, the one latent-attention function
Kimi Linear shares, and the counters the benchmark reads.
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
from paddle_tpu.models import blocks, deepseek_v3, kimi_linear
from paddle_tpu.ops import pallas as plk
from paddle_tpu.parallel import moe

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("chipbench/reference/deepseek_v3.py",
                  "reference_deepseek_v3")


def reference_config(cfg):
    """The keys the reference reads of a configuration file."""
    first, held = cfg.experts_held or (0, cfg.num_experts)
    return {
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rope_interleave": cfg.rope_interleave, "rms_norm_eps": cfg.rms_eps,
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.first_dense,
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


def seeded(cfg, seed=0, rows=2, seq=80, init=deepseek_v3.init_params):
    """Parameters with gains and the selection bias away from their starts,
    so that a norm or a bias applied in the wrong place shows."""
    params = init(jax.random.PRNGKey(seed), cfg)

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("_g']") or "router_bias" in name:
            return a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)) \
                .reshape(a.shape)
        return a

    params = jax.tree_util.tree_map_with_path(moved, params)
    return params, deepseek_v3.synthetic_batch(cfg, rows, seq, seed=seed)


@pytest.fixture(scope="module")
def tiny():
    return deepseek_v3.deepseek_v3_tiny(experts_held=(4, 4),
                                        dtype=jnp.float32)


# ---------------------------------------------------------------------------
# the rotation of the decoupled channels
# ---------------------------------------------------------------------------
def by_hand(x, theta, rot):
    """The last ``rot`` channels of x [B, S, N, D] in pairs (2i, 2i + 1),
    pair i of position p turned by ``p * theta^(-2i / rot)``: a loop."""
    x = np.array(x, np.float64)
    out = x.copy()
    start = x.shape[-1] - rot
    for p in range(x.shape[1]):
        for i in range(rot // 2):
            angle = p * theta ** (-2.0 * i / rot)
            a, b = x[:, p, :, start + 2 * i], x[:, p, :, start + 2 * i + 1]
            out[:, p, :, start + 2 * i] = a * np.cos(angle) - b * np.sin(angle)
            out[:, p, :, start + 2 * i + 1] = b * np.cos(angle) \
                + a * np.sin(angle)
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interleaved_pairs_are_turned_where_they_lie(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 37, 3, 24)).astype(dtype)
    angles = blocks.rope_angles(37, 8, 1e4)
    got = blocks.apply_rope_tail(x, *angles, True)
    assert got.dtype == dtype and got.shape == x.shape
    want = by_hand(x.astype(jnp.float32), 1e4, 8)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)
    # the channels that carry no position pass through to the bit
    assert jnp.array_equal(got[..., :16], x[..., :16])
    # and the reference's direct rotation is the same law
    ref = reference.rotate(x[0, :, :, 16:].astype(jnp.float32),
                           jnp.arange(37), 1e4, True)
    np.testing.assert_allclose(np.asarray(ref), want[0, :, :, 16:], atol=1e-5)


def test_the_half_split_pairing_is_another_rotation():
    """``rope_interleave`` false pairs (i, i + rot/2): ``apply_rope``'s law on
    the tail, which is the interleaved one on de-interleaved channels and
    differs from it on the channels as they lie."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 19, 2, 24))
    angles = blocks.rope_angles(19, 8, 1e4)
    halves = blocks.apply_rope_tail(x, *angles, False)
    pairs = blocks.apply_rope_tail(x, *angles, True)
    assert relative_error(halves[..., 16:], pairs[..., 16:]) > 0.1
    np.testing.assert_allclose(
        np.asarray(halves[..., 16:]),
        np.asarray(blocks.apply_rope(x[..., 16:], *angles)), atol=1e-6)
    # de-interleaved, turned in halves, interleaved again: the pairs' law,
    # and a score of two operands so permuted is the score as it was
    order = np.r_[0:8:2, 1:8:2]
    again = blocks.apply_rope(x[..., 16:][..., order], *angles)
    np.testing.assert_allclose(np.asarray(again[..., np.argsort(order)]),
                               np.asarray(pairs[..., 16:]), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(reference.rotate(x[0, :, :, 16:], jnp.arange(19), 1e4,
                                    False)),
        np.asarray(halves[0, :, :, 16:]), atol=1e-6)


def test_the_shared_key_rotated_once_is_each_head_s_copy_rotated():
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 23, 1, 8))
    angles = blocks.rope_angles(23, 8, 1e6)
    once = jnp.broadcast_to(blocks.apply_rope_tail(k, *angles, True),
                            (2, 23, 4, 8))
    each = blocks.apply_rope_tail(jnp.broadcast_to(k, (2, 23, 4, 8)),
                                  *angles, True)
    assert jnp.array_equal(once, each)


def test_the_pairing_follows_rope_interleave_and_nothing_else(tiny):
    params, batch = seeded(tiny)
    halves = dataclasses.replace(tiny, rope_interleave=False)
    assert deepseek_v3.DECODER.rotary(tiny, 8)[2] is True
    assert deepseek_v3.DECODER.rotary(halves, 8)[2] is False
    with jax.default_matmul_precision("highest"):
        a, b = (deepseek_v3.stages(params, cfg, batch["input_ids"])[0]
                for cfg in (tiny, halves))
        want = reference.loss_and_outputs(
            params, reference_config(halves), batch)[1]
    assert relative_error(a[1], b[1]) > 1e-3       # the first mixer's output
    assert relative_error(over_norms(b), want) < 1e-5


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_the_tiny_preset_is_the_cut_s_five_layers(tiny):
    params = deepseek_v3.init_params(jax.random.PRNGKey(0), tiny)
    first = params["layers"][0]
    assert first["q_w"].shape == (64, 4 * 24)
    assert first["kva_w"].shape == (64, 32 + 8)
    assert first["kv_norm_g"].shape == (32,)
    assert first["kvb_w"].shape == (32, 4 * 32)
    assert first["o_w"].shape == (4 * 16, 64)
    assert first["ffn_gate"].shape == (64, 160) and "router_w" not in first
    for lp in params["layers"][1:]:
        assert lp["router_w"].shape == (64, 16)      # routes over all 16
        assert lp["w_gate"].shape == (4, 64, 32)     # holds 4 of them
        assert lp["shared_gate"].shape == (64, 64)   # two shared experts
        assert lp["shared_down"].shape == (64, 64)
        assert "ffn_gate" not in lp
    specs = deepseek_v3.param_specs(tiny)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(s, type(
                specs["embed"]))))


#: the parts of the cut at the published widths (ISSUE 44's arithmetic)
PARTS = {"attention": 26_345_984, "dense": 37_748_736,
         "held experts": 75_497_472, "shared": 9_437_184,
         "router and bias": 262_272, "tables": 66_060_288}


def test_published_sizes_count_the_parameters_of_the_cut():
    """One of 8 chips: 16 of 128 experts a layer, the padded eighth of the
    vocabulary, the published layers 0 to 4: 576.3 M parameters."""
    cfg = deepseek_v3.kanana_2_30b_a3b(num_layers=5, vocab_size=16128,
                                       experts_held=(0, 16))
    shapes = jax.eval_shape(lambda: deepseek_v3.init_params(
        jax.random.PRNGKey(0), cfg))

    def count(tree, names):
        return sum(int(np.prod(tree[k].shape)) for k in names)

    layers = shapes["layers"]
    mixer = ("q_w", "kva_w", "kv_norm_g", "kvb_w", "o_w")
    assert [int(np.prod(layers[0][k].shape)) for k in mixer] \
        == [12_582_912, 1_179_648, 512, 4_194_304, 8_388_608]
    assert all(count(lp, mixer) == PARTS["attention"] for lp in layers)
    assert count(layers[0], ("ffn_gate", "ffn_up", "ffn_down")) \
        == PARTS["dense"]
    assert count(layers[1], ("w_gate", "w_up", "w_down")) \
        == PARTS["held experts"]
    assert count(layers[1], ("shared_gate", "shared_up", "shared_down")) \
        == PARTS["shared"]
    assert count(layers[1], ("router_w", "router_bias")) \
        == PARTS["router and bias"]
    assert count(shapes, ("embed", "head_w")) == PARTS["tables"]

    def whole(lp):
        return sum(int(np.prod(a.shape)) for a in lp.values())

    assert whole(layers[0]) == 64_098_816
    assert all(whole(lp) == 111_547_008 for lp in layers[1:])
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == 576_349_184
    assert total == 64_098_816 + 4 * 111_547_008 + PARTS["tables"] + 2048
    # with Adam's two moments, float32: 6.44 GiB of the step's arguments
    assert round(3 * 4 * total / 2**30, 2) == 6.44
    # the uncut model is the published 30 B
    full = jax.eval_shape(lambda: deepseek_v3.init_params(
        jax.random.PRNGKey(0), deepseek_v3.kanana_2_30b_a3b()))
    assert round(sum(int(np.prod(a.shape))
                     for a in jax.tree.leaves(full)) / 1e9, 1) == 30.7


def test_a_configuration_says_what_it_cannot_be():
    with pytest.raises(ValueError, match="pairs"):
        deepseek_v3.deepseek_v3_tiny(qk_rope_head_dim=7)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_loss_outputs_and_every_gradient_match_the_reference(tiny, seed):
    params, batch = seeded(tiny, seed)
    config = reference_config(tiny)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: deepseek_v3.lm_loss(p, tiny, batch))(params)
        parts, aux = deepseek_v3.stages(params, tiny, batch["input_ids"])
        hidden = deepseek_v3.forward(params, tiny, batch["input_ids"])
    want_loss, want_parts = reference.loss_and_outputs(params, config, batch)
    assert parts.shape == (2 * tiny.num_layers + 2, *batch["input_ids"].shape,
                           tiny.hidden)
    assert relative_error(loss, want_loss) < 1e-5
    # every stage's output, each over its norm: the embedding, the stream
    # after each mixer and feed-forward, the final normed hidden states
    stagewise = [relative_error(a, b)
                 for a, b in zip(over_norms(parts), want_parts)]
    assert max(stagewise) < 1e-5, stagewise
    assert relative_error(parts[-1], hidden) == 0
    counts, choice = deepseek_v3.routing_stats(params, tiny, batch,
                                               choices=True)
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


def test_bfloat16_program_is_within_reach_of_the_reference(tiny):
    """The program's own dtype, under its own admissible routing, each part
    held to float32 on the program's own state before it: inside the cell's
    limit. What a precision below the configuration's reads: every part's
    states in 4 stored bits fail by the outputs, bfloat16's 7 pass; a router
    that chooses by coarse scores fails the routing check; parameters kept in
    bfloat16 are refused as parameters; a softmax whose logsumexp keeps 4
    bits reads several times the program's distance. And what another model
    reads: the decoupled channels not turned, turned in the other pairing,
    or the scores not divided by the root of the head's width, each several
    times the program's distance."""
    cfg = dataclasses.replace(tiny, dtype=jnp.bfloat16)
    params, batch = seeded(cfg, seed=1)
    # at 64 channels a mixer's output is a hundredth of the stream it is
    # added to and its scores are flat; at the published 2048 it is of the
    # stream's size and the softmax has a shape. Wider projections put the
    # tiny model's mixers where the cell's are, so that their arithmetic
    # shows in what the parts hand on
    for lp in params["layers"]:
        for name, by in (("q_w", 6.0), ("kva_w", 6.0), ("o_w", 6.0)):
            lp[name] = lp[name] * by
    config = reference_config(cfg)
    parts, aux = deepseek_v3.stages(params, cfg, batch["input_ids"])
    sample = dict(batch, program_stream=np.asarray(parts),
                  program_choice=np.asarray(aux["choice"]).reshape(
                      4, *batch["input_ids"].shape, -1))
    want_loss, want_parts = reference.loss_and_outputs(params, config, sample)
    assert np.isfinite(np.asarray(want_parts)).all()     # admissible
    assert relative_error(deepseek_v3.lm_loss(params, cfg, batch),
                          want_loss) < 2e-3
    sound = relative_error(over_norms(parts), want_parts)
    assert sound < reference.TOLERANCE["outputs"]

    def control(**kw):
        return reference.loss_and_outputs(params, config, sample, **kw)[1]

    assert relative_error(control(state_bits=4), want_parts) \
        > reference.TOLERANCE["outputs"]
    assert relative_error(control(state_bits=7), want_parts) \
        < reference.TOLERANCE["outputs"]
    assert np.isnan(np.asarray(control(router_bits=4))).all()
    # a softmax normalised by a logsumexp of 4 stored bits
    assert relative_error(control(softmax_bits=4), want_parts) > 3 * sound
    for other in (dict(rotation="none"), dict(rotation="other"),
                  dict(score_scale=1.0)):
        read = relative_error(control(**other), want_parts)
        assert read > reference.TOLERANCE["outputs"] and read > 3 * sound, \
            (other, read, sound)
    # bfloat16 parameters, in either dtype's clothes
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    for tree in (low, jax.tree.map(lambda a: a.astype(jnp.float32), low)):
        assert not reference.parameters_are_float32(tree)
        assert np.isnan(np.asarray(reference.loss_and_outputs(
            tree, config, sample)[1])).all()
    assert reference.parameters_are_float32(params)


def test_routing_stats_count_over_every_expert_of_the_router(tiny):
    params, batch = seeded(tiny)
    counts, choice = deepseek_v3.routing_stats(params, tiny, batch,
                                               choices=True)
    assert counts.shape == (4, 16) and choice.shape == (4, 160, 4)
    assert (counts.sum(axis=1) == 4 * 160).all()
    assert choice.max() > 7                 # experts this chip does not hold
    held = counts[:, 4:8].sum(axis=1)
    assert ((0 < held) & (held < 4 * 160)).all()


def test_train_step_lowers_the_loss_and_moves_the_selection_bias(tiny):
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = deepseek_v3.make_train_step(
        tiny, pt.optimizer.Adam(1e-3), mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = deepseek_v3.synthetic_batch(tiny, 2, 48)
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
    assert deepseek_v3.make_train_step.__func__ \
        is lm_trainer.Decoder.make_train_step


# ---------------------------------------------------------------------------
# one latent-attention function, with Kimi Linear
# ---------------------------------------------------------------------------
def _kimi_mla_as_it_was(lp, x, heads, rank, nope, eps, rotary=None,
                        mesh=None):
    """``kimi_linear._mla`` as PR 43 left it, word for word but for the
    configuration's fields, which arrive as arguments."""
    assert rotary is None
    with jax.named_scope("attention"):
        b, s, _ = x.shape
        dt = x.dtype
        q = (x @ lp["q_w"].astype(dt)).reshape(b, s, heads, -1)
        latent, k_shared = jnp.split(x @ lp["kva_w"].astype(dt), [rank],
                                     axis=-1)
        with jax.named_scope("mla_expand"):
            kv = (blocks.rms_normalize(latent, lp["kv_norm_g"], eps)
                  @ lp["kvb_w"].astype(dt)).reshape(b, s, heads, -1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_shared[:, :, None, :],
                                  (b, s, heads, k_shared.shape[-1]))],
                axis=-1)
        ctx = blocks.causal_attention(q, k, kv[..., nope:], mesh=mesh)
        return ctx.reshape(b, s, -1) @ lp["o_w"].astype(dt)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kimi_linear_and_this_model_share_one_latent_attention(dtype,
                                                               monkeypatch):
    """Kimi Linear's MLA layers call ``blocks.latent_attention`` without
    positions: its tiny loss and every gradient are, to the bit, those of the
    mixer it had; this model calls the same function with a rotation."""
    cfg = kimi_linear.kimi_linear_tiny(experts_held=(4, 4), dtype=dtype)
    params, batch = seeded(cfg, init=kimi_linear.init_params)
    assert "_mla" not in vars(kimi_linear)

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(
            lambda p: kimi_linear.lm_loss(p, cfg, batch)))(params)

    calls = []
    shared = blocks.latent_attention

    def counted(*a, **kw):          # does the call hand over a rotation?
        calls.append(kw.get("rotary", a[6] if len(a) > 6 else None)
                     is not None)
        return shared(*a, **kw)

    monkeypatch.setattr(blocks, "latent_attention", counted)
    now = loss_and_grads()
    assert calls == [False]               # one MLA layer of five, no rotary
    tiny = deepseek_v3.deepseek_v3_tiny(dtype=dtype)
    tiny_params, tiny_batch = seeded(tiny)
    del calls[:]
    deepseek_v3.lm_loss(tiny_params, tiny, tiny_batch)
    assert calls == [True] * 5
    monkeypatch.setattr(blocks, "latent_attention", _kimi_mla_as_it_was)
    was = loss_and_grads()
    assert float(now[0]) > 0
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), now, was)))


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
def at_1024(tiny):
    """Three layers at 1024 positions, so that ``auto`` takes the flash
    kernels; every parameter has a gradient."""
    cfg = dataclasses.replace(tiny, num_layers=3)
    return (cfg,) + seeded(cfg, rows=1, seq=1024)


#: what the mixers could be under instead of ``blocks.recomputed``
OTHERWISE = {"plain_checkpoint": jax.checkpoint, "kept": lambda mixer: mixer}


@pytest.mark.parametrize("mixers", ["recomputed", *OTHERWISE])
def test_a_gradient_holds_a_flash_forward_once_a_layer(at_1024, mixers,
                                                       monkeypatch):
    """Under ``blocks.recomputed`` the recomputation has no use for the
    forward kernel, whose o and lse are residuals of the checkpoint: the
    gradient's jaxpr holds ``flash_fwd`` once a layer, as with the mixers
    kept whole, where the plain ``jax.checkpoint`` holds it twice."""
    cfg, params, batch = at_1024
    if mixers in OTHERWISE:
        monkeypatch.setattr(blocks, "recomputed", OTHERWISE[mixers])
    with plk.override("on"):
        found = equations(jax.make_jaxpr(jax.grad(
            lambda p: deepseek_v3.lm_loss(p, cfg, batch)))(params).jaxpr)
    assert found["flash_fwd"] == (6 if mixers == "plain_checkpoint" else 3)
    assert found["flash_bwd"] == 3


@pytest.mark.parametrize("other", sorted(OTHERWISE))
def test_recomputing_the_mixers_changes_no_bit_of_a_gradient(at_1024, other,
                                                             monkeypatch):
    """What the backward pass forms again is what the forward pass formed:
    loss and every gradient leaf equal, bit for bit, those of the mixers kept
    whole and those of the plain ``jax.checkpoint`` (the Pallas bodies in
    interpreter mode)."""
    cfg, params, batch = at_1024

    def loss_and_grads():
        with plk.override("on"):
            return jax.jit(jax.value_and_grad(
                lambda p: deepseek_v3.lm_loss(p, cfg, batch)))(params)

    recomputed = loss_and_grads()
    monkeypatch.setattr(blocks, "recomputed", OTHERWISE[other])
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), recomputed,
        loss_and_grads())))
    assert float(recomputed[0]) > 0 and all(
        float(jnp.abs(lp[name]).max()) > 0
        for lp in recomputed[1]["layers"]
        for name in ("q_w", "kva_w", "kv_norm_g", "kvb_w", "o_w", "ln1_g"))


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
          "shared_gate": 0.3 * jax.random.normal(ks[5], (d, 2 * f)),
          "shared_up": 0.3 * jax.random.normal(ks[6], (d, 2 * f)),
          "shared_down": 0.3 * jax.random.normal(ks[7], (2 * f, d))}
    return lp, jax.random.normal(ks[8], (tokens, d))


SCORING = deepseek_v3.kanana_2_30b_a3b().scoring


def test_the_shares_of_8_chips_add_up_to_the_uncut_layer():
    """The share test at the cell's cut: the experts over 8 chips (here 16
    experts, 2 a chip, where the cell holds 16 of 128). Sigmoid scores, the
    bias in the choice and not in the weights, renormalised over the four
    chosen, scaled by 2.448; every chip computes the shared feed-forward
    alike, so it is counted once: the routed parts of the 8 shares and one
    shared part add up to the uncut reference layer. In float32, so the
    experts chosen are the same everywhere."""
    assert SCORING == moe.Scoring("sigmoid", renormalize=True, scale=2.448)
    lp, x = expert_layer(seed=1)
    config = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.448,
              "experts_held": [0, 16]}
    with jax.default_matmul_precision("highest"):
        want, ranked, used, _ = reference._experts(lp, x, config)
        shared = reference._gated(x, lp["shared_gate"], lp["shared_up"],
                                  lp["shared_down"])
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
            total = total + (part - shared)         # this chip's routed part
    assert rows == int(used.sum()) == 4 * 96     # every assignment, once
    assert relative_error(total + shared, want) < 1e-5
    assert relative_error(total, want) > 1e-2    # the shared part is a part
    # the shared feed-forward of two experts' width is the two's sum
    f = lp["shared_gate"].shape[1] // 2
    two = sum(reference._gated(x, lp["shared_gate"][:, cols],
                               lp["shared_up"][:, cols],
                               lp["shared_down"][cols])
              for cols in (slice(0, f), slice(f, None)))
    assert relative_error(two, shared) < 1e-5
    # the bias is in the choice: without it other experts are chosen
    _, _, unbiased, _ = reference._experts(
        dict(lp, router_bias=jnp.zeros(16)), x, config)
    assert (np.asarray(unbiased) != np.asarray(used)).any()
    # and not in the weights: a token's weights are its chosen scores over
    # their sum, times the scale
    scores = np.asarray(ranked - lp["router_bias"]) * np.asarray(used)
    weights = 2.448 * scores / scores.sum(-1, keepdims=True)
    by_hand = np.asarray(shared) + sum(
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
    """16 384 tokens, 6 experts a token, 16 of 128 held: 12 288 rows at par,
    768 an expert; a pass takes 24 576 (twice par in whole tiles of 8192),
    so the layer is one pass up to a share of 25%."""
    assignments = 16384 * 6
    assert assignments * 16 // 128 == 12288 == 16 * 768
    assert moe._held_row_tile(assignments, 16, 128) \
        == 3 * moe.HELD_ROW_TILE == 24576
    assert 24576 / assignments == 0.25
