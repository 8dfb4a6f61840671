"""Qwen3-Next on the training path, against the plain reference of the
benchmark, and the pieces it forced into the shared modules.

``chipbench/reference/qwen3_next.py`` runs the delta rule one position a
step, the dense [S, S] scores of the attention layer and its own loop over
the held experts, in float32 ``jax.numpy``, and shares no code with
``paddle_tpu``; it reads the program's parameter tree by its key names. Here,
on the CPU at ``qwen3_next_tiny``'s sizes and seeded random weights: loss,
every part of the forward pass and the gradient of every parameter leaf in
float32 on several seeds, the program's bfloat16 within reach of them; the
delta rule with a decay a head and grouped key heads against the recurrence
and against the per-channel call on the broadcast operands, in both bodies;
the rotation of a quarter of a head; the sixteen shares of a softmax-routed
expert layer with its gated shared expert; the sizes of the cut.
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
from paddle_tpu.models import blocks
from paddle_tpu.models import qwen3_next as qn
from paddle_tpu.ops import kda
from paddle_tpu.ops import pallas as plk
from paddle_tpu.parallel import moe

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("chipbench/reference/qwen3_next.py", "reference_qwen3_next")


def reference_config(cfg):
    """The keys the reference reads of a configuration file."""
    first, held = cfg.experts_held or (0, cfg.num_experts)
    return {
        "head_dim": cfg.head_dim, "num_key_value_heads": cfg.kv_heads,
        "num_attention_heads": cfg.num_heads,
        "partial_rotary_factor": cfg.rotary_factor,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
        "full_attention_interval": cfg.full_attention_interval,
        "linear_num_key_heads": cfg.linear_key_heads,
        "linear_num_value_heads": cfg.linear_value_heads,
        "linear_key_head_dim": cfg.linear_key_dim,
        "linear_value_head_dim": cfg.linear_value_dim,
        "num_experts_per_tok": cfg.experts_per_token,
        "experts_held": [first, held],
        "router_aux_loss_coef": cfg.balance_weight}


def relative_error(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def over_norms(parts):
    parts = parts.astype(jnp.float32)
    return parts / jnp.sqrt(jnp.sum(jnp.square(parts), axis=(1, 2, 3),
                                    keepdims=True))


def seeded(cfg, seed=0, rows=2, seq=80):
    """Parameters with every gain away from its start (the ``1 + w`` gains
    from 0, the delta rule's plain one from 1), so that a norm with the wrong
    kind of gain shows; 80 positions: a chunk of 64 and a rest."""
    params = qn.init_params(jax.random.PRNGKey(seed), cfg)

    def moved(path, a):
        name = jax.tree_util.keystr(path)
        if name.endswith("norm_w']") or name.endswith("norm_g']") \
                or name.endswith("ln1_w']") or name.endswith("ln2_w']"):
            return a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)) \
                .reshape(a.shape)
        return a

    params = jax.tree_util.tree_map_with_path(moved, params)
    return params, qn.synthetic_batch(cfg, rows, seq, seed=seed)


@pytest.fixture(scope="module")
def tiny():
    return qn.qwen3_next_tiny(experts_held=(4, 4), dtype=jnp.float32)


# ---------------------------------------------------------------------------
# the delta rule with a decay a head and grouped key heads
# ---------------------------------------------------------------------------
def head_decay_inputs(seed, batch=2, key_heads=2, heads=4, positions=150,
                      d=32, fast=False, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (batch, positions, key_heads, d))
            for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, positions, heads, d))
    g = -jnp.exp(jax.random.normal(ks[3], (batch, positions, heads)) - 1.0)
    if fast:                          # one head loses e^-20 a position
        g = g.at[..., 0].set(-20.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, positions,
                                                    heads)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def on_body(body, *args):
    with plk.override("on" if body == "pallas" else "off"):
        return kda.kda_chunked(*args)


def sized_for(body, seed, **kw):
    """The Pallas body wants a head of 128 channels; one batch row keeps the
    interpreter quick."""
    if body == "pallas":
        kw = dict(batch=1, d=128, **kw)
    return head_decay_inputs(seed, **kw)


def broadcast(q, k, v, g, beta):
    """The per-channel call's operands for the same rule."""
    n = v.shape[2] // q.shape[2]
    return (jnp.repeat(q, n, axis=2), jnp.repeat(k, n, axis=2), v,
            jnp.broadcast_to(g[..., None], (*g.shape, q.shape[-1])), beta)


@pytest.mark.parametrize("body", ["reference", "pallas"])
@pytest.mark.parametrize("positions", [150, 256])
def test_head_decay_outputs_are_the_recurrence(body, positions):
    """150 positions: two chunks of 64 and a rest; a unit of 128 and 22 left
    for the kernels, the state carried in their scratch."""
    with jax.default_matmul_precision("highest"):
        args = sized_for(body, 0, positions=positions)
        got = on_body(body, *args)
        want = kda.kda_recurrent(*args)
        assert got.shape == want.shape == args[2].shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-6)
        # and the per-channel call on the broadcast operands is the same rule
        np.testing.assert_allclose(
            np.asarray(on_body(body, *broadcast(*args))), np.asarray(want),
            atol=5e-6)


@pytest.mark.parametrize("body", ["reference", "pallas"])
def test_head_decay_gradients_are_the_recurrence_s(body):
    with jax.default_matmul_precision("highest"):
        args = sized_for(body, 1, positions=150)
        w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
        got = jax.grad(lambda *a: jnp.sum(on_body(body, *a) * w),
                       (0, 1, 2, 3, 4))(*args)
        want = jax.grad(lambda *a: jnp.sum(kda.kda_recurrent(*a) * w),
                        (0, 1, 2, 3, 4))(*args)
        for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
            assert a.shape == b.shape, name
            assert relative_error(a, b) < 2e-5, name
        # a key head's gradient is the sum of its value heads': the
        # per-channel call's on the repeated heads, folded
        wide = jax.grad(lambda *a: jnp.sum(on_body(body, *a) * w),
                        (0, 1, 3))(*broadcast(*args))
        b_, s_, hk, d = args[0].shape
        for a, b in zip(got[:2], wide[:2]):
            assert relative_error(a, b.reshape(b_, s_, hk, -1, d)
                                  .sum(axis=3)) < 2e-5
        assert relative_error(got[3], wide[2].sum(axis=-1)) < 2e-5


@pytest.mark.parametrize("body", ["reference", "pallas"])
def test_a_head_that_forgets_at_once_overflows_nothing(body):
    """exp(-20) a position: 64 positions of it are e^-1280 in the chunk's
    own decay; every exponent formed is a difference and at most 0."""
    with jax.default_matmul_precision("highest"):
        args = sized_for(body, 2, positions=140, fast=True)
        got = on_body(body, *args)
        want = kda.kda_recurrent(*args)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-6)
        grads = jax.grad(lambda *a: jnp.sum(on_body(body, *a)),
                         (0, 1, 2, 3, 4))(*args)
        assert all(np.isfinite(np.asarray(t)).all() for t in grads)


def test_head_decay_in_bfloat16_rounds_where_the_recurrence_does_not():
    """The kernels and the ``jax.numpy`` body round the same operands: they
    lie closer to each other than either to the float32 recurrence."""
    args = head_decay_inputs(3, batch=1, d=128, positions=256,
                             dtype=jnp.bfloat16)
    want = kda.kda_recurrent(*args)
    ref, ker = (on_body(body, *args) for body in ("reference", "pallas"))
    assert ker.dtype == ref.dtype == jnp.bfloat16
    assert relative_error(ref, want) < 1e-2
    assert relative_error(ker, want) < 1e-2
    assert relative_error(ker, ref) < 6e-3


def test_the_operands_choose_the_rule_and_the_kernels():
    """Rank 3 and grouped heads run ``gdn_fwd``; rank 4 and equal heads
    ``kda_fwd``, as before; a head that is no lane tile the reference body;
    head counts that do not divide are refused."""
    pk = importlib.import_module("paddle_tpu.ops.pallas.kda")
    args = head_decay_inputs(0, batch=1, d=128, positions=128)

    def calls(*a):
        with plk.override("on"):
            text = str(jax.make_jaxpr(kda.kda_chunked)(*a))
        return [name for name in ("gdn_fwd", "kda_fwd") if name in text]

    assert calls(*args) == ["gdn_fwd"]
    assert calls(*broadcast(*args)) == ["kda_fwd"]
    assert calls(*head_decay_inputs(0, batch=1, d=32, positions=128)) == []
    assert pk._heads_per_step(32, 2) == 8 and pk._heads_per_step(32) == 8
    assert pk._heads_per_step(6, 3) == 6 and pk._heads_per_step(6, 2) == 6
    assert pk._heads_per_step(32, 16) is None
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="multiple"):
        kda.kda_chunked(jnp.repeat(q, 3, axis=2)[:, :, :3],
                        jnp.repeat(k, 3, axis=2)[:, :, :3], v, g, beta)
    assert kda.CHUNK == 32 and 128 % kda.CHUNK_HEAD == 0


# ---------------------------------------------------------------------------
# the blocks the model shares
# ---------------------------------------------------------------------------
def test_rotation_touches_64_of_256_channels():
    cfg = qn.qwen3_next_80b_a3b()
    rot = int(cfg.head_dim * cfg.rotary_factor)
    cos, sin = blocks.rope_angles(40, rot, cfg.rope_theta)
    assert rot == 64 and cos.shape == (40, 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 256))
    turned = blocks.apply_rope(x, cos, sin)
    assert np.array_equal(np.asarray(turned[..., 64:]),
                          np.asarray(x[..., 64:]))
    assert not np.allclose(np.asarray(turned[:, 1:, :, :64]),
                           np.asarray(x[:, 1:, :, :64]))
    # the pairs are (i, i + 32) of the first 64, at theta 1e7
    inv = 1e7 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(np.asarray(cos),
                               np.cos(np.arange(40)[:, None] * inv),
                               atol=1e-5)
    a, b = np.asarray(x[..., :32]), np.asarray(x[..., 32:64])
    c, s = np.asarray(cos)[None, :, None], np.asarray(sin)[None, :, None]
    np.testing.assert_allclose(np.asarray(turned[..., :32]), a * c - b * s,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(reference._rotate(x[0], cos, sin)),
                               np.asarray(turned[0]), rtol=1e-5, atol=1e-6)


def test_the_short_convolution_and_the_l2_norm_are_the_blocks_own():
    """Both delta-rule models call ``ops.pallas.short_conv_norm``, whose
    reference body is ``short_conv`` then ``l2_normalize``
    (``ops/pallas/delta_glue.py``); neither keeps a copy."""
    from paddle_tpu.models import kimi_linear
    from paddle_tpu.ops.pallas import delta_glue
    for module in (qn, kimi_linear):
        assert not hasattr(module, "_short_conv")
        assert not hasattr(module, "_l2_normalize")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    got = delta_glue.short_conv(x, taps)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got[b]), np.asarray(reference._conv_silu(x[b], taps)),
            rtol=1e-5, atol=1e-6)
    # causal: position t sees t - 3 .. t
    moved = delta_glue.short_conv(x.at[:, 5].add(1.0), taps)
    assert np.array_equal(np.asarray(moved[:, :5]), np.asarray(got[:, :5]))
    assert not np.allclose(np.asarray(moved[:, 5:9]), np.asarray(got[:, 5:9]))
    np.testing.assert_allclose(
        np.asarray(delta_glue.l2_normalize(x, 0.5)),
        0.5 * np.asarray(reference._l2(x)), rtol=1e-5)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def test_the_tiny_preset_has_both_mixers_and_experts_everywhere(tiny):
    assert [tiny.mixer(i) for i in range(4)] == [qn.LINEAR] * 3 + [qn.FULL]
    full = qn.qwen3_next_80b_a3b()
    assert [i for i in range(48) if full.mixer(i) == qn.FULL] \
        == list(range(3, 48, 4))
    params = qn.init_params(jax.random.PRNGKey(0), tiny)
    for lp in params["layers"][:3]:
        assert lp["qkvz_w"].shape == (64, 2 * 32 + 2 * 64)
        assert lp["ba_w"].shape == (64, 8) and lp["conv"].shape == (4, 128)
        assert lp["A_log"].shape == lp["dt_bias"].shape == (4,)
        assert (np.asarray(lp["o_norm_g"]) == 1).all()     # a plain gain
    last = params["layers"][3]
    assert last["q_w"].shape == (64, 8 * 2 * 32)       # queries and gates
    assert last["k_w"].shape == last["v_w"].shape == (64, 2 * 32)
    for lp in params["layers"]:
        assert lp["router_w"].shape == (64, 16)      # routes over all 16
        assert lp["w_gate"].shape == (4, 64, 32)     # holds 4 of them
        assert lp["shared_scale_w"].shape == (64,)   # a scalar a token
        assert "router_bias" not in lp
        assert not np.asarray(lp["ln1_w"]).any()     # gains 1 + w, w from 0
    specs = qn.param_specs(tiny)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(s, type(
                specs["embed"]))))
    with pytest.raises(ValueError, match="multiple"):
        qn.qwen3_next_tiny(linear_key_heads=3)


def test_published_sizes_count_626_m_parameters_for_the_cut():
    """One of 16 chips: 32 of 512 experts a layer, the vocabulary's padded
    eighth, the published layers 0 to 3 (ISSUE 38's table)."""
    cfg = qn.qwen3_next_80b_a3b(num_layers=4, vocab_size=19072,
                                experts_held=(0, 32))
    shapes = jax.eval_shape(lambda: qn.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    count = lambda tree: sum(int(np.prod(a.shape))      # noqa: E731
                             for a in jax.tree.leaves(tree))
    assert count(shapes) == 625_994_816
    assert [count(lp) for lp in shapes["layers"]] \
        == [138_582_208] * 3 + [132_127_232]
    lp = shapes["layers"][0]
    mixer = count({k: lp[k] for k in ("qkvz_w", "ba_w", "conv", "out_w")})
    assert mixer == 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048
    assert round(mixer / 1e6, 2) == 33.72
    experts = count({k: v for k, v in lp.items()
                     if k.startswith(("router", "w_", "shared"))})
    assert round(experts / 1e6, 2) == 104.86
    full = shapes["layers"][3]
    attention = count({k: full[k] for k in ("q_w", "k_w", "v_w", "o_w")})
    assert round(attention / 1e6, 2) == 27.26
    assert 8 * 19072 == 152576 >= 151936 and 19072 % 128 == 0
    assert count((shapes["embed"], shapes["head_w"])) == 2 * 19072 * 2048


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_loss_outputs_and_every_gradient_match_the_reference(tiny, seed):
    params, batch = seeded(tiny, seed)
    config = reference_config(tiny)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: qn.lm_loss(p, tiny, batch))(params)
        parts, aux = qn.stages(params, tiny, batch["input_ids"])
        hidden = qn.forward(params, tiny, batch["input_ids"])
    want_loss, want_parts = reference.loss_and_outputs(params, config, batch)
    assert parts.shape == (2 * tiny.num_layers + 2, *batch["input_ids"].shape,
                           tiny.hidden)
    assert relative_error(loss, want_loss) < 1e-5
    # every part of the pass, each over its norm: the embedding, the stream
    # after each mixer and each expert layer, the final normed hidden states
    assert relative_error(over_norms(parts), want_parts) < 1e-4
    for index in range(parts.shape[0]):
        assert relative_error(over_norms(parts)[index],
                              want_parts[index]) < 1e-4, index
    assert relative_error(parts[-1], hidden) == 0
    counts, choice = qn.routing_stats(params, tiny, batch, choices=True)
    assert (np.asarray(aux["counts"]) == counts).all()
    assert (np.asarray(aux["choice"]) == choice).all()
    want = jax.grad(lambda p: reference.loss(p, config, batch))(params)
    assert relative_error(reference.loss(params, config, batch),
                          want_loss) < 1e-6
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
        assert relative_error(got, ref) < 2e-3, jax.tree_util.keystr(path)


def test_the_loss_holds_the_balancing_term(tiny):
    """``balance_weight`` times the mean over the layers of
    ``moe.balance_loss`` over all the router's outputs."""
    params, batch = seeded(tiny)
    plain = dataclasses.replace(tiny, balance_weight=0.0)
    _, aux = qn.stages(params, tiny, batch["input_ids"])
    assert aux["balance"].shape == (4,)
    assert float(qn.lm_loss(params, tiny, batch)
                 - qn.lm_loss(params, plain, batch)) == pytest.approx(
        0.001 * float(jnp.mean(aux["balance"])), rel=1e-3)
    assert 0.9 < float(jnp.mean(aux["balance"])) < 4.0


def test_bfloat16_program_is_within_reach_of_the_reference(tiny):
    """The program's own dtype, under its own admissible routing, each part
    held to float32 on the program's own state before it. Below the
    configuration's precision the comparison fails: every part's states in 4
    stored bits, the decay's running sum in bfloat16, a router that chooses
    by rounded logits; and so does a fault in one part (the output
    gate left off the attention layer)."""
    cfg = dataclasses.replace(tiny, dtype=jnp.bfloat16)
    params, batch = seeded(cfg, seed=1)
    config = reference_config(cfg)
    parts, aux = qn.stages(params, cfg, batch["input_ids"])

    def sample_of(parts, aux):
        return dict(batch, program_stream=np.asarray(parts),
                    program_choice=np.asarray(aux["choice"]).reshape(
                        4, *batch["input_ids"].shape, -1))

    sample = sample_of(parts, aux)
    want_loss, want_parts = reference.loss_and_outputs(params, config, sample)
    assert np.isfinite(np.asarray(want_parts)).all()     # admissible
    assert relative_error(qn.lm_loss(params, cfg, batch), want_loss) < 2e-3
    sound = relative_error(over_norms(parts), want_parts)
    sound_parts = want_parts
    assert sound < reference.TOLERANCE["outputs"]
    # the controls: a precision below the configuration's
    _, low = reference.loss_and_outputs(params, config, sample, state_bits=4)
    assert relative_error(low, want_parts) > reference.TOLERANCE["outputs"]
    _, same = reference.loss_and_outputs(params, config, sample, state_bits=7)
    assert relative_error(same, want_parts) < reference.TOLERANCE["outputs"]
    _, summed = reference.loss_and_outputs(params, config, sample,
                                           decay_bits=7)
    assert relative_error(summed, want_parts) > relative_error(
        same, want_parts)
    # (160 tokens a layer and logits of a tenth here: 2 stored bits of the
    # router's logits show what bfloat16's 7 show on the cell's 16 384 tokens
    # and logits of 2 to 3, PERF.md section 6, PR 38)
    _, routed = reference.loss_and_outputs(params, config, sample,
                                           router_bits=2)
    assert np.isnan(np.asarray(routed)).all()           # a wrong router
    # a fault: no gate on the attention layer's output (sigmoid -> 1/2)
    wrong = jax.tree.map(lambda a: a, params)
    d = cfg.head_dim
    q_w = params["layers"][3]["q_w"].reshape(cfg.hidden, cfg.num_heads, 2, d)
    wrong["layers"][3]["q_w"] = q_w.at[:, :, 1].set(0.0).reshape(
        cfg.hidden, -1)
    faulty, aux = qn.stages(wrong, cfg, batch["input_ids"])
    _, want_parts = reference.loss_and_outputs(params, config,
                                               sample_of(faulty, aux))
    far = relative_error(over_norms(faulty), want_parts)
    assert far > reference.TOLERANCE["outputs"] and far > 2.5 * sound
    # the part at fault (the stream after layer 3's mixer) alone
    assert relative_error(over_norms(faulty)[7], want_parts[7]) \
        > 5 * relative_error(over_norms(parts)[7], sound_parts[7])


def test_the_decay_summed_in_bfloat16_is_another_rule():
    """The control ``decay_bits``: the log decay as a rule that keeps its
    running sum inside a block of 64 positions in bfloat16 would see it,
    against the decay itself."""
    g = -jnp.exp(jax.random.normal(jax.random.PRNGKey(0), (200, 4)) - 1.0)
    same = reference._summed_in(g, 23)
    np.testing.assert_allclose(np.asarray(same), np.asarray(g), atol=1e-5)
    low = reference._summed_in(g, 7)
    assert low.shape == g.shape
    assert 1e-3 < float(jnp.max(jnp.abs(low - g))) < 0.5


def test_train_step_lowers_the_loss_and_keeps_the_routers_counts(tiny):
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = qn.make_train_step(tiny, pt.optimizer.Adam(1e-3), mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = qn.synthetic_batch(tiny, 2, 48)
    losses = []
    for _ in range(4):
        loss, params, opt_state = step_fn(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses
    counts = np.asarray(step_fn.aux[0])              # the last step's load
    assert counts.shape == (4, 16) and (counts.sum(axis=1) == 4 * 96).all()
    from paddle_tpu.models import lm_trainer
    assert qn.make_train_step.__func__ is lm_trainer.Decoder.make_train_step
    counts, choice = qn.routing_stats(params, tiny, batch, choices=True)
    assert counts.shape == (4, 16) and choice.shape == (4, 96, 4)
    assert choice.max() > 7                 # experts this chip does not hold


@pytest.mark.parametrize("layers", [4, 8])
def test_a_step_traces_each_kernel_once_a_layer_type(layers, monkeypatch):
    """The delta-rule and flash calls are jitted functions of their own: a
    layer type's layers share one trace of each, however many they are
    (PERF.md section 6, PR 29 and 32)."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    pk = importlib.import_module("paddle_tpu.ops.pallas.kda")
    cfg = qn.qwen3_next_tiny(num_layers=layers, linear_key_dim=128,
                             linear_value_dim=128, experts_held=(0, 4))
    traced = []

    def counting(module, name):
        kernel = getattr(module, name)

        def counted(*a, **kw):
            traced.append(name)
            return kernel(*a, **kw)

        monkeypatch.setattr(module, name, counted)

    counting(fa, "_flash_fwd_kernel")
    counting(pk, "_head_decay_fwd_kernel")
    for jitted in (fa._flash_fwd, pk._gdn_fwd):
        jitted.clear_cache()
    params = qn.init_params(jax.random.PRNGKey(0), cfg)
    batch = qn.synthetic_batch(cfg, 1, 1024)
    with plk.override("on"):
        jax.jit(lambda p: qn.lm_loss(p, cfg, batch)).lower(params)
    for jitted in (fa._flash_fwd, pk._gdn_fwd):
        jitted.clear_cache()
    assert sorted(traced) == ["_flash_fwd_kernel", "_head_decay_fwd_kernel"]


@pytest.mark.parametrize("layers", [4, 8])
def test_a_training_step_enters_each_kernel_body_once(layers, monkeypatch):
    """The whole step, backward and the recomputed mixers included: the
    delta rule's kernels and the passes around it are entered once a
    program however many Gated DeltaNet layers call them (the convolution's
    for q and k, normed and 128 wide, and for v, plain and 256 wide: two
    programs each way). The mixers are under ``jax.checkpoint``, whose JVP
    jax traces under an empty abstract mesh, a trace context of its own, so a
    forward kernel would be traced and lowered twice (the cell's ``setup_s``
    read +14% with that, over its bound: PERF.md section 6, PR 39);
    ``registry.traced_once`` gives both one context. Observed on jax 0.9.0:
    this count is the guard when jax moves."""
    from paddle_tpu.ops.pallas import delta_glue as glue_mod
    from paddle_tpu.ops.pallas import kda as kda_mod
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

    entered = {}

    def counted(module, name):
        body = getattr(module, name)

        def enter(*args, **kw):
            entered[name] = entered.get(name, 0) + 1
            return body(*args, **kw)

        monkeypatch.setattr(module, name, enter)

    for module, names in ((kda_mod, ("_head_decay_fwd_kernel",
                                     "_head_decay_bwd_kernel")),
                          (glue_mod, ("_conv_fwd_kernel", "_conv_bwd_kernel",
                                      "_gate_fwd_kernel",
                                      "_gate_bwd_kernel"))):
        for name in names:
            counted(module, name)
    cfg = qn.qwen3_next_tiny(num_layers=layers, linear_key_dim=128,
                             linear_value_dim=128, experts_held=(0, 4))
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = qn.make_train_step(cfg, pt.optimizer.Adam(1e-3), mesh)
    params, opt_state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = jax.eval_shape(step_fn.place, qn.synthetic_batch(cfg, 1, 256))
    jax.clear_caches()            # what earlier tests of this process traced
    with plk.override("on"):
        step_fn.jitted.trace(params, opt_state, batch)
    assert entered == {"_head_decay_fwd_kernel": 1,
                       "_head_decay_bwd_kernel": 1,
                       "_conv_fwd_kernel": 2, "_conv_bwd_kernel": 2,
                       "_gate_fwd_kernel": 1, "_gate_bwd_kernel": 1}, entered


# ---------------------------------------------------------------------------
# the expert layer's share: sixteenths, as the cell cuts it
# ---------------------------------------------------------------------------
def expert_layer(seed=0, d=32, f=16, experts=32, tokens=96):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    lp = {"router_w": jax.random.normal(ks[0], (d, experts)),
          "w_gate": 0.3 * jax.random.normal(ks[2], (experts, d, f)),
          "w_up": 0.3 * jax.random.normal(ks[3], (experts, d, f)),
          "w_down": 0.3 * jax.random.normal(ks[4], (experts, f, d)),
          "shared_gate": 0.3 * jax.random.normal(ks[5], (d, f)),
          "shared_up": 0.3 * jax.random.normal(ks[6], (d, f)),
          "shared_down": 0.3 * jax.random.normal(ks[7], (f, d)),
          "shared_scale_w": jax.random.normal(ks[1], (d,))}
    return lp, jax.random.normal(ks[8], (tokens, d))


SCORING = moe.Scoring("softmax", renormalize=True)


def test_the_shares_of_16_chips_add_up_to_the_uncut_layer():
    """The share test at the cell's cut: the experts over 16 chips (here 32
    experts, 2 a chip, where the cell holds 32 of 512), a softmax router, 10
    a token, renormalised. The routed part each share computes, summed over
    the shares, plus what every chip computes alike (the shared expert times
    its scalar gate) counted once, is the uncut reference layer. In float32,
    so the experts chosen are the same everywhere."""
    lp, x = expert_layer(seed=1)
    config = {"num_experts_per_tok": 10, "experts_held": [0, 32]}
    alike = ("shared_gate", "shared_up", "shared_down", "shared_scale_w")
    with jax.default_matmul_precision("highest"):
        want, probs, used, _ = reference._experts(lp, x, config)
        routed = jnp.zeros_like(x)
        rows = 0
        for chip in range(16):
            first = 2 * chip
            share = {k: v[first:first + 2] if k.startswith("w_") else v
                     for k, v in lp.items() if k not in alike}
            part, aux = moe.dropless_moe_ffn(share, x, 10, scoring=SCORING,
                                             held=(first, 2))
            assert aux["counts"].shape == (32,)     # over all the router's
            rows += int(aux["counts"][first:first + 2].sum())
            routed = routed + part
        shared = jax.nn.sigmoid(x @ lp["shared_scale_w"])[:, None] \
            * reference._gated(x, lp["shared_gate"], lp["shared_up"],
                               lp["shared_down"])
    assert rows == int(used.sum()) == 10 * 96     # every assignment, once
    np.testing.assert_allclose(np.asarray(probs.sum(axis=-1)), 1.0, rtol=1e-5)
    assert relative_error(routed + shared, want) < 1e-5
    # and one share with its shared expert is the reference given that share
    share = {k: v[6:8] if k.startswith("w_") else v for k, v in lp.items()}
    with jax.default_matmul_precision("highest"):
        got, _ = moe.dropless_moe_ffn(share, x, 10, scoring=SCORING,
                                      held=(6, 2))
        want, *_ = reference._experts(share, x, dict(config,
                                                     experts_held=[6, 2]))
    assert relative_error(got, want) < 1e-5


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["all", "held"])
def test_the_shared_expert_s_scale_is_a_scalar_a_token(held):
    """``shared_scale_w`` multiplies the shared expert's output by
    ``sigmoid(x . w)``; without it the layer is what it was, and with a zero
    vector the shared expert counts half; its gradient reaches ``w``."""
    lp, x = expert_layer(seed=2)
    if held:
        lp = {k: v[held[0]:held[0] + held[1]] if k.startswith("w_") else v
              for k, v in lp.items()}
    plain = {k: v for k, v in lp.items() if k != "shared_scale_w"}
    none = {k: v for k, v in plain.items() if not k.startswith("shared_")}
    with jax.default_matmul_precision("highest"):
        run = lambda p: moe.dropless_moe_ffn(       # noqa: E731
            p, x, 10, scoring=SCORING, held=held)[0]
        shared = run(plain) - run(none)
        scale = jax.nn.sigmoid(x @ lp["shared_scale_w"])[:, None]
        assert relative_error(run(lp) - run(none), scale * shared) < 1e-5
        half = dict(lp, shared_scale_w=jnp.zeros_like(lp["shared_scale_w"]))
        assert relative_error(run(half) - run(none), 0.5 * shared) < 1e-5
        grad = jax.grad(lambda w: jnp.sum(run(dict(lp, shared_scale_w=w))))(
            lp["shared_scale_w"])
        want = jax.grad(lambda w: jnp.sum(
            jax.nn.sigmoid(x @ w)[:, None] * shared))(lp["shared_scale_w"])
        assert relative_error(grad, want) < 1e-4


def test_a_router_at_par_takes_one_pass_up_to_a_share_of_15_percent():
    """6.25% of 10 x 16 384 assignments are 10 240 rows: the layer takes
    twice that in whole tiles, 24 576 rows a pass: one pass up to a share of
    15%, a second beyond."""
    assignments = 10 * 16384
    assert assignments * 32 // 512 == 10240
    tile = moe._held_row_tile(assignments, 32, 512)
    assert tile == 3 * moe.HELD_ROW_TILE == 24576
    assert tile / assignments == 0.15
    assert -(-10240 // tile) == 1 and -(-24576 // tile) == 1 \
        and -(-24577 // tile) == 2
    assert moe._held_row_tile(4 * 160, 4, 16) == 4 * 160   # a tiny layer
