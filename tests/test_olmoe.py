"""OLMoE on the training path, against the plain reference of the benchmark.

``chipbench/reference/olmoe.py`` is written from the paper's equations in
float32 ``jax.numpy`` and shares no code with ``paddle_tpu``; it reads the
program's parameter tree by its key names. Here, on the CPU at
``olmoe_tiny``'s sizes and seeded random weights: loss, final hidden states
and the gradient of every parameter leaf, in float32 and in the program's
bfloat16; routing that drops nothing under a load the capacity layer would
drop most of; invariance to the order of the tokens; RoPE and the
whole-width query/key norm against their formulas.
"""

import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import blocks, olmoe
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("chipbench/reference/olmoe.py", "reference_olmoe")


def reference_config(cfg):
    """The keys the reference reads of a configuration file."""
    return {"num_attention_heads": cfg.num_heads,
            "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.experts_per_token,
            "router_aux_loss_coef": cfg.balance_weight,
            "router_z_loss_coef": cfg.z_weight}


def relative_error(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def seeded(cfg, seed=0, rows=3, seq=64):
    params = olmoe.init_params(jax.random.PRNGKey(seed), cfg)
    # gains away from 1, so that a norm applied in the wrong place shows
    params = jax.tree.map(
        lambda a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32))
        .reshape(a.shape) if a.ndim == 1 else a, params)
    batch = olmoe.synthetic_batch(cfg, rows, seq, seed=seed + 1)
    return params, batch


@pytest.mark.parametrize("dtype,tolerance", [
    (jnp.float32, {"loss": 1e-5, "outputs": 1e-5, "grads": 2e-5}),
    (jnp.bfloat16, dict(reference.TOLERANCE, grads=5e-2))],
    ids=["float32", "bfloat16"])
def test_loss_hidden_states_and_every_gradient_match_the_reference(
        dtype, tolerance):
    cfg = olmoe.olmoe_tiny(dtype=dtype)
    params, batch = seeded(cfg)
    config = reference_config(cfg)
    loss, grads = jax.value_and_grad(
        lambda p: olmoe.lm_loss(p, cfg, batch))(params)
    hidden = olmoe.forward(params, cfg, batch["input_ids"])
    want_loss, want_hidden = reference.loss_and_outputs(params, config, batch)
    want_grads = jax.grad(
        lambda p: reference.loss_and_outputs(p, config, batch)[0])(params)
    assert relative_error(loss, want_loss) <= tolerance["loss"]
    assert relative_error(hidden, want_hidden) <= tolerance["outputs"]
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = jax.tree.leaves(want_grads)
    assert len(flat) == 4 + 12 * cfg.num_layers - 1 == len(want_flat)
    for (path, got), want in zip(flat, want_flat):
        assert float(jnp.linalg.norm(want)) > 0, path
        assert relative_error(got, want) <= tolerance["grads"], \
            jax.tree_util.keystr(path)


def test_the_reference_holds_the_programs_choices_to_its_own_probabilities(
        capsys):
    """With ``program_choice`` on the batch (the chip benchmark's probe
    leaves it there) the reference checks each choice against its own
    float32 probabilities and computes with it: the program's own choices
    are admissible and change nothing here, in float32; a router that ranks
    by something else is refused with hidden states of NaN."""
    cfg = olmoe.olmoe_tiny(dtype=jnp.float32)
    params, batch = seeded(cfg)
    config = reference_config(cfg)
    want_loss, want_hidden = reference.loss_and_outputs(params, config, batch)
    counts, choice = olmoe.routing_stats(params, cfg, batch, choices=True)
    assert counts.sum() == choice.size
    shape = (cfg.num_layers, *batch["input_ids"].shape, -1)
    told = dict(batch, program_choice=choice.reshape(shape))
    loss, hidden = reference.loss_and_outputs(params, config, told)
    assert "0 of 768" in capsys.readouterr().out
    assert relative_error(hidden, want_hidden) < 1e-6
    assert relative_error(loss, want_loss) < 1e-6
    wrong = dict(batch, program_choice=(choice.reshape(shape) + 1)
                 % cfg.num_experts)
    loss, hidden = reference.loss_and_outputs(params, config, wrong)
    assert "A WRONG ROUTER" in capsys.readouterr().out
    assert np.isnan(np.asarray(hidden)).all()
    # a near-tie may fall either way: swap a token's last choice for the
    # next most probable where the two are within the margin
    logits = reference._all(params, config, batch)[2]
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    ranked = np.argsort(-probs, axis=-1)
    k = cfg.experts_per_token
    ratio = np.take_along_axis(probs, ranked[..., k:k + 1], -1) \
        / np.take_along_axis(probs, ranked[..., k - 1:k], -1)
    layer, token = np.unravel_index(np.argmax(ratio[..., 0]), ratio.shape[:2])
    assert ratio[layer, token, 0] > 1 - reference.ROUTER_MARGIN
    swapped = np.sort(ranked[..., :k], axis=-1)
    near = np.sort(np.r_[ranked[layer, token, :k - 1], ranked[layer, token, k]])
    swapped[layer, token] = near
    loss, hidden = reference.loss_and_outputs(
        params, config, dict(batch, program_choice=swapped.reshape(shape)))
    assert "1 of 768" in capsys.readouterr().out
    assert np.isfinite(np.asarray(hidden)).all()
    assert 0 < relative_error(hidden, want_hidden) < 0.2


def crowded(cfg, tokens=96, seed=0):
    """A layer's parameters and inputs under which expert 0 is every
    token's first choice: one constant feature that only its router column
    reads."""
    lp = olmoe.init_params(jax.random.PRNGKey(seed), cfg)["layers"][0]
    x = np.random.RandomState(seed).randn(tokens, cfg.hidden) \
        .astype(np.float32)
    x[:, 0] = 4.0
    lp["router_w"] = lp["router_w"].at[0, :].set(0.0).at[0, 0].set(3.0)
    return lp, jnp.asarray(x)


def test_no_assignment_is_dropped_when_one_expert_takes_most_tokens():
    cfg = olmoe.olmoe_tiny(dtype=jnp.float32)
    lp, x = crowded(cfg)
    tokens, k = x.shape[0], cfg.experts_per_token
    y, aux = moe.dropless_moe_ffn(lp, x, k)
    counts = np.asarray(aux["counts"])
    assert counts.sum() == k * tokens and counts[0] == tokens
    # the capacity layer beside it would have kept this many of them
    capacity = moe.MoEConfig(cfg.hidden, cfg.expert_width, cfg.num_experts,
                             top_k=k).capacity(tokens)
    assert capacity < tokens / 2
    want, logits, chosen, _ = reference._experts(lp, x,
                                                 reference_config(cfg))
    assert np.asarray(chosen)[:, 0].all()
    assert relative_error(y, want) < 1e-5
    assert float(aux["balance"]) > 2.0            # 1 is balanced, 8 collapsed
    np.testing.assert_allclose(
        float(aux["z"]),
        float(jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)), rtol=1e-5)
    # and the gradient reaches every expert that took a token
    g = jax.grad(lambda p: jnp.sum(moe.dropless_moe_ffn(p, x, k)[0] ** 2))(lp)
    took = counts > 0
    assert (np.abs(np.asarray(g["w_down"])).sum(axis=(1, 2)) > 0).tolist() \
        == took.tolist()


def test_the_expert_layer_does_not_depend_on_the_order_of_the_tokens():
    cfg = olmoe.olmoe_tiny(dtype=jnp.float32)
    lp, x = crowded(cfg, seed=1)
    perm = np.random.RandomState(2).permutation(x.shape[0])
    y, aux = moe.dropless_moe_ffn(lp, x, cfg.experts_per_token)
    yp, auxp = moe.dropless_moe_ffn(lp, x[perm], cfg.experts_per_token)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(y)[perm],
                               rtol=1e-5, atol=1e-6)
    assert np.array_equal(aux["counts"], auxp["counts"])
    assert np.array_equal(np.asarray(aux["choice"])[perm], auxp["choice"])


def test_one_expert_taken_by_every_token_is_the_dense_gated_ffn():
    cfg = olmoe.olmoe_tiny(num_experts=1, experts_per_token=1,
                           dtype=jnp.float32)
    lp = olmoe.init_params(jax.random.PRNGKey(3), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, cfg.hidden))
    y, aux = moe.dropless_moe_ffn(lp, x, 1)
    want = blocks.gated_ffn(x, lp["w_gate"][0], lp["w_up"][0],
                            lp["w_down"][0])       # the one probability is 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert float(aux["balance"]) == pytest.approx(1.0)


def test_rope_turns_each_pair_by_its_position_and_frequency():
    s, n, d, theta = 12, 2, 16, 10000.0
    x = np.random.RandomState(0).randn(1, s, n, d).astype(np.float32)
    got = np.asarray(blocks.apply_rope(
        jnp.asarray(x), *blocks.rope_angles(s, d, theta)))
    for p in (0, 5, 11):
        for i in (0, 3, 7):
            angle = p * theta ** (-2 * i / d)
            a, b = x[0, p, :, i], x[0, p, :, i + d // 2]
            np.testing.assert_allclose(
                got[0, p, :, i], a * math.cos(angle) - b * math.sin(angle),
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                got[0, p, :, i + d // 2],
                b * math.cos(angle) + a * math.sin(angle), rtol=1e-5,
                atol=1e-6)
    # a rotation: norms stay, and q.k depends on the distance alone
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    same = np.broadcast_to(x[:, :1], x.shape)
    turned = np.asarray(blocks.apply_rope(
        jnp.asarray(same), *blocks.rope_angles(s, d, theta)))
    dots = np.einsum("qnd,knd->nqk", turned[0], turned[0])
    np.testing.assert_allclose(dots[:, 2, 5], dots[:, 7, 10], rtol=1e-4)


def test_query_and_key_are_normalised_over_their_whole_width():
    """RMSNorm with 64 gains over the 64-wide projection before the split
    into 4 heads of 16 (the released model), not 16 gains a head: with
    dense attention and one position the context is v, so the norm shows
    in the scores only; check the pieces the block is made of."""
    cfg = olmoe.olmoe_tiny(dtype=jnp.float32)
    params, batch = seeded(cfg)
    lp = params["layers"][0]
    assert lp["q_norm_g"].shape == (cfg.num_heads * cfg.head_dim,)
    x = jax.random.normal(jax.random.PRNGKey(0), (5, cfg.hidden))
    q = x @ lp["q_w"]
    want = q / np.sqrt(np.mean(np.square(q), axis=-1, keepdims=True)
                       + cfg.rms_eps) * lp["q_norm_g"]
    np.testing.assert_allclose(
        np.asarray(blocks.rms_norm(q, lp["q_norm_g"], cfg.rms_eps)),
        np.asarray(want), rtol=1e-5, atol=1e-6)
    per_head = q.reshape(5, cfg.num_heads, -1)
    per_head = per_head / np.sqrt(
        np.mean(np.square(per_head), axis=-1, keepdims=True) + cfg.rms_eps)
    assert relative_error(per_head.reshape(5, -1) * lp["q_norm_g"],
                          want) > 0.05


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_causal_attention_sees_no_later_position(impl):
    b, s, n, d = 2, 24, 2, 16
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, s, n, d))
               for i in range(3))
    got = blocks.causal_attention(q, k, v, impl)
    later = blocks.causal_attention(q, k.at[:, 12:].add(3.0),
                                    v.at[:, 12:].add(-2.0), impl)
    np.testing.assert_allclose(np.asarray(got[:, :12]),
                               np.asarray(later[:, :12]), rtol=1e-5,
                               atol=1e-6)
    scores = np.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.einsum("bnqk,bknd->bqnd", probs, v),
                               rtol=2e-5, atol=2e-6)


def test_train_step_learns_donates_and_hands_out_its_parts():
    cfg = olmoe.olmoe_tiny()
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    init_fn, step_fn = olmoe.make_train_step(cfg, pt.optimizer.Adam(1e-3),
                                             mesh)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = olmoe.synthetic_batch(cfg, 4, 32)
    first = params["head_w"]
    losses = []
    for _ in range(4):
        out = step_fn(params, opt_state, batch)
        assert len(out) == 3
        loss, params, opt_state = out
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3
    assert first.is_deleted()                      # donated
    assert hasattr(step_fn.jitted, "lower")
    placed = step_fn.place(batch)
    assert set(placed) == {"input_ids", "labels"}
    stats = olmoe.routing_stats(params, cfg, batch)
    assert stats.shape == (cfg.num_layers, cfg.num_experts)
    assert (stats.sum(axis=1) == cfg.experts_per_token * 4 * 32).all()


def test_data_and_model_parallel_mesh_gives_the_one_device_losses():
    cfg = olmoe.olmoe_tiny()
    batch = olmoe.synthetic_batch(cfg, 4, 32)
    losses = {}
    for name, mesh_cfg, n in (("one", MeshConfig(data=1), 1),
                              ("dp2_mp2", MeshConfig(data=2, model=2), 4)):
        mesh = make_mesh(mesh_cfg, devices=jax.devices()[:n])
        init_fn, step_fn = olmoe.make_train_step(
            cfg, pt.optimizer.Adam(1e-3), mesh)
        params, opt_state = init_fn(jax.random.PRNGKey(0))
        losses[name] = []
        for _ in range(3):
            loss, params, opt_state = step_fn(params, opt_state, batch)
            losses[name].append(float(loss))
    np.testing.assert_allclose(losses["dp2_mp2"], losses["one"], rtol=2e-3)


def test_the_published_preset_has_the_published_sizes():
    cfg = olmoe.olmoe_1b_7b()
    assert (cfg.hidden, cfg.num_layers, cfg.num_heads, cfg.head_dim,
            cfg.expert_width, cfg.num_experts, cfg.experts_per_token,
            cfg.vocab_size, cfg.max_seq) == (2048, 16, 16, 128, 1024, 64, 8,
                                             50304, 4096)
    shapes = jax.eval_shape(lambda k: olmoe.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    sizes = [int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)]
    assert sum(sizes) == 6_919_161_856            # "7B"
    layer = sum(int(np.prod(a.shape))
                for a in jax.tree.leaves(shapes["layers"][0]))
    assert layer == 419_569_664                   # 402.7 M in its experts
    assert sum(sizes) - 16 * layer == 2 * 50304 * 2048 + 2048
