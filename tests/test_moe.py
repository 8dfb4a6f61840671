"""Expert-parallel MoE tests (EP — beyond the 2019 reference, SURVEY
§2.5 stretch row): routing correctness vs a per-token reference loop,
capacity dropping, load-balance aux loss, gradient flow, and
expert-sharded parity on the 8-device virtual mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import (
    EXPERT_AXIS, MeshConfig, make_mesh,
)


def _ffn_e(params, e, x):
    h = np.maximum(x @ np.asarray(params["w1"][e])
                   + np.asarray(params["b1"][e]), 0)
    return h @ np.asarray(params["w2"][e]) + np.asarray(params["b2"][e])


def _reference(params, cfg, xt):
    """Per-token loop: top-k experts, renormalized gates, no drops."""
    gates = np.asarray(jax.nn.softmax(
        xt @ np.asarray(params["gate_w"]), axis=-1))
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        idx = np.argsort(-gates[t])[:cfg.top_k]
        w = gates[t, idx] / gates[t, idx].sum()
        for j, e in enumerate(idx):
            out[t] += w[j] * _ffn_e(params, e, xt[t])
    return out


class TestMoE:
    def _setup(self, top_k=2, cf=8.0, e=4, d=6, h=8, t=16, seed=0):
        cfg = moe.MoEConfig(d_model=d, d_hidden=h, num_experts=e,
                            top_k=top_k, capacity_factor=cf)
        params = moe.init_moe_params(jax.random.PRNGKey(seed), cfg)
        x = np.random.RandomState(seed).randn(t, d).astype(np.float32)
        return cfg, params, x

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_matches_reference_when_capacity_ample(self, top_k):
        cfg, params, x = self._setup(top_k=top_k)
        y, aux = moe.moe_ffn(params, cfg, jnp.asarray(x))
        want = _reference(params, cfg, x)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4,
                                   atol=1e-5)
        assert float(aux) > 0

    def test_capacity_drops_overflow_tokens(self):
        """capacity_factor small enough that some tokens overflow: the
        dropped claims contribute zero (residual path carries them) and
        nothing crashes."""
        cfg, params, x = self._setup(top_k=1, cf=0.25)
        y, _ = moe.moe_ffn(params, cfg, jnp.asarray(x))
        want = _reference(params, cfg, x)
        kept_rows = np.isclose(np.asarray(y), want, rtol=1e-4,
                               atol=1e-5).all(axis=-1)
        dropped_rows = np.isclose(np.asarray(y), 0.0).all(axis=-1)
        assert kept_rows.sum() > 0
        assert dropped_rows.sum() > 0
        assert (kept_rows | dropped_rows).all()

    def test_gradients_flow_to_all_parts(self):
        cfg, params, x = self._setup()

        def loss(p):
            y, aux = moe.moe_ffn(p, cfg, jnp.asarray(x))
            return jnp.sum(y ** 2) + 0.01 * aux

        g = jax.grad(loss)(params)
        for k in ("gate_w", "w1", "w2", "b1", "b2"):
            assert float(jnp.abs(g[k]).sum()) > 0, k

    def test_expert_sharded_matches_single_device(self):
        """Experts over a 4-way "expert" axis (+2-way data) == the
        unsharded computation; the mesh carries the EP all_to_all."""
        cfg, params, x = self._setup(t=32)
        want, aux_want = moe.moe_ffn(params, cfg, jnp.asarray(x))

        mesh = make_mesh(MeshConfig(data=2, expert=4))
        assert dict(mesh.shape)[EXPERT_AXIS] == 4
        specs = moe.moe_param_specs()
        pl = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
        xd = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))

        @jax.jit
        def run(p, xv):
            return moe.moe_ffn(p, cfg, xv, mesh=mesh)

        y, aux = run(pl, xd)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(aux), float(aux_want),
                                   rtol=1e-5)

    def test_load_balance_loss_prefers_uniform(self):
        """The aux value moe_ffn RETURNS: ~1 for a uniform router, ~E
        for a collapsed router — and the collapse penalty must survive
        tight capacity (pre-drop dispatch fraction, the Switch
        definition; a post-drop fraction masks collapse exactly when
        drops begin)."""
        cfg, params, x = self._setup(top_k=1, e=4, cf=0.25)
        # uniform router: zero gate weights -> equal gates
        params_u = dict(params, gate_w=jnp.zeros_like(params["gate_w"]))
        _, aux_u = moe.moe_ffn(params_u, cfg, jnp.asarray(x))
        np.testing.assert_allclose(float(aux_u), 1.0, rtol=0.35)
        # collapsed router: every token to expert 0, capacity tight
        params_c = dict(params, gate_w=jnp.zeros_like(
            params["gate_w"]).at[0, 0].set(50.0))
        xc = np.abs(x) + 0.5          # positive feature 0 -> expert 0
        _, aux_c = moe.moe_ffn(params_c, cfg, jnp.asarray(xc))
        assert float(aux_c) > 3.0, float(aux_c)
        assert float(aux_c) > float(aux_u) * 2


# ---------------------------------------------------------------------------
# the router's selection and counts against their plain forms
# ---------------------------------------------------------------------------
def _plain_top_k(probs, bias, top_k):
    """What ``moe._biased_top_k`` stands for: the experts by ``lax.top_k``
    of the biased scores, the scores gathered at them."""
    _, top_e = jax.lax.top_k(probs + jax.lax.stop_gradient(bias), top_k)
    return jnp.take_along_axis(probs, top_e, axis=-1), top_e


def _plain_counts(top_e, experts):
    return jnp.bincount(top_e.reshape(-1), length=experts)


_SIGMOID = dict(activation="sigmoid", renormalize=True)
#: name: router width, experts a token, scoring, selection bias, held,
#: gated experts, latent width, tokens. The first seven are the expert
#: cells' routers (chipbench/configs) at a few tokens.
ROUTERS = {
    "nemotron_3_super": (512, 22, moe.Scoring(scale=5.0, **_SIGMOID), True,
                         (0, 8), False, 8, 40),
    "lfm2": (64, 4, moe.Scoring(**_SIGMOID), True, (0, 8), True, None, 40),
    "laguna": (256, 8, moe.Scoring(scale=2.5, **_SIGMOID), True, (0, 32),
               True, None, 40),
    "kanana_2": (128, 6, moe.Scoring(scale=2.448, **_SIGMOID), True,
                 (0, 16), True, None, 40),
    "kimi_linear": (256, 8, moe.Scoring(scale=2.446, **_SIGMOID), True,
                    (0, 8), True, None, 40),
    "qwen3_next": (512, 10, moe.Scoring("softmax", renormalize=True), False,
                   (0, 32), True, None, 40),
    "olmoe": (64, 8, moe.Scoring(), False, None, True, None, 40),
    "equal-scores": (16, 4, moe.Scoring(**_SIGMOID), True, (0, 8), True,
                     None, 40),
    "bias-decides": (16, 4, moe.Scoring(**_SIGMOID), True, None, True,
                     None, 40),
    "held-from-3": (16, 4, moe.Scoring(scale=2.5, **_SIGMOID), True, (3, 4),
                    False, None, 40),
    "37-tokens": (16, 3, moe.Scoring(**_SIGMOID), True, None, True, None,
                  37),
}


def _router_case(name):
    experts, top_k, scoring, biased, held, gated, latent, tokens = \
        ROUTERS[name]
    keys = iter(jax.random.split(jax.random.PRNGKey(len(name)), 9))
    n, width = (experts if held is None else held[1]), latent or 16

    def normal(*shape, scale=0.3):
        return scale * jax.random.normal(next(keys), shape)

    params = {"router_w": normal(16, experts, scale=1.0),
              "w_up": normal(n, width, 8), "w_down": normal(n, 8, width)}
    if gated:
        params["w_gate"] = normal(n, width, 8)
    if latent:
        params.update(latent_down=normal(16, latent),
                      latent_up=normal(latent, 16))
    if biased:
        params["router_bias"] = normal(experts, scale=0.05)
    x = normal(tokens, 16, scale=1.0)
    if name == "equal-scores":
        # experts 2k and 2k + 1 score alike on every token, every expert
        # alike on the first eight: the lower index has to win
        params["router_w"] = jnp.repeat(params["router_w"][:, ::2], 2, axis=1)
        params["router_bias"] = jnp.zeros_like(params["router_bias"])
        x = x.at[:8].set(0.0)
    if name == "bias-decides":
        # a bias larger than any score: experts 12 to 15 are every token's
        # choice whatever they score, and their weights are still the scores
        params["router_bias"] = params["router_bias"].at[12:].add(2.0)
    return params, x, top_k, scoring, held, "silu" if gated else "relu2"


@pytest.mark.parametrize("name", list(ROUTERS))
def test_selection_and_counts_equal_their_plain_forms_bit_for_bit(
        name, monkeypatch):
    """``route``'s chosen experts and scores, ``dropless_moe_ffn``'s counts
    and result, and the gradients of the tokens, the router and every
    expert stack, against ``lax.top_k`` + ``take_along_axis`` + ``bincount``
    in their place: the same bits. Operation by operation, not under one
    ``jit``: XLA fuses the two forms' neighbours differently on the CPU and
    a fused product may round its last bit another way."""
    params, x, top_k, scoring, held, activation = _router_case(name)
    weight = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def everything():
        def loss(params, x):
            y, aux = moe.dropless_moe_ffn(params, x, top_k, scoring=scoring,
                                          held=held, activation=activation)
            return (jnp.sum(y * weight) + aux["balance"] + 0.1 * aux["z"],
                    (y, aux["counts"], aux["choice"]))

        routed = moe.route(x, params["router_w"], top_k, scoring,
                           params.get("router_bias"))
        (_, kept), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        return jax.tree.map(np.asarray, (routed, kept, grads))

    got = everything()
    monkeypatch.setattr(moe, "_biased_top_k", _plain_top_k)
    monkeypatch.setattr(moe, "_counts", _plain_counts)
    want = everything()
    (_, _, top_p, top_e), (_, counts, choice), (d_params, _) = got
    assert counts.sum() == top_k * x.shape[0] and counts.dtype == np.int32
    assert np.array_equal(choice, top_e) and top_p.shape == top_e.shape
    assert all(len(set(row)) == top_k for row in top_e.tolist())
    if name == "equal-scores":
        assert top_e[:8].tolist() == [list(range(top_k))] * 8
        assert all(e % 2 == 0 or e - 1 in row
                   for row in top_e.tolist() for e in row)
    if name == "bias-decides":
        assert (np.sort(top_e, axis=-1) == np.arange(12, 16)).all()
    assert all(np.abs(d_params[w]).sum() > 0 for w in d_params
               if w != "router_bias")
    flat_got, tree = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree == tree_want
    for a, b in zip(flat_got, flat_want):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("bias", [0.0, -0.0, -0.75],
                         ids=["plus-zero", "minus-zero", "negative"])
def test_biased_top_k_orders_every_float_as_lax_top_k_does(bias):
    """Zeros of either sign, a pair of equals, negatives, subnormals,
    infinities and NaNs in one row: the integer keys order them as
    ``lax.top_k`` orders the biased scores themselves, for every k, and the
    scores come back with their bits."""
    scores = jnp.array([[0.5, jnp.nan, -0.0, 0.0, -1.0, jnp.inf, -jnp.inf,
                         0.5, -jnp.nan, 1e-40, -1e-40, 3.0, 0.75, 0.25]])
    bias = jnp.full((scores.shape[-1],), bias)
    for top_k in (1, 5, scores.shape[-1]):
        top_p, top_e = moe._biased_top_k(scores, bias, top_k)
        want_p, want_e = _plain_top_k(scores, bias, top_k)
        assert np.array_equal(top_e, want_e)
        assert np.array_equal(np.asarray(top_p).view(np.int32),
                              np.asarray(want_p).view(np.int32))
