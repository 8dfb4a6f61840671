"""The held experts' way back (``ops/pallas/moe_combine.py``): a pass's rows,
each times its float32 weight, summed into the rows of their tokens. Both
bodies against a float64 loop and against each other, the Pallas body in
interpret mode, chosen the registry's way (``override``); the work list; and
``dropless_moe_ffn(held=...)`` with its gradients through both bodies."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas as plk
from paddle_tpu.ops.pallas.moe_combine import (_work_list,
                                              moe_combine_reference,
                                              rows_held)
from paddle_tpu.parallel import moe

#: the override that makes the registry hand out each body on the CPU
OVERRIDE = {"reference": "off", "pallas_interpret": "on"}


def a_pass(rs, rows, tokens, top_k, held, spread=None):
    """The tokens [rows] and weights [rows] of a pass, as ``_held_experts``
    hands them over: ``held`` rows at distinct (token, choice) places, in an
    order of its own (expert order), then rows of weight 0 at any place.
    ``spread``: the held rows' tokens come from the first ``spread`` alone,
    so that each takes ``top_k`` rows or nearly."""
    places = rs.permutation((spread or tokens) * top_k)[:held]
    rest = rs.randint(0, tokens * top_k, size=rows - held)
    weight = np.concatenate([rs.uniform(0.05, 2.5, size=held),
                             np.zeros(rows - held)]).astype(np.float32)
    return np.concatenate([places, rest]).astype(np.int32) // top_k, weight


def loop_over_rows(y, rows, token, weight):
    """float64: each row times its weight into its token's row."""
    out = np.asarray(y, np.float64).copy()
    rows = np.asarray(rows.astype(jnp.float32), np.float64)
    for r in np.flatnonzero(weight):
        out[token[r]] += np.float64(weight[r]) * rows[r]
    return out


#: (rows, tokens, width, top_k, rows held, tokens they come from, dtype):
#: the four held cells' kinds cut small (a pass of as many rows as tokens
#: with half of it held and 4 a token; three halves of the tokens and 10 a
#: token; twice the tokens and 8 a token; Kimi's width of 18 lane tiles with
#: a quarter held), and the edges
CASES = {
    "lfm2_kind": (1024, 1024, 256, 4, 512, None, jnp.bfloat16),
    "qwen3_next_kind": (768, 512, 256, 10, 320, None, jnp.bfloat16),
    "laguna_kind": (1024, 512, 256, 8, 512, None, jnp.bfloat16),
    "kimi_kind": (512, 512, 2304, 8, 128, None, jnp.bfloat16),
    # every token of a few takes all its top_k rows: duplicate tokens inside
    # a row tile, and runs of one token that straddle two row tiles (10 rows
    # a token and 128 rows a tile: a run crosses every tile's edge)
    "duplicates_and_straddles": (512, 256, 128, 10, 400, 40, jnp.bfloat16),
    "no_row_held": (256, 512, 128, 4, 0, None, jnp.bfloat16),
    "held_rows_end_mid_tile": (512, 256, 128, 4, 200, None, jnp.bfloat16),
    "every_row_held": (256, 1024, 128, 2, 256, None, jnp.bfloat16),
    "one_row": (128, 256, 128, 4, 1, None, jnp.bfloat16),
    # rows that are no multiple of a row tile, tokens of three sublane tiles
    "ragged_shapes": (100, 24, 128, 8, 57, None, jnp.bfloat16),
    "float32_rows": (384, 256, 128, 4, 300, None, jnp.float32),
    # a width the kernel does not take: the reference body inside it
    "narrow": (64, 32, 48, 4, 40, None, jnp.float32),
}


@pytest.mark.parametrize("body", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_are_summed_into_their_tokens(case, body):
    rows, tokens, width, top_k, held, spread, dtype = CASES[case]
    rs = np.random.RandomState(len(case))
    token, weight = a_pass(rs, rows, tokens, top_k, held, spread)
    x = jnp.asarray(rs.randn(rows, width), dtype)
    y = jnp.asarray(rs.randn(tokens, width), jnp.float32)
    want = loop_over_rows(y, x, token, weight)
    with plk.override(OVERRIDE[body]):
        assert plk.selected_body("moe_combine") == body
        got = jax.jit(plk.moe_combine)(y, x, jnp.asarray(token),
                                      jnp.asarray(weight))
    assert got.dtype == jnp.float32 and got.shape == y.shape
    # float32 sums of at most top_k float32 products a token, in any order
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=2e-6 * np.abs(want).max())
    ref = moe_combine_reference(y, x, jnp.asarray(token),
                                   jnp.asarray(weight))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=0,
                               atol=2e-6 * np.abs(want).max())
    if not held:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(y))


def test_a_token_past_the_last_and_a_weight_of_zero_add_nothing():
    """The rows past the rows held are zeros times 0 in the layer; here they
    are anything finite, and a token of T or more is dropped by both."""
    rs = np.random.RandomState(3)
    token = np.asarray([5, 300, 5, 256, 7, 9], np.int32)
    weight = np.asarray([1.5, 2.0, 0.25, 1.0, 0.0, 0.5], np.float32)
    x = jnp.asarray(rs.randn(6, 128), jnp.bfloat16)
    y = jnp.asarray(rs.randn(256, 128), jnp.float32)
    x32 = np.asarray(x.astype(jnp.float32))
    want = np.asarray(y).copy()
    want[5] += 1.5 * x32[0] + 0.25 * x32[2]
    want[9] += 0.5 * x32[5]
    for mode in ("off", "on"):
        with plk.override(mode):
            got = plk.moe_combine(y, x, jnp.asarray(token),
                                 jnp.asarray(weight))
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)


@pytest.mark.parametrize("held, tt, tr, want", [
    # 300 rows over 1024 tokens, 128 a tile each way
    (300, 128, 128, "in_order"),
    (0, 128, 128, "nothing"),
    (128, 128, 128, "one_tile"),
    (129, 256, 128, "in_order"),
])
def test_the_work_list_visits_what_intersects_once_and_in_order(held, tt, tr,
                                                                want):
    rs = np.random.RandomState(held)
    t, r = 1024, 512
    tokens = np.sort(rs.randint(0, t, size=held))
    key = np.concatenate([tokens, np.full(r - held, t)]).astype(np.int32)
    rows_of, tokens_of, n = _work_list(jnp.asarray(key), jnp.int32(held),
                                          t // tt, tt, tr)
    n = int(n[0])
    assert rows_of.shape == tokens_of.shape == (r // tr + t // tt,)
    pairs = list(zip(np.asarray(rows_of)[:n], np.asarray(tokens_of)[:n]))
    expected = sorted({(i // tr, int(tok) // tt)
                       for i, tok in enumerate(tokens)})
    # a row tile visits every token tile between its first and its last
    # row's, touched or not; with the rows this dense none is skipped
    assert set(expected) <= set(pairs) and len(pairs) == len(set(pairs))
    assert pairs == sorted(pairs)
    assert all(b[0] - a[0] + b[1] - a[1] >= 1 for a, b in zip(pairs,
                                                              pairs[1:]))
    if want == "nothing":
        assert n == 0
    if want == "one_tile":
        assert {p[0] for p in pairs} == {0}
    # the entries past the work repeat the last one: nothing is fetched
    assert (np.asarray(rows_of)[n:] == np.asarray(rows_of)[max(n - 1, 0)]
            ).all()
    assert (np.asarray(tokens_of)[n:] == np.asarray(tokens_of)[max(n - 1, 0)]
            ).all()


@pytest.mark.parametrize("held", [0, 1, 64, 65, 200, 256])
def test_the_rows_held_are_gathered_and_the_rest_are_zeros(monkeypatch, held):
    """A chunk at a time as far as the rows held go: the rows of the chunks
    that hold one are ``x[index]``, every row past them is zero."""
    monkeypatch.setattr(sys.modules[rows_held.__module__], "_GATHER", 64)
    rs = np.random.RandomState(held)
    x = jnp.asarray(rs.randn(96, 128), jnp.bfloat16)
    index = jnp.asarray(rs.randint(0, 96, size=256), jnp.int32)
    got = np.asarray(jax.jit(rows_held)(x, index, jnp.int32(held))
                     .astype(jnp.float32))
    want = np.asarray(x.astype(jnp.float32))[np.asarray(index)]
    reach = -(-held // 64) * 64
    np.testing.assert_array_equal(got[:reach], want[:reach])
    assert not got[reach:].any()
    # a tile that is one chunk, or no whole number of them: one gather
    np.testing.assert_array_equal(
        np.asarray(rows_held(x, index[:100], jnp.int32(3))
                   .astype(jnp.float32)), want[:100])


def expert_layer(seed, d=128, f=64, experts=16, tokens=256):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router_w": jax.random.normal(ks[0], (d, experts)),
            "w_gate": 0.3 * jax.random.normal(ks[1], (experts, d, f)),
            "w_up": 0.3 * jax.random.normal(ks[2], (experts, d, f)),
            "w_down": 0.3 * jax.random.normal(ks[3], (experts, f, d))}, \
        jax.random.normal(ks[4], (tokens, d))


@pytest.mark.parametrize("tile", [8192, 128], ids=["one_pass", "passes"])
@pytest.mark.parametrize("top_k, held", [(4, (4, 4)), (8, (0, 2)),
                                         (10, (6, 8))])
def test_the_layer_s_gradients_are_the_same_through_both_bodies(
        monkeypatch, top_k, held, tile):
    """``jax.grad`` of ``dropless_moe_ffn(held=...)``: ``dx``, the scores'
    gradient (through the router's weights) and the three stacks, the Pallas
    way back against XLA's scatter-add, to 1e-6 of each gradient's size."""
    monkeypatch.setattr(moe, "HELD_ROW_TILE", tile)
    # and the rows gathered 64 at a time: a pass is two chunks or sixteen
    monkeypatch.setattr(sys.modules[rows_held.__module__], "_GATHER", 64)
    lp, x = expert_layer(seed=top_k)
    first, n = held
    share = {k: v[first:first + n] if k.startswith("w_") else v
             for k, v in lp.items()}
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    scoring = moe.Scoring("sigmoid", renormalize=True, scale=2.5)

    def program(body):
        def loss(p, x):
            y, _ = moe.dropless_moe_ffn(p, x, top_k, scoring=scoring,
                                        held=held)
            return jnp.sum(y * w), y
        # around the gradient too: the backward pass is traced after ``loss``
        with plk.override(body), jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, (0, 1), has_aux=True)(share, x)

    (_, y_ref), want = program("off")
    (_, y_got), got = program("on")
    assert plk.registry._last_selection["moe_combine"] == "pallas_interpret"
    np.testing.assert_allclose(y_got, y_ref, rtol=0,
                               atol=1e-6 * np.abs(y_ref).max())
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert np.abs(np.asarray(b)).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-6 * np.abs(np.asarray(b)).max(),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("body", ["off", "on"], ids=["reference", "pallas"])
def test_dy_gathered_before_the_cast_gives_the_same_gradients(body):
    """``_held_bwd`` gathers ``dy``'s rows in ``dy``'s own dtype and widens
    them after: handed a bfloat16 cotangent, every gradient is bit for bit
    what the same values give gathered in float32."""
    lp, x = expert_layer(seed=1)
    share = {k: (v[4:8] if k.startswith("w_") else v).astype(jnp.bfloat16)
             for k, v in lp.items()}
    share["router_w"] = lp["router_w"]
    x = x.astype(jnp.bfloat16)
    dy = jax.random.normal(jax.random.PRNGKey(2), x.shape).astype(x.dtype)
    xt, top_p, weights, order, sizes, tile = _held_operands(share, x, 4,
                                                            (4, 4))
    kept = (xt, top_p, weights, order, sizes)
    # op by op on both sides, so that the two differ in the gather alone
    with plk.override(body), jax.disable_jit():
        narrow = moe._held_bwd(4, None, tile, "silu", kept, dy)
        wide = _held_bwd_float32_gather(4, tile, kept,
                                        dy.astype(jnp.float32))
    for a, b in zip(jax.tree.leaves(narrow), jax.tree.leaves(wide)):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def _held_operands(params, x, top_k, held):
    """What ``dropless_moe_ffn`` hands ``_held_experts``."""
    first, n = held
    e = params["router_w"].shape[-1]
    _, _, top_p, top_e = moe.route(x.astype(jnp.float32), params["router_w"],
                                   top_k)
    counts = jnp.bincount(top_e.reshape(-1), length=e)
    here = (top_e >= first) & (top_e < first + n)
    order = jnp.argsort(jnp.where(here, top_e - first, n).reshape(-1),
                        stable=True)
    tile = moe._held_row_tile(order.shape[0], n, e)
    order = jnp.pad(order, (0, -order.shape[0] % tile))
    weights = (params["w_gate"], params["w_up"], params["w_down"])
    return x, top_p, weights, order, counts[first:first + n], tile


def _held_bwd_float32_gather(top_k, tile, kept, dy):
    """``moe._held_bwd`` with ``dy``'s rows gathered in float32 and the rows'
    gradients scattered by XLA: the backward pass as it was."""
    xt, top_p, weights, order, sizes = kept
    dx = jnp.zeros(xt.shape, jnp.float32)
    dp = jnp.zeros(top_p.size, jnp.float32)
    dw = jax.tree.map(jnp.zeros_like, weights)
    for i in range(int(-(-jnp.sum(sizes) // tile))):
        at, weight, part, _ = moe._held_pass(i, order, top_p, sizes, tile)
        token = at // top_k
        rows = jnp.take(xt, token, axis=0)
        dy_rows = jnp.take(dy, token, axis=0)
        out, back = jax.vjp(
            lambda r, w: moe._experts(r, w, part, None, "silu"), rows,
            weights)
        d_rows, dw_pass = back((dy_rows * weight[:, None]).astype(out.dtype))
        dp = dp.at[at].add(jnp.sum(out.astype(jnp.float32) * dy_rows,
                                   axis=-1))
        dx = plk.moe_combine(dx, d_rows, token,
                            (weight != 0).astype(jnp.float32))
        dw = jax.tree.map(jnp.add, dw, dw_pass)
    return (dx.astype(xt.dtype), dp.reshape(top_p.shape).astype(top_p.dtype),
            dw, None, None)


def test_under_a_mesh_of_more_than_one_device_auto_takes_the_reference(
        monkeypatch):
    """What GSPMD can partition: inside ``mesh_scope`` of a multi-device
    mesh ``auto`` hands out the scatter-add, on a chip too."""
    from jax.sharding import Mesh
    monkeypatch.setattr(plk.registry, "platform", lambda: "tpu")
    assert plk.selected_body("moe_combine") == "pallas"
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
    with plk.mesh_scope(mesh):
        assert plk.selected_body("moe_combine") == "reference"
