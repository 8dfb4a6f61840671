"""The one rotary pass of ``models/blocks.py`` (``_turn``, behind
``apply_rope`` and ``apply_rope_tail``) against the slice-and-concatenate
body it replaced, which stays here as the plain reference and is
differentiated as written: values, the gradient rule (the same pass with the
sine's sign turned, none for the angles), what the backward pass is made of,
and every decoder that calls the pass on its tiny configuration.
"""

import itertools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import (blocks, deepseek_v3, kimi_linear, laguna, lfm2,
                               olmoe, qwen3_next)


def reference(x, cos, sin, tail=False, interleaved=False):
    """``apply_rope`` as it was, taken to the tail and to interleaved pairs:
    the span sliced out of the head in float32, its two channel sets turned
    apart and put back by a concatenate."""
    half = cos.shape[-1]
    rot = 2 * half
    start = x.shape[-1] - rot if tail else 0
    x32 = x.astype(jnp.float32)
    span = x32[..., start:start + rot]
    a, b = (span[..., 0::2], span[..., 1::2]) if interleaved \
        else (span[..., :half], span[..., half:])
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    turned = [a * cos - b * sin, b * cos + a * sin]
    turned = jnp.stack(turned, axis=-1).reshape(span.shape) if interleaved \
        else jnp.concatenate(turned, axis=-1)
    return jnp.concatenate([x32[..., :start], turned, x32[..., start + rot:]],
                           axis=-1).astype(x.dtype)


def ulp(want):
    """One unit in the last place of each bfloat16 value (8 bits of
    significand)."""
    want = np.abs(np.asarray(want, np.float32))
    return 2.0 ** (np.floor(np.log2(np.maximum(want, 2.0 ** -120))) - 7)


def operands(d, whole, scaled, dtype, heads=4):
    rot = d if whole else (64 if d == 192 else d // 2)
    x, dy = (jax.random.normal(jax.random.PRNGKey(k), (2, 19, heads, d))
             .astype(dtype) for k in (0, 1))
    cos, sin = blocks.rope_angles(19, rot, factor=1.4159 if scaled else 1.0)
    return x, dy, cos, sin


CASES = list(itertools.product(
    (False, True), (False, True), (True, False), (64, 128, 192),
    (False, True), (jnp.bfloat16, jnp.float32)))


def case_id(case):
    tail, interleaved, whole, d, scaled, dtype = case
    return "-".join(("last" if tail else "first",
                     "interleaved" if interleaved else "halves",
                     "whole" if whole else "part", str(d),
                     "scaled" if scaled else "plain", jnp.dtype(dtype).name))


@pytest.mark.parametrize("case", CASES, ids=case_id)
def test_the_pass_and_its_gradient_are_the_slices_and_theirs(case):
    tail, interleaved, whole, d, scaled, dtype = case
    x, dy, cos, sin = operands(d, whole, scaled, dtype)

    def turn(x, cos, sin):
        return blocks._turn(x, cos, sin, tail, interleaved)

    def plain(x, cos, sin):
        return reference(x, cos, sin, tail, interleaved)

    got, back = jax.vjp(turn, x, cos, sin)
    want, plain_back = jax.vjp(plain, x, cos, sin)
    dx, dcos, dsin = back(dy)
    want_dx = plain_back(dy)[0]
    assert got.dtype == dtype and got.shape == x.shape
    assert dx.dtype == dtype and dx.shape == x.shape
    for a, b in ((got, want), (dx, want_dx)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)
        else:
            assert (np.abs(a - b) <= ulp(b)).all()
    # by contract the angles take no gradient (the tables are constants of
    # the position in every caller); the plain body's is not zero
    assert dcos.shape == cos.shape and not np.asarray(dcos).any()
    assert dsin.shape == sin.shape and not np.asarray(dsin).any()
    assert np.asarray(plain_back(dy)[1]).any()


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


@pytest.mark.parametrize("entry, d, whole", [
    ("head", 128, False), ("head", 128, True), ("head", 64, True),
    ("head", 256, False), ("tail", 192, False)])
def test_the_backward_pass_cuts_no_array_of_x_s_size(entry, d, whole):
    """The gradient is one product and one elementwise pass over whole
    heads: no pad, split, slice, gather or concatenate touches a float32
    array of x's size (the plain body's transpose holds three pads and a
    split of them); what is cut and padded is the tables, [S, D]."""
    rot = d if whole else 64
    x, dy = (jax.random.normal(jax.random.PRNGKey(k), (2, 19, 4, d))
             .astype(jnp.bfloat16) for k in (0, 1))
    cos, sin = blocks.rope_angles(19, rot)

    def turn(x):
        return blocks.apply_rope(x, cos, sin) if entry == "head" \
            else blocks.apply_rope_tail(x, cos, sin, True)

    def cuts(fn):
        jaxpr = jax.make_jaxpr(lambda x, dy: jax.vjp(fn, x)[1](dy))(x, dy)
        return [eqn.primitive.name for eqn in equations(jaxpr.jaxpr)
                if eqn.primitive.name in ("pad", "split", "slice", "gather",
                                          "dynamic_slice", "concatenate")
                and any(v.aval.dtype == jnp.float32 and v.aval.size >= x.size
                        for v in (*eqn.invars, *eqn.outvars)
                        if hasattr(v.aval, "size"))]

    assert cuts(turn) == []
    assert cuts(lambda x: reference(x, cos, sin, entry == "tail",
                                    entry == "tail"))


def test_the_two_entries_are_the_one_pass_under_the_scope_rope():
    x, _, cos, sin = operands(128, False, True, jnp.bfloat16)
    assert jnp.array_equal(blocks.apply_rope(x, cos, sin),
                           blocks._turn(x, cos, sin, False, False))
    for interleaved in (False, True):
        assert jnp.array_equal(
            blocks.apply_rope_tail(x, cos, sin, interleaved),
            blocks._turn(x, cos, sin, True, interleaved))
    for entry in (lambda x: blocks.apply_rope(x, cos, sin),
                  lambda x: blocks.apply_rope_tail(x, cos, sin, True)):
        text = jax.jit(jax.value_and_grad(
            lambda x: entry(x).astype(jnp.float32).sum())) \
            .lower(x).as_text(debug_info=True)
        # the pass and its transpose: one product each, both under ``rope``
        assert text.count("stablehlo.dot_general") == 2
        assert "/jvp(rope)/dot_general" in text
        assert "transpose(jvp(rope))/dot_general" in text


# ---------------------------------------------------------------------------
# the decoders that call the pass, on their tiny configurations: the loss
# and every gradient with the pass against the same step with the plain body
# in its place (that a train step compiles and lowers the loss is each
# model's own test file's)
# ---------------------------------------------------------------------------
DECODERS = {"laguna": (laguna, laguna.laguna_tiny),
            "lfm2": (lfm2, lfm2.lfm2_tiny),
            "qwen3_next": (qwen3_next, qwen3_next.qwen3_next_tiny),
            "olmoe": (olmoe, olmoe.olmoe_tiny),
            "deepseek_v3": (deepseek_v3, deepseek_v3.deepseek_v3_tiny)}


def relative_error(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


@pytest.mark.parametrize("name", list(DECODERS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_decoder_s_loss_and_gradients_are_the_plain_body_s(name, dtype,
                                                             monkeypatch):
    model, tiny = DECODERS[name]
    cfg = tiny(dtype=dtype)
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    batch = model.synthetic_batch(cfg, 2, 48)

    def step():
        return jax.jit(jax.value_and_grad(
            lambda p: model.lm_loss(p, cfg, batch)))(params)

    loss, grads = step()
    monkeypatch.setattr(blocks, "apply_rope", jax.named_scope("rope")(
        lambda x, cos, sin: reference(x, cos, sin)))
    monkeypatch.setattr(blocks, "apply_rope_tail", jax.named_scope("rope")(
        lambda x, cos, sin, interleaved: reference(x, cos, sin, True,
                                                   interleaved)))
    want_loss, want = step()
    # float32: the same sums of the same products, up to how the compiler
    # contracts them; bfloat16: a rounding of q, k or a gradient may fall the
    # other way (the tolerances of the files' own step tests: 1e-5 on the
    # loss and 2e-3 on a gradient against the reference in float32)
    loss_tol, grad_tol = (1e-6, 1e-5) if dtype == jnp.float32 \
        else (1e-3, 2e-2)
    assert np.isfinite(float(loss))
    assert relative_error(loss, want_loss) < loss_tol
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
        if np.asarray(ref).any():
            assert relative_error(got, ref) < grad_tol, \
                jax.tree_util.keystr(path)


def test_a_model_with_no_rotary_positions_never_enters_the_pass():
    cfg = kimi_linear.kimi_linear_tiny()
    params = jax.eval_shape(
        lambda: kimi_linear.init_params(jax.random.PRNGKey(0), cfg))
    batch = kimi_linear.synthetic_batch(cfg, 2, 48)
    text = jax.jit(jax.grad(lambda p: kimi_linear.lm_loss(p, cfg, batch))) \
        .lower(params).as_text(debug_info=True)
    assert not re.search(r"[/(]rope[/)]", text)
    # and a model that has them does show the scope in such a text
    model, tiny = DECODERS["olmoe"]
    cfg = tiny()
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg))
    batch = model.synthetic_batch(cfg, 2, 48)
    text = jax.jit(jax.grad(lambda p: model.lm_loss(p, cfg, batch))) \
        .lower(params).as_text(debug_info=True)
    assert re.search(r"[/(]rope[/)]", text)
