"""The update rules every trainer runs, against NumPy in float64.

``Optimizer.apply_gradients``, the static executor's ``apply_optimizer`` op
and the parameter server's dense step all call ``Optimizer._update`` on
every leaf, in the leaf's own shape and type: one path on the CPU, on one
chip and under a mesh. These cases hold the four rules that the benchmark's
cells and the book models train with to the arithmetic of sgd_op.cc,
momentum_op.cc and adam_op.cc, written out here independently."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import optimizer as opt_mod

STEPS = 3
LR = 0.05
RULES = {
    "sgd": lambda: opt_mod.SGDOptimizer(LR),
    "momentum": lambda: opt_mod.MomentumOptimizer(LR, 0.9),
    "nesterov": lambda: opt_mod.MomentumOptimizer(LR, 0.8,
                                                  use_nesterov=True),
    "adam": lambda: opt_mod.AdamOptimizer(LR, beta1=0.9, beta2=0.999,
                                          epsilon=1e-8),
}


def _reference(rule, p, g, steps=STEPS):
    """(parameter, slots) after ``steps`` updates with the gradient ``g``,
    in float64."""
    p, g = np.asarray(p, np.float64), np.asarray(g, np.float64)
    if rule == "sgd":
        return p - steps * LR * g, {}
    if rule in ("momentum", "nesterov"):
        mu = 0.9 if rule == "momentum" else 0.8
        v = np.zeros_like(p)
        for _ in range(steps):
            v = mu * v + g
            p = p - LR * ((g + mu * v) if rule == "nesterov" else v)
        return p, {"velocity": v}
    m1, m2 = np.zeros_like(p), np.zeros_like(p)
    for t in range(1, steps + 1):
        m1 = 0.9 * m1 + 0.1 * g
        m2 = 0.999 * m2 + 0.001 * g * g
        p = p - LR * np.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t) \
            * m1 / (np.sqrt(m2) + 1e-8)
    return p, {"moment1": m1, "moment2": m2}


def _inputs(shape, dtype, seed):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(*shape), dtype),
            jnp.asarray(rs.randn(*shape), dtype))


def _tolerance(dtype):
    # bfloat16 slots are rounded after every operation of every step
    return dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(7,), (3, 37), (130, 129)],
                         ids=["7", "3x37", "130x129"])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_apply_gradients_matches_float64_numpy(rule, shape, dtype):
    p, g = _inputs(shape, dtype, seed=len(shape) * 100 + shape[0])
    opt = RULES[rule]()
    params, grads = {"w": p}, {"w": g}
    state = opt.init(params)
    step = jax.jit(opt.apply_gradients)
    for _ in range(STEPS):
        params, state = step(params, grads, state)
    want_p, want_slots = _reference(rule, p, g)
    assert int(state["step"]) == STEPS
    assert params["w"].shape == shape
    np.testing.assert_allclose(np.asarray(params["w"], np.float64), want_p,
                               **_tolerance(dtype))
    slots = state["slots"]["w"]
    assert sorted(slots) == sorted(want_slots)
    for name, want in want_slots.items():
        assert slots[name].shape == shape
        np.testing.assert_allclose(np.asarray(slots[name], np.float64),
                                   want, **_tolerance(dtype))
    # the learning rate is a float32 scalar: a bfloat16 parameter comes back
    # as float32 while its slots stay bfloat16. Nothing around the rule
    # casts, so the types are the rule's own.
    assert params["w"].dtype == jnp.float32
    assert all(s.dtype == dtype for s in slots.values())


@pytest.mark.parametrize("rule", sorted(RULES))
def test_parameter_server_dense_step_matches_float64_numpy(rule):
    """``_DenseVar._step`` on the jnp route (the native C kernels, where
    built, are taken out of the way): the same rule, the same reference."""
    from paddle_tpu.distributed.ps import _DenseVar
    p, g = _inputs((6, 130), jnp.float32, seed=11)
    var = _DenseVar(np.asarray(p), RULES[rule]())
    var._native = (None, None)
    for _ in range(STEPS):
        var._step(np.asarray(g))
    want_p, want_slots = _reference(rule, p, g)
    assert var.value.dtype == np.float32 and var.value.shape == (6, 130)
    np.testing.assert_allclose(var.value, want_p, **_tolerance(jnp.float32))
    for name, want in want_slots.items():
        np.testing.assert_allclose(np.asarray(var.slots[name]), want,
                                   **_tolerance(jnp.float32))
