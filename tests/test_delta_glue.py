"""The two passes around the delta rule (``ops/pallas/delta_glue.py``): each
Pallas body in interpreter mode against its reference body (the models'
former code), values and every gradient, the taps' and the gain's among them;
the fallback where a head is no lane tile; a shard of the batch at a time
under a data mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.ops import pallas as plk
from paddle_tpu.ops.pallas import delta_glue as dg
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

F32, BF16 = jnp.float32, jnp.bfloat16
RNG = np.random.RandomState(7)


@pytest.fixture(autouse=True)
def pallas_bodies():
    with plk.override("on"):
        yield


def _f(shape, dtype=F32, scale=1.0):
    return jnp.asarray(RNG.randn(*shape) * scale, dtype)


def _close(got, want, tol):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape
    assert np.all(np.isfinite(got))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale


def _no_mosaic(fn, *args):
    return "pallas_call" not in str(jax.make_jaxpr(fn)(*args))


# ---------------------------------------------------------------------------
# short_conv_norm
# ---------------------------------------------------------------------------
Q, K_, V = 128 ** -0.5, 1.0, None
#: name -> (batch, positions, taps, head, parts, dtype)
CONV_CASES = {
    # Gated DeltaNet's [q | k | v]: 16 key heads under 32 value heads of 128
    "packed_16_key_heads_under_32_value_heads": (
        1, 32, 4, 128, ((2048, Q), (2048, K_), (4096, V)), BF16),
    "packed_float32": (2, 48, 4, 128, ((256, Q), (256, K_), (512, V)), F32),
    # 600 = one block of 512 and 88 rows of the next: padded to 1024
    "positions_no_multiple_of_the_blocks_rows": (
        1, 600, 4, 128, ((256, Q), (128, V)), F32),
    "positions_no_multiple_of_a_chunk": (2, 37, 4, 128, ((128, K_),), BF16),
    # three blocks: the taps reach over 511 | 512 and 1023 | 1024, and the
    # backward's carry comes back over them
    "four_taps_across_block_boundaries": (
        1, 1040, 4, 128, ((128, Q), (128, V)), F32),
    "two_taps": (1, 530, 2, 128, ((256, K_),), F32),
    "nine_taps_the_most": (1, 530, 9, 128, ((128, Q),), F32),
    "a_head_of_two_lane_tiles": (1, 64, 4, 256, ((512, Q), (256, V)), F32),
    "unnormed_alone": (1, 64, 4, 128, ((384, V),), BF16),
}


def _conv_operands(case):
    b, s, k, head, parts, dtype = CONV_CASES[case]
    c = sum(width for width, _ in parts)
    x = _f((b, s, c), dtype)
    taps = jnp.asarray(RNG.uniform(-0.5, 0.5, (k, c)), F32)
    weights = [_f((b, s, width)) for width, _ in parts]
    return x, taps, head, parts, weights


def _conv_loss(body, head, parts, weights):
    def loss(x, taps):
        outs = body(x, taps, head, parts)
        return sum(jnp.sum(o.astype(F32) * w)
                   for o, w in zip(outs, weights)), outs
    return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)


@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_conv_norm_kernel_against_its_reference_body(case):
    x, taps, head, parts, weights = _conv_operands(case)
    tol = 2e-2 if x.dtype == BF16 else 2e-5
    (_, got), got_grads = _conv_loss(plk.short_conv_norm, head, parts,
                                     weights)(x, taps)
    (_, want), want_grads = _conv_loss(
        plk.get_body("short_conv_norm", "reference"), head, parts,
        weights)(x, taps)
    assert [o.shape for o in got] == [x.shape[:2] + (w,) for w, _ in parts]
    assert all(o.dtype == x.dtype for o in got)
    for g, w in zip(got, want):
        _close(g, w, tol)
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype
        _close(g, w, tol)


def test_three_separate_calls_are_the_packed_call():
    """Kimi Linear's three projections, a call each, give what Gated
    DeltaNet's one packed call gives on the same columns: values, dx and the
    taps' gradient."""
    parts = ((256, Q), (256, K_), (256, V))
    x, taps = _f((2, 40, 768)), _f((4, 768), scale=0.3)
    weights = [_f((2, 40, 256)) for _ in parts]

    def packed(x, taps):
        outs = plk.short_conv_norm(x, taps, 128, parts)
        return sum(jnp.sum(o * w) for o, w in zip(outs, weights))

    def apart(x, taps):
        total = 0.0
        for i, (part, w) in enumerate(zip(parts, weights)):
            cols = slice(256 * i, 256 * i + 256)
            out, = plk.short_conv_norm(x[..., cols], taps[:, cols], 128,
                                       (part,))
            total = total + jnp.sum(out * w)
        return total

    want, want_grads = jax.value_and_grad(packed, (0, 1))(x, taps)
    got, got_grads = jax.value_and_grad(apart, (0, 1))(x, taps)
    _close(got, want, 1e-6)
    for g, w in zip(got_grads, want_grads):
        _close(g, w, 1e-6)


def test_a_position_moves_the_taps_reach_and_nothing_before_it():
    """Causal, over a block boundary: position 511 reaches 511 .. 514."""
    x, taps = _f((1, 600, 128)), _f((4, 128), scale=0.3)
    base, = plk.short_conv_norm(x, taps, 128, ((128, V),))
    moved, = plk.short_conv_norm(x.at[:, 511].add(1.0), taps, 128,
                                 ((128, V),))
    changed = np.flatnonzero(np.any(np.asarray(base != moved), axis=(0, 2)))
    assert changed.tolist() == [511, 512, 513, 514]


@pytest.mark.parametrize("op", ["short_conv_norm", "gated_head_norm"])
def test_a_head_of_16_takes_the_reference_body(op):
    """``kimi_linear_tiny``: a head that is no whole lane tile. The Pallas
    body hands the call to the reference body: no Mosaic call is traced."""
    if op == "short_conv_norm":
        args = (_f((2, 24, 96)), _f((4, 96)))
        fn = lambda x, t: plk.short_conv_norm(  # noqa: E731
            x, t, 16, ((32, 0.25), (32, 1.0), (32, None)))
        want = dg._short_conv_norm_reference(
            *args, 16, ((32, 0.25), (32, 1.0), (32, None)))
    else:
        args = (_f((2, 24, 64)), _f((2, 24, 64)), _f((16,)) + 1.0)
        fn = lambda o, z, g: plk.gated_head_norm(  # noqa: E731
            o, z, g, 1e-5, "sigmoid")
        want = dg._gated_head_norm_reference(*args, 1e-5, "sigmoid")
    assert plk.selected_body(op) == "pallas_interpret"
    assert _no_mosaic(fn, *args)
    for g, w in zip(jax.tree.leaves(fn(*args)), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_whole_lane_tiles_trace_the_kernels():
    assert not _no_mosaic(
        lambda x, t: plk.short_conv_norm(x, t, 128, ((128, 1.0),)),
        _f((1, 16, 128)), _f((4, 128)))
    assert not _no_mosaic(
        lambda o, z, g: plk.gated_head_norm(o, z, g, 1e-6, "silu"),
        _f((1, 16, 128)), _f((1, 16, 128)), _f((128,)))


@pytest.mark.parametrize("bad", ["parts_short", "taps_narrow"])
def test_conv_norm_refuses_columns_it_does_not_cover(bad):
    x = _f((1, 16, 256))
    taps = _f((4, 128 if bad == "taps_narrow" else 256))
    parts = ((128, 1.0),) if bad == "parts_short" else ((256, 1.0),)
    with pytest.raises(ValueError, match="do not cover"):
        plk.short_conv_norm(x, taps, 128, parts)


# ---------------------------------------------------------------------------
# gated_head_norm
# ---------------------------------------------------------------------------
#: name -> (batch, positions, columns, head, act, eps, dtype)
GATE_CASES = {
    "silu_32_heads_of_128": (1, 32, 4096, 128, "silu", 1e-6, BF16),
    "sigmoid_32_heads_of_128": (1, 32, 4096, 128, "sigmoid", 1e-5, BF16),
    "silu_float32_over_blocks": (2, 600, 256, 128, "silu", 1e-6, F32),
    "sigmoid_float32_no_multiple_of_a_chunk": (2, 37, 384, 128, "sigmoid",
                                               1e-5, F32),
    "silu_a_head_of_two_lane_tiles": (1, 48, 512, 256, "silu", 1e-6, F32),
}


@pytest.mark.parametrize("case", sorted(GATE_CASES))
def test_gated_norm_kernel_against_its_reference_body(case):
    b, s, c, d, act, eps, dtype = GATE_CASES[case]
    tol = 2e-2 if dtype == BF16 else 2e-5
    o, z, gain = _f((b, s, c), dtype), _f((b, s, c), dtype), _f((d,)) + 1.0
    weight = _f((b, s, c))

    def grads(body):
        def loss(o, z, gain):
            y = body(o, z, gain, eps, act)
            return jnp.sum(y.astype(F32) * weight), y
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(o, z, gain)

    (_, got), got_grads = grads(plk.gated_head_norm)
    (_, want), want_grads = grads(plk.get_body("gated_head_norm",
                                               "reference"))
    assert got.shape == o.shape and got.dtype == dtype
    _close(got, want, tol)
    for g, w in zip(got_grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype
        _close(g, w, tol)


def test_gated_norm_reference_is_the_mixers_former_expression():
    """``rms_normalize(o) * gain`` a head times ``silu(z)``, float32 inside:
    Gated DeltaNet's expression before the op (``models/qwen3_next.py`` at
    PR 38), to the bit."""
    from paddle_tpu.models import blocks
    o, z, gain = _f((2, 8, 64), BF16), _f((2, 8, 64), BF16), _f((16,)) + 1.0
    want = (blocks.rms_normalize(o.reshape(2, 8, 4, 16).astype(F32), gain,
                                 1e-6)
            * jax.nn.silu(z.reshape(2, 8, 4, 16).astype(F32))).astype(BF16)
    got = dg._gated_head_norm_reference(o, z, gain, 1e-6, "silu")
    np.testing.assert_array_equal(np.asarray(got.reshape(2, 8, 4, 16)),
                                  np.asarray(want))


def test_gated_norm_refuses_an_activation_it_does_not_have():
    with pytest.raises(ValueError, match="act"):
        plk.gated_head_norm(_f((1, 16, 128)), _f((1, 16, 128)), _f((128,)),
                            1e-6, "gelu")


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------
def _gauge():
    from paddle_tpu.monitor.registry import gauge
    return gauge("pallas_kernels_selected",
                 "Which body the Pallas kernel registry selected "
                 "(1 = active), per kernel",
                 labels=("kernel", "body"))


CALLS = {
    "short_conv_norm": lambda: plk.short_conv_norm(
        _f((1, 16, 128)), _f((4, 128)), 128, ((128, 1.0),)),
    "gated_head_norm": lambda: plk.gated_head_norm(
        _f((1, 16, 128)), _f((1, 16, 128)), _f((128,)), 1e-6, "silu"),
}


@pytest.mark.parametrize("op", sorted(CALLS))
def test_the_gauge_says_which_body_ran(op):
    """``reference`` on the CPU as the program runs there, the kernel where
    it is forced; nothing a user sets chooses."""
    with plk.override("auto"):
        CALLS[op]()
        assert _gauge().value(kernel=op, body="reference") == 1
    CALLS[op]()
    assert _gauge().value(kernel=op, body="pallas_interpret") == 1
    assert _gauge().value(kernel=op, body="reference") == 0


@pytest.mark.parametrize("op", sorted(CALLS))
def test_a_shard_of_the_batch_at_a_time_on_a_data_mesh(op):
    """Under a mesh that splits only the batch both ops run a shard at a
    time inside shard_map, the taps and the gain whole on every shard: the
    one-device call's values and gradients, the weights' summed over the
    shards."""
    mesh = make_mesh(MeshConfig(data=4), jax.devices()[:4])
    rows = NamedSharding(mesh, P("data"))
    if op == "short_conv_norm":
        batched = (_f((8, 40, 256)),)
        whole = (_f((4, 256), scale=0.3),)
        weight = [_f((8, 40, 128)), _f((8, 40, 128))]

        def call(x, taps):
            return plk.short_conv_norm(x, taps, 128, ((128, Q), (128, V)))
    else:
        batched = (_f((8, 40, 256)), _f((8, 40, 256)))
        whole = (_f((128,)) + 1.0,)
        weight = [_f((8, 40, 256))]

        def call(o, z, gain):
            return (plk.gated_head_norm(o, z, gain, 1e-6, "silu"),)

    def loss(*args, mesh=None):
        with plk.mesh_scope(mesh):
            outs = call(*args)
        return sum(jnp.sum(o * w) for o, w in zip(outs, weight)), outs

    grad = jax.value_and_grad(loss, tuple(range(len(batched + whole))),
                              has_aux=True)
    with plk.mesh_scope(mesh):
        assert plk.selected_body(op, 8) == "pallas_per_shard_interpret"
        assert plk.selected_body(op, 6) == "pallas_interpret"   # forced
    (_, want), want_grads = grad(*batched, *whole)
    (_, got), got_grads = jax.jit(lambda *a: grad(*a, mesh=mesh))(
        *(jax.device_put(t, rows) for t in batched), *whole)
    assert _gauge().value(kernel=op, body="pallas_per_shard_interpret") == 1
    for g, w in zip(got, want):
        assert g.sharding.is_equivalent_to(rows, g.ndim)
        _close(g, w, 1e-5)
    for g, w in zip(got_grads, want_grads):
        _close(g, w, 1e-5)
