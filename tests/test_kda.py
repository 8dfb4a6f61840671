"""The chunked gated delta rule (``paddle_tpu/ops/kda.py``) against the
recurrence it stands for, one position a step: outputs and the gradients of
all five inputs, at a length that is no multiple of the chunk, at chunk sizes
with and without sub-blocks below the diagonal, and with channels that forget
so fast that ``exp(G_i)`` and ``exp(-G_j)`` formed apart would overflow.

Both bodies of the registered kernel ``kda_chunked``: the ``jax.numpy``
reference, which is what ``kda.kda_chunked`` runs on the CPU, and the Pallas
body (``ops/pallas/kda.py``: the kernels ``kda_fwd`` and ``kda_bwd``) in
interpreter mode under ``override("on")``, at the head size 128 it takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import kda
from paddle_tpu.ops import pallas as plk
from paddle_tpu.ops.pallas import kda as pallas_kda

NAMES = ("q", "k", "v", "g", "beta")
BODIES = ("reference", "pallas")


def chunked(body, *args):
    """``kda.kda_chunked`` on the named body."""
    with plk.override("on" if body == "pallas" else "off"):
        return kda.kda_chunked(*args)


def sized(body, seed, **kw):
    """The file's inputs at a size the body takes: the Pallas body wants a
    head of 128 channels; one batch row and two heads keep the interpreter
    quick."""
    if body == "pallas":
        kw = dict(batch=1, heads=2, d=128, **kw)
    return inputs(seed, **kw)


def inputs(seed, batch=2, heads=3, positions=150, d=32, fast=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, heads, positions, d)
    q, k = (jax.random.normal(key, shape) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], shape)
    g = -jnp.exp(jax.random.normal(ks[3], shape) - 1.0)
    if fast:                          # four channels lose e^-20 a position
        g = g.at[..., :4].set(-20.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:-1]))
    # the op's layout: [batch, positions, heads, d]
    return (*(t.transpose(0, 2, 1, 3) for t in (q, k, v, g)),
            beta.transpose(0, 2, 1))


@pytest.fixture(autouse=True)
def full_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunked_outputs_are_the_recurrence(chunk):
    """150 positions: 9 chunks of 16 and 6 left, 2 of 64 and 22 left."""
    args = inputs(0)
    got = kda._kda_chunked(*args, chunk)
    want = kda.kda_recurrent(*args)
    assert got.shape == want.shape == (2, 150, 3, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("positions", [512, 600])
def test_the_kernels_outputs_are_the_recurrence(positions):
    """Grid steps of 128 positions: four whole ones, and 600 positions padded
    to five (the state is carried from step to step in the kernel's
    scratch)."""
    args = sized("pallas", 0, positions=positions)
    got = chunked("pallas", *args)
    want = kda.kda_recurrent(*args)
    assert got.shape == want.shape == (1, positions, 2, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(chunked("reference", *args)),
                               atol=2e-6)


def test_the_kernels_state_is_reset_between_heads():
    """Two heads with different inputs through one grid step, over five
    steps a head, equal what each gives alone."""
    args = sized("pallas", 5, positions=600)
    both = chunked("pallas", *args)
    for head in range(2):
        alone = chunked("pallas", *(t[:, :, head:head + 1] for t in args))
        np.testing.assert_allclose(np.asarray(both[:, :, head:head + 1]),
                                   np.asarray(alone), atol=1e-7)


@pytest.mark.parametrize("d", [16, 128])
def test_the_shape_rule_sends_a_head_of_16_to_the_reference(d):
    """The Pallas body takes heads that are whole lane tiles; at 16 channels
    (``kimi_linear_tiny``) it hands over to the reference body."""
    args = inputs(6, batch=1, heads=2, positions=64, d=d)
    with plk.override("on"):
        assert plk.selected_body("kda_chunked") == "pallas_interpret"
        program = str(jax.make_jaxpr(kda.kda_chunked)(*args))
    assert ("kda_fwd" in program) == (d == 128)
    assert ("triangular_solve" in program) == (d == 16)


@pytest.mark.parametrize("body", BODIES)
@pytest.mark.parametrize("fast", [False, True], ids=["slow", "fast_decay"])
def test_chunked_gradients_are_the_recurrence(fast, body):
    args = sized(body, 1, fast=fast, **(
        dict(positions=300) if body == "pallas" else {}))
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = jax.grad(lambda *a: jnp.sum(chunked(body, *a) * w),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(kda.kda_recurrent(*a) * w),
                    argnums=range(5))(*args)
    for name, a, b in zip(NAMES, got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * max(scale, 1.0), err_msg=name)


@pytest.mark.parametrize("body", BODIES)
def test_a_fast_channel_stays_finite_where_split_exponentials_overflow(body):
    """g = -20 a position: exp(-G_j) passes float32's largest number at the
    fifth position of a chunk, so a form that splits exp(G_i - G_j) over the
    chunk reads inf or nan; this one reads the recurrence."""
    args = sized(body, 2, fast=True)
    G = jnp.cumsum(args[3][:, :kda.CHUNK], axis=1)
    assert not np.isfinite(np.asarray(jnp.exp(-G))).all()
    got = chunked(body, *args)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(kda.kda_recurrent(*args)), atol=2e-6)


@pytest.mark.parametrize("body", BODIES)
def test_matmul_operands_follow_the_inputs_dtype_and_state_stays_float32(
        body):
    """bfloat16 q, k, v: the output is bfloat16 and a percent or two from
    the float32 recurrence, as operands of 8 bits make it; a state or a
    running sum of 8 bits would be ten times that after 300 positions."""
    q, k, v, g, beta = sized(body, 3, positions=300)
    half = [t.astype(jnp.bfloat16) for t in (q, k, v)]
    got = chunked(body, *half, g, beta)
    assert got.dtype == jnp.bfloat16
    want = kda.kda_recurrent(*half, g, beta)
    err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert err < 2e-2, err
    grads = jax.grad(lambda *a: jnp.sum(chunked(body, *a).astype(
        jnp.float32)), argnums=range(5))(*half, g, beta)
    assert [t.dtype for t in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    assert all(np.isfinite(np.asarray(t, np.float32)).all() for t in grads)


def test_the_sub_blocks_cover_the_chunk():
    """Scores inside one chunk of 64 (four sub-blocks of 16) against the
    plain [C, C, d] sum."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    a, b = (jax.random.normal(key, (2, 64, 8)) for key in ks[:2])
    G = jnp.cumsum(-jnp.exp(jax.random.normal(ks[2], (2, 64, 8))), axis=-2)
    got = kda._pair_scores(a, b, G, jnp.float32)
    full = jnp.einsum("nic,njc,nijc->nij", a, b,
                      jnp.exp(jnp.minimum(G[:, :, None] - G[:, None], 0.0)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.tril(full)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_the_kernels_take_the_chunk_they_are_given(chunk):
    """``kda.CHUNK`` is the chunk of whichever body runs: the kernels take it
    as an argument and work 8, 4 or 2 chunks in a tile of 128 positions."""
    args = sized("pallas", 7, positions=200)
    got = pallas_kda._kda_chunked_pallas(*args, chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(kda.kda_recurrent(*args)), atol=2e-6)
