"""The chunked gated delta rule (``paddle_tpu/ops/kda.py``) against the
recurrence it stands for, one position a step: outputs and the gradients of
all five inputs, at a length that is no multiple of the chunk, at chunk sizes
with and without sub-blocks below the diagonal, and with channels that forget
so fast that ``exp(G_i)`` and ``exp(-G_j)`` formed apart would overflow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import kda

NAMES = ("q", "k", "v", "g", "beta")


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


@pytest.mark.parametrize("fast", [False, True], ids=["slow", "fast_decay"])
def test_chunked_gradients_are_the_recurrence(fast):
    args = inputs(1, fast=fast)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a) * w),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(kda.kda_recurrent(*a) * w),
                    argnums=range(5))(*args)
    for name, a, b in zip(NAMES, got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * max(scale, 1.0), err_msg=name)


def test_a_fast_channel_stays_finite_where_split_exponentials_overflow():
    """g = -20 a position: exp(-G_j) passes float32's largest number at the
    fifth position of a chunk, so a form that splits exp(G_i - G_j) over the
    chunk reads inf or nan; this one reads the recurrence."""
    args = inputs(2, fast=True)
    G = jnp.cumsum(args[3][:, :kda.CHUNK], axis=1)
    assert not np.isfinite(np.asarray(jnp.exp(-G))).all()
    got = kda.kda_chunked(*args)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(kda.kda_recurrent(*args)), atol=2e-6)


def test_matmul_operands_follow_the_inputs_dtype_and_state_stays_float32():
    q, k, v, g, beta = inputs(3, positions=128)
    half = [t.astype(jnp.bfloat16) for t in (q, k, v)]
    got = kda.kda_chunked(*half, g, beta)
    assert got.dtype == jnp.bfloat16
    want = kda.kda_recurrent(*half, g, beta)
    err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert err < 2e-2, err


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
