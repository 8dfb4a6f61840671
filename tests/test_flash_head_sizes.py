"""Flash attention where the value heads have a size of their own and the
score heads are no multiple of the 128-lane grain (latent attention: 192 for
the scores, 128 for the values): the Pallas body in interpret mode against
the dense body, outputs and the three gradients, through the kernel's entry
point (heads-major operands, and rows-major ones of the same sizes) and
through ``blocks.causal_attention``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import blocks
from paddle_tpu.ops import pallas as plk

#: (positions, score head size, value head size): the published 192 / 128 at
#: one tile a head; several tiles a head; a length and sizes that are
#: multiples of nothing; equal sizes, as every other model has them
CASES = [(256, 192, 128), (1024, 48, 32), (300, 24, 16), (512, 64, 64)]


def qkv(positions, d, dv, seed=0, heads=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k = (jax.random.normal(key, (1, heads, positions, d)) for key in ks[:2])
    v, w = (jax.random.normal(key, (1, heads, positions, dv))
            for key in ks[2:])
    return q, k, v, w


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("positions,d,dv", CASES)
def test_kernel_matches_the_dense_body(positions, d, dv, causal):
    q, k, v, w = qkv(positions, d, dv)

    def f(q, k, v, mode):
        with plk.override(mode):
            return plk.flash_attention(q, k, v, causal=causal)

    out = f(q, k, v, "on")
    assert out.shape == (1, 2, positions, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(q, k, v, "off")),
                               atol=2e-5)
    got, want = (jax.grad(lambda q, k, v: jnp.sum(f(q, k, v, mode) * w),
                          (0, 1, 2))(q, k, v) for mode in ("on", "off"))
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("positions,d,dv", CASES)
def test_rows_major_operands_take_any_head_size(positions, d, dv):
    """[B, S, heads D] queries and keys and [B, S, heads Dv] values, as a
    projection leaves them: two heads of 64 run the rows-major blocks, every
    other size here is transposed to heads-major inside the call; either
    way the dense body's context and gradients."""
    from paddle_tpu.ops.pallas.flash_attention import operand_layout

    def rows(t):
        return t.transpose(0, 2, 1, 3).reshape(1, positions, -1)

    q, k, v, w = (rows(t) for t in qkv(positions, d, dv, seed=2))
    assert operand_layout(q, k, v, num_heads=2) == (
        "rows_major" if (d, dv) == (64, 64) else "")

    def f(q, k, v, mode):
        with plk.override(mode):
            return plk.flash_attention(q, k, v, causal=True, num_heads=2)

    out = f(q, k, v, "on")
    assert out.shape == (1, positions, 2 * dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(q, k, v, "off")),
                               atol=2e-5)
    got, want = (jax.grad(lambda q, k, v: jnp.sum(f(q, k, v, mode) * w),
                          (0, 1, 2))(q, k, v) for mode in ("on", "off"))
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   err_msg=f"d{name}")


def test_tiles_in_groups_are_the_one_loop_to_the_bit(monkeypatch):
    """192 beside 128 on square blocks of 16: ahead of its last tile a query
    block's forward loop holds 0, 1, G - 1, G and G + 1 tiles, which run in
    groups of G and then singly, and a key block's backward loop 1 to G + 2.
    Output and the three gradients are the dense body's, and bit for bit
    those of the kernels that visit their tiles in one loop, one a trip."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    groups = (fa._FLASH_FWD_GROUP, fa._FLASH_BWD_GROUP)
    positions = 16 * (max(groups) + 2)
    blocks_ = np.arange(positions // 16)
    sizes = (positions // 16, 16, 16, True, None)
    for blocks, g, last in zip((fa._key_blocks, fa._query_blocks), groups,
                               (1, 0)):
        first, end = blocks(blocks_, *sizes, xp=np)
        assert {1, g - 1, g, g + 1} <= set((end - first - last).tolist())
        assert fa._causal_group(blocks, positions // 16, sizes, g) == g
    q, k, v, w = qkv(positions, 192, 128, seed=5)

    def run(mode, **heads):
        with plk.override(mode):
            return jax.value_and_grad(
                lambda *a: jnp.sum(plk.flash_attention(
                    *a, causal=True, block_q=16, block_k=16, **heads) * w),
                (0, 1, 2))(q, k, v)

    got, want = run("on"), run("off")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=5e-5)
    monkeypatch.setattr(fa, "_causal_group", lambda *a: 1)
    # the two calls are jitted on their static arguments: the count of
    # heads, which heads-major operands do not read, makes these new ones
    one_loop = run("on", num_heads=2)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one_loop)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_causal_attention_takes_a_value_head_size_of_its_own(impl):
    """[B, S, N, 24] queries and keys, [B, S, N, 16] values, against the
    softmax written out; the scale is 1 / sqrt(24)."""
    q, k, v, _ = (t.transpose(0, 2, 1, 3) for t in qkv(96, 24, 16, seed=1))
    with plk.override("on"):
        got = blocks.causal_attention(q, k, v, impl=impl)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / np.sqrt(24)
    scores = jnp.where(jnp.tril(jnp.ones((96, 96), bool)), scores, -jnp.inf)
    want = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    assert got.shape == (1, 96, 2, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_a_head_of_256_over_two_key_value_heads_matches_the_dense_body():
    """Two lane tiles a head and groups of 8: 16 query heads on 2 key/value
    heads of 256, as Qwen3-Next's attention layers have them; 640 positions
    are five query blocks of 128, so a key/value head is read by several
    programs. Outputs and the three gradients, the key/value gradients the
    sum of their group's."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, w = (jax.random.normal(key, (1, 16, 640, 256)) for key in ks[:2])
    k, v = (jax.random.normal(key, (1, 2, 640, 256)) for key in ks[2:])

    def f(q, k, v, mode):
        with plk.override(mode):
            return plk.flash_attention(q, k, v, causal=True)

    out = f(q, k, v, "on")
    assert out.shape == (1, 16, 640, 256)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(q, k, v, "off")),
                               atol=2e-5)
    got, want = (jax.grad(lambda q, k, v: jnp.sum(f(q, k, v, mode) * w),
                          (0, 1, 2))(q, k, v) for mode in ("on", "off"))
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"d{name}")
    # and through the decoders' entry, [B, S, N, D] operands
    with plk.override("on"):
        ctx = blocks.causal_attention(*(t.transpose(0, 2, 1, 3)
                                        for t in (q, k, v)), impl="flash")
    np.testing.assert_allclose(np.asarray(ctx.transpose(0, 2, 1, 3)),
                               np.asarray(out), atol=2e-5)


def test_a_head_of_64_with_32_heads_over_8_matches_the_dense_body():
    """Half a lane tile a head and groups of 4: 32 causal query heads on 8
    key/value heads of 64, as LFM2's attention layers have them; 640
    positions are five query blocks of 128, so a key/value head is read by
    several programs of each of its group's four heads. Outputs and the
    three gradients, the key/value gradients the sum of their group's."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q, w = (jax.random.normal(key, (1, 32, 640, 64)) for key in ks[:2])
    k, v = (jax.random.normal(key, (1, 8, 640, 64)) for key in ks[2:])

    def f(q, k, v, mode):
        with plk.override(mode):
            return plk.flash_attention(q, k, v, causal=True)

    out = f(q, k, v, "on")
    assert out.shape == (1, 32, 640, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(q, k, v, "off")),
                               atol=2e-5)
    got, want = (jax.grad(lambda q, k, v: jnp.sum(f(q, k, v, mode) * w),
                          (0, 1, 2))(q, k, v) for mode in ("on", "off"))
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"d{name}")
    # query head i reads key/value head i // 4: the dense scores written out
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, jnp.repeat(k, 4, axis=1)) / 8.0
    scores = jnp.where(jnp.tril(jnp.ones((640, 640), bool)), scores,
                       -jnp.inf)
    by_hand = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(scores, axis=-1),
                         jnp.repeat(v, 4, axis=1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(by_hand),
                               atol=2e-5)
    # and through the decoders' entry, [B, S, N, D] operands
    with plk.override("on"):
        ctx = blocks.causal_attention(*(t.transpose(0, 2, 1, 3)
                                        for t in (q, k, v)), impl="flash")
    np.testing.assert_allclose(np.asarray(ctx.transpose(0, 2, 1, 3)),
                               np.asarray(out), atol=2e-5)
