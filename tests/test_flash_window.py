"""The flash kernels with a window and with fewer key/value heads than query
heads, against a dense ``jax.numpy`` masked softmax written here.

The kernel's own code runs (``override("on")``: interpreter mode on the
CPU), forward and backward, at tiles of 16 so that a sequence of 64 is four
tiles a side and the loops' bounds are what is tested: the forward's key loop
starts where the band does, the backward's query loop ends where it does,
and a group of query heads reads one key/value head through the index maps.
"""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import blocks
from paddle_tpu.ops import pallas as plk

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def dense(q, k, v, window):
    """softmax(q k^T / sqrt d) v over ``query - window < key <= query``,
    query head i on key/value head i // group: [B, H, S, D] layout."""
    b, h, s, d = q.shape
    group = h // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = ki <= qi
    if window is not None:
        seen &= ki > qi - window
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def arrays(seed, heads, kv_heads, s, d=16):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(1, n, s, d), jnp.float32)
                 for n in (heads, kv_heads, kv_heads, heads))


def value_and_grads(fn, q, k, v, w):
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * w),
                              (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("s", [64, 72], ids=["tiles", "padded"])
@pytest.mark.parametrize("group", [1, 6, 8])
@pytest.mark.parametrize("window", [None, 8, 100],
                         ids=["causal", "window8", "window_past_s"])
def test_kernels_match_the_dense_masked_softmax(window, group, s):
    """Output and the gradients of q, k and v; 72 positions are padded to
    128 inside the body, the padded keys masked by the bias."""
    q, k, v, w = arrays(s + group, 2 * group, 2, s)
    with plk.override("on"):
        got = value_and_grads(
            lambda *a: plk.flash_attention(*a, causal=True, window=window,
                                           block_q=16, block_k=16),
            q, k, v, w)
    want = value_and_grads(lambda *a: dense(*a, window), q, k, v, w)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5)


@pytest.mark.parametrize("body", ["off", "on"], ids=["reference", "pallas"])
def test_a_window_that_reaches_the_whole_sequence_is_the_causal_call(body):
    """To the bit, with one key/value head a query head: the op hands such a
    window on as no window, so it is the kernels that were there before."""
    q, k, v, w = arrays(3, 4, 4, 64)
    with plk.override(body):
        def run(window):
            return value_and_grads(
                lambda *a: plk.flash_attention(
                    *a, causal=True, window=window, block_q=16, block_k=16),
                q, k, v, w)
        for window in (64, 1000):
            for g, r in zip(jax.tree.leaves(run(window)),
                            jax.tree.leaves(run(None))):
                assert np.array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("window, group", [(24, 8), (None, 6)])
def test_the_reference_body_knows_windows_and_groups(window, group):
    q, k, v, w = arrays(5, 2 * group, 2, 80)
    with plk.override("off"):
        got = value_and_grads(
            lambda *a: plk.flash_attention(*a, causal=True, window=window),
            q, k, v, w)
    want = value_and_grads(lambda *a: dense(*a, window), q, k, v, w)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5)


def test_a_call_says_what_it_cannot_do():
    q, k, v, _ = arrays(0, 6, 4, 32)
    with pytest.raises(ValueError, match="query heads over"):
        plk.flash_attention(q, k, v, causal=True)
    q, k, v, _ = arrays(0, 4, 4, 32)
    with pytest.raises(ValueError, match="window"):
        plk.flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="window"):
        plk.flash_attention(q, k, v, causal=True, window=0)


# ---------------------------------------------------------------------------
# the loops' bounds, and the counter that is computed from them
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s, window, bq, bk", [
    (256, 64, 32, 32), (256, 33, 32, 32), (256, 1, 32, 32),
    (256, 96, 64, 32), (256, 96, 32, 64), (512, 200, 128, 128),
    (256, None, 32, 32)])
def test_the_loops_visit_exactly_the_tiles_that_hold_a_visible_pair(
        s, window, bq, bk):
    qi, ki = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (ki <= qi) & ((ki > qi - window) if window else True)
    tiles = seen.reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3))
    nq, nk = tiles.shape
    first, end = fa._key_blocks(np.arange(nq), nk, bq, bk, True, window,
                                xp=np)
    first, end = np.broadcast_to(first, (nq,)), np.broadcast_to(end, (nq,))
    for iq in range(nq):
        assert list(np.flatnonzero(tiles[iq])) \
            == list(range(first[iq], end[iq])), iq
    first, end = fa._query_blocks(np.arange(nk), nq, bq, bk, True, window,
                                  xp=np)
    first, end = np.broadcast_to(first, (nk,)), np.broadcast_to(end, (nk,))
    for ik in range(nk):
        assert list(np.flatnonzero(tiles[:, ik])) \
            == list(range(first[ik], end[ik])), ik


def test_tiles_visited_pct_counts_a_band(monkeypatch):
    """63 of 528 tiles each way at the cell's sizes; a kernel whose loops ran
    over the causal call's bounds (masking, not skipping) would read 100."""
    assert fa.tiles_visited_pct(16384, 512) == pytest.approx(100 * 63 / 528)
    assert fa.tiles_visited_pct(16384, 512) < 15
    assert fa.tiles_visited_pct(16384, None) == 100.0
    assert fa.tiles_visited_pct(16384, 16384) == 100.0
    assert fa.tiles_visited_pct(4096, 512) == pytest.approx(100 * 15 / 36)
    assert fa.tiles_visited_pct(80, 24) == 100.0       # one tile a side
    keys, queries = fa._key_blocks, fa._query_blocks
    monkeypatch.setattr(fa, "_key_blocks", lambda i, n, bq, bk, c, w, xp=jnp:
                        keys(i, n, bq, bk, c, None, xp))
    monkeypatch.setattr(fa, "_query_blocks",
                        lambda i, n, bq, bk, c, w, xp=jnp:
                        queries(i, n, bq, bk, c, None, xp))
    assert fa.tiles_visited_pct(16384, 512) == 100.0


def test_the_windowed_calls_have_names_of_their_own():
    q, k, v, w = arrays(1, 16, 2, 64)

    def traced(window):
        with plk.override("on"):
            return str(jax.make_jaxpr(lambda *a: value_and_grads(
                lambda *b: plk.flash_attention(
                    *b, causal=True, window=window, block_q=16, block_k=16),
                *a))(q, k, v, w))

    text = traced(16)
    assert "flash_fwd_window" in text and "flash_bwd_window" in text
    text = traced(None)
    assert "flash_fwd" in text and "_window" not in text


# ---------------------------------------------------------------------------
# blocks.causal_attention: the window and the groups, both bodies
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["dense", "flash"])
@pytest.mark.parametrize("window", [None, 24, 500])
def test_causal_attention_takes_a_window_and_groups(impl, window):
    q, k, v, w = arrays(7, 12, 2, 80)
    to_bsnd = lambda t: t.transpose(0, 2, 1, 3)      # noqa: E731
    got = jax.value_and_grad(lambda *a: jnp.sum(blocks.causal_attention(
        *map(to_bsnd, a), impl=impl, window=window) * to_bsnd(w)),
        (0, 1, 2))(q, k, v)
    want = value_and_grads(lambda *a: dense(*a, window), q, k, v, w)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5)


def test_auto_is_asked_with_the_keys_a_query_sees(monkeypatch):
    asked = []
    monkeypatch.setattr(blocks, "attention_body",
                        lambda positions, mesh=None, batch=None:
                        asked.append(positions) or "dense")
    q, k, v, _ = arrays(0, 4, 2, 64)
    to_bsnd = lambda t: t.transpose(0, 2, 1, 3)      # noqa: E731
    for window in (None, 24, 64, 100):
        blocks.causal_attention(*map(to_bsnd, (q, k, v)), window=window)
    assert asked == [64, 24, 64, 64]


def test_the_windowed_core_has_a_scope_of_its_own():
    q, k, v, _ = arrays(0, 4, 2, 64)
    to_bsnd = lambda t: t.transpose(0, 2, 1, 3)      # noqa: E731

    def names(window):
        jaxpr = jax.make_jaxpr(lambda *a: blocks.causal_attention(
            *a, impl="dense", window=window))(*map(to_bsnd, (q, k, v)))
        return {str(e.source_info.name_stack) for e in jaxpr.eqns}

    assert "attention_core/attention_window" in names(24)
    assert all(n.startswith("attention_core") for n in names(24))
    assert "attention_core" in names(None)
    assert not any("attention_window" in n for n in names(None))
