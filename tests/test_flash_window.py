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


def _seen(s, window):
    qi, ki = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = ki <= qi
    if window is not None:
        seen &= ki > qi - window
    return jnp.asarray(seen)


def _dense(q, k, v, seen):
    b, h, s, d = q.shape
    group = h // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def dense(q, k, v, window):
    """softmax(q k^T / sqrt d) v over ``query - window < key <= query``,
    query head i on key/value head i // group: [B, H, S, D] layout."""
    return _dense(q, k, v, _seen(q.shape[2], window))


@jax.jit
def _dense_value_and_grads(q, k, v, w, seen):
    return jax.value_and_grad(lambda *a: jnp.sum(_dense(*a, seen) * w),
                              (0, 1, 2))(q, k, v)


def dense_value_and_grads(q, k, v, w, window):
    """``value_and_grads`` of ``dense``: one compiled program a shape,
    whatever the window (the visible pairs are an operand)."""
    return _dense_value_and_grads(q, k, v, w, _seen(q.shape[2], window))


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
    want = dense_value_and_grads(q, k, v, w, window)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5)


def _loop_counts(s, bq, bk, window):
    """How many tiles each query block's forward loop and each key block's
    backward loop visits, by the bounds the kernels run over."""
    nq, nk = s // bq, s // bk
    first, end = fa._key_blocks(np.arange(nq), nk, bq, bk, True, window,
                                xp=np)
    fwd = set(np.broadcast_to(end - first, (nq,)).tolist())
    first, end = fa._query_blocks(np.arange(nk), nq, bq, bk, True, window,
                                  xp=np)
    return fwd, set(np.broadcast_to(end - first, (nk,)).tolist())


#: enough tiles a side that some block has more than a group and one,
#: whichever direction's groups are the longer
GROUPS = (fa._FLASH_FWD_GROUP, fa._FLASH_BWD_GROUP)
LONG = 16 * (max(GROUPS) + 2)


@pytest.mark.parametrize("bq, bk, group", [(32, 16, 6), (16, 32, 8)])
def test_causal_blocks_of_every_count_of_tiles(bq, bk, group):
    """A causal block's tiles run in straight-line groups of G and then the
    fewer than G left, one a trip; the forward's last stands behind the
    loops. Oblong blocks both ways, so the diagonal crosses two tiles a
    block of the longer side and the counts step by two (square blocks, one
    query head a key/value head and every count: ``test_flash_head_sizes``).
    Output and the three gradients."""
    s = LONG * max(bq, bk) // 16
    for counts, g in zip(_loop_counts(s, bq, bk, None), GROUPS):
        assert min(counts) <= 2 and max(counts) > g + 1 \
            and any(c % g for c in counts), (counts, g)
    q, k, v, w = arrays(bq + group, group, 1, s, d=8)
    with plk.override("on"):
        got = value_and_grads(
            lambda *a: plk.flash_attention(*a, causal=True, block_q=bq,
                                           block_k=bk), q, k, v, w)
    want = dense_value_and_grads(q, k, v, w, None)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5)


def test_a_window_wider_than_a_group_of_blocks_runs_in_groups():
    """Behind a window of 80 on blocks of 16 a block's loop holds up to six
    tiles either direction (the band's far edge crosses the first, the
    diagonal the last), more than a group: the windowed call takes the
    groups, which Laguna's window of one block does not."""
    s, window, bq, bk = LONG, 80, 16, 16
    sizes = (bq, bk, True, window)
    assert fa._causal_group(fa._key_blocks, s // bq, (s // bk,) + sizes,
                            GROUPS[0]) == GROUPS[0]
    assert fa._causal_group(fa._query_blocks, s // bk, (s // bq,) + sizes,
                            GROUPS[1]) == GROUPS[1]
    q, k, v, w = arrays(11, 2, 1, s, d=8)
    with plk.override("on"):
        got = value_and_grads(
            lambda *a: plk.flash_attention(*a, causal=True, window=window,
                                           block_q=bq, block_k=bk),
            q, k, v, w)
    want = dense_value_and_grads(q, k, v, w, window)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5)


def test_a_call_without_a_bias_is_the_call_with_a_bias_of_zeros():
    """To the bit: output, dq, dk and dv. The call without one has no bias
    operand and sums no bias gradient; the call with one still returns the
    dense body's."""
    q, k, v, w = arrays(13, 4, 2, 64)
    bias = jnp.asarray(np.random.RandomState(2).randn(1, 64), jnp.float32)

    def run(mode, bias, argnums):
        with plk.override(mode):
            return jax.value_and_grad(
                lambda q, k, v, bias: jnp.sum(plk.flash_attention(
                    q, k, v, bias=bias, causal=True, block_q=16,
                    block_k=16) * w), argnums)(q, k, v, bias)

    none, zeros = run("on", None, (0, 1, 2)), run("on", 0 * bias, (0, 1, 2))
    for g, r in zip(jax.tree.leaves(none), jax.tree.leaves(zeros)):
        assert np.array_equal(np.asarray(g), np.asarray(r))
    got, want = run("on", bias, (0, 1, 2, 3)), run("off", bias, (0, 1, 2, 3))
    assert np.abs(np.asarray(want[1][3])).max() > 0.1
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
    want = dense_value_and_grads(q, k, v, w, window)
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


def test_a_call_with_no_more_tiles_than_a_group_keeps_its_one_loop():
    """The groups are for calls some block of which has more tiles than one
    holds, by the sizes alone: the cells' causal calls (32, 16 and 8 tiles at
    most a block on blocks of 512) and not Laguna's window of one block (two
    tiles a block), whose kernels keep the one loop they had."""
    for blocks, g in zip((fa._key_blocks, fa._query_blocks), GROUPS):
        for s, window, want in ((16384, None, g), (8192, None, g),
                                (4096, None, g), (16384, 512, 1),
                                (512 * g, None, 1), (512 * (g + 1), None, g),
                                (16384, 512 * g, g)):
            n = s // 512
            assert fa._causal_group(blocks, n, (n, 512, 512, True, window),
                                    g) == want, (blocks, s, window)


@pytest.mark.parametrize("group", [1, 2, 3, 4, 8])
def test_in_groups_visits_every_tile_once_and_in_order(group):
    """Whole groups, then the fewer than a group left, whether the bounds
    are the call's own (static: a divisor of the count, nothing left over)
    or follow a program id (traced); an empty range visits nothing."""
    def visit(lo, hi):
        def tile(j, carry):
            seen, at = carry
            return seen.at[at].set(j), at + 1
        return fa._in_groups(lo, hi, tile,
                             (jnp.full((12,), -1, jnp.int32), 0), group)

    traced = jax.jit(visit)
    for lo, hi in ((0, 0), (0, 1), (3, 3), (2, 9), (0, 8), (1, 12), (5, 6)):
        want = list(range(lo, hi)) + [-1] * (12 - (hi - lo))
        for seen, at in (visit(lo, hi), traced(lo, hi)):
            assert seen.tolist() == want and int(at) == hi - lo, (lo, hi)


def test_the_four_calls_keep_their_names():
    """The roofline readers and ``swa_core_ms`` find the device time by
    them: one ``flash_fwd`` and one ``flash_bwd`` a call, ``_window`` behind
    both where the call has a window, with a bias or without one."""
    import re
    q, k, v, w = arrays(1, 4, 2, 64)
    bias = jnp.zeros((1, 64), jnp.float32)

    def names(window, bias):
        with plk.override("on"):
            text = str(jax.make_jaxpr(lambda *a: value_and_grads(
                lambda *b: plk.flash_attention(
                    *b, bias=bias, causal=True, window=window, block_q=16,
                    block_k=16), *a))(q, k, v, w))
        return sorted(re.findall(r"name=(flash_(?:fwd|bwd)\w*)", text))

    for b in (None, bias):
        assert names(None, b) == ["flash_bwd", "flash_fwd"]
        assert names(16, b) == ["flash_bwd_window", "flash_fwd_window"]
        assert names(40, b) == ["flash_bwd_window", "flash_fwd_window"]


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
    want = dense_value_and_grads(q, k, v, w, window)
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
