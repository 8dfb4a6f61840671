"""Pallas kernel tests (interpret mode on CPU — same code path as TPU).

Pattern: every kernel checked against its dense jnp reference, values and
gradients (the reference's OpTest numeric-vs-analytic discipline,
unittests/op_test.py). On the CPU the registry's own choice is the
reference body, so the file's fixture forces the Pallas body for every
call here, and ``test_entry_point_runs_the_pallas_body`` holds that it does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import eva, kda, ssd
from paddle_tpu.ops import pallas as K
from paddle_tpu.ops.selected_rows import (
    SelectedRows, get_tensor_from_selected_rows,
)


@pytest.fixture(autouse=True)
def pallas_bodies():
    with K.override("on"):
        yield


def _matmul_ins(quant):
    x = jnp.ones((8, 16), jnp.float32)
    if quant == "int8":
        return {"X": [x, jnp.ones((16, 8), jnp.int8), jnp.ones((8,))]}
    return {"X": [x, jnp.ones((16, 8), jnp.float32)]}


#: every registered kernel through the entry point the program calls it by
ENTRY_POINTS = {
    "flash_attention": lambda: K.flash_attention(
        *(jnp.ones((1, 1, 128, 16)),) * 3),
    "fused_layer_norm": lambda: K.fused_layer_norm(
        jnp.ones((4, 8)), jnp.ones((8,)), jnp.zeros((8,))),
    "softmax_cross_entropy": lambda: K.softmax_cross_entropy(
        jnp.ones((4, 8)), jnp.zeros((4,), jnp.int32)),
    "grouped_matmul": lambda: K.grouped_matmul(
        jnp.ones((8, 128)), jnp.ones((2, 128, 128)),
        jnp.asarray([5, 3], jnp.int32)),
    "fused_matmul": lambda: K.try_fused_matmul(
        _matmul_ins(None), {"mm_type": "matmul"}),
    "fused_matmul_int8": lambda: K.try_fused_matmul(
        _matmul_ins("int8"), {"mm_type": "matmul", "quant": "int8"}),
    "embedding_scatter_add": lambda: get_tensor_from_selected_rows(
        SelectedRows(jnp.asarray([2, 5, 2], jnp.int32), jnp.ones((3, 6)), 9)),
    "kda_chunked": lambda: kda.kda_chunked(
        *(jnp.ones((1, 128, 1, 128)),) * 3, -jnp.ones((1, 128, 1, 128)),
        jnp.ones((1, 128, 1))),
    "short_conv_norm": lambda: K.short_conv_norm(
        jnp.ones((1, 16, 256)), jnp.ones((4, 256)), 128,
        ((128, 1.0), (128, None))),
    "gated_head_norm": lambda: K.gated_head_norm(
        jnp.ones((1, 16, 128)), jnp.ones((1, 16, 128)), jnp.ones((128,)),
        1e-6, "silu"),
    "gated_short_conv": lambda: K.gated_short_conv(
        jnp.ones((1, 16, 384)), jnp.ones((3, 128))),
    "moe_combine": lambda: K.moe_combine(
        jnp.zeros((16, 128)), jnp.ones((8, 128), jnp.bfloat16),
        jnp.asarray([3, 3, 0, 9, 15, 2, 2, 2], jnp.int32),
        jnp.asarray([1, .5, 2, 1, 1, 0, 0, 0], jnp.float32)),
    "ssd": lambda: ssd.ssd_chunked(
        jnp.ones((1, 32, 2, 64)), jnp.ones((1, 32, 2)), -jnp.ones((1, 32, 2)),
        jnp.ones((1, 32, 1, 16)), jnp.ones((1, 32, 1, 16)), chunk=16),
    "eva_attention": lambda: eva.eva_attention(
        *(jnp.ones((1, 64, 1, 16)),) * 3, *(jnp.ones((1, 8, 1, 16)),) * 2,
        window=32, chunk=8),
}


def test_every_registered_kernel_has_an_entry_point_here():
    assert sorted(ENTRY_POINTS) == K.list_kernels()


@pytest.mark.parametrize("name", sorted(ENTRY_POINTS))
def test_entry_point_runs_the_pallas_body(name, monkeypatch):
    """Under the file's fixture a bare call of a kernel's entry point runs
    its Pallas body, in interpreter mode on the CPU."""
    assert K.selected_body(name) == (
        "pallas_interpret" if K.platform() == "cpu" else "pallas")
    kernel = K.get_kernel(name)
    body, calls = kernel.pallas, []

    def spy(*args, **kwargs):
        calls.append(kwargs["interpret"])
        return body(*args, **kwargs)

    monkeypatch.setattr(kernel, "pallas", spy)
    assert ENTRY_POINTS[name]() is not None
    assert calls == [K.platform() == "cpu"]


def _dense_attention(q, k, v, bias=None, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        sq = s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sq), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


class TestFlashAttention:
    def _rand(self, b=2, h=2, s=128, d=32, seed=0):
        rng = np.random.RandomState(seed)
        q = rng.randn(b, h, s, d).astype(np.float32) * 0.5
        k = rng.randn(b, h, s, d).astype(np.float32) * 0.5
        v = rng.randn(b, h, s, d).astype(np.float32)
        return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)

    def test_matches_dense(self):
        q, k, v = self._rand()
        got = K.flash_attention(q, k, v, block_q=64, block_k=64)
        want = _dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_causal(self):
        q, k, v = self._rand(s=128)
        got = K.flash_attention(q, k, v, causal=True, block_q=64,
                                block_k=64)
        want = _dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_key_padding_bias(self):
        q, k, v = self._rand(s=128)
        bias = np.zeros((2, 128), np.float32)
        bias[:, 100:] = -1e30  # mask tail keys
        got = K.flash_attention(q, k, v, bias=jnp.asarray(bias),
                                block_q=64, block_k=64)
        want = _dense_attention(q, k, v, bias=jnp.asarray(bias))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_unaligned_seq_pads(self):
        q, k, v = self._rand(s=100)  # not a multiple of any block
        got = K.flash_attention(q, k, v)
        want = _dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_unaligned_seq_single_block_branch_bf16(self):
        """Odd S in (128, 512] takes the default single-block branch
        (block_q=block_k=512 default) — it must pad to the 128-lane
        grain before handing Mosaic a whole-array block (ADVICE r1)."""
        q, k, v = self._rand(s=300)
        got = K.flash_attention(q, k, v)  # default blocks: single-block
        want = _dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
        gotb = K.flash_attention(qb, kb, vb)
        np.testing.assert_allclose(np.asarray(gotb, np.float32),
                                   np.asarray(want), atol=2e-2)

    def test_gradients_match_dense(self):
        q, k, v = self._rand(b=1, h=2, s=64, d=16, seed=1)

        def f_flash(q, k, v):
            return jnp.sum(K.flash_attention(q, k, v, block_q=32,
                                             block_k=32) ** 2)

        def f_dense(q, k, v):
            return jnp.sum(_dense_attention(q, k, v) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=3e-4)

    def test_causal_gradients(self):
        q, k, v = self._rand(b=1, h=1, s=64, d=16, seed=2)

        def f_flash(q):
            return jnp.sum(K.flash_attention(q, k, v, causal=True,
                                             block_q=32, block_k=32))

        def f_dense(q):
            return jnp.sum(_dense_attention(q, k, v, causal=True))

        np.testing.assert_allclose(
            np.asarray(jax.grad(f_flash)(q)),
            np.asarray(jax.grad(f_dense)(q)), atol=3e-4)

    #: the one backward kernel, every branch of it: (sequence, block_q,
    #: block_k, causal, key bias, input dtype, atol). The first nine have at
    #: least two blocks on both axes, so dQ accumulates across key blocks
    #: and dK/dV across query blocks; the ``one_tile`` cases are the geometry
    #: of the cell mlm_s512, a head one tile and both heads in one program.
    BACKWARD_CASES = {
        "plain": (256, 128, 128, False, False, jnp.float32, 3e-4),
        "causal": (256, 128, 128, True, False, jnp.float32, 3e-4),
        "key_padding_bias": (256, 128, 128, False, True, jnp.float32, 3e-4),
        "causal_bias": (256, 128, 128, True, True, jnp.float32, 3e-4),
        "s300_padded": (300, 128, 128, False, True, jnp.float32, 3e-4),
        "block_q_gt_block_k": (512, 256, 128, False, True, jnp.float32,
                               3e-4),
        "block_q_lt_block_k": (512, 128, 256, True, False, jnp.float32,
                               3e-4),
        # 1152 / 128 = 9 query tiles: groups of three inside a loop
        "nine_query_tiles": (1152, 128, 128, False, False, jnp.float32,
                             3e-4),
        "bf16": (256, 128, 128, False, True, jnp.bfloat16, 4e-2),
        "one_tile_s512": (512, 512, 512, False, True, jnp.float32, 3e-4),
        "one_tile_s512_bf16": (512, 512, 512, False, True, jnp.bfloat16,
                               4e-2),
        "one_tile_s384": (384, 512, 512, False, True, jnp.float32, 3e-4),
        "one_tile_s380_padded": (380, 512, 512, False, True, jnp.float32,
                                 3e-4),
        "one_tile_s380_padded_bf16": (380, 512, 512, False, True,
                                      jnp.bfloat16, 4e-2),
        "one_tile_causal": (256, 512, 512, True, False, jnp.float32, 3e-4),
    }

    @pytest.mark.parametrize("case", sorted(BACKWARD_CASES))
    def test_one_backward_kernel_matches_dense(self, case):
        s, bq, bk, causal, with_bias, dtype, atol = self.BACKWARD_CASES[case]
        q, k, v = (t.astype(dtype)
                   for t in self._rand(b=2, h=2, s=s, d=32, seed=3))
        rng = np.random.RandomState(4)
        w = jnp.asarray(rng.randn(2, 2, s, 32).astype(np.float32))
        bias = np.zeros((2, s), np.float32)
        if with_bias:
            # a soft bias on the kept keys and a padding mask on the tail
            bias[:] = rng.randn(2, s) * 0.3
            bias[:, s - s // 5:] = -1e30
        bias = jnp.asarray(bias)

        def f_flash(q, k, v, bias):
            out = K.flash_attention(q, k, v, bias=bias, causal=causal,
                                    block_q=bq, block_k=bk)
            return jnp.sum(out.astype(jnp.float32) * w)

        def f_dense(q, k, v, bias):
            return jnp.sum(_dense_attention(q, k, v, bias=bias,
                                            causal=causal) * w)

        got = jax.grad(f_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        want = jax.grad(f_dense, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for name, a, b_ in zip(("dq", "dk", "dv", "dbias"), got, want):
            assert a.dtype == b_.dtype, name
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b_, np.float32),
                atol=atol, err_msg=f"{case}: {name}")

    @pytest.mark.parametrize("heads,programs", [(12, 3), (7, 7), (4, 1)])
    def test_several_heads_a_program_match_dense(self, heads, programs):
        """Where a head is one tile a program takes several (the largest
        divisor of the head count up to four): the forward and all four
        gradients, heads in one program and in more than one."""
        import importlib
        fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
        assert heads // fa._heads_per_program(heads, 1, 1) == programs
        assert fa._heads_per_program(heads, 2, 2) == 1
        q, k, v = self._rand(b=2, h=heads, s=128, d=16, seed=7)
        rng = np.random.RandomState(8)
        w = jnp.asarray(rng.randn(2, heads, 128, 16).astype(np.float32))
        bias = rng.randn(2, 128).astype(np.float32) * 0.3
        bias[:, 100:] = -1e30
        bias = jnp.asarray(bias)

        def f_flash(q, k, v, bias):
            return jnp.sum(K.flash_attention(q, k, v, bias=bias) * w)

        def f_dense(q, k, v, bias):
            return jnp.sum(_dense_attention(q, k, v, bias=bias) * w)

        np.testing.assert_allclose(
            np.asarray(K.flash_attention(q, k, v, bias=bias)),
            np.asarray(_dense_attention(q, k, v, bias=bias)), atol=2e-5)
        for name, a, b_ in zip(
                ("dq", "dk", "dv", "dbias"),
                jax.grad(f_flash, (0, 1, 2, 3))(q, k, v, bias),
                jax.grad(f_dense, (0, 1, 2, 3))(q, k, v, bias)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=3e-4, err_msg=name)

    def test_causal_at_head_size_128_matches_dense(self):
        """OLMoE's shape of attention (16 heads of 128, causal) through the
        Pallas bodies, two blocks each way: the forward and all three
        gradients."""
        q, k, v = self._rand(b=1, h=1, s=512, d=128, seed=5)
        w = jnp.asarray(np.random.RandomState(6).randn(1, 1, 512, 128)
                        .astype(np.float32))
        blocks = dict(block_q=256, block_k=256)

        def f_flash(q, k, v):
            return jnp.sum(K.flash_attention(q, k, v, causal=True, **blocks)
                           * w)

        def f_dense(q, k, v):
            return jnp.sum(_dense_attention(q, k, v, causal=True) * w)

        np.testing.assert_allclose(
            np.asarray(K.flash_attention(q, k, v, causal=True, **blocks)),
            np.asarray(_dense_attention(q, k, v, causal=True)), atol=2e-5)
        for name, a, b_ in zip(("dq", "dk", "dv"),
                               jax.grad(f_flash, (0, 1, 2))(q, k, v),
                               jax.grad(f_dense, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-4, err_msg=name)

    #: the rows-major operands ([B, S, heads D]: the projections' layout):
    #: query heads, key/value heads, positions, head size, blocks, causal,
    #: window, with a key bias, q k v packed in one array, dtype, tolerance
    #: and the layout the call must take ("" = transposed to heads-major)
    ROWS_MAJOR_CASES = {
        # BERT's: 12 heads of 64, two a lane tile, one tile a head, packed
        "d64_pairs_one_tile": (12, 12, 128, 64, 512, False, None, True,
                               True, jnp.float32, 3e-4, "rows_major"),
        "d64_pairs_many_tiles": (4, 4, 256, 64, 128, False, None, True,
                                 True, jnp.float32, 3e-4, "rows_major"),
        "d64_pairs_bf16": (4, 4, 256, 64, 128, False, None, True, True,
                           jnp.bfloat16, 6e-2, "rows_major"),
        "d64_three_arrays_causal": (4, 4, 256, 64, 128, True, None, False,
                                    False, jnp.float32, 3e-4, "rows_major"),
        "d64_one_tile_causal": (2, 2, 128, 64, 512, True, None, False, True,
                                jnp.float32, 3e-4, "rows_major"),
        "d64_window": (2, 2, 384, 64, 128, True, 100, False, True,
                       jnp.float32, 3e-4, "rows_major"),
        "d64_padded_s300": (6, 6, 300, 64, 512, False, None, True, True,
                            jnp.float32, 3e-4, "rows_major"),
        # a head a lane tile
        "d128_packed_bias": (4, 4, 128, 128, 512, False, None, True, True,
                             jnp.float32, 3e-4, "rows_major"),
        "d128_group_causal": (4, 2, 256, 128, 128, True, None, False, False,
                              jnp.float32, 5e-4, "rows_major"),
        "d128_group_window": (4, 2, 384, 128, 128, True, 100, False, False,
                              jnp.float32, 5e-4, "rows_major"),
        "d128_group_bias": (4, 2, 256, 128, 128, False, None, True, False,
                            jnp.float32, 5e-4, "rows_major"),
        # shapes the rows-major blocks do not take: an odd count of 64-wide
        # heads; a pair of query heads over one key/value head; D = 192
        "d64_seven_heads": (7, 7, 128, 64, 512, False, None, True, True,
                            jnp.float32, 3e-4, ""),
        "d64_group": (4, 2, 128, 64, 512, True, None, False, False,
                      jnp.float32, 3e-4, ""),
        "d192": (2, 2, 128, 192, 512, True, None, False, False,
                 jnp.float32, 5e-4, ""),
    }

    @pytest.mark.parametrize("case", sorted(ROWS_MAJOR_CASES))
    def test_rows_major_matches_dense_and_heads_major(self, case):
        """q, k and v as the projections leave them against the dense
        attention and against the heads-major call on the same numbers: the
        context, dQ, dK, dV and the key-bias gradient."""
        import importlib
        fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
        (h, hkv, s, d, block, causal, window, with_bias, packed, dtype, atol,
         layout) = self.ROWS_MAJOR_CASES[case]
        b = 2
        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(b, s, h * d) * 0.5, dtype)
        k = jnp.asarray(rng.randn(b, s, hkv * d) * 0.5, dtype)
        v = jnp.asarray(rng.randn(b, s, hkv * d), dtype)
        w = jnp.asarray(rng.randn(b, s, h * d).astype(np.float32))
        bias = np.zeros((b, s), np.float32)
        if with_bias:
            bias[:] = rng.randn(b, s) * 0.3
            bias[:, s - s // 5:] = -1e30
        bias = jnp.asarray(bias)
        static = dict(causal=causal, window=window, block_q=block,
                      block_k=block)

        def heads(t):
            return t.reshape(b, s, -1, d).transpose(0, 2, 1, 3)

        def rows(t):
            return t.transpose(0, 2, 1, 3).reshape(b, s, -1)

        def f_rows(q, k, v, bias):
            if packed:
                out = K.flash_attention(jnp.concatenate([q, k, v], -1),
                                        bias=bias, num_heads=h, **static)
            else:
                out = K.flash_attention(q, k, v, bias=bias, num_heads=h,
                                        **static)
            return jnp.sum(out.astype(jnp.float32) * w), out

        def f_heads(q, k, v, bias):
            out = rows(K.flash_attention(heads(q), heads(k), heads(v),
                                         bias=bias, **static))
            return jnp.sum(out.astype(jnp.float32) * w), out

        def f_dense(q, k, v, bias):
            with K.override("off"):
                return f_heads(q, k, v, bias)

        operands = (jnp.concatenate([q, k, v], -1),) if packed else (q, k, v)
        assert fa.operand_layout(*operands, num_heads=h) == layout
        (_, out), got = jax.value_and_grad(f_rows, (0, 1, 2, 3),
                                           has_aux=True)(q, k, v, bias)
        assert out.shape == (b, s, h * d) and out.dtype == dtype
        for other in (f_heads, f_dense):
            (_, want_out), want = jax.value_and_grad(
                other, (0, 1, 2, 3), has_aux=True)(q, k, v, bias)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(want_out, np.float32),
                atol=atol / 10, err_msg=f"{case}: {other.__name__}")
            for name, a, b_ in zip(("dq", "dk", "dv", "dbias"), got, want):
                assert a.dtype == b_.dtype and a.shape == b_.shape, name
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b_, np.float32),
                    atol=atol, err_msg=f"{case}: {other.__name__}: {name}")

    def test_rows_major_operands_say_their_heads(self):
        q = jnp.ones((1, 128, 256))
        with pytest.raises(ValueError, match="num_heads"):
            K.flash_attention(q, q, q)
        with pytest.raises(ValueError, match="k and v both"):
            K.flash_attention(q, q, num_heads=4)
        with pytest.raises(ValueError, match="4 query heads over 3"):
            K.flash_attention(q, q[..., :192], q[..., :192], num_heads=4)

    def test_bfloat16(self):
        q, k, v = self._rand(s=64, d=32)
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
        got = K.flash_attention(qb, kb, vb, block_q=32, block_k=32)
        assert got.dtype == jnp.bfloat16
        want = _dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), atol=2e-2)


class TestFusedLayerNorm:
    def _ref(self, x, g, b, eps=1e-12):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
        return (x32 - mu) * jax.lax.rsqrt(var + eps) * g + b

    def test_matches_reference(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(6, 5, 64).astype(np.float32))
        g = jnp.asarray(rng.rand(64).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(64).astype(np.float32))
        got = K.fused_layer_norm(x, g, b, block_n=8)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(self._ref(x, g, b)),
                                   atol=1e-5)

    def test_gradients(self):
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(10, 32).astype(np.float32))
        g = jnp.asarray(rng.rand(32).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(32).astype(np.float32))

        def f1(x, g, b):
            return jnp.sum(K.fused_layer_norm(x, g, b, block_n=4) ** 2)

        def f2(x, g, b):
            return jnp.sum(self._ref(x, g, b) ** 2)

        g1 = jax.grad(f1, argnums=(0, 1, 2))(x, g, b)
        g2 = jax.grad(f2, argnums=(0, 1, 2))(x, g, b)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4)

    def test_unaligned_rows(self):
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(7, 16).astype(np.float32))  # 7 % 4 != 0
        g = jnp.ones(16)
        b = jnp.zeros(16)
        got = K.fused_layer_norm(x, g, b, block_n=4)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(self._ref(x, g, b)),
                                   atol=1e-5)


class TestSoftmaxXent:
    def test_matches_reference(self):
        rng = np.random.RandomState(6)
        logits = jnp.asarray(rng.randn(12, 50).astype(np.float32) * 3)
        labels = jnp.asarray(rng.randint(0, 50, 12))
        got = K.softmax_cross_entropy(logits, labels, block_n=4)
        want = -jax.nn.log_softmax(logits)[jnp.arange(12), labels]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_gradients(self):
        rng = np.random.RandomState(7)
        logits = jnp.asarray(rng.randn(8, 20).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, 20, 8))

        def f1(lg):
            return jnp.mean(K.softmax_cross_entropy(lg, labels, block_n=4))

        def f2(lg):
            return jnp.mean(-jax.nn.log_softmax(lg)[jnp.arange(8), labels])

        np.testing.assert_allclose(np.asarray(jax.grad(f1)(logits)),
                                   np.asarray(jax.grad(f2)(logits)),
                                   atol=1e-5)

    def test_leading_dims(self):
        rng = np.random.RandomState(8)
        logits = jnp.asarray(rng.randn(2, 5, 30).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, 30, (2, 5)))
        got = K.softmax_cross_entropy(logits, labels)
        assert got.shape == (2, 5)


class TestBertFlashIntegration:
    def test_bert_flash_matches_dense(self):
        from paddle_tpu.models import bert
        cfg_d = bert.bert_tiny(attention_impl="dense")
        cfg_f = bert.bert_tiny(attention_impl="flash")
        params = bert.init_params(jax.random.PRNGKey(0), cfg_d)
        batch = bert.synthetic_batch(cfg_d, batch_size=2, seq_len=64)
        out_d = bert.forward(params, cfg_d, batch["input_ids"],
                             batch["token_type_ids"],
                             batch["attention_mask"])
        out_f = bert.forward(params, cfg_f, batch["input_ids"],
                             batch["token_type_ids"],
                             batch["attention_mask"])
        np.testing.assert_allclose(np.asarray(out_d, np.float32),
                                   np.asarray(out_f, np.float32),
                                   atol=3e-2)


class TestFlashBlockRegression:
    def test_mismatched_blocks_pad_to_lcm(self):
        # S=192 with block_q=64, block_k=128 silently dropped keys
        # 128..191 before the lcm padding fix
        rng = np.random.RandomState(40)
        q = jnp.asarray(rng.randn(1, 2, 192, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 2, 192, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 2, 192, 16).astype(np.float32))
        got = K.flash_attention(q, k, v, block_q=64, block_k=128)
        want = _dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_oversize_blocks(self):
        rng = np.random.RandomState(41)
        q = jnp.asarray(rng.randn(1, 1, 300, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 1, 300, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 1, 300, 16).astype(np.float32))
        got = K.flash_attention(q, k, v, block_q=256, block_k=256)
        want = _dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
