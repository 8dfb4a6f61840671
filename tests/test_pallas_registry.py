"""Pallas kernel registry: selection semantics + kernel/stock parity.

Every registered kernel must agree with its stock-jnp reference — forward
AND backward (value_and_grad) — across dtypes (fp32/bf16) and ragged
shapes (non-multiples of the Mosaic block grain, zero-row gathers,
duplicate-index scatter-adds). On CPU the Pallas bodies run in
interpreter mode: the same kernel code the TPU compiles, so these tests
pin TPU semantics from the CI host."""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu.ops.pallas as plk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.RandomState(42)

KERNELS = ["embedding_scatter_add", "eva_attention", "flash_attention",
           "fused_layer_norm",
           "fused_matmul", "fused_matmul_int8", "gated_head_norm",
           "gated_short_conv", "grouped_matmul", "kda_chunked", "moe_combine",
           "short_conv_norm", "softmax_cross_entropy", "ssd"]


def _f(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.randn(*shape) * scale, dtype)


def _selection_gauge():
    from paddle_tpu.monitor.registry import gauge
    return gauge("pallas_kernels_selected",
                 "Which body the Pallas kernel registry selected "
                 "(1 = active), per kernel",
                 labels=("kernel", "body"))


def _close(a, b, dtype=jnp.float32, **kw):
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)
    tol.update(kw)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **tol)


def _tree_close(a, b, dtype=jnp.float32, **kw):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for u, v in zip(la, lb):
        _close(u, v, dtype, **kw)


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_all_kernels_registered(self):
        names = plk.list_kernels()
        for want in KERNELS:
            assert want in names
        # the optimizer's rules are plain jnp on every leaf: no kernel
        assert not [n for n in names if "adam" in n or "sgd" in n
                    or "momentum" in n]

    def test_selection_policy_cpu(self):
        if plk.platform() != "cpu":
            pytest.skip("selection table below is the CPU one")
        with plk.override("auto"):
            assert plk.selected_body("fused_matmul") == "reference"
            assert not plk.use_pallas("fused_matmul")
        with plk.override("on"):
            assert plk.selected_body("fused_matmul") == "pallas_interpret"
            assert plk.use_pallas("fused_matmul")
        with plk.override("off"):
            assert plk.selected_body("fused_matmul") == "reference"

    #: what a fresh interpreter, told nothing but this, prints as JSON.
    #: The package alone registers everything and there is one kernel home;
    #: the selectors a person could once set choose nothing.
    FRESH = {
        "the_package_alone_registers_every_kernel": (
            "import paddle_tpu.ops.pallas as p; print(json.dumps("
            "p.list_kernels()))", {}, KERNELS),
        "the_old_module_is_gone": (
            "import importlib.util as u, paddle_tpu.ops as o; print(json.dumps("
            "u.find_spec(o.__name__ + '.pallas_kernels') is None))", {}, True),
        "flag_and_variable_select_nothing": (
            "import paddle_tpu.ops.pallas as p; print(json.dumps("
            "[p.selected_body(k) for k in p.list_kernels()]))",
            {"PADDLE_TPU_PALLAS": "1", "FLAGS_use_pallas_kernels": "on"},
            ["reference"] * len(KERNELS)),
    }

    @pytest.mark.parametrize("case", sorted(FRESH))
    def test_in_a_fresh_interpreter(self, case):
        code, env, want = self.FRESH[case]
        env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", "import json; " + code],
                           capture_output=True, text=True, timeout=300,
                           env=env, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        assert json.loads(r.stdout.splitlines()[-1]) == want

    def test_override_refuses_what_is_not_a_mode(self):
        with pytest.raises(ValueError, match="one of"):
            with plk.override("1"):
                pass

    def test_reference_only_kernel_never_selects_pallas(self):
        plk.register_kernel("_test_ref_only", lambda x: x + 1)
        try:
            with plk.override("on"):
                assert plk.selected_body("_test_ref_only") == "reference"
                assert plk.dispatch("_test_ref_only", 1) == 2
        finally:
            # leave the registry as it was: test_tpu_aot_compile.py holds
            # every registered kernel to a shape, on whichever worker it runs
            plk.registry._REGISTRY.pop("_test_ref_only")

    def test_selection_gauge_published(self):
        with plk.override("on"):
            plk.dispatch("fused_layer_norm", _f((4, 8)), _f((8,)),
                         _f((8,)))
        body = "pallas_interpret" if plk.platform() == "cpu" else "pallas"
        assert _selection_gauge().value(kernel="fused_layer_norm",
                                        body=body) == 1.0

    def test_override_nests_and_restores(self):
        with plk.override("off"):
            with plk.override("on"):
                assert plk.selection_mode() == "on"
            assert plk.selection_mode() == "off"

    def test_platform_probe_is_cached(self):
        assert plk.platform() is plk.platform.__wrapped__() \
            or plk.platform() == plk.platform.__wrapped__()
        info = plk.platform.cache_info()
        assert info.hits >= 1

    def test_platform_probe_does_not_hide_a_dead_backend(self, monkeypatch):
        def boom():
            raise RuntimeError("no backend")
        monkeypatch.setattr(jax, "devices", boom)
        with pytest.raises(RuntimeError, match="no backend"):
            plk.platform.__wrapped__()

    def test_mesh_scope_takes_reference_on_a_multi_device_mesh(
            self, monkeypatch):
        """On a chip, `auto` picks the Pallas body — except inside a
        mesh_scope of more than one device, where GSPMD would have to
        partition a Mosaic call and the lowering refuses."""
        from types import SimpleNamespace as Mesh
        from paddle_tpu.ops.pallas import registry
        monkeypatch.setattr(registry, "platform", lambda: "tpu")
        assert plk.selected_body("fused_layer_norm") == "pallas"
        with plk.mesh_scope(Mesh(size=4)):
            assert plk.selected_body("fused_layer_norm") == "reference"
            assert not plk.use_pallas("fused_matmul")
            with plk.mesh_scope(None):          # e.g. a shard_map body
                assert plk.selected_body("fused_layer_norm") == "pallas"
            with plk.override("on"):            # forced on stays forced
                assert plk.selected_body("fused_layer_norm") == "pallas"
            assert plk.selected_body("fused_layer_norm") == "reference"
        with plk.mesh_scope(Mesh(size=1)):
            assert plk.selected_body("fused_layer_norm") == "pallas"
        assert plk.selected_body("fused_layer_norm") == "pallas"


# ---------------------------------------------------------------------------
# fused_matmul parity
# ---------------------------------------------------------------------------
class TestFusedMatmul:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 7, 9), (1, 3, 5), (16, 64, 32),
                                       (130, 260, 140)])
    @pytest.mark.parametrize("act", [None, "relu", "gelu"])
    def test_forward_backward_parity(self, dtype, shape, act):
        m, k, n = shape
        x = _f((m, k), dtype)
        w = _f((k, n), dtype)
        b = _f((n,), dtype)
        def run(*args):
            def loss(x, w, b):
                out = plk.dispatch("fused_matmul", x, w, bias=b, act=act)
                return jnp.sum(out.astype(jnp.float32) ** 2), out
            return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
                *args)

        with plk.override("off"):
            (lr, outr), gr = run(x, w, b)
        with plk.override("on"):
            (lp, outp), gp = run(x, w, b)

        # both sides accumulate in different orders (the kernel splits K
        # into tiles; bf16 additionally rounds at different points), so
        # cancellation makes per-element relative error unbounded near
        # zero — compare with atol scaled to the array's magnitude
        rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        def close(u, v):
            scale = float(max(1.0, np.abs(np.asarray(v, np.float32)).max()))
            _close(u, v, dtype, rtol=rtol, atol=rtol * scale)
        close(outr, outp)
        close(lr, lp)
        for u, v in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
            close(u, v)
        assert outp.dtype == outr.dtype
        for u, v in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
            assert u.dtype == v.dtype

    @pytest.mark.parametrize("act", [None, "sigmoid", "tanh"])
    def test_leading_dims_and_acts(self, act):
        x = _f((2, 3, 5))
        w = _f((5, 11))
        ref = plk.get_body("fused_matmul", "reference")(x, w, act=act)
        pal = plk.get_body("fused_matmul", "pallas")(
            x, w, act=act, interpret=plk.platform() == "cpu")
        _close(ref, pal)
        assert pal.shape == (2, 3, 11)

    def test_int8_matches_sidecar_dequant(self):
        for m, k, n in [(4, 7, 9), (16, 256, 128), (3, 130, 200)]:
            x = _f((m, k))
            w8 = jnp.asarray(RNG.randint(-127, 128, (k, n)), jnp.int8)
            scale = jnp.abs(_f((n,))) + 0.01
            b = _f((n,))
            for act in (None, "relu", "gelu"):
                ref = plk.get_body("fused_matmul_int8", "reference")(
                    x, w8, scale, bias=b, act=act)
                with plk.override("on"):
                    pal = plk.dispatch("fused_matmul_int8", x, w8, scale,
                                       bias=b, act=act)
                _close(ref, pal)

    def test_static_program_fused_matmul_forced_on(self):
        """End-to-end: the optimized static program's fused_matmul op
        must produce identical fetches with the registry forced on."""
        import paddle_tpu as pt
        from paddle_tpu import layers

        pt.enable_static()
        from paddle_tpu.framework import unique_name
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup), unique_name.guard():
            x = pt.static.data("x", [24], dtype="float32")
            h = layers.fc(x, 48, act="relu")
            out = layers.fc(h, 8, act="gelu")
        scope = pt.static.Scope()
        with pt.static.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            feed = {"x": RNG.rand(6, 24).astype(np.float32)}
            a = exe.run(main, feed=feed, fetch_list=[out])[0]
            with plk.override("on"):
                b = exe.run(main, feed=feed, fetch_list=[out])[0]
        _close(a, b)


# ---------------------------------------------------------------------------
# embedding scatter-add parity
# ---------------------------------------------------------------------------
class TestEmbedding:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_scatter_add_duplicates_deterministic(self, dtype):
        dst = _f((33, 130), dtype)
        # heavy duplication: 40 updates onto 5 distinct rows
        ids = jnp.asarray(RNG.randint(0, 5, 40), jnp.int32)
        upd = _f((40, 130), dtype)
        ref = plk.get_body("embedding_scatter_add", "reference")(
            dst, ids, upd)
        with plk.override("on"):
            a = plk.dispatch("embedding_scatter_add", dst, ids, upd)
            b = plk.dispatch("embedding_scatter_add", dst, ids, upd)
        _close(ref, a, dtype)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_scatter_add_backward(self):
        dst = _f((16, 24))
        ids = jnp.asarray([3, 3, 0, 15, 7], jnp.int32)
        upd = _f((5, 24))

        def loss(d, u):
            return jnp.sum(
                plk.dispatch("embedding_scatter_add", d, ids, u) ** 2)

        with plk.override("off"):
            lr, gr = jax.value_and_grad(loss, (0, 1))(dst, upd)
        with plk.override("on"):
            lp, gp = jax.value_and_grad(loss, (0, 1))(dst, upd)
        _close(lr, lp)
        _tree_close(gr, gp)

    def test_selected_rows_ops_forced_on(self):
        from paddle_tpu.ops.selected_rows import (
            SelectedRows, get_tensor_from_selected_rows,
            merge_selected_rows, sparse_sgd_update)

        sr = SelectedRows(jnp.asarray([2, 5, 2, 0], jnp.int32),
                          _f((4, 6)), 9)
        dense_off = get_tensor_from_selected_rows(sr)
        merged_off, valid_off = merge_selected_rows(sr)
        upd_off = sparse_sgd_update(_f((9, 6)), sr, 0.1)
        with plk.override("on"):
            dense_on = get_tensor_from_selected_rows(sr)
            merged_on, valid_on = merge_selected_rows(sr)
        _close(dense_off, dense_on)
        _close(merged_off.values, merged_on.values)
        np.testing.assert_array_equal(np.asarray(valid_off),
                                      np.asarray(valid_on))

    def test_nn_embedding_forced_on(self):
        from paddle_tpu.ops import nn

        tbl = _f((30, 18))
        ids = jnp.asarray(RNG.randint(0, 30, (4, 7)), jnp.int32)
        off = nn.embedding(ids, tbl, padding_idx=0)
        with plk.override("on"):
            on = nn.embedding(ids, tbl, padding_idx=0)
        _close(off, on)


# ---------------------------------------------------------------------------
# migrated legacy kernels (flash attention / layer norm / xent)
# ---------------------------------------------------------------------------
class TestMigratedKernels:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [False, True])
    def test_attention_parity(self, dtype, causal):
        q = _f((2, 2, 72, 16), dtype, 0.5)   # ragged S=72 (pads to 128)
        k = _f((2, 2, 72, 16), dtype, 0.5)
        v = _f((2, 2, 72, 16), dtype, 0.5)
        bias = jnp.where(jnp.arange(72)[None, :] < 60, 0.0, -1e9) \
            * jnp.ones((2, 1))

        def loss(body, q, k, v):
            out = body(q, k, v, bias=bias, causal=causal)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        ref = plk.get_body("flash_attention", "reference")
        lr, gr = jax.value_and_grad(
            lambda *a: loss(ref, *a), (0, 1, 2))(q, k, v)
        with plk.override("on"):
            lp, gp = jax.value_and_grad(
                lambda *a: loss(plk.flash_attention, *a), (0, 1, 2))(
                q, k, v)
        tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
            else dict(rtol=2e-4, atol=2e-4)
        _close(lr, lp, dtype, **tol)
        _tree_close(gr, gp, dtype, **tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_layer_norm_parity(self, dtype):
        x = _f((5, 33, 130), dtype)   # ragged rows AND hidden
        g = _f((130,))
        b = _f((130,))

        def loss(body, x, g, b):
            return jnp.sum(body(x, g, b).astype(jnp.float32) ** 2)

        ref = plk.get_body("fused_layer_norm", "reference")
        lr, gr = jax.value_and_grad(
            lambda *a: loss(ref, *a), (0, 1, 2))(x, g, b)
        with plk.override("on"):
            lp, gp = jax.value_and_grad(
                lambda *a: loss(plk.fused_layer_norm, *a), (0, 1, 2))(
                x, g, b)
        tol = dict(rtol=5e-2, atol=5e-1) if dtype == jnp.bfloat16 \
            else dict(rtol=1e-4, atol=1e-3)
        _close(lr, lp, dtype, **tol)
        _tree_close(gr, gp, dtype, **tol)

    def test_layer_norm_reference_is_flag_off_dispatch(self):
        """auto mode on CPU must return the stock reference result
        bit-for-bit (models/bert._layer_norm routes through it)."""
        if plk.platform() != "cpu":
            pytest.skip("CPU selection table")
        x, g, b = _f((7, 64)), _f((64,)), _f((64,))
        a = plk.fused_layer_norm(x, g, b)
        r = plk.get_body("fused_layer_norm", "reference")(x, g, b)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_xent_parity(self, dtype):
        logits = _f((13, 77), dtype, 2.0)    # ragged rows and vocab
        labels = jnp.asarray(RNG.randint(0, 77, 13), jnp.int32)

        def loss(body, lg):
            return jnp.sum(body(lg, labels))

        ref = plk.get_body("softmax_cross_entropy", "reference")
        lr, gr = jax.value_and_grad(lambda lg: loss(ref, lg))(logits)
        with plk.override("on"):
            lp, gp = jax.value_and_grad(
                lambda lg: loss(plk.softmax_cross_entropy, lg))(logits)
        tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
            else dict(rtol=1e-4, atol=1e-4)
        _close(lr, lp, dtype, **tol)
        _close(gr, gp, dtype, **tol)


# ---------------------------------------------------------------------------
# a kernel that leads with the batch, a shard at a time on a data mesh
# ---------------------------------------------------------------------------
def _mesh(**axes):
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    n = int(np.prod(list(axes.values())))
    return make_mesh(MeshConfig(**axes), devices=jax.devices()[:n])


def _key_bias(b, s):
    """A key-padding bias: rows 1 and 2 mask their last keys."""
    keys = np.arange(s)[None, :]
    kept = np.full((b, 1), s)
    kept[1], kept[2] = s - 37, s // 2
    return jnp.asarray(np.where(keys < kept, 0.0, -1e30), jnp.float32)


#: (q, k, v shapes, with a key bias, the call's static arguments)
PER_SHARD_FORMS = {
    # BERT's: every head its own keys, a padding bias, sized for the
    # interpreter (the cell is [64, 12, 512, 64] a shard, one tile a head)
    "bert": ((8, 4, 256, 64), (8, 4, 256, 64), (8, 4, 256, 64), True, {}),
    # the decoders': causal, a window, 8 query heads over 2 key/value
    # heads, value heads of their own size
    "decoder": ((8, 8, 256, 64), (8, 2, 256, 64), (8, 2, 256, 32), False,
                {"causal": True, "window": 128}),
    # the projections' own layout, [B, S, heads D]: BERT's since PR 36, four
    # heads of 64 as two lane tiles of two; and heads of 128 over a
    # key/value group, causal
    "bert_rows_major": ((8, 256, 256), (8, 256, 256), (8, 256, 256), True,
                        {"num_heads": 4}),
    "decoder_rows_major": ((8, 256, 512), (8, 256, 256), (8, 256, 256),
                           False, {"causal": True, "num_heads": 4}),
}


class TestPerShard:
    @pytest.mark.parametrize("form", sorted(PER_SHARD_FORMS))
    def test_flash_on_a_data_mesh_is_the_one_device_call(self, form):
        """Under a mesh that splits only the batch the registry runs the
        Pallas body a shard at a time inside shard_map: the same values and
        the same gradients of q, k, v as the call on one device, and a
        result that stays split by the batch."""
        qs, ks, vs, biased, static = PER_SHARD_FORMS[form]
        q, k, v = _f(qs, scale=0.5), _f(ks, scale=0.5), _f(vs)
        positions = qs[1] if "num_heads" in static else qs[2]
        bias = _key_bias(qs[0], positions) if biased else None
        weight = _f(qs if "num_heads" in static else qs[:3] + vs[3:])
        mesh = _mesh(data=4)

        def loss(q, k, v, mesh=None):
            with plk.mesh_scope(mesh):
                out = plk.flash_attention(q, k, v, bias=bias, block_q=128,
                                          block_k=128, **static)
            return jnp.sum(out * weight), out

        grad = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        rows = NamedSharding(mesh, P("data"))
        with plk.override("on"):
            with plk.mesh_scope(mesh):
                assert plk.selected_body("flash_attention", qs[0]) == \
                    "pallas_per_shard_interpret"
            (_, want), want_grads = grad(q, k, v)
            (_, got), got_grads = jax.jit(
                lambda *qkv: grad(*qkv, mesh=mesh))(
                *(jax.device_put(t, rows) for t in (q, k, v)))
        assert got.sharding.is_equivalent_to(rows, got.ndim)
        _close(got, want, atol=1e-6)
        _tree_close(got_grads, want_grads, atol=1e-6)

    def test_packed_operand_a_shard_at_a_time_and_the_gauge_says_so(self):
        """BERT's own call since PR 36: x @ qkv_w as it is, one [B, S, 3 H D]
        array, k and v None. On data=4 it runs a shard at a time like the
        one-device call, and the gauge's body names the operand layout
        beside the place the body runs."""
        qkv, bias = _f((8, 128, 3 * 256), scale=0.5), _key_bias(8, 128)
        weight = _f((8, 128, 256))
        mesh = _mesh(data=4)

        def loss(qkv, mesh=None):
            with plk.mesh_scope(mesh):
                out = plk.flash_attention(qkv, bias=bias, num_heads=4)
            return jnp.sum(out * weight), out

        grad = jax.value_and_grad(loss, has_aux=True)
        rows = NamedSharding(mesh, P("data"))
        gauge = _selection_gauge()
        with plk.override("on"):
            (_, want), want_grad = grad(qkv)
            assert gauge.value(kernel="flash_attention",
                               body="pallas_rows_major_interpret") == 1
            (_, got), got_grad = jax.jit(lambda t: grad(t, mesh=mesh))(
                jax.device_put(qkv, rows))
            assert gauge.value(
                kernel="flash_attention",
                body="pallas_per_shard_rows_major_interpret") == 1
            assert gauge.value(kernel="flash_attention",
                               body="pallas_rows_major_interpret") == 0
            # heads-major operands keep the names there were
            plk.flash_attention(*(_f((1, 2, 128, 32)),) * 3)
            assert gauge.value(kernel="flash_attention",
                               body="pallas_interpret") == 1
        assert got.sharding.is_equivalent_to(rows, got.ndim)
        _close(got, want, atol=1e-6)
        _close(got_grad, want_grad, atol=1e-6)

    #: (kernel, mesh axes, batch) -> the body `auto` selects on a chip
    SELECTIONS = {
        "flash_on_data4": ("flash_attention", {"data": 4}, 256,
                           "pallas_per_shard"),
        "flash_before_the_operands_are_there": (
            "flash_attention", {"data": 4}, None, "pallas_per_shard"),
        "flash_on_a_batch_the_axis_does_not_divide": (
            "flash_attention", {"data": 4}, 6, "reference"),
        "flash_on_data2_model2": ("flash_attention",
                                  {"data": 2, "model": 2}, 256, "reference"),
        "flash_on_data2_seq2": ("flash_attention", {"data": 2, "seq": 2},
                                256, "reference"),
        "flash_on_one_device": ("flash_attention", {"data": 1}, 256,
                                "pallas"),
        "layer_norm_declares_nothing": ("fused_layer_norm", {"data": 4}, 256,
                                        "reference"),
        "grouped_matmul_declares_nothing": ("grouped_matmul", {"data": 4},
                                            256, "reference"),
    }

    @pytest.mark.parametrize("case", sorted(SELECTIONS))
    def test_selection_from_the_mesh_and_the_batch(self, case, monkeypatch):
        kernel, axes, batch, body = self.SELECTIONS[case]
        monkeypatch.setattr(plk.registry, "platform", lambda: "tpu")
        with plk.mesh_scope(_mesh(**axes)):
            assert plk.selected_body(kernel, batch) == body

    def test_inside_a_shard_map_body_there_is_no_second_split(
            self, monkeypatch):
        """A trainer's shard_map body that passes its mesh on to the model
        (DataParallelTrainer's ZeRO step with a loss that takes the mesh)
        keeps the parent's answer under that scope, and does not nest a
        shard_map over an axis that is manual already."""
        monkeypatch.setattr(plk.registry, "platform", lambda: "tpu")
        mesh, seen = _mesh(data=4), []

        def body(q):
            with plk.mesh_scope(mesh):
                seen.append(plk.selected_body("flash_attention", q.shape[0]))
            return q

        jax.eval_shape(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data")), _f((32, 2, 128, 32)))
        assert seen == ["reference"]

    def test_a_dispatch_reads_the_batch_from_its_operands(self, monkeypatch):
        """What a caller tells ``selected_body`` a dispatch reads off its
        first operand: six rows over data=4 take the reference on a chip."""
        kernel = plk.get_kernel("flash_attention")
        ran = []
        monkeypatch.setattr(plk.registry, "platform", lambda: "tpu")
        monkeypatch.setattr(kernel, "reference",
                            lambda q, *a, **kw: ran.append(q.shape) or q)
        q = _f((6, 2, 128, 32))
        with plk.mesh_scope(_mesh(data=4)):
            plk.flash_attention(q, q, q)
        assert ran == [(6, 2, 128, 32)]

    def test_the_layers_share_one_trace_of_the_per_shard_call(
            self, monkeypatch):
        """The wrapper is a jitted function a (kernel, mesh, arguments): a
        second layer with the same shapes traces neither the shard_map nor
        the kernel inside it again."""
        kernel = plk.get_kernel("flash_attention")
        body, traced = kernel.pallas, []

        @functools.wraps(body)     # the registry binds by the body's names
        def spy(*args, **kwargs):
            traced.append(kwargs["interpret"])
            return body(*args, **kwargs)

        monkeypatch.setattr(kernel, "pallas", spy)
        plk.registry._per_shard_call.cache_clear()
        mesh = _mesh(data=4)
        # placed on the mesh as a step's batch is: an operand that knows
        # no mesh is another type to jit than a layer's result
        q = jax.device_put(_f((4, 2, 128, 32)), NamedSharding(mesh, P("data")))

        @jax.jit
        def two_layers(q):
            with plk.mesh_scope(mesh), plk.override("on"):
                x = plk.flash_attention(q, q, q, causal=True)
                return plk.flash_attention(x, x, x, causal=True)

        two_layers(q)
        assert traced == [True]
        info = plk.registry._per_shard_call.cache_info()
        assert (info.misses, info.hits) == (1, 1)

    def test_the_gauge_tells_the_per_shard_body_apart(self):
        q = _f((4, 2, 128, 32))
        with plk.mesh_scope(_mesh(data=4)), plk.override("on"):
            plk.flash_attention(q, q, q)
        g = _selection_gauge()
        assert g.value(kernel="flash_attention",
                       body="pallas_per_shard_interpret") == 1.0
        assert g.value(kernel="flash_attention",
                       body="pallas_interpret") in (None, 0.0)
