"""Structural (no-pickle) serialization tests (VERDICT-r2 Weak #7;
ref framework/framework.proto:184 ProgramDesc proto).

Covers: attr codec round-trips (incl. framework objects + refusal of
callables), full program JSON round-trip executing identically,
control-flow sub-programs (while_block / scan_block) surviving the
round trip, checkpoint/pytree manifests, and that saved artifacts
contain no pickle.
"""

import io
import json
import os
import pickletools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import initializer as I
from paddle_tpu import layers
from paddle_tpu.static import serialize as S


class TestValueCodec:
    @pytest.mark.parametrize("v", [
        None, True, 3, 2.5, "s", [1, 2], (1, (2, "x")),
        {"a": 1, "b": [2.0, None]}, b"\x00\xffbytes",
    ])
    def test_plain_roundtrip(self, v):
        enc = S.encode_value(v)
        json.dumps(enc)                       # must be JSON-able
        assert S.decode_value(enc) == v
        got = S.decode_value(enc)
        assert type(got) is type(v)

    def test_ndarray(self):
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        got = S.decode_value(S.encode_value(a))
        np.testing.assert_array_equal(got, a)
        assert got.dtype == a.dtype

    def test_dtype(self):
        assert S.decode_value(S.encode_value(np.dtype("int64"))) \
            == np.dtype("int64")
        assert S.decode_value(S.encode_value(jnp.bfloat16)) \
            is jnp.bfloat16

    def test_framework_objects(self):
        init = I.Constant(2.5)
        got = S.decode_value(S.encode_value(init))
        assert type(got) is I.Constant
        assert got.__dict__ == init.__dict__
        opt = pt.optimizer.Adam(learning_rate=0.01, beta1=0.8)
        got = S.decode_value(S.encode_value(opt))
        assert type(got) is pt.optimizer.AdamOptimizer
        assert got.beta1 == 0.8 and got.learning_rate == 0.01

    def test_callable_refused(self):
        with pytest.raises(S.SerializationError, match="callable"):
            S.encode_value(lambda x: x, where="op py_func")

    def test_foreign_class_refused_on_decode(self):
        evil = {"__obj__": "os:environ.__class__", "state": {}}
        with pytest.raises(S.SerializationError, match="outside"):
            S.decode_value(evil)
        evil2 = {"__obj__": "subprocess:Popen", "state": {}}
        with pytest.raises(S.SerializationError):
            S.decode_value(evil2)


def _no_pickle_opcodes(path):
    """A real guarantee, not grep: pickletools.dis on arbitrary bytes
    raises almost immediately unless the stream IS a pickle."""
    with open(path, "rb") as f:
        blob = f.read()
    try:
        pickletools.dis(blob, out=io.StringIO())
        return False      # parsed as pickle -> fail
    except Exception:
        return True


class TestProgramRoundTrip:
    def _build_and_run(self, run_dir):
        pt.enable_static()
        try:
            main, startup = pt.static.Program(), pt.static.Program()
            with pt.static.program_guard(main, startup):
                x = pt.static.data("x", shape=[4, 6],
                                   append_batch_size=False)
                w = layers.create_parameter(
                    [6, 3], "float32", name="w",
                    default_initializer=I.Constant(0.5))
                h = layers.matmul(x, w)
                out = layers.relu(h)
            exe = pt.static.Executor()
            scope = pt.static.Scope()
            with pt.static.scope_guard(scope):
                exe.run(startup)
                feed = {"x": np.arange(24, dtype=np.float32).reshape(4, 6)}
                want = exe.run(main, feed=feed, fetch_list=[out])[0]
                pt.static.io.save_inference_model(
                    run_dir, ["x"], [out], exe, main_program=main)
            return feed, want
        finally:
            pt.disable_static()

    def test_saved_model_runs_identically_and_has_no_pickle(self, tmp_path):
        d = str(tmp_path / "m")
        feed, want = self._build_and_run(d)
        assert _no_pickle_opcodes(os.path.join(d, "__model__"))
        pt.enable_static()
        try:
            exe = pt.static.Executor()
            scope = pt.static.Scope()
            with pt.static.scope_guard(scope):
                prog, feeds, fetches = pt.static.io.load_inference_model(
                    d, exe)
                got = exe.run(prog, feed=feed, fetch_list=fetches)[0]
            np.testing.assert_allclose(got, want, rtol=1e-6)
        finally:
            pt.disable_static()

    def test_fingerprint_stability_and_sensitivity(self):
        pt.enable_static()
        try:
            from paddle_tpu.framework import unique_name

            def build(k):
                main, startup = pt.static.Program(), pt.static.Program()
                with pt.static.program_guard(main, startup), \
                        unique_name.guard():
                    x = pt.static.data("x", shape=[2, 2],
                                       append_batch_size=False)
                    y = layers.scale(x, scale=k)
                return main, y
            p1, _ = build(2.0)
            p2, _ = build(2.0)
            p3, _ = build(3.0)
            f = S.program_fingerprint
            assert f(p1) == f(p2)
            assert f(p1) != f(p3)
            # round-trip preserves the fingerprint (the AOT index key)
            rt = S.program_from_dict(S.program_to_dict(p1))
            assert f(rt) == f(p1)
        finally:
            pt.disable_static()


class TestControlFlowRoundTrip:
    def test_while_block(self, tmp_path):
        pt.enable_static()
        try:
            main, startup = pt.static.Program(), pt.static.Program()
            with pt.static.program_guard(main, startup):
                x = pt.static.data("x", shape=[3],
                                   append_batch_size=False)
                i = layers.fill_constant(shape=[1], dtype="int32",
                                         value=0)
                limit = layers.fill_constant(shape=[1], dtype="int32",
                                             value=4)

                def cond(i, v):
                    return layers.reduce_all(layers.less_than(i, limit))

                def body(i, v):
                    return [layers.increment(i, value=1),
                            layers.scale(v, scale=2.0)]

                i_out, v_out = layers.while_loop(cond, body, [i, x])
            exe = pt.static.Executor()
            scope = pt.static.Scope()
            xval = np.array([1.0, -2.0, 0.5], np.float32)
            with pt.static.scope_guard(scope):
                exe.run(startup)
                want = exe.run(main, feed={"x": xval},
                               fetch_list=[v_out])[0]
            np.testing.assert_allclose(want, xval * 16.0, rtol=1e-6)

            # round trip through the schema'd JSON (sub-programs ride
            # the op attrs) and run again
            rt = S.program_from_dict(S.program_to_dict(main))
            scope2 = pt.static.Scope()
            with pt.static.scope_guard(scope2):
                got = exe.run(rt, feed={"x": xval},
                              fetch_list=[v_out.name])[0]
            np.testing.assert_allclose(got, want, rtol=1e-6)
        finally:
            pt.disable_static()

    def test_static_rnn_block(self):
        pt.enable_static()
        try:
            main, startup = pt.static.Program(), pt.static.Program()
            with pt.static.program_guard(main, startup):
                seq = pt.static.data("seq", shape=[2, 5, 3],
                                     append_batch_size=False)
                h0 = pt.static.data("h0", shape=[2, 3],
                                    append_batch_size=False)

                def step(h, x_t):
                    nh = layers.elementwise_add(h, x_t)
                    return nh, layers.scale(nh, scale=1.0)

                final, outs = layers.static_rnn(step, seq, h0)
            exe = pt.static.Executor()
            rng = np.random.RandomState(0)
            sv = rng.randn(2, 5, 3).astype(np.float32)
            hv = np.zeros((2, 3), np.float32)
            scope = pt.static.Scope()
            with pt.static.scope_guard(scope):
                want_f, want_o = exe.run(
                    main, feed={"seq": sv, "h0": hv},
                    fetch_list=[final, outs])
            np.testing.assert_allclose(want_f, sv.sum(axis=1), rtol=1e-5)
            np.testing.assert_allclose(want_o, np.cumsum(sv, axis=1),
                                       rtol=1e-5)

            rt = S.program_from_dict(S.program_to_dict(main))
            scope2 = pt.static.Scope()
            with pt.static.scope_guard(scope2):
                got_f, got_o = exe.run(
                    rt, feed={"seq": sv, "h0": hv},
                    fetch_list=[final.name, outs.name])
            np.testing.assert_allclose(got_f, want_f, rtol=1e-6)
            np.testing.assert_allclose(got_o, want_o, rtol=1e-6)
        finally:
            pt.disable_static()

    def test_while_with_captured_parameter(self):
        """Body closes over a parent parameter -> capture rides the op
        inputs and survives the round trip."""
        pt.enable_static()
        try:
            main, startup = pt.static.Program(), pt.static.Program()
            with pt.static.program_guard(main, startup):
                x = pt.static.data("x", shape=[3],
                                   append_batch_size=False)
                w = layers.create_parameter(
                    [3], "float32", name="w_cap",
                    default_initializer=I.Constant(3.0))
                i = layers.fill_constant(shape=[1], dtype="int32",
                                         value=0)
                two = layers.fill_constant(shape=[1], dtype="int32",
                                           value=2)

                def cond(i, v):
                    return layers.reduce_all(layers.less_than(i, two))

                def body(i, v):
                    return [layers.increment(i, value=1),
                            layers.elementwise_add(v, w)]

                _, v_out = layers.while_loop(cond, body, [i, x])
            exe = pt.static.Executor()
            xval = np.ones(3, np.float32)
            scope = pt.static.Scope()
            with pt.static.scope_guard(scope):
                exe.run(startup)
                want = exe.run(main, feed={"x": xval},
                               fetch_list=[v_out])[0]
            np.testing.assert_allclose(want, xval + 6.0)

            rt = S.program_from_dict(S.program_to_dict(main))
            scope2 = pt.static.Scope()
            with pt.static.scope_guard(scope2):
                exe.run(startup)   # re-init param in scope2
                got = exe.run(rt, feed={"x": xval},
                              fetch_list=[v_out.name])[0]
            np.testing.assert_allclose(got, want)
        finally:
            pt.disable_static()


class TestTreeManifest:
    def test_roundtrip(self):
        tree = {"p": {"w": np.ones((2, 3), np.float32),
                      "b": np.zeros(3)},
                "step": 7, "tag": "adam",
                "nested": [np.arange(4), (1.5, None)]}
        manifest, arrays = S.tree_manifest(tree)
        json.dumps(manifest)
        got = S.tree_from_manifest(manifest, arrays)
        assert got["step"] == 7 and got["tag"] == "adam"
        assert got["nested"][1] == (1.5, None)
        np.testing.assert_array_equal(got["p"]["w"], tree["p"]["w"])

    def test_save_load_pytree_no_pickle(self, tmp_path):
        p = str(tmp_path / "ck.npz")
        tree = {"w": np.full((4,), 2.0, np.float32), "n": 3}
        pt.io.save_pytree(tree, p)
        got = pt.io.load_pytree(p)
        assert int(got["n"]) == 3
        np.testing.assert_array_equal(np.asarray(got["w"]), tree["w"])
        # npz loads with allow_pickle=False by construction; also ensure
        # no member parses as pickle
        import zipfile
        with zipfile.ZipFile(p) as z:
            for name in z.namelist():
                blob = z.read(name)
                try:
                    pickletools.dis(blob, out=io.StringIO())
                    parsed = True
                except Exception:
                    parsed = False
                assert not parsed, f"{name} parses as pickle"


class TestFreshProcessLoad:
    def test_while_model_loads_in_fresh_interpreter(self, tmp_path):
        """Regression for the op-registration gap: a deserialized
        control-flow program must execute in a process that never ran
        the builder APIs (only load_inference_model + Executor.run)."""
        import subprocess
        import sys
        d = str(tmp_path / "wm")
        pt.enable_static()
        try:
            main, startup = pt.static.Program(), pt.static.Program()
            with pt.static.program_guard(main, startup):
                x = pt.static.data("x", shape=[3],
                                   append_batch_size=False)
                i = layers.fill_constant(shape=[1], dtype="int32",
                                         value=0)
                three = layers.fill_constant(shape=[1], dtype="int32",
                                             value=3)

                def cond(i, v):
                    return layers.reduce_all(layers.less_than(i, three))

                def body(i, v):
                    return [layers.increment(i, value=1),
                            layers.scale(v, scale=2.0)]

                _, v_out = layers.while_loop(cond, body, [i, x])
            exe = pt.static.Executor()
            scope = pt.static.Scope()
            with pt.static.scope_guard(scope):
                exe.run(startup)
                pt.static.io.save_inference_model(
                    d, ["x"], [v_out], exe, main_program=main)
        finally:
            pt.disable_static()

        code = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu as pt
pt.enable_static()
exe = pt.static.Executor()
prog, feeds, fetches = pt.static.io.load_inference_model({d!r}, exe)
out = exe.run(prog, feed={{"x": np.ones(3, np.float32)}},
              fetch_list=fetches)[0]
np.testing.assert_allclose(out, np.full(3, 8.0, np.float32))
print("FRESH_OK")
"""
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=300,
                           env={**os.environ,
                                "PYTHONPATH": "/root/repo:" + os.environ.get(
                                    "PYTHONPATH", "")})
        assert "FRESH_OK" in r.stdout, (r.stdout, r.stderr)
