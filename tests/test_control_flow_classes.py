"""Class-shaped control flow (While/Switch/IfElse/StaticRNN/DynamicRNN)
+ install_check + save/load_dygraph."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.layers import DynamicRNN, IfElse, StaticRNN, Switch, While


class TestWhile:
    def test_countdown(self):
        w = While(lambda i, acc: i < 5)
        i, acc = w(lambda i, acc: (i + 1, acc + i),
                   [jnp.asarray(0), jnp.asarray(0.0)])
        assert int(i) == 5 and float(acc) == 10.0

    def test_jittable(self):
        def f(n):
            w = While(lambda i, s: i < n)
            return w(lambda i, s: (i + 1, s + 2.0),
                     [jnp.asarray(0), jnp.asarray(0.0)])[1]
        assert float(jax.jit(f)(jnp.asarray(4))) == 8.0

    def test_with_block_refused(self):
        with pytest.raises(Exception, match="callable"):
            While(jnp.asarray(True))


class TestSwitch:
    def test_first_true_case_wins(self):
        x = jnp.asarray(2.0)
        with Switch() as sw:
            with sw.case(x > 3.0):
                a = x * 10.0
            with sw.case(x > 1.0):
                b = x * 100.0
            with sw.default():
                c = x
        out = sw.select(a, b, c)
        assert float(out) == 200.0

    def test_default_when_no_case(self):
        x = jnp.asarray(0.5)
        with Switch() as sw:
            with sw.case(x > 3.0):
                a = x * 10.0
            with sw.default():
                c = -x
        assert float(sw.select(a, c)) == -0.5

    def test_missing_default_refused(self):
        x = jnp.asarray(0.5)
        with Switch() as sw:
            with sw.case(x > 3.0):
                a = x * 10.0
        with pytest.raises(Exception, match="default"):
            sw.select(a)

    def test_ifelse_output_without_input_refused(self):
        ie = IfElse(jnp.asarray([True, False]))
        with ie.true_block():
            ie.output(jnp.ones((2, 1)))
        with ie.false_block():
            ie.output(jnp.zeros((2, 1)))
        with pytest.raises(Exception, match="input"):
            ie()


class TestIfElse:
    def test_row_partition_merge(self):
        x = jnp.asarray([[1.0], [2.0], [3.0], [4.0]])
        cond = x[:, 0] > 2.5
        ie = IfElse(cond)
        with ie.true_block():
            ie.output(ie.input(x) * 10.0)
        with ie.false_block():
            ie.output(ie.input(x) * -1.0)
        (out,) = ie()
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   [-1.0, -2.0, 30.0, 40.0])


class TestStaticDynamicRNN:
    def test_static_rnn_cumsum(self):
        x = jnp.asarray(np.arange(12, dtype=np.float32)
                        .reshape(2, 3, 2))          # [B, T, D]
        rnn = StaticRNN()
        rnn.step_input(x)
        rnn.memory(init=jnp.zeros((2, 2)))

        def step(x_t, h):
            h = h + x_t
            return {"mem": [h], "out": [h]}

        (out,) = rnn(step)
        np.testing.assert_allclose(np.asarray(out[:, -1]),
                                   np.asarray(x.sum(axis=1)))

    def test_dynamic_rnn_respects_lengths(self):
        x = jnp.ones((2, 4, 1))
        rnn = DynamicRNN(lengths=jnp.asarray([2, 4]))
        rnn.step_input(x)
        rnn.memory(init=jnp.zeros((2, 1)))

        def step(x_t, h):
            h = h + x_t
            return {"mem": [h], "out": [h]}

        (out,) = rnn(step)
        # seq 0 freezes after t=2; outputs beyond its length are zeroed
        np.testing.assert_allclose(np.asarray(out[0, :, 0]),
                                   [1, 2, 0, 0])
        np.testing.assert_allclose(np.asarray(out[1, :, 0]),
                                   [1, 2, 3, 4])


class TestInstallCheckAndDygraphIO:
    def test_install_check(self):
        # fresh interpreter, like real post-install usage (and the CPU
        # backend's multi-device collectives are flaky when sharing a
        # process with unrelated jit state)
        import os
        import subprocess
        import sys
        code = (
            "import paddle_tpu\n"
            "paddle_tpu.install_check.run_check()\n")
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        # jax's virtual-multi-device CPU collectives occasionally abort
        # under machine load (observed ~1/20 under the full suite):
        # retry a couple of times before declaring the install broken
        import time as _time
        for attempt in range(5):
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True,
                               timeout=300)
            if r.returncode == 0:
                break
            # only the known abort mode is flaky: the process dies on a
            # signal (negative returncode) inside the virtual-device
            # collective. A python-level failure (returncode 1: import
            # error, assert, wrong device count) is deterministic - fail
            # fast instead of masking it behind retries. The abort rate
            # climbs under machine load (3-in-a-row observed during a
            # full parallel run), hence 5 attempts with backoff.
            if r.returncode > 0:
                break
            _time.sleep(2 * (attempt + 1))
        assert r.returncode == 0, r.stderr[-800:]
        assert "works" in r.stdout
        assert "data parallel x8: OK" in r.stdout

    def test_save_load_dygraph(self, tmp_path):
        sd = {"w": jnp.ones((2, 2)), "b": jnp.zeros(2)}
        path = str(tmp_path / "model")
        pt.io.save_dygraph(sd, path)
        loaded, opt = pt.io.load_dygraph(path)
        assert opt is None
        np.testing.assert_allclose(np.asarray(loaded["w"]), 1.0)
