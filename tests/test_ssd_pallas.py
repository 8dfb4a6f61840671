"""The Mosaic body of the state-space scan (``ops/pallas/ssd.py``: the kernels
``ssd_fwd`` and ``ssd_bwd``) in Pallas interpreter mode on the CPU, through
``registry.override("on")``: the values and the gradients of all five
operands and of ``D`` against the recurrence (``ssd.ssd_recurrent``, float32,
one position a step) and against the reference body (``ssd._ssd_chunked``),
over the groups' three shapes (one group, two, a group a head), both operand
dtypes, lengths that are and are not whole grid steps, no skip, a head that
forgets everything at once and one that forgets nothing; and the shapes the
blocks cannot tile, which take the reference body.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssd
from paddle_tpu.ops.pallas import registry
from paddle_tpu.ops.pallas import ssd as ssd_kernels

CHUNK = 16
#: (heads, head size, groups, state): a lane tile of four heads under one
#: group (whose state may be any width), of two heads under two groups, and
#: a head a tile with a group of its own
SHAPES = {"one_group": (4, 32, 1, 16), "two_groups": (4, 64, 2, 128),
          "group_a_head": (2, 128, 2, 128)}


def operands(shape, s, dtype=jnp.float32, b=2, seed=0):
    h, p, g, n = SHAPES[shape]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 1.0)
    rate = -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7))
    return (jax.random.normal(k[0], (b, s, h, p)).astype(dtype), dt,
            rate * dt,
            (0.5 * jax.random.normal(k[3], (b, s, g, n))).astype(dtype),
            (0.5 * jax.random.normal(k[4], (b, s, g, n))).astype(dtype),
            jax.random.normal(k[5], (h,)))


def relative_error(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def kernels(*ops, chunk=CHUNK):
    with registry.override("on"):
        return ssd.ssd_chunked(*ops, chunk=chunk)


def value_and_grads(fn, ops):
    """(y, the gradient of sum(sin y) in every operand that is there)."""
    given = tuple(i for i, t in enumerate(ops) if t is not None)
    with jax.default_matmul_precision("highest"):
        return fn(*ops), jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))),
            argnums=given)(*ops)


def runs_the_kernels(ops, chunk=CHUNK):
    with registry.override("on"):
        text = str(jax.make_jaxpr(
            lambda *a: ssd.ssd_chunked(*a, chunk=chunk))(*ops))
    return "ssd_fwd" in text


@pytest.mark.parametrize("positions", [64, 40])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernels_are_the_recurrence_and_the_reference_body(
        shape, dtype, positions):
    """Two batch rows; 64 positions are two grid steps of two chunks, 40 are
    padded with positions that change nothing. Float32 operands: to
    rounding. bfloat16 operands: both bodies within bfloat16's reach of the
    float32 recurrence on the same rounded operands, the kernels no further
    from it than the reference body by more than a half."""
    ops = operands(shape, positions, jnp.dtype(dtype))
    assert runs_the_kernels(ops)
    got, got_grads = value_and_grads(kernels, ops)
    want, want_grads = value_and_grads(ssd.ssd_recurrent, ops)
    ref, ref_grads = value_and_grads(
        lambda *a: ssd._ssd_chunked(*a, CHUNK), ops)
    assert got.shape == ops[0].shape and got.dtype == ops[0].dtype
    names = ("y", "x", "dt", "a", "B", "C", "D")
    for name, g, w, r in zip(names, (got, *got_grads), (want, *want_grads),
                             (ref, *ref_grads)):
        assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g))), name
        if dtype == "float32":
            assert relative_error(g, w) < 1e-4, name
            assert relative_error(g, r) < 1e-4, name
        else:
            assert relative_error(g, w) < max(
                3e-2, 1.5 * relative_error(r, w)), name


@pytest.mark.parametrize("shape", list(SHAPES))
def test_without_a_skip(shape):
    """``D`` None: no skip, and nothing to differentiate in its place."""
    ops = operands(shape, 40)[:5] + (None,)
    got, got_grads = value_and_grads(kernels, ops)
    want, want_grads = value_and_grads(ssd.ssd_recurrent, ops)
    assert len(got_grads) == 5
    for g, w in zip((got, *got_grads), (want, *want_grads)):
        assert relative_error(g, w) < 1e-4


@pytest.mark.parametrize("decay", [-30.0, 0.0])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_head_that_forgets_at_once_and_one_that_never_forgets(shape, decay):
    """A log-decay of -30 a position (every exponent is a difference that is
    never positive: nothing overflows, nothing is NaN, in the values or in
    any gradient) and of 0 (the state only grows)."""
    x, dt, a, B, C, D = operands(shape, 64)
    ops = (x, dt, jnp.full_like(a, decay), B, C, D)
    got, got_grads = value_and_grads(kernels, ops)
    want, want_grads = value_and_grads(ssd.ssd_recurrent, ops)
    for g, w in zip((got, *got_grads), (want, *want_grads)):
        assert bool(jnp.all(jnp.isfinite(g)))
        # a's gradient at -30 is of the order of exp(-30); the kernels take
        # it as the difference of two float32 sums of order 1 to 10 (what a
        # row of the decays reads less what a column writes), so it is
        # right to their rounding and no further
        assert relative_error(g, w) < 1e-4 \
            or float(jnp.abs(g - w).max()) < 2e-5


def test_one_row_of_a_batch_reads_nothing_of_another():
    ops = operands("two_groups", 40)
    both = kernels(*ops)
    for row in range(2):
        alone = kernels(*(t[row:row + 1] for t in ops[:5]), ops[5])
        assert bool(jnp.array_equal(alone[0], both[row]))


@pytest.mark.parametrize("why, shape, chunk", [
    ("a chunk that is no multiple of 8 rows", (4, 64, 2, 128), 12),
    ("a head that neither divides nor fills lane tiles", (4, 24, 1, 16), 16),
    ("a group's heads short of a lane tile", (4, 8, 2, 128), 16),
    ("two groups whose state is no lane tile", (4, 64, 2, 16), 16)])
def test_a_shape_the_blocks_cannot_tile_takes_the_reference_body(
        why, shape, chunk, monkeypatch):
    monkeypatch.setitem(SHAPES, "odd", shape)
    ops = operands("odd", 48)
    assert not runs_the_kernels(ops, chunk), why
    with jax.default_matmul_precision("highest"):
        assert relative_error(kernels(*ops, chunk=chunk),
                              ssd.ssd_recurrent(*ops)) < 1e-5


def test_states_beyond_the_vmem_budget_take_the_reference_body(monkeypatch):
    ops = operands("two_groups", 32)
    assert runs_the_kernels(ops)
    monkeypatch.setattr(registry, "DEFAULT_VMEM_BUDGET", 1 << 10)
    assert not runs_the_kernels(ops)


def test_the_registry_selects_the_body_and_says_which():
    """The CPU's selection is the reference body; forced on, the gauge every
    kernel has names the interpreter's."""
    from paddle_tpu.monitor.registry import gauge
    ops = operands("two_groups", 32)
    assert "ssd" in registry.list_kernels()
    assert registry.selected_body("ssd") == "reference"
    assert "ssd_fwd" not in str(jax.make_jaxpr(
        lambda *a: ssd.ssd_chunked(*a, chunk=CHUNK))(*ops))
    kernels(*ops)
    selected = gauge("pallas_kernels_selected", "", labels=("kernel", "body"))
    assert selected.value(kernel="ssd", body="pallas_interpret") == 1
    ssd.ssd_chunked(*ops, chunk=CHUNK)
    assert selected.value(kernel="ssd", body="pallas_interpret") == 0
    assert selected.value(kernel="ssd", body="reference") == 1


def test_what_the_backward_needs_is_kept_by_name():
    """Under ``blocks.recomputed`` the gradient's jaxpr holds the forward
    kernel once (``y`` and the units' starting states carry ``KEPT``), under
    the plain ``jax.checkpoint`` twice; the gradients are the same bits."""
    from paddle_tpu.models import blocks
    ops = operands("two_groups", 64)

    def loss(wrap):
        mixer = wrap(lambda *a: kernels(*a))
        return lambda *a: jnp.sum(jnp.sin(mixer(*a)))

    counts, grads = {}, {}
    for name, wrap in (("kept", blocks.recomputed),
                       ("plain", jax.checkpoint)):
        with registry.override("on"):
            grad = jax.grad(loss(wrap), argnums=tuple(range(6)))
            text = str(jax.make_jaxpr(grad)(*ops))
            grads[name] = grad(*ops)
        counts[name] = (text.count("name=ssd_fwd"),
                        text.count("name=ssd_bwd"))
    assert counts == {"kept": (1, 1), "plain": (2, 1)}, counts
    assert ssd_kernels.KEPT in text
    for a, b in zip(grads["kept"], grads["plain"]):
        assert bool(jnp.array_equal(a, b))


def test_two_layers_share_one_lowering_of_each_kernel(monkeypatch):
    """Two mixers, each under a ``blocks.recomputed`` of its own as a model's
    layers are: the gradient's lowering holds ONE ``_ssd_fwd`` and ONE
    ``_ssd_bwd`` function, each called twice. The forward call goes through
    ``registry.lowered_once``; as a bare jitted call inside the ``custom_vjp``
    forward rule (``traced_once`` alone) the checkpoint's partial evaluation
    cuts its jaxpr anew a call site and the lowering holds it once a layer,
    which on the chip is a Mosaic lowering a layer in every set-up."""
    import re
    from paddle_tpu.models import blocks
    ops = operands("two_groups", 32)

    def functions():
        layers = [blocks.recomputed(lambda x, *a: kernels(x, *a))
                  for _ in range(2)]

        def loss(x, *a):
            for layer in layers:
                x = layer(x, *a)
            return jnp.sum(jnp.sin(x))

        with registry.override("on"):
            text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
                *ops).as_text()
        return sorted(re.findall(r"func\.func private @(_ssd_\w+)\(", text))

    assert functions() == ["_ssd_bwd", "_ssd_fwd"]
    monkeypatch.setattr(
        registry, "lowered_once",
        lambda jitted, arrays, static=(): registry.traced_once(
            jitted, *arrays, *static))
    jax.clear_caches()
    assert len(functions()) == 3
