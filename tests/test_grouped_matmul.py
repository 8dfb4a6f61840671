"""The grouped matmul of the dropless expert layer
(``ops/pallas/grouped_matmul.py``): both bodies, and their gradients,
against a loop over the groups; the Pallas body in interpret mode, chosen
the registry's way (``override``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas as plk
from paddle_tpu.ops.pallas.grouped_matmul import _work_list

#: the override that makes the registry hand out each body on the CPU
OVERRIDE = {"reference": "off", "pallas_interpret": "on"}


def loop_over_groups(lhs, rhs, sizes):
    """Each group's rows times its matrix, one group at a time; rows past
    the last group come out zero."""
    ends = np.cumsum(sizes)
    rows = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g, (start, end) in enumerate(zip(ends - sizes, ends)):
        out = out + jnp.where((rows >= start) & (rows < end),
                              lhs @ rhs[g], 0.0)
    return out


#: (M, K, N, group sizes): empty groups first, last and in a row; sizes that
#: are no multiple of the 256-row tile; a tile shared by four groups; rows
#: past the last group; M no multiple of the tile; one group of everything
CASES = {
    "empty_and_ragged": (700, 256, 384, [0, 300, 1, 0, 0, 255, 100, 0]),
    "one_group_takes_all": (512, 128, 128, [512, 0]),
    "many_in_one_tile": (1024, 256, 128, [100, 56, 60, 40, 256, 0, 512]),
    "rows_left_over": (600, 128, 256, [10, 20, 30]),
    "aligned": (768, 128, 128, [256, 256, 256]),
    # a share of the experts held: most tiles lie past the groups' rows
    "tiles_past_the_rows": (2048, 128, 128, [40, 0, 300, 17]),
    "no_rows_at_all": (768, 128, 128, [0, 0, 0]),
    "two_contraction_blocks": (512, 4608, 128, [200, 0, 100]),
}


@pytest.mark.parametrize("body", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_product_and_gradients_match_the_loop_over_groups(case, body):
    m, k, n, sizes = CASES[case]
    sizes = np.asarray(sizes, np.int32)
    rs = np.random.RandomState(len(case))
    lhs = jnp.asarray(rs.randn(m, k), jnp.float32)
    rhs = jnp.asarray(rs.randn(len(sizes), k, n), jnp.float32)
    w = jnp.asarray(rs.randn(m, n), jnp.float32)

    def f(lhs, rhs):
        with plk.override(OVERRIDE[body]):
            assert plk.selected_body("grouped_matmul") == body
            return plk.grouped_matmul(lhs, rhs, jnp.asarray(sizes))

    want = loop_over_groups(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(f(lhs, rhs)), np.asarray(want),
                               rtol=1e-4, atol=1e-3)
    got = jax.grad(lambda a, b: jnp.sum(f(a, b) * w), (0, 1))(lhs, rhs)
    want = jax.grad(lambda a, b: jnp.sum(loop_over_groups(a, b, sizes) * w),
                    (0, 1))(lhs, rhs)
    for name, a, b in zip(("dlhs", "drhs"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=2e-3, err_msg=name)
    # an empty group's matrix gets a gradient of zeros, not of leftovers
    assert not np.asarray(got[1])[sizes == 0].any()


def test_the_work_list_visits_each_tile_of_each_group_once_in_order():
    sizes = jnp.asarray([0, 300, 1, 0, 255, 100, 0], jnp.int32)
    offsets, groups, tiles, n_work = _work_list(sizes, 768, 256)
    n = int(n_work[0])
    assert offsets.tolist() == [0, 0, 300, 301, 301, 556, 656, 656]
    visits = list(zip(groups[:n].tolist(), tiles[:n].tolist()))
    assert visits == [(0, 0), (1, 0), (1, 1), (2, 1), (3, 1), (4, 1), (4, 2),
                      (5, 2), (6, 2)]
    assert len(groups) == 768 // 256 + 7
    assert tiles[n:].tolist() == [2] * (len(groups) - n)    # skipped entries
    assert n_work.tolist() == [n, n]          # no tile past the groups' rows


def test_the_work_list_zeroes_the_tiles_past_the_rows_without_a_product():
    """A layer that holds a share of the experts: 357 rows of 2048 are real.
    The products visit the two tiles that hold them; the six tiles wholly
    past them come after, one entry each, to be zeroed; the rest is
    skipped."""
    sizes = jnp.asarray([40, 0, 300, 17], jnp.int32)
    _, groups, tiles, counts = _work_list(sizes, 2048, 256)
    n_real, n_work = counts.tolist()
    visits = list(zip(groups[:n_real].tolist(), tiles[:n_real].tolist()))
    assert visits == [(0, 0), (1, 0), (2, 0), (2, 1), (3, 1)]
    assert tiles[n_real:n_work].tolist() == [2, 3, 4, 5, 6, 7]
    assert set(groups[n_real:].tolist()) == {3}     # the last group's matrix
    assert len(groups) == 2048 // 256 + 4
    # nothing held at all: every group its one empty visit, every tile zeroed
    _, _, tiles, counts = _work_list(jnp.zeros((3,), jnp.int32), 768, 256)
    assert counts.tolist() == [3, 6] and tiles.tolist() == [0, 0, 0, 0, 1, 2]


def test_bfloat16_rows_take_float32_master_weights():
    """The expert layer's call: bf16 rows, the weights cast to them, float32
    accumulation; both gradients come back in their operand's dtype."""
    sizes = jnp.asarray([40, 0, 88], jnp.int32)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (128, 64), jnp.bfloat16)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 64, 128))

    def f(lhs, rhs, mode):
        with plk.override(mode):
            return plk.grouped_matmul(lhs, rhs.astype(lhs.dtype), sizes)

    out = f(lhs, rhs, "on")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(loop_over_groups(lhs.astype(jnp.float32), rhs,
                                    np.asarray(sizes))), rtol=2e-2, atol=0.1)
    for mode in ("on", "off"):
        dl, dr = jax.grad(lambda a, b: jnp.sum(
            f(a, b, mode).astype(jnp.float32)), (0, 1))(lhs, rhs)
        assert dl.dtype == jnp.bfloat16 and dr.dtype == jnp.float32


def test_the_registry_selects_between_the_two_bodies():
    assert "grouped_matmul" in plk.list_kernels()
    assert plk.selected_body("grouped_matmul") == "reference"     # the CPU
    with plk.override("on"):
        assert plk.selected_body("grouped_matmul") == "pallas_interpret"
        sizes = jnp.asarray([5, 3], jnp.int32)
        lhs, rhs = jnp.ones((8, 128)), jnp.ones((2, 128, 128))
        np.testing.assert_allclose(
            np.asarray(plk.grouped_matmul(lhs, rhs, sizes)), 128.0)
