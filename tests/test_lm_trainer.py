"""The decoder skeleton (``models/lm_trainer.Decoder``): what it promises
once for every model file that hands it a block, on the CPU at tiny sizes.
The models' own arithmetic is held to references in their own test files.
"""

import ast
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import (deepseek_v3, kimi_linear, laguna, lfm2,
                               lm_trainer, olmoe, qwen3_next)
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

FAMILIES = {
    "olmoe": (olmoe, lambda: olmoe.olmoe_tiny(dtype=jnp.float32)),
    "kimi_linear": (kimi_linear, lambda: kimi_linear.kimi_linear_tiny(
        experts_held=(4, 4), dtype=jnp.float32)),
    "laguna": (laguna, lambda: laguna.laguna_tiny(
        experts_held=(4, 4), dtype=jnp.float32)),
    "qwen3_next": (qwen3_next, lambda: qwen3_next.qwen3_next_tiny(
        experts_held=(4, 4), dtype=jnp.float32)),
    "lfm2": (lfm2, lambda: lfm2.lfm2_tiny(
        experts_held=(4, 4), dtype=jnp.float32)),
    "deepseek_v3": (deepseek_v3, lambda: deepseek_v3.deepseek_v3_tiny(
        experts_held=(4, 4), dtype=jnp.float32)),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_model_file_brings_its_block_and_the_skeleton_the_rest(family):
    module, tiny = FAMILIES[family]
    cfg = tiny()
    rows, positions = 2, 32
    batch = module.synthetic_batch(cfg, rows, positions)
    assert batch["input_ids"].shape == batch["labels"].shape == (rows,
                                                                 positions)
    params = module.init_params(jax.random.PRNGKey(0), cfg)

    # one pass: the last of the stages is what forward returns
    hidden = module.forward(params, cfg, batch["input_ids"])
    assert hidden.shape == (rows, positions, cfg.hidden)
    if hasattr(module, "stages"):
        parts, aux = module.stages(params, cfg, batch["input_ids"])
        assert parts.shape == (2 * cfg.num_layers + 2, *hidden.shape)
        np.testing.assert_array_equal(np.asarray(parts[-1]),
                                      np.asarray(hidden))
        assert aux["counts"].shape[1] == cfg.num_experts

    # the counter: every token's assignments, in every expert layer
    counts, choice = module.routing_stats(params, cfg, batch, choices=True)
    assert counts.shape[1] == cfg.num_experts
    assert (counts.sum(axis=1)
            == cfg.experts_per_token * rows * positions).all()
    assert choice.shape == (counts.shape[0], rows * positions,
                            cfg.experts_per_token)

    # lm_loss is the first result of the loss the step differentiates, and
    # the counts beside it are those the counter reads
    want = float(module.lm_loss(params, cfg, batch))
    both = module.DECODER._loss_and_counts(params, cfg, batch)
    assert float(both[0]) == want
    np.testing.assert_array_equal(np.asarray(both[1]), counts)

    # the guard against a sixth copy: the pass, the loss and the counter are
    # bound to the skeleton, the step is its one jitted ``step``, and the
    # model file defines none of them
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    _, step_fn = module.make_train_step(cfg, pt.optimizer.Adam(1e-3), mesh)
    assert step_fn.jitted.__wrapped__.__name__ == "step"
    assert step_fn.jitted.__wrapped__.__module__ == lm_trainer.__name__
    for name in ("forward", "stages", "lm_loss", "routing_stats"):
        if not hasattr(module, name):       # olmoe exports no ``stages``
            continue
        assert getattr(module, name).__self__ is module.DECODER, name
        assert getattr(module, name).__func__ \
            is getattr(lm_trainer.Decoder, name), name
    defined = {node.name for node in ast.walk(ast.parse(
        inspect.getsource(module))) if isinstance(node, ast.FunctionDef)}
    assert not defined & {"_hidden_and_aux", "_shard_act", "move_biases",
                          "_loss_and_counts", "_feed_forward"}, defined
