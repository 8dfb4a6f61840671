"""The decoder skeleton (``models/lm_trainer.Decoder``): what it promises
once for every model file that hands it a block, on the CPU at tiny sizes.
The models' own arithmetic is held to references in their own test files.
"""

import ast
import hashlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import (deepseek_v3, evabyte, kimi_linear, laguna, lfm2,
                               lm_trainer, nemotron_h, olmoe, qwen3_next)
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


def one_device():
    return make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


def test_a_decoder_without_expert_layers_takes_a_step():
    """EvaByte's: no layer returns aux terms, so the pass stacks none, the
    loss hands no counts out, ``make_train_step`` passes no ``after_update``
    and the jitted step returns the loss, the parameters and Adam's state and
    nothing else. The stream is in ``cfg.stream_dtype`` (float32) where the
    layers compute in ``cfg.dtype`` (bfloat16); the head's operand, the last
    of the stages, is back in ``cfg.dtype``."""
    cfg = evabyte.evabyte_tiny()
    assert (cfg.dtype, cfg.stream_dtype) == (jnp.bfloat16, jnp.float32)
    assert evabyte.DECODER.routed is False
    batch = evabyte.synthetic_batch(cfg, 2, 128, seed=3)
    init_fn, step_fn = evabyte.make_train_step(
        cfg, pt.optimizer.Adam(learning_rate=1e-2), one_device())
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    assert len(step_fn.jitted.eval_shape(params, opt_state,
                                         step_fn.place(batch))) == 3
    assert step_fn.jitted.__wrapped__.__module__ == lm_trainer.__name__

    hidden, aux, stream, further = evabyte.DECODER._pass(
        params, cfg, batch["input_ids"])
    assert aux == {} and further == []
    assert len(stream) == 2 * cfg.num_layers + 1
    assert all(part.dtype == jnp.float32 for part in stream)
    assert hidden.dtype == jnp.bfloat16
    loss, counts = evabyte.DECODER._loss_and_counts(params, cfg, batch)
    assert counts is None
    assert float(loss) == float(evabyte.lm_loss(params, cfg, batch))
    heads = evabyte.head_losses(params, cfg, batch)
    assert heads.shape == (cfg.pred_heads,)
    assert float(loss) == pytest.approx(float(jnp.mean(heads)), rel=1e-6)

    losses = []
    for _ in range(4):
        loss, params, opt_state = step_fn(params, opt_state, batch)
        losses.append(float(loss))
    assert step_fn.aux == []
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05, losses
    # the gains stay ``1 + w`` parameters and the summary vectors move
    assert params["layers"][0]["mu"].shape == (cfg.num_heads, cfg.head_dim)
    assert float(jnp.abs(params["final_norm_w"]).max()) > 0


#: sha256 of ``lowered.as_text()`` of two tiny train steps (experts 4 to 7
#: held, Adam, batch 2 x 48, one device) on PR 52's tree, the parent of the
#: PR that let the skeleton run a decoder with no expert layer, carry the
#: stream in a dtype of its own and score several prediction heads: Laguna's
#: reaches none of it, and Nemotron-H's multi-token-prediction term now
#: shares its masking with those heads (``lm_trainer._ce_ahead``), the same
#: operations in the same order. A PR that changes these steps on purpose
#: recomputes them. (``tests/test_nemotron_h.py`` holds Kanana-2's and Kimi
#: Linear's the same way.)
STEPS_AS_BEFORE = {
    "laguna": (laguna, lambda: laguna.laguna_tiny(experts_held=(4, 4)),
               "b1a6e21ece701a1da91760ecc8841a6a3a296d98746fe1f2d042856d500c"
               "4a52"),
    "nemotron_h": (nemotron_h,
                   lambda: nemotron_h.nemotron_h_tiny(experts_held=(4, 4)),
                   "a86515c470fa65bfd95db3ba2c3866a911dd5ec9e399ec76f295765b"
                   "5e31c7d8"),
}


@pytest.mark.parametrize("family", sorted(STEPS_AS_BEFORE))
def test_an_expert_decoder_lowers_as_before_the_unrouted_one(family):
    module, tiny, want = STEPS_AS_BEFORE[family]
    cfg = tiny()
    init_fn, step_fn = module.make_train_step(
        cfg, pt.optimizer.Adam(learning_rate=1e-3), one_device())
    params, opt_state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = module.synthetic_batch(cfg, 2, 48)
    text = step_fn.jitted.lower(params, opt_state,
                                step_fn.place(batch)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == want
