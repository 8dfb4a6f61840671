"""EvaByte: a byte-level decoder whose attention is EVA (EvaByte 6.5B, HKU NLP
and SambaNova, 2025-01, the released ``EvaByte/EvaByte`` config; Zheng, Yuan,
Wang and Kong 2023, "Efficient Attention via Control Variates",
arXiv:2302.04542, sec. 4).

Pre-norm blocks on a **float32 residual stream** (``fp32_skip_add``) whose
layers compute in ``cfg.dtype`` (bfloat16): ``h = x + Attn(norm(x))``, ``y =
h + FFN(norm(h))``, ``norm`` an RMSNorm with gain ``1 + w``
(``norm_add_unit_offset``: the parameters are kept as ``w``, zero at rest):

- attention: ``num_heads`` heads of ``head_dim``, no biases, no grouping;
  q and k rotated over the whole head (rotate-half); each chunk of ``chunk``
  keys and values pooled into one summary by two learned vectors a head
  (``mu``, ``phi``: ``ops/eva.eva_summaries``); a query attends to the tokens
  of its own window of ``window`` positions up to its own and to the
  summaries of every earlier window, under one softmax
  (``ops/eva.eva_attention``: the Mosaic kernels ``flash_fwd_eva`` and
  ``flash_bwd_eva`` on one chip);
- a SiLU-gated feed-forward, no biases, no experts: the first decoder of
  ``lm_trainer.Decoder`` with no router;
- a final RMSNorm and an untied head of ``pred_heads`` x ``vocab_size``
  columns, float32 logits: head i at position t predicts the byte i + 1
  positions on, and the loss is the mean of the heads' cross-entropies
  (``lm_trainer.Decoder._head_losses``).

Built like ``models/olmoe.py``: float32 master parameters, one jitted step
= forward + backward + update from ``lm_trainer.Decoder.make_train_step``;
the mesh's ``data`` axis splits the batch and its ``model`` axis the
projections' heads, the feed-forward's width and the head's columns.

**Recomputation.** Each mixer is under ``blocks.recomputed`` (the norm, the
projections, the rotation and the summaries are formed again in the backward
pass; the aggregation's forward kernel is not: its context and logsumexp are
kept by name), each feed-forward under ``jax.checkpoint``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import blocks, lm_trainer
from paddle_tpu.ops import eva
from paddle_tpu.parallel.mesh import MODEL_AXIS

__all__ = ["EvaByteConfig", "evabyte_6b5", "evabyte_tiny", "init_params",
           "param_specs", "forward", "stages", "lm_loss", "head_losses",
           "make_train_step", "synthetic_batch"]


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class EvaByteConfig:
    vocab_size: int = 320
    hidden: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    head_dim: int = 128
    ffn_width: int = 11008
    window: int = 2048               # ``window_size``
    chunk: int = 16                  # ``chunk_size``
    pred_heads: int = 8              # ``num_pred_heads``
    max_seq: int = 32768
    rope_theta: float = 100000.0
    rms_eps: float = 1e-5
    init_std: float = 0.01275
    dtype: object = jnp.bfloat16         # the layers' compute dtype
    stream_dtype: object = jnp.float32   # the residual stream's


def evabyte_6b5(**kw):
    """The published sizes: 6.5 B parameters in 32 layers."""
    return EvaByteConfig(**kw)


def evabyte_tiny(**kw):
    """Small config for tests / dry runs: four windows of 64 at 256
    positions, 8 chunks of 8 a window, 3 heads over 32 ids."""
    for k, v in dict(vocab_size=32, hidden=64, num_layers=2, num_heads=4,
                     head_dim=16, ffn_width=96, window=64, chunk=8,
                     pred_heads=3, max_seq=512).items():
        kw.setdefault(k, v)
    return EvaByteConfig(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree: every matrix N(0,
    ``init_std``), every gain parameter 0 (gain 1), a head's two summary
    vectors N(0, 1) clipped to [-1, 1], times 1 / sqrt(head_dim)."""
    h, f = cfg.hidden, cfg.ffn_width
    n, d = cfg.num_heads, cfg.head_dim
    keys = iter(jax.random.split(rng, 2 + 9 * cfg.num_layers))

    def normal(*shape):
        return (cfg.init_std * jax.random.normal(next(keys), shape)) \
            .astype(jnp.float32)

    def vectors():
        return (jnp.clip(jax.random.normal(next(keys), (n, d)), -1.0, 1.0)
                / math.sqrt(d)).astype(jnp.float32)

    def zeros():
        return jnp.zeros((h,), jnp.float32)

    return {
        "embed": normal(cfg.vocab_size, h),
        "layers": [{
            "ln1_w": zeros(), "ln2_w": zeros(),
            "q_w": normal(h, n * d), "k_w": normal(h, n * d),
            "v_w": normal(h, n * d), "o_w": normal(n * d, h),
            "mu": vectors(), "phi": vectors(),
            "ffn_gate": normal(h, f), "ffn_up": normal(h, f),
            "ffn_down": normal(f, h),
        } for _ in range(cfg.num_layers)],
        "final_norm_w": zeros(),
        "head_w": normal(h, cfg.pred_heads * cfg.vocab_size),
    }


def param_specs(cfg):
    """PartitionSpecs over ("model",): the projections split their heads,
    the summary vectors with them, the feed-forward its width, the head its
    columns; the embedding (320 rows) and the gains are replicated."""
    col, row = P(None, MODEL_AXIS), P(MODEL_AXIS, None)
    layer = {"ln1_w": P(), "ln2_w": P(), "q_w": col, "k_w": col, "v_w": col,
             "o_w": row, "mu": row, "phi": row, "ffn_gate": col,
             "ffn_up": col, "ffn_down": row}
    return {"embed": P(),
            "layers": [dict(layer) for _ in range(cfg.num_layers)],
            "final_norm_w": P(), "head_w": col}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Named scopes as models/olmoe.py (embed, attention, attention_core, rope,
# ffn, layer_norm, loss) plus eva_summary, eva_core (ops/eva.py enters it
# inside attention_core) and multibyte_head (lm_trainer enters it inside
# loss): chipbench's per-layer metrics key on them.
@jax.named_scope("attention")
def _attention(lp, x, cfg, rope, mesh=None):
    b, s, _ = x.shape
    dt = x.dtype
    q, k, v = ((x @ lp[f"{name}_w"].astype(dt)).reshape(
        b, s, cfg.num_heads, cfg.head_dim) for name in "qkv")
    q, k = blocks.apply_rope(q, *rope), blocks.apply_rope(k, *rope)
    with jax.named_scope("eva_summary"):
        ksum, vsum = eva.eva_summaries(k, v, lp["mu"], lp["phi"], cfg.chunk)
    ctx = eva.eva_attention(q, k, v, ksum, vsum, cfg.window, cfg.chunk,
                            mesh=mesh)
    return ctx.reshape(b, s, -1) @ lp["o_w"].astype(dt)


def _block(lp, x, cfg, layer, rope, mesh=None):
    """One layer: (the stream after the mixer, after the feed-forward, None:
    no expert layer, no aux terms). The stream ``x`` is in
    ``cfg.stream_dtype``; what a norm hands a mixer or a feed-forward is in
    ``cfg.dtype``, and their results are added in the stream's."""
    def mix(lp, x):
        normed = blocks.rms_norm(x, 1.0 + lp["ln1_w"], cfg.rms_eps)
        return x + _attention(lp, normed.astype(cfg.dtype), cfg, rope,
                              mesh).astype(x.dtype)

    def feed(lp, h):
        normed = blocks.rms_norm(h, 1.0 + lp["ln2_w"], cfg.rms_eps)
        with jax.named_scope("ffn"):
            return h + blocks.gated_ffn(
                normed.astype(cfg.dtype), lp["ffn_gate"], lp["ffn_up"],
                lp["ffn_down"]).astype(h.dtype)

    h = blocks.recomputed(mix)(lp, x)
    return h, jax.checkpoint(feed)(lp, h), None


# everything around the block is the skeleton's (``lm_trainer.Decoder``)
DECODER = lm_trainer.Decoder(
    init_params=init_params, param_specs=param_specs, block=_block,
    rotary=lambda cfg, positions: blocks.rope_angles(
        positions, cfg.head_dim, cfg.rope_theta),
    final_gain=lambda params: 1.0 + params["final_norm_w"], routed=False)
forward = DECODER.forward
stages = DECODER.stages
lm_loss = DECODER.lm_loss
head_losses = DECODER.head_losses
make_train_step = DECODER.make_train_step
synthetic_batch = lm_trainer.synthetic_batch
