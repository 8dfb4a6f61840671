"""DeepSeek-V3-shaped decoders (``model_type`` ``deepseek_v3``): every layer
mixes tokens by rotary latent attention and feeds forward densely in the
leading layers and through sigmoid-routed experts beside shared ones in the
others (DeepSeek-V3 technical report, arXiv:2412.19437, sec. 2.1; the sizes
here are kakaocorp's ``kanana-2-30b-a3b-instruct-2601``, 30B-A3B, whose
released config is of that type).

Pre-norm blocks, ``h = x + Mix(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``.

- **mixer**, every layer: ``blocks.latent_attention`` with positions. Per
  head a query of ``qk_nope_head_dim`` channels and ``qk_rope_head_dim``
  decoupled ones behind them (no query latent: ``q_lora_rank`` null); keys
  and values expanded a head from an RMS-normed latent of ``kv_lora_rank``,
  and one decoupled key that all heads share. The decoupled channels of
  every query head and the shared key, once, are turned by position at
  ``rope_theta`` with no scaling: in neighbouring pairs ``(2i, 2i + 1)``
  where ``rope_interleave`` is set, as the released checkpoints lay them
  out, in halves otherwise (``blocks.apply_rope_tail``; nothing else chooses
  the pairing). Scores over all ``qk_nope + qk_rope`` channels divided by
  the root of that width, causal softmax through ``blocks.causal_attention``
  (the flash kernels at score size 192 and value size 128), then ``o_w``.
  No weight absorption: that is a serving form.
- **feed-forward**: the first ``first_dense`` layers a SiLU-gated one of
  ``dense_width``; the others a float32 sigmoid router over all
  ``num_experts`` with a selection bias outside the gradient (``noaux_tc``
  with one group: no group limit), ``experts_per_token`` a token,
  renormalised and scaled by ``routed_scale``, plus a shared feed-forward of
  ``shared_experts x expert_width`` on every token: what that many shared
  experts sum to, since the gate is elementwise
  (``parallel/moe.dropless_moe_ffn``, which reads the shared width from the
  parameter). ``experts_held`` = (first, n) makes the layer one chip's share
  of an expert-parallel job, as ``models/kimi_linear.py`` says; the bias's
  step (``moe.bias_step``, ``bias_rate``) is DeepSeek-V3's own 0.001.
- a final RMSNorm and an untied head on every position; the loss is the
  mean next-token cross-entropy (the config names no auxiliary loss).
  ``vocab_size`` may be a slice of the published vocabulary.

**Recomputation.** Every mixer is under ``blocks.recomputed``: the backward
pass keeps the layer's input, the flash call's own outputs (the context ``o``
and the logsumexp, by the name the kernel's forward rule gives them) and the
turned queries (``blocks.LATENT_KEPT``, 192 MiB a layer at 16 384 positions),
and forms the norm, the latent's projection, the shared key's rotation and
the latent's expansion again; ``flash_fwd`` runs once a layer, and so do the
query projection and its rotation. It is the fastest of the choices that fit
a v5e beside 6.44 GiB of parameters and Adam state at the benchmark's one
sequence of 16 384 positions (my chip runs, PR 44): 704.5 ms a step at 14.08
GiB by the compiler's account; with the queries formed again too 732.4 ms at
13.54; with nothing recomputed 710.3 ms at 15.78, past the 15.75 a program
may count on, where the compiler rematerialises on its own
(``chipbench/configs/kanana_2_30b_a3b.json``, ``program.recomputation``).
The feed-forwards keep what they computed; the experts' rows are formed
again by ``moe.dropless_moe_ffn`` itself. No option chooses any of it.

Built like ``models/laguna.py``: float32 master parameters, ``cfg.dtype``
(bfloat16) activations and matmul operands, one jitted step
(``models/lm_trainer.py``). No attention, router or trainer code of its own.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import blocks, lm_trainer
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import MODEL_AXIS

__all__ = ["DeepseekV3Config", "kanana_2_30b_a3b", "deepseek_v3_tiny",
           "init_params", "param_specs", "forward", "stages", "lm_loss",
           "routing_stats", "make_train_step", "synthetic_batch"]


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    rope_interleave: bool = True         # pairs (2i, 2i + 1), as released
    dense_width: int = 6144
    first_dense: int = 1                 # the leading dense feed-forwards
    expert_width: int = 768
    shared_experts: int = 2              # one feed-forward of their sum
    num_experts: int = 128
    experts_per_token: int = 6
    routed_scale: float = 2.448
    bias_rate: float = 0.001             # the selection bias's step
    experts_held: tuple = None           # (first, n); None: all of them
    rms_eps: float = 1e-6
    dtype: object = jnp.bfloat16         # activation/compute dtype

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("the decoupled channels are turned in pairs")

    @property
    def scoring(self):
        return moe.Scoring("sigmoid", renormalize=True,
                           scale=self.routed_scale)

    @property
    def experts_here(self):
        return self.experts_held[1] if self.experts_held else self.num_experts


def kanana_2_30b_a3b(**kw):
    """The published sizes: 30 B parameters, 3 B a token."""
    return DeepseekV3Config(**kw)


def deepseek_v3_tiny(**kw):
    """Small config for tests / dry runs: five layers, the first one dense,
    the head's three widths in the published 2 : 1 : 2."""
    for k, v in dict(vocab_size=512, hidden=64, num_layers=5, num_heads=4,
                     kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                     v_head_dim=16, dense_width=160, expert_width=32,
                     num_experts=16, experts_per_token=4).items():
        kw.setdefault(k, v)
    return DeepseekV3Config(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree, every matrix in the
    released checkpoint's column order (a head's ``[nope | rope]`` in
    ``q_w``, ``[latent | shared key]`` in ``kva_w``, a head's ``[k_nope |
    v]`` in ``kvb_w``): matrices N(0, 0.02), gains 1, the selection bias
    0."""
    h, n = cfg.hidden, cfg.num_heads
    keys = iter(jax.random.split(rng, 2 + 12 * cfg.num_layers))

    def normal(*shape):
        return (0.02 * jax.random.normal(next(keys), shape)) \
            .astype(jnp.float32)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def mixer():
        return {"q_w": normal(h, n * (cfg.qk_nope_head_dim
                                      + cfg.qk_rope_head_dim)),
                "kva_w": normal(h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                "kv_norm_g": ones(cfg.kv_lora_rank),
                "kvb_w": normal(cfg.kv_lora_rank,
                                n * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "o_w": normal(n * cfg.v_head_dim, h)}

    def feed_forward(layer):
        if layer < cfg.first_dense:
            f = cfg.dense_width
            return {"ffn_gate": normal(h, f), "ffn_up": normal(h, f),
                    "ffn_down": normal(f, h)}
        e, f = cfg.experts_here, cfg.expert_width
        fs = cfg.shared_experts * f
        return {"router_w": normal(h, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "w_gate": normal(e, h, f), "w_up": normal(e, h, f),
                "w_down": normal(e, f, h),
                "shared_gate": normal(h, fs), "shared_up": normal(h, fs),
                "shared_down": normal(fs, h)}

    p = {"embed": normal(cfg.vocab_size, h), "layers": [],
         "final_norm_g": ones(h), "head_w": normal(h, cfg.vocab_size)}
    for layer in range(cfg.num_layers):
        p["layers"].append({"ln1_g": ones(h), "ln2_g": ones(h), **mixer(),
                            **feed_forward(layer)})
    return p


def param_specs(cfg):
    """PartitionSpecs over ("model",): the query, expansion and output
    projections split their heads, the dense feed-forward its width, the
    embedding its rows and the head its columns; the latent's projection,
    everything small, the experts and the router are replicated."""
    col, row = P(None, MODEL_AXIS), P(MODEL_AXIS, None)
    split = {"q_w": col, "kvb_w": col, "o_w": row, "ffn_gate": col,
             "ffn_up": col, "ffn_down": row}
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return {"embed": row,
            "layers": [{name: split.get(name, P()) for name in lp}
                       for lp in shapes["layers"]],
            "final_norm_g": P(), "head_w": col}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Named scopes as models/kimi_linear.py's MLA layers (embed, attention,
# mla_expand, attention_core, ffn, layer_norm, loss, moe_router,
# moe_dispatch, moe_experts, moe_shared) plus rope: chipbench's per-layer
# metrics key on them.
def _block(lp, x, cfg, layer, rotary, mesh=None):
    """One layer: (the stream after the mixer, after the feed-forward, the
    expert layer's aux terms or None). The mixer is recomputed in the
    backward pass from its input, but for its flash call's forward kernel,
    whose outputs it keeps, and its turned queries (the module docstring
    says why); the experts recompute their own part; nothing else is."""
    def mix(lp, x):
        normed = blocks.rms_norm(x, lp["ln1_g"], cfg.rms_eps)
        return x + blocks.latent_attention(
            lp, normed, cfg.num_heads, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.rms_eps, rotary, mesh)

    h = blocks.recomputed(mix)(lp, x)
    m, aux = lm_trainer.feed_forward(
        lp, blocks.rms_norm(h, lp["ln2_g"], cfg.rms_eps), cfg, mesh)
    return h, h + m, aux


def _rotary(cfg, positions):
    """What ``blocks.apply_rope_tail`` takes: the angles of the decoupled
    channels and the configuration's pairing."""
    return (*blocks.rope_angles(positions, cfg.qk_rope_head_dim,
                                cfg.rope_theta), cfg.rope_interleave)


# everything around the block is the skeleton's (``lm_trainer.Decoder``)
DECODER = lm_trainer.Decoder(init_params=init_params,
                             param_specs=param_specs, block=_block,
                             rotary=_rotary)
forward = DECODER.forward
stages = DECODER.stages
lm_loss = DECODER.lm_loss
routing_stats = DECODER.routing_stats
make_train_step = DECODER.make_train_step
synthetic_batch = lm_trainer.synthetic_batch
