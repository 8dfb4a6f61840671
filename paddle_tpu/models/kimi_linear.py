"""Kimi Linear: a decoder-only language model whose layers mix tokens by
two kinds of attention and feed forward by two kinds of layer (Kimi Linear
technical report, Moonshot AI 2025, arXiv:2510.26692; the released
``moonshotai/Kimi-Linear-48B-A3B-Instruct`` config).

Pre-norm blocks, ``h = x + Mix(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``.
Which mixer a layer has is read from two lists of the configuration
(``kda_layers``, ``full_attn_layers``: the published 1-based numbers, three
KDA layers to one MLA layer); the first ``first_dense`` layers have a dense
feed-forward, the others the experts.

- **KDA** (Kimi Delta Attention): q, k and v through a causal depthwise
  convolution of ``conv_size`` taps and SiLU; q and k L2-normalised a head,
  q scaled by 1 / sqrt(d) (the three in one pass a projection,
  ``ops.pallas.short_conv_norm``); a log decay per channel ``-exp(A_log) *
  softplus(low-rank(x) + dt_bias)`` and a write strength per head
  ``sigmoid(x w_beta)``; the gated delta rule (``ops/kda.py``, chunked);
  an RMSNorm a head gated by ``sigmoid(low-rank(x))``
  (``ops.pallas.gated_head_norm``); the output projection. From the
  projections to the rule and from the rule to ``o_w`` the activations
  stay ``[B, S, H d]``, the projections' own layout, a head a lane tile:
  the ``[B, S, H, d]`` the rule's entry point takes is a view that the
  compiler folds away (PERF.md section 6, PR 39).
- **MLA without positions** (``blocks.latent_attention``, which
  ``models/deepseek_v3.py`` calls with a rotation): queries of ``qk_nope +
  qk_rope`` channels a head; keys and values expanded per head from an
  RMS-normalised latent of ``kv_lora_rank``, with ``qk_rope`` more key
  channels shared by the heads and, under ``mla_use_nope``, not rotated;
  causal softmax through ``blocks.causal_attention`` (the flash kernels at
  score size 192 and value size 128). No weight absorption: that is a
  serving form.
- **experts**: a float32 sigmoid router over all ``num_experts`` with a
  selection bias, ``experts_per_token`` a token, renormalised and scaled by
  ``routed_scale``, plus one shared expert
  (``parallel/moe.dropless_moe_ffn``). ``experts_held`` = (first, n) makes
  the layer one chip's share of an expert-parallel job: it holds n experts,
  routes over all and leaves the other chips' part out. The selection bias
  is a parameter outside the gradient; the train step moves it by
  DeepSeek-V3's sign rule on the load the router saw (``moe.bias_step``,
  ``bias_rate`` a step). On one chip's share it has more to do than in the
  deployment: only the held experts' outputs reach the loss, so the
  router's gradient, here without the other chips' parts, draws every token
  toward them; with the bias at rest they took four times their share
  within twenty steps (PERF.md section 6, PR 30). ``bias_rate`` = 0.05 is
  what holds that load level in the benchmark's cell, fitted there, and no
  property of the model (DeepSeek-V3 trains with 0.001).
- a final RMSNorm and an untied head on every position; the loss is the
  mean next-token cross-entropy (the config names no auxiliary loss).
  ``vocab_size`` may be a slice of the published vocabulary: ids, head and
  loss are then over the slice.

**Recomputation.** Every KDA mixer is under ``jax.checkpoint``: the backward
pass keeps its input and forms the projections, convolutions and gates
again. The checkpoint (``blocks.recomputed``) also keeps what the delta
rule's forward kernel hands its backward one, by the name the kernel's
forward rule gives those arrays: the output, the state a unit starts from
and three tiles a unit and head, 0.5 GiB a layer (``ops/pallas/kda.py``).
With every output of ``kda_fwd`` kept, the backward pass does not run it
again: one call a layer in the step, not two (PERF.md section 6, PR 42).
Recomputing the mixers is the least that lets one sequence of 8192
positions fit a v5e beside 6.7 GiB of parameters and Adam state: nothing
recomputed was 20.8 GiB by the compiler's account and the scan alone 15.1
(PERF.md section 6, PR 30, when the rule was a ``jax.numpy`` body); the
step is 13.28 GiB now, 10.39 with the forward kernel run again. The MLA
mixer and the feed-forwards keep what they computed; the experts' rows are
formed again by ``moe.dropless_moe_ffn`` itself. No option chooses any of
it.

Built like ``models/olmoe.py``: float32 master parameters, ``cfg.dtype``
(bfloat16) activations and matmul operands, one jitted step
(``models/lm_trainer.py``).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import blocks, lm_trainer
from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import gated_head_norm, short_conv_norm
from paddle_tpu.ops.pallas.registry import mesh_scope
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import MODEL_AXIS

__all__ = ["KimiLinearConfig", "kimi_linear_48b_a3b", "kimi_linear_tiny",
           "init_params", "param_specs", "forward", "stages", "lm_loss",
           "routing_stats", "make_train_step", "synthetic_batch"]

_KDA_48B = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23,
            25, 26)
_MLA_48B = (4, 8, 12, 16, 20, 24, 27)


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden: int = 2304
    num_layers: int = 27
    kda_layers: tuple = _KDA_48B         # 1-based, as published
    full_attn_layers: tuple = _MLA_48B
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    num_heads: int = 32                  # MLA
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_width: int = 9216
    first_dense: int = 1
    expert_width: int = 1024
    num_experts: int = 256
    experts_per_token: int = 8
    routed_scale: float = 2.446
    bias_rate: float = 0.05              # the selection bias's step
    experts_held: tuple = None           # (first, n); None: all of them
    rms_eps: float = 1e-5
    dtype: object = jnp.bfloat16         # activation/compute dtype

    def mixer(self, layer):
        """"kda" or "mla" for the 0-based ``layer``."""
        if layer + 1 in self.kda_layers:
            return "kda"
        if layer + 1 in self.full_attn_layers:
            return "mla"
        raise ValueError(f"layer {layer + 1} is in neither list of mixers")

    @property
    def scoring(self):
        return moe.Scoring("sigmoid", renormalize=True,
                           scale=self.routed_scale)

    @property
    def experts_here(self):
        return self.experts_held[1] if self.experts_held else self.num_experts


def kimi_linear_48b_a3b(**kw):
    """The published sizes: 48 B parameters, 3 B a token."""
    return KimiLinearConfig(**kw)


def kimi_linear_tiny(**kw):
    """Small config for tests / dry runs: the first five published layers
    (KDA, KDA, KDA, MLA, KDA; the first one dense)."""
    for k, v in dict(vocab_size=512, hidden=64, num_layers=5, kda_heads=4,
                     kda_head_dim=16, num_heads=4, kv_lora_rank=32,
                     qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                     dense_width=128, expert_width=32, num_experts=16,
                     experts_per_token=4).items():
        kw.setdefault(k, v)
    return KimiLinearConfig(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree. Matrices N(0, 0.02),
    gains 1, the convolutions' taps U(-1/2, 1/2) (a depthwise Conv1d of 4
    taps as PyTorch starts it), ``A_log`` = log U(1, 16) a head and
    ``dt_bias`` the inverse softplus of a step log-uniform in [0.001, 0.1]
    (the Mamba-2 / Gated DeltaNet start), the selection bias 0."""
    h = cfg.hidden
    keys = iter(jax.random.split(rng, 2 + 24 * cfg.num_layers))

    def normal(*shape):
        return (0.02 * jax.random.normal(next(keys), shape)) \
            .astype(jnp.float32)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def kda_mixer():
        n, d = cfg.kda_heads, cfg.kda_head_dim
        width = n * d
        step = jnp.exp(jax.random.uniform(
            next(keys), (width,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        lp = {"q_w": normal(h, width), "k_w": normal(h, width),
              "v_w": normal(h, width), "o_w": normal(width, h),
              "f_a": normal(h, d), "f_b": normal(d, width),
              "g_a": normal(h, d), "g_b": normal(d, width),
              "beta_w": normal(h, n),
              "A_log": jnp.log(jax.random.uniform(
                  next(keys), (n,), jnp.float32, 1.0, 16.0)),
              "dt_bias": step + jnp.log(-jnp.expm1(-step)),
              "o_norm_g": ones(d)}
        for name in ("q_conv", "k_conv", "v_conv"):
            lp[name] = jax.random.uniform(
                next(keys), (cfg.conv_size, width), jnp.float32, -0.5, 0.5)
        return lp

    def mla_mixer():
        n = cfg.num_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {"q_w": normal(h, n * qk),
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
        return {"router_w": normal(h, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "w_gate": normal(e, h, f), "w_up": normal(e, h, f),
                "w_down": normal(e, f, h),
                "shared_gate": normal(h, f), "shared_up": normal(h, f),
                "shared_down": normal(f, h)}

    p = {"embed": normal(cfg.vocab_size, h), "layers": [],
         "final_norm_g": ones(h), "head_w": normal(h, cfg.vocab_size)}
    for layer in range(cfg.num_layers):
        mixer = kda_mixer() if cfg.mixer(layer) == "kda" else mla_mixer()
        p["layers"].append({"ln1_g": ones(h), "ln2_g": ones(h), **mixer,
                            **feed_forward(layer)})
    return p


def param_specs(cfg):
    """PartitionSpecs over ("model",): the mixers' projections split their
    heads' dim, the dense feed-forward its width, the embedding its rows
    and the head its columns; everything small, the experts and the router
    are replicated."""
    col, row = P(None, MODEL_AXIS), P(MODEL_AXIS, None)
    split = {"q_w": col, "k_w": col, "v_w": col, "o_w": row, "f_b": col,
             "g_b": col, "kvb_w": col, "q_conv": col, "k_conv": col,
             "v_conv": col, "dt_bias": P(MODEL_AXIS), "ffn_gate": col,
             "ffn_up": col, "ffn_down": row}
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return {"embed": row,
            "layers": [{name: split.get(name, P()) for name in lp}
                       for lp in shapes["layers"]],
            "final_norm_g": P(), "head_w": col}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Named scopes as models/olmoe.py (embed, attention, attention_core, ffn,
# layer_norm, loss, moe_router, moe_dispatch, moe_experts) plus kda_core,
# short_conv, kda_gate, mla_expand and moe_shared: chipbench's per-layer
# metrics key on them.
@jax.named_scope("attention")
def _kda(lp, x, cfg, mesh=None):
    b, s, _ = x.shape
    dt = x.dtype
    n, d = cfg.kda_heads, cfg.kda_head_dim

    def heads(t):
        return t.reshape(b, s, n, d)

    def conv(name, scale):
        projected = x @ lp[f"{name}_w"].astype(dt)
        with jax.named_scope("short_conv"):
            return short_conv_norm(projected, lp[f"{name}_conv"], d,
                                   ((n * d, scale),))[0]

    with mesh_scope(mesh):
        # rows-major from the projections to the rule and from the rule to
        # o_w: the convolution, SiLU and the norm a head are one pass
        q, k, v = (conv(name, scale) for name, scale in
                   (("q", d ** -0.5), ("k", 1.0), ("v", None)))
        decay_in = (x @ lp["f_a"].astype(dt)) @ lp["f_b"].astype(dt)
        gate_in = (x @ lp["g_a"].astype(dt)) @ lp["g_b"].astype(dt)
        beta_in = x @ lp["beta_w"].astype(dt)
        with jax.named_scope("kda_gate"):
            # the decay too stays [B, S, H d] up to the rule's view a head:
            # multiplied a head in the four-dimensional view, the compiler
            # gave the recomputed decay and its gradient positions-minor
            # layouts and copied both, float32, for ``kda_bwd`` (2.6 ms a
            # step; PERF.md section 6, PR 42)
            rate = jnp.broadcast_to(-jnp.exp(lp["A_log"])[:, None],
                                    (n, d)).reshape(-1)
            g = heads(rate * jax.nn.softplus(
                decay_in.astype(jnp.float32) + lp["dt_bias"]))
            beta = jax.nn.sigmoid(beta_in.astype(jnp.float32))
        with jax.named_scope("kda_core"):
            o = kda.kda_chunked(heads(q), heads(k), heads(v), g, beta)
        with jax.named_scope("kda_gate"):
            o = gated_head_norm(o.reshape(b, s, -1), gate_in, lp["o_norm_g"],
                                cfg.rms_eps, "sigmoid")
    return o @ lp["o_w"].astype(dt)


def _block(lp, x, cfg, layer, rotary, mesh=None):
    """One layer: (the stream after the mixer, after the feed-forward, the
    expert layer's aux terms or None); no mixer takes positions, ``rotary``
    is None. A KDA mixer is recomputed in the backward pass from its input,
    but for the delta rule's forward kernel, whose outputs it keeps (the
    module docstring says why); the experts recompute their own part
    (``moe.dropless_moe_ffn``); nothing else is."""
    kind = cfg.mixer(layer)

    def mix(lp, x):
        normed = blocks.rms_norm(x, lp["ln1_g"], cfg.rms_eps)
        return x + (_kda(lp, normed, cfg, mesh) if kind == "kda"
                    else blocks.latent_attention(
                        lp, normed, cfg.num_heads, cfg.kv_lora_rank,
                        cfg.qk_nope_head_dim, cfg.rms_eps, mesh=mesh))

    h = (blocks.recomputed(mix) if kind == "kda" else mix)(lp, x)
    m, aux = lm_trainer.feed_forward(
        lp, blocks.rms_norm(h, lp["ln2_g"], cfg.rms_eps), cfg, mesh)
    return h, h + m, aux


# everything around the block is the skeleton's (``lm_trainer.Decoder``)
DECODER = lm_trainer.Decoder(
    init_params=init_params, param_specs=param_specs, block=_block,
    rotary=lambda cfg, positions: None)
forward = DECODER.forward
stages = DECODER.stages
lm_loss = DECODER.lm_loss
routing_stats = DECODER.routing_stats
make_train_step = DECODER.make_train_step
synthetic_batch = lm_trainer.synthetic_batch
