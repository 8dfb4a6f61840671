"""LFM2: a decoder-only language model whose layers mix tokens by a
double-gated short convolution three times in four and by grouped softmax
attention the fourth, and feed forward densely in the leading layers and
through experts in the others (LiquidAI's ``LFM2-24B-A2B``, ``model_type``
``lfm2_moe``; the released ``LiquidAI/LFM2-24B-A2B`` config).

Pre-norm blocks, ``h = x + Op(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``, plain
gains. What a layer is comes from the configuration: ``layer_types[l]`` names
its operator, the first ``num_dense_layers`` have the dense feed-forward.

- **short convolution** (``conv``): ``[B | C | u] = x W_in`` (one product,
  three column ranges), ``z = B * u``, a causal depthwise convolution of
  ``conv_taps`` taps over positions (no bias, no activation), ``y = C *
  conv(z)``, then ``W_out``. From the product to ``W_out`` it is one op on
  the product's own array, ``ops.pallas.gated_short_conv``: nothing is split
  or concatenated in HBM, either way.
- **attention** (``full_attention``): ``num_heads`` query heads of
  ``head_dim`` over ``kv_heads`` key/value heads, no bias; queries and keys
  RMS-normed a head (gains of ``head_dim``, scope ``qk_norm``), then rotary
  positions on the whole head (rotate-half, ``rope_theta``); causal softmax
  through ``blocks.causal_attention`` (the flash kernels, a group's
  key/value head read in place); the output projection.
- **feed-forward**: the leading layers a SiLU-gated one of ``dense_width``;
  the others a float32 sigmoid router over all ``num_experts`` with a
  selection bias outside the gradient, ``experts_per_token`` a token,
  renormalised, scaled by ``routed_scale``; no shared expert
  (``parallel/moe.dropless_moe_ffn``). ``experts_held`` = (first, n) makes
  the layer one chip's share of an expert-parallel job, as
  ``models/kimi_linear.py`` says; the bias's step (``moe.bias_step``,
  ``bias_rate``) is that file's too, fitted to the benchmark's cell and no
  property of the model.
- a final RMSNorm and **a tied head** on every position: the logits are the
  hidden states times the embedding's transpose, so the table is one leaf
  with one Adam update, its gradient the head product's plus the gather's.
  The loss is the mean next-token cross-entropy (the config names no
  auxiliary loss). ``vocab_size`` may be a slice of the published vocabulary.

**Recomputation.** Nothing here is under ``jax.checkpoint``: at the
benchmark's 4 x 8192 positions the step keeps every operator's and the dense
feed-forward's activations and needs 15.17 GiB of the 15.75 a v5e gives a
program, beside 5.24 GiB of parameters and Adam state, by the compiler's
account; it is the fastest of the choices tried (485 ms a step; the dense
feed-forward recomputed 14.73 GiB and 497 ms, the convolutions too 12.73 and
529: PERF.md section 6, PR 40, has the table). The convolution's backward
kernel forms ``B u`` and its convolution again in VMEM, and the experts' rows
are formed again by ``moe.dropless_moe_ffn`` itself; no option chooses any
of it.

Built like ``models/laguna.py``: float32 master parameters, ``cfg.dtype``
(bfloat16) activations and matmul operands, one jitted step
(``models/lm_trainer.py``). No attention, router or trainer code of its own.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import blocks, lm_trainer
from paddle_tpu.ops.pallas import gated_short_conv
from paddle_tpu.ops.pallas.registry import mesh_scope
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import MODEL_AXIS

__all__ = ["Lfm2Config", "lfm2_24b_a2b", "lfm2_tiny", "init_params",
           "param_specs", "forward", "stages", "lm_loss", "routing_stats",
           "make_train_step", "synthetic_batch"]

CONV, FULL = "conv", "full_attention"
_PERIOD = (CONV, CONV, FULL, CONV)


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class Lfm2Config:
    vocab_size: int = 65536
    hidden: int = 2048
    num_layers: int = 40
    layer_types: tuple = _PERIOD * 10    # a layer an entry, as published
    num_dense_layers: int = 2            # the leading dense feed-forwards
    conv_taps: int = 3                   # conv_L_cache
    num_heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    dense_width: int = 11776
    expert_width: int = 1536
    num_experts: int = 64
    experts_per_token: int = 4
    routed_scale: float = 1.0
    bias_rate: float = 0.001             # the selection bias's step
    experts_held: tuple = None           # (first, n); None: all of them
    rms_eps: float = 1e-5
    dtype: object = jnp.bfloat16         # activation/compute dtype

    def __post_init__(self):
        if len(self.layer_types) < self.num_layers \
                or set(self.layer_types) - {CONV, FULL}:
            raise ValueError(f"layer_types names {CONV!r} or {FULL!r}, an "
                             f"entry a layer")
        if self.num_heads % self.kv_heads:
            raise ValueError("the query heads are a multiple of the "
                             "key/value heads")

    @property
    def scoring(self):
        return moe.Scoring("sigmoid", renormalize=True,
                           scale=self.routed_scale)

    @property
    def experts_here(self):
        return self.experts_held[1] if self.experts_held else self.num_experts


def lfm2_24b_a2b(**kw):
    """The published sizes: 24 B parameters, 2 B a token."""
    return Lfm2Config(**kw)


def lfm2_tiny(**kw):
    """Small config for tests / dry runs: the published layers 1 to 5 (a
    convolution with the dense feed-forward, then attention, convolution
    three times, each with experts), 8 query heads over 2 key/value heads."""
    for k, v in dict(vocab_size=512, hidden=64, num_layers=5,
                     layer_types=(CONV, FULL, CONV, CONV, CONV),
                     num_dense_layers=1, num_heads=8, kv_heads=2,
                     head_dim=8, dense_width=160, expert_width=32,
                     num_experts=16, experts_per_token=4).items():
        kw.setdefault(k, v)
    return Lfm2Config(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree: matrices N(0, 0.02),
    gains 1, the selection bias 0, the convolution's taps U(-1/sqrt(K),
    1/sqrt(K)) (a depthwise Conv1d of K taps as PyTorch starts it)."""
    h, d = cfg.hidden, cfg.head_dim
    keys = iter(jax.random.split(rng, 1 + 8 * cfg.num_layers))

    def normal(*shape):
        return (0.02 * jax.random.normal(next(keys), shape)) \
            .astype(jnp.float32)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def convolution():
        reach = cfg.conv_taps ** -0.5
        return {"in_w": normal(h, 3 * h),
                "conv": jax.random.uniform(next(keys), (cfg.conv_taps, h),
                                           jnp.float32, -reach, reach),
                "out_w": normal(h, h)}

    def attention():
        n, kv = cfg.num_heads, cfg.kv_heads
        return {"q_w": normal(h, n * d), "k_w": normal(h, kv * d),
                "v_w": normal(h, kv * d), "q_norm_g": ones(d),
                "k_norm_g": ones(d), "o_w": normal(n * d, h)}

    def feed_forward(layer):
        if layer < cfg.num_dense_layers:
            f = cfg.dense_width
            return {"ffn_gate": normal(h, f), "ffn_up": normal(h, f),
                    "ffn_down": normal(f, h)}
        e, f = cfg.experts_here, cfg.expert_width
        return {"router_w": normal(h, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "w_gate": normal(e, h, f), "w_up": normal(e, h, f),
                "w_down": normal(e, f, h)}

    p = {"embed": normal(cfg.vocab_size, h), "layers": [],
         "final_norm_g": ones(h)}
    for layer in range(cfg.num_layers):
        op = attention() if cfg.layer_types[layer] == FULL else convolution()
        p["layers"].append({"ln1_g": ones(h), "ln2_g": ones(h), **op,
                            **feed_forward(layer)})
    return p


def param_specs(cfg):
    """PartitionSpecs over ("model",): the query and output projections
    split their heads, the convolution's output projection its rows, the
    dense feed-forward its width, the embedding (which is the head too) its
    rows; the convolution's fused ``[B | C | u]`` projection (three widths
    side by side), the key and value projections (8 heads), everything
    small, the experts and the router are replicated."""
    col, row = P(None, MODEL_AXIS), P(MODEL_AXIS, None)
    split = {"q_w": col, "o_w": row, "out_w": row, "ffn_gate": col,
             "ffn_up": col, "ffn_down": row}
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return {"embed": row,
            "layers": [{name: split.get(name, P()) for name in lp}
                       for lp in shapes["layers"]],
            "final_norm_g": P()}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Named scopes as models/laguna.py (embed, attention, attention_core, rope,
# ffn, layer_norm, loss, moe_router, moe_dispatch, moe_experts) plus
# gated_conv (the convolution operator between its two projections) and
# qk_norm: chipbench's per-layer metrics key on them.
@jax.named_scope("attention")
def _short_conv(lp, x, mesh=None):
    dt = x.dtype
    bcu = x @ lp["in_w"].astype(dt)                           # [B, S, 3 H]
    with jax.named_scope("gated_conv"), mesh_scope(mesh):
        y = gated_short_conv(bcu, lp["conv"])
    return y @ lp["out_w"].astype(dt)


@jax.named_scope("attention")
def _attention(lp, x, cfg, angles, mesh=None):
    b, s, _ = x.shape
    dt = x.dtype
    q, k, v = ((x @ lp[f"{name}_w"].astype(dt)).reshape(b, s, -1,
                                                        cfg.head_dim)
               for name in "qkv")
    with jax.named_scope("qk_norm"):
        q = blocks.rms_normalize(q, lp["q_norm_g"], cfg.rms_eps)
        k = blocks.rms_normalize(k, lp["k_norm_g"], cfg.rms_eps)
    q, k = blocks.apply_rope(q, *angles), blocks.apply_rope(k, *angles)
    ctx = blocks.causal_attention(q, k, v, mesh=mesh)
    return ctx.reshape(b, s, -1) @ lp["o_w"].astype(dt)


def _block(lp, x, cfg, layer, angles, mesh=None):
    """One layer: (the stream after the operator, after the feed-forward,
    the expert layer's aux terms or None)."""
    normed = blocks.rms_norm(x, lp["ln1_g"], cfg.rms_eps)
    h = x + (_attention(lp, normed, cfg, angles, mesh)
             if cfg.layer_types[layer] == FULL
             else _short_conv(lp, normed, mesh))
    m, aux = lm_trainer.feed_forward(
        lp, blocks.rms_norm(h, lp["ln2_g"], cfg.rms_eps), cfg, mesh)
    return h, h + m, aux


def _tied_head(params, hidden):
    """Float32 logits through the tied head: the embedding's transpose,
    contracted in place."""
    return jnp.einsum("bsh,vh->bsv", hidden,
                      params["embed"].astype(hidden.dtype),
                      preferred_element_type=jnp.float32)


# everything around the block is the skeleton's (``lm_trainer.Decoder``)
DECODER = lm_trainer.Decoder(
    init_params=init_params, param_specs=param_specs, block=_block,
    rotary=lambda cfg, positions: blocks.rope_angles(
        positions, cfg.head_dim, cfg.rope_theta),
    logits=_tied_head)
forward = DECODER.forward
stages = DECODER.stages
lm_loss = DECODER.lm_loss
routing_stats = DECODER.routing_stats
make_train_step = DECODER.make_train_step
synthetic_batch = lm_trainer.synthetic_batch
