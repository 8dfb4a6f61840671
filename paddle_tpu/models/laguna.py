"""Laguna: a decoder-only language model whose layers attend in two ways and
feed forward in two (poolside's ``Laguna-XS.2``, 33.4B-A3B; the released
``poolside/Laguna-XS.2`` config).

Pre-norm blocks, ``h = x + Attn(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``.
What a layer is comes from the configuration's lists, a layer an entry
(``layer_types``, ``heads``), as the published config gives them:

- **attention**, no biases and no query/key norm. ``heads[l]`` query heads of
  ``head_dim`` over ``kv_heads`` key/value heads: query head i reads
  key/value head ``i // (heads[l] / kv_heads)``. A ``full_attention`` layer
  sees every key up to the query's own, a ``sliding_attention`` layer the
  ``window`` keys that end there; the published model gives its sliding
  layers more query heads (64 against 48). Rotary positions (rotate-half) on
  the first ``rotary_factor`` of each query and key head by the layer type's
  own law (``RotaryLaw``: plain, or YaRN with its factor on cos and sin).
  Both through ``blocks.causal_attention``: the flash kernels, which skip the
  band's outside and read a group's key/value head in place
  (``ops/pallas/flash_attention.py``).
- **a gate on the attention output**: one scalar a head, ``sigmoid(h W_g)``
  from the layer's normed input, on the head's context before the output
  projection (Qiu et al. 2025, arXiv:2505.06708: position G1, headwise).
  The config says ``gating: true`` and not which form: this one is assumed
  (``chipbench/configs/laguna_xs2.json`` has the alternative).
- **feed-forward**: the layers of ``dense_layers`` a SiLU-gated one of
  ``dense_width``; the others a float32 sigmoid router over all
  ``num_experts``, ``experts_per_token`` a token, renormalised and scaled by
  ``routed_scale``, the weights on the experts' outputs, plus one shared
  expert (``parallel/moe.dropless_moe_ffn``). ``experts_held`` = (first, n)
  makes the layer one chip's share of an expert-parallel job, as
  ``models/kimi_linear.py`` says; the selection bias and its step
  (``moe.bias_step``, ``bias_rate``) are that file's too, and here as there
  the rate is fitted to the benchmark's cell and no property of the model.
- a final RMSNorm and an untied head on every position; the loss is the
  mean next-token cross-entropy (the config names no auxiliary loss).
  ``vocab_size`` may be a slice of the published vocabulary.

**Recomputation.** Every mixer and the dense feed-forward are under
``jax.checkpoint``: the backward pass keeps their inputs and forms the
projections, rotary positions, gate and the feed-forward's three products
again. A mixer's checkpoint (``blocks.recomputed``) keeps two arrays more,
by the name its flash call's forward rule gives them: the context ``o`` and
the logsumexp, which only the kernel can make (192 | 256 MiB and 3 | 4 MiB a
full | sliding layer, 1.15 GiB over the five). With every output of the
forward kernel kept, the backward pass does not run it again: ``flash_fwd``
and ``flash_fwd_window`` are one call a layer in the step, not two, 65.8 ms
of a 704.6 ms step (PERF.md section 6, PR 42); q, k and v, which the
weights' gradients need in any case, are still formed again. Full
recomputation is the least, of the subsets tried, that lets one sequence of
16 384 positions fit a v5e beside 7.73 GiB of parameters and Adam state:
nothing recomputed is 16.05 GiB by the compiler's account and every proper
subset (the sliding mixers, the full ones, the dense feed-forward, any two
of them) between 15.88 and 16.55 of the 15.75 a program may take (PERF.md
section 6, PR 33, has the table). That account is not a sum of live bytes:
with the mixers' outputs kept it reads 15.88 GiB (12.04 without; PR 33 read
the same 15.88 for "the flash calls' outputs kept"), of which 1.15 are the
kept arrays. The rest is the order of the step. XLA's memory scheduler
takes the cheapest of three orders by an estimate of its own; without the
kept arrays the list order wins by 0.12 GiB (13.90 against 14.02, and 11.89
once buffers are assigned), with them the depth-first order does (14.38
against 15.04), which updates ``head_w`` after the backward pass and so
holds 0.77 GiB of logits through it, and packs with 1.03 GiB of holes that
the account counts twice. The compiler's own total is 15.18 GiB, and the
step runs (PERF.md section 6, PR 42, has the numbers and what
would free them). The experts' rows are formed again by
``moe.dropless_moe_ffn`` itself; the router and the shared expert keep what
they computed. No option chooses any of it.

Built like ``models/kimi_linear.py``: float32 master parameters,
``cfg.dtype`` (bfloat16) activations and matmul operands, one jitted step
(``models/lm_trainer.py``). No attention, router or trainer code of its own.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import blocks, lm_trainer
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import MODEL_AXIS

__all__ = ["RotaryLaw", "LagunaConfig", "laguna_xs2", "laguna_tiny",
           "init_params", "param_specs", "forward", "stages", "lm_loss",
           "routing_stats", "make_train_step", "synthetic_batch"]

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class RotaryLaw:
    """How one layer type turns its heads: ``rotary_factor`` of a head's
    channels (the first ones) by the plain law of ``theta``, or by YaRN's
    where ``yarn`` = (factor, original positions, beta_fast, beta_slow);
    ``attention_factor`` multiplies cos and sin."""
    theta: float = 10000.0
    rotary_factor: float = 1.0
    yarn: tuple = None
    attention_factor: float = 1.0

    def angles(self, positions, head_dim):
        """(cos, sin), each [positions, rotated width / 2] float32."""
        rot = int(head_dim * self.rotary_factor)
        inv_freq = None if self.yarn is None else blocks.yarn_inv_freq(
            rot, self.theta, *self.yarn)
        return blocks.rope_angles(positions, rot, self.theta, inv_freq,
                                  self.attention_factor)


#: the published laws: YaRN by 64 from 4096 positions on half of a full
#: layer's head, the plain law on the whole of a sliding layer's
_YARN_XS2 = RotaryLaw(500000.0, 0.5, (64.0, 4096, 64.0, 1.0),
                      0.1 * math.log(64.0) + 1.0)
_PERIOD = (FULL, SLIDING, SLIDING, SLIDING)


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class LagunaConfig:
    vocab_size: int = 100352
    hidden: int = 2048
    num_layers: int = 40
    layer_types: tuple = _PERIOD * 10    # a layer an entry, as published
    heads: tuple = (48, 64, 64, 64) * 10
    kv_heads: int = 8
    head_dim: int = 128
    window: int = 512
    rope_full: RotaryLaw = _YARN_XS2
    rope_sliding: RotaryLaw = RotaryLaw()
    dense_width: int = 8192
    dense_layers: tuple = (0,)           # 0-based: ``mlp_layer_types``
    expert_width: int = 512
    shared_width: int = 512
    num_experts: int = 256
    experts_per_token: int = 8
    routed_scale: float = 2.5
    bias_rate: float = 0.001             # the selection bias's step
    experts_held: tuple = None           # (first, n); None: all of them
    rms_eps: float = 1e-6
    dtype: object = jnp.bfloat16         # activation/compute dtype

    def __post_init__(self):
        for name in ("layer_types", "heads"):
            if len(getattr(self, name)) < self.num_layers:
                raise ValueError(f"{name} has fewer entries than layers")
        if any(n % self.kv_heads for n in self.heads[:self.num_layers]):
            raise ValueError("a layer's query heads are a multiple of the "
                             "key/value heads")

    def rope(self, kind):
        return self.rope_full if kind == FULL else self.rope_sliding

    @property
    def scoring(self):
        return moe.Scoring("sigmoid", renormalize=True,
                           scale=self.routed_scale)

    @property
    def experts_here(self):
        return self.experts_held[1] if self.experts_held else self.num_experts


def laguna_xs2(**kw):
    """The published sizes: 33.4 B parameters, 3 B a token."""
    return LagunaConfig(**kw)


def laguna_tiny(**kw):
    """Small config for tests / dry runs: the first five published layers
    (full with the dense feed-forward, three sliding, full), groups of 6 and
    8 query heads over 2 key/value heads, a window of 24."""
    for k, v in dict(vocab_size=512, hidden=64, num_layers=5,
                     heads=(12, 16, 16, 16) * 10, kv_heads=2, head_dim=16,
                     window=24, dense_width=128, expert_width=32,
                     shared_width=32, num_experts=16, experts_per_token=4,
                     rope_full=dataclasses.replace(
                         _YARN_XS2, yarn=(64.0, 32, 64.0, 1.0))).items():
        kw.setdefault(k, v)
    return LagunaConfig(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree: matrices N(0, 0.02),
    gains 1, the selection bias 0."""
    h, d = cfg.hidden, cfg.head_dim
    keys = iter(jax.random.split(rng, 2 + 16 * cfg.num_layers))

    def normal(*shape):
        return (0.02 * jax.random.normal(next(keys), shape)) \
            .astype(jnp.float32)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def attention(layer):
        n, kv = cfg.heads[layer], cfg.kv_heads
        return {"q_w": normal(h, n * d), "k_w": normal(h, kv * d),
                "v_w": normal(h, kv * d), "g_w": normal(h, n),
                "o_w": normal(n * d, h)}

    def feed_forward(layer):
        if layer in cfg.dense_layers:
            f = cfg.dense_width
            return {"ffn_gate": normal(h, f), "ffn_up": normal(h, f),
                    "ffn_down": normal(f, h)}
        e, f, fs = cfg.experts_here, cfg.expert_width, cfg.shared_width
        return {"router_w": normal(h, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "w_gate": normal(e, h, f), "w_up": normal(e, h, f),
                "w_down": normal(e, f, h),
                "shared_gate": normal(h, fs), "shared_up": normal(h, fs),
                "shared_down": normal(fs, h)}

    p = {"embed": normal(cfg.vocab_size, h), "layers": [],
         "final_norm_g": ones(h), "head_w": normal(h, cfg.vocab_size)}
    for layer in range(cfg.num_layers):
        p["layers"].append({"ln1_g": ones(h), "ln2_g": ones(h),
                            **attention(layer), **feed_forward(layer)})
    return p


def param_specs(cfg):
    """PartitionSpecs over ("model",): the query, gate and output
    projections split their heads, the dense feed-forward its width, the
    embedding its rows and the head its columns; the key and value
    projections (8 heads), everything small, the experts and the router are
    replicated."""
    col, row = P(None, MODEL_AXIS), P(MODEL_AXIS, None)
    split = {"q_w": col, "g_w": col, "o_w": row, "ffn_gate": col,
             "ffn_up": col, "ffn_down": row}
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return {"embed": row,
            "layers": [{name: split.get(name, P()) for name in lp}
                       for lp in shapes["layers"]],
            "final_norm_g": P(), "head_w": col}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Named scopes as models/kimi_linear.py (embed, attention, attention_core,
# rope, ffn, layer_norm, loss, moe_router, moe_dispatch, moe_experts,
# moe_shared) plus attention_window (blocks.causal_attention enters it) and
# attn_gate: chipbench's per-layer metrics key on them.
@jax.named_scope("attention")
def _attention(lp, x, cfg, kind, angles, mesh=None):
    b, s, _ = x.shape
    dt = x.dtype
    q, k, v = ((x @ lp[f"{name}_w"].astype(dt)).reshape(b, s, -1,
                                                        cfg.head_dim)
               for name in "qkv")
    gate_in = jnp.dot(x, lp["g_w"].astype(dt),
                      preferred_element_type=jnp.float32)      # [B, S, N]
    q, k = blocks.apply_rope(q, *angles), blocks.apply_rope(k, *angles)
    ctx = blocks.causal_attention(
        q, k, v, mesh=mesh, window=cfg.window if kind == SLIDING else None)
    with jax.named_scope("attn_gate"):
        ctx = (ctx.astype(jnp.float32)
               * jax.nn.sigmoid(gate_in)[..., None]).astype(dt)
    return ctx.reshape(b, s, -1) @ lp["o_w"].astype(dt)


def _block(lp, x, cfg, layer, angles, mesh=None):
    """One layer: (the stream after the mixer, after the feed-forward, the
    expert layer's aux terms or None); ``angles`` holds the rotary table of
    each layer kind. The mixer and the dense feed-forward are recomputed in
    the backward pass from their inputs, the mixer but for its flash call's
    forward kernel, whose outputs it keeps (the module docstring says why);
    the experts recompute their own part."""
    kind = cfg.layer_types[layer]

    def mix(lp, x):
        normed = blocks.rms_norm(x, lp["ln1_g"], cfg.rms_eps)
        return x + _attention(lp, normed, cfg, kind, angles[kind], mesh)

    def feed(lp, h):
        return lm_trainer.feed_forward(
            lp, blocks.rms_norm(h, lp["ln2_g"], cfg.rms_eps), cfg, mesh)

    h = blocks.recomputed(mix)(lp, x)
    m, aux = (jax.checkpoint(feed) if "ffn_gate" in lp else feed)(lp, h)
    return h, h + m, aux


def _angles(cfg, positions):
    """The rotary table of each layer kind the model has."""
    return {kind: cfg.rope(kind).angles(positions, cfg.head_dim)
            for kind in dict.fromkeys(cfg.layer_types[:cfg.num_layers])}


# everything around the block is the skeleton's (``lm_trainer.Decoder``)
DECODER = lm_trainer.Decoder(init_params=init_params,
                             param_specs=param_specs, block=_block,
                             rotary=_angles)
forward = DECODER.forward
stages = DECODER.stages
lm_loss = DECODER.lm_loss
routing_stats = DECODER.routing_stats
make_train_step = DECODER.make_train_step
synthetic_batch = lm_trainer.synthetic_batch
