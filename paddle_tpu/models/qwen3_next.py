"""Qwen3-Next: a decoder-only language model whose layers mix tokens by a
gated delta rule three times in four and by gated softmax attention the
fourth, every one of them with experts (Qwen3-Next-80B-A3B, Qwen 2025-09;
the released ``Qwen/Qwen3-Next-80B-A3B-Instruct`` config).

Pre-norm blocks, ``h = x + Mix(norm(x; w1))``, ``y = h + MoE(norm(h; w2))``,
with ``norm(x; w) = x / sqrt(mean x^2 + eps) * (1 + w)``: the gains are kept
as their distance from 1 and start at zero (an expression where the norm is
called, ``blocks.rms_norm(x, 1 + w)``). Layer ``l`` has full attention where
``(l + 1) % full_attention_interval == 0``, else Gated DeltaNet.

- **Gated DeltaNet** (Yang et al. 2024, arXiv:2412.06464): ``[q | k | v |
  z] = x W_qkvz`` (two products, ``[q | k | v]`` and ``z``: the weight's
  columns are cut, never the activations') and ``[b | a] = x W_ba``; q, k
  and v through one causal depthwise convolution of ``conv_size`` taps and
  SiLU; ``linear_key_heads`` heads of q and k under ``linear_value_heads``
  of v, value head h on key head ``h // n``; q and k L2-normalised a head,
  q scaled by 1 / sqrt(d) (convolution, SiLU and norm in one pass over
  column ranges of the one array, ``ops.pallas.short_conv_norm``); the
  write strength ``sigmoid(b)`` and the log decay ``-exp(A_log) *
  softplus(a + dt_bias)``, **one number a value head and position**, in
  float32; the gated delta rule (``ops/kda.kda_chunked``: the decay's rank
  3 and the head counts are all it is told); an RMSNorm a head (plain
  gain) times ``silu(z)`` (``ops.pallas.gated_head_norm``); the output
  projection. Between the projections nothing is viewed a head at a time
  in HBM: the arrays stay ``[B, S, H d]``, a head a lane tile.
- **gated attention**: ``[q | gate] = x W_q``, a head's ``head_dim`` query
  channels then its ``head_dim`` gate channels; ``kv_heads`` key/value
  heads under ``num_heads`` query heads; q and k normed a head (``1 + w``);
  rotary positions on the first ``rotary_factor`` of each head
  (``blocks.apply_rope``); causal softmax through ``blocks.causal_attention``
  (the flash kernels at head size 256, a group's key/value head read in
  place); the context times ``sigmoid(gate)`` **a channel**; the output
  projection.
- **experts**, every layer: a float32 softmax router over all
  ``num_experts``, ``experts_per_token`` a token, their probabilities
  renormalised; one shared expert whose output is scaled by ``sigmoid(x .
  w)``, a scalar a token (``parallel/moe.dropless_moe_ffn`` with
  ``shared_scale_w``). ``experts_held`` = (first, n) makes the layer one
  chip's share of an expert-parallel job, as ``models/kimi_linear.py`` says.
  There is no selection bias: the router is balanced by ``balance_weight``
  times ``moe.balance_loss`` in the loss (the mean over the layers).
- a final norm and an untied head on every position; the loss is the mean
  next-token cross-entropy plus the balancing term. ``vocab_size`` may be a
  slice of the published vocabulary. The multi-token-prediction module the
  family describes is no part of the config and is not here.

**Recomputation.** Every Gated DeltaNet mixer is under ``jax.checkpoint``:
the backward pass keeps its input and forms the projections, the
convolution, the gates and the delta rule again. It is the least that lets
one sequence of 16 384 positions fit a v5e beside 7.0 GiB of parameters and
Adam state: 14.09 GiB of the 15.75 a program may take by the compiler's
account (15.04 before the passes around the rule were kernels, PR 39), where the compiler refuses the program with nothing recomputed, or
the attention mixer alone, at 18.2 GiB and more (PR 38's tree); with the
attention mixer recomputed as well it was 14.75 GiB there and a step 5%
longer (PERF.md section 6, PR 38). The checkpoint is the plain one, which
runs ``gdn_fwd`` again in the backward pass, and not ``blocks.recomputed``:
what that kernel hands its backward is 0.62 GiB a layer (the state a unit
starts from and the inverse, 537 MB, beside 128 MiB of ``o``), 1.9 GiB on
13.73, and Kimi Linear's step grew by 0.9 GiB more than it kept (PERF.md
section 6, PR 42). The attention mixer keeps what it computed; the experts' rows are
formed again by ``moe.dropless_moe_ffn`` itself; the router and the shared
expert keep what they computed. No option chooses any of it.

Built like ``models/laguna.py``: float32 master parameters, ``cfg.dtype``
(bfloat16) activations and matmul operands, one jitted step
(``models/lm_trainer.py``). No attention, delta-rule, router or trainer code
of its own.
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

__all__ = ["Qwen3NextConfig", "qwen3_next_80b_a3b", "qwen3_next_tiny",
           "init_params", "param_specs", "forward", "stages", "lm_loss",
           "routing_stats", "make_train_step", "synthetic_batch"]

FULL, LINEAR = "full_attention", "linear_attention"


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    linear_key_heads: int = 16           # Gated DeltaNet
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_size: int = 4
    num_heads: int = 16                  # gated attention
    kv_heads: int = 2
    head_dim: int = 256
    rotary_factor: float = 0.25
    rope_theta: float = 1e7
    expert_width: int = 512
    shared_width: int = 512
    num_experts: int = 512
    experts_per_token: int = 10
    balance_weight: float = 0.001        # router_aux_loss_coef
    experts_held: tuple = None           # (first, n); None: all of them
    rms_eps: float = 1e-6
    dtype: object = jnp.bfloat16         # activation/compute dtype

    def __post_init__(self):
        if self.linear_value_heads % self.linear_key_heads \
                or self.num_heads % self.kv_heads:
            raise ValueError("value heads are a multiple of the key heads, "
                             "query heads of the key/value heads")

    def mixer(self, layer):
        """``FULL`` or ``LINEAR`` for the 0-based ``layer``."""
        return FULL if (layer + 1) % self.full_attention_interval == 0 \
            else LINEAR

    @property
    def scoring(self):
        return moe.Scoring("softmax", renormalize=True)

    @property
    def experts_here(self):
        return self.experts_held[1] if self.experts_held else self.num_experts


def qwen3_next_80b_a3b(**kw):
    """The published sizes: 80 B parameters, 3 B a token."""
    return Qwen3NextConfig(**kw)


def qwen3_next_tiny(**kw):
    """Small config for tests / dry runs: the first four published layers
    (three Gated DeltaNet, one full), 2 key heads under 4 value heads, 8
    query heads over 2 key/value heads, a quarter of a head rotated."""
    for k, v in dict(vocab_size=512, hidden=64, num_layers=4,
                     linear_key_heads=2, linear_value_heads=4,
                     linear_key_dim=16, linear_value_dim=16, num_heads=8,
                     kv_heads=2, head_dim=32, expert_width=32,
                     shared_width=32, num_experts=16,
                     experts_per_token=4).items():
        kw.setdefault(k, v)
    return Qwen3NextConfig(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree. Matrices N(0, 0.02); the
    gains that are kept as ``1 + w`` zero, the delta rule's output gain 1;
    the convolution's taps U(-1/2, 1/2) (a depthwise Conv1d of 4 taps as
    PyTorch starts it), ``A_log`` = log U(1, 16) a value head and
    ``dt_bias`` the inverse softplus of a step log-uniform in [0.001, 0.1]
    (the Mamba-2 / Gated DeltaNet start, as ``models/kimi_linear.py``)."""
    h = cfg.hidden
    keys = iter(jax.random.split(rng, 2 + 16 * cfg.num_layers))

    def normal(*shape):
        return (0.02 * jax.random.normal(next(keys), shape)) \
            .astype(jnp.float32)

    def zeros(n):
        return jnp.zeros((n,), jnp.float32)

    def delta_net():
        nv = cfg.linear_value_heads
        kw = cfg.linear_key_heads * cfg.linear_key_dim
        vw = nv * cfg.linear_value_dim
        step = jnp.exp(jax.random.uniform(
            next(keys), (nv,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {"qkvz_w": normal(h, 2 * kw + 2 * vw),
                "ba_w": normal(h, 2 * nv),
                "conv": jax.random.uniform(
                    next(keys), (cfg.conv_size, 2 * kw + vw), jnp.float32,
                    -0.5, 0.5),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (nv,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "o_norm_g": jnp.ones((cfg.linear_value_dim,), jnp.float32),
                "out_w": normal(vw, h)}

    def attention():
        n, kv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        return {"q_w": normal(h, n * 2 * d), "k_w": normal(h, kv * d),
                "v_w": normal(h, kv * d), "q_norm_w": zeros(d),
                "k_norm_w": zeros(d), "o_w": normal(n * d, h)}

    def experts():
        e, f, fs = cfg.experts_here, cfg.expert_width, cfg.shared_width
        return {"router_w": normal(h, cfg.num_experts),
                "w_gate": normal(e, h, f), "w_up": normal(e, h, f),
                "w_down": normal(e, f, h),
                "shared_gate": normal(h, fs), "shared_up": normal(h, fs),
                "shared_down": normal(fs, h), "shared_scale_w": normal(h)}

    p = {"embed": normal(cfg.vocab_size, h), "layers": [],
         "final_norm_w": zeros(h), "head_w": normal(h, cfg.vocab_size)}
    for layer in range(cfg.num_layers):
        mixer = attention() if cfg.mixer(layer) == FULL else delta_net()
        p["layers"].append({"ln1_w": zeros(h), "ln2_w": zeros(h), **mixer,
                            **experts()})
    return p


def param_specs(cfg):
    """PartitionSpecs over ("model",): the attention's query and output
    projections split their heads, the delta rule's output projection its
    rows, the embedding its rows and the head its columns; the fused
    ``[q | k | v | z]`` projection (four widths side by side), the key and
    value projections (2 heads), everything small, the experts and the
    router are replicated."""
    col, row = P(None, MODEL_AXIS), P(MODEL_AXIS, None)
    split = {"q_w": col, "o_w": row, "out_w": row}
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return {"embed": row,
            "layers": [{name: split.get(name, P()) for name in lp}
                       for lp in shapes["layers"]],
            "final_norm_w": P(), "head_w": col}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Named scopes as models/kimi_linear.py and models/laguna.py (embed,
# attention, attention_core, rope, attn_gate, short_conv, ffn, layer_norm,
# loss, moe_router, moe_dispatch, moe_experts, moe_shared) plus gdn_core and
# gdn_gate: chipbench's per-layer metrics key on them.
@jax.named_scope("attention")
def _gated_delta_net(lp, x, cfg, mesh=None):
    b, s, _ = x.shape
    dt = x.dtype
    nk, nv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    kw, vw = nk * dk, nv * dv
    # the weight's columns are cut, not the product's: [q | k | v] goes into
    # the convolution as it is and z into the gate, rows-major both
    qkv_w, z_w = jnp.split(lp["qkvz_w"].astype(dt), [2 * kw + vw], axis=-1)
    ba = jnp.dot(x, lp["ba_w"].astype(dt),
                 preferred_element_type=jnp.float32)           # [B, S, 2 nv]
    z = x @ z_w
    qkv = x @ qkv_w
    with mesh_scope(mesh):
        with jax.named_scope("short_conv"):
            q, k, v = short_conv_norm(
                qkv, lp["conv"], dk,
                ((kw, dk ** -0.5), (kw, 1.0), (vw, None)))
        with jax.named_scope("gdn_gate"):
            beta = jax.nn.sigmoid(ba[..., :nv])
            g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[..., nv:]
                                                        + lp["dt_bias"])
        with jax.named_scope("gdn_core"):
            o = kda.kda_chunked(q.reshape(b, s, nk, dk),
                                k.reshape(b, s, nk, dk),
                                v.reshape(b, s, nv, dv), g, beta)
        with jax.named_scope("gdn_gate"):
            o = gated_head_norm(o.reshape(b, s, -1), z, lp["o_norm_g"],
                                cfg.rms_eps, "silu")
    return o @ lp["out_w"].astype(dt)


@jax.named_scope("attention")
def _gated_attention(lp, x, cfg, angles, mesh=None):
    b, s, _ = x.shape
    dt = x.dtype
    n, kv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    q, gate = jnp.split((x @ lp["q_w"].astype(dt)).reshape(b, s, n, 2 * d),
                        2, axis=-1)
    k, v = ((x @ lp[f"{name}_w"].astype(dt)).reshape(b, s, kv, d)
            for name in "kv")
    q = blocks.rms_normalize(q, 1.0 + lp["q_norm_w"], cfg.rms_eps)
    k = blocks.rms_normalize(k, 1.0 + lp["k_norm_w"], cfg.rms_eps)
    q, k = blocks.apply_rope(q, *angles), blocks.apply_rope(k, *angles)
    ctx = blocks.causal_attention(q, k, v, mesh=mesh)
    with jax.named_scope("attn_gate"):
        ctx = (ctx.astype(jnp.float32)
               * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
    return ctx.reshape(b, s, -1) @ lp["o_w"].astype(dt)


def _block(lp, x, cfg, layer, angles, mesh=None):
    """One layer: (the stream after the mixer, after the experts, the expert
    layer's aux terms). A Gated DeltaNet mixer is recomputed in the backward
    pass from its input (the module docstring says why); the experts
    recompute their own part; nothing else is."""
    kind = cfg.mixer(layer)

    def mix(lp, x):
        normed = blocks.rms_norm(x, 1.0 + lp["ln1_w"], cfg.rms_eps)
        return x + (_gated_attention(lp, normed, cfg, angles, mesh)
                    if kind == FULL
                    else _gated_delta_net(lp, normed, cfg, mesh))

    h = (jax.checkpoint(mix) if kind == LINEAR else mix)(lp, x)
    with jax.named_scope("ffn"):
        m, aux = moe.dropless_moe_ffn(
            lp, blocks.rms_norm(h, 1.0 + lp["ln2_w"], cfg.rms_eps),
            cfg.experts_per_token, mesh=mesh, scoring=cfg.scoring,
            held=cfg.experts_held)
    return h, h + m, aux


def _add_aux(cfg, ce, aux):
    """The loss: the cross-entropy plus ``balance_weight`` times the
    load-balancing term (the mean over the layers of ``moe.balance_loss``
    over all the router's outputs)."""
    return ce + cfg.balance_weight * jnp.mean(aux["balance"])


# everything around the block is the skeleton's (``lm_trainer.Decoder``); no
# router has a selection bias, so its step moves nothing outside the gradient
DECODER = lm_trainer.Decoder(
    init_params=init_params, param_specs=param_specs, block=_block,
    rotary=lambda cfg, positions: blocks.rope_angles(
        positions, int(cfg.head_dim * cfg.rotary_factor), cfg.rope_theta),
    final_gain=lambda params: 1.0 + params["final_norm_w"],
    add_aux=_add_aux)
forward = DECODER.forward
stages = DECODER.stages
lm_loss = DECODER.lm_loss
routing_stats = DECODER.routing_stats
make_train_step = DECODER.make_train_step
synthetic_batch = lm_trainer.synthetic_batch
