"""OLMoE: a decoder-only language model with sparse experts in every block
(Muennighoff et al. 2024, arXiv:2409.02060; the released
``allenai/OLMoE-1B-7B-0125-Instruct`` config and ``modeling_olmoe.py``).

Pre-norm blocks, ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``:

- attention: no biases; RMSNorm of the whole query and key projections
  before they are split into heads; rotary positions (rotate-half); causal
  softmax, the flash kernels past 1024 positions (``blocks.py``);
- experts: a float32 softmax router, the ``experts_per_token`` largest
  probabilities not renormalised (``moe.Scoring``'s default; the layer also
  knows a sigmoid with a bias, renormalised and scaled), SiLU-gated experts,
  every assignment computed whatever the load, every expert held here
  (``parallel/moe.dropless_moe_ffn``, which can also hold a range of them);
- a final RMSNorm and an untied head on every position; the loss is the
  mean next-token cross-entropy plus the load-balancing loss and the router
  z-loss, each the mean over the layers, times their weights.

Built like ``models/bert.py``: float32 master parameters, ``cfg.dtype``
(bfloat16) activations and matmul operands, one jitted step = forward +
backward + update, the mesh's ``data`` axis splits the batch and its
``model`` axis the attention projections and the vocabulary; the experts are
replicated (sharding them is ROADMAP B, "experts over a mesh").
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import blocks, lm_trainer
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import MODEL_AXIS

__all__ = ["OlmoeConfig", "olmoe_1b_7b", "olmoe_tiny", "init_params",
           "param_specs", "forward", "lm_loss", "routing_stats",
           "make_train_step", "synthetic_batch"]


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class OlmoeConfig:
    vocab_size: int = 50304
    hidden: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    head_dim: int = 128
    expert_width: int = 1024
    num_experts: int = 64
    experts_per_token: int = 8
    max_seq: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    balance_weight: float = 0.01     # load-balancing loss (the paper's)
    z_weight: float = 0.001          # router z-loss (the paper's)
    dtype: object = jnp.bfloat16     # activation/compute dtype


def olmoe_1b_7b(**kw):
    """The published sizes: 1.3 B parameters a token, 6.9 B in all."""
    return OlmoeConfig(**kw)


def olmoe_tiny(**kw):
    """Small config for tests / dry runs."""
    for k, v in dict(vocab_size=512, hidden=64, num_layers=2, num_heads=4,
                     head_dim=16, expert_width=32, num_experts=8,
                     experts_per_token=2, max_seq=64).items():
        kw.setdefault(k, v)
    return OlmoeConfig(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree: matrices N(0, 0.02),
    gains 1."""
    h, f, e = cfg.hidden, cfg.expert_width, cfg.num_experts
    qkv = cfg.num_heads * cfg.head_dim
    keys = iter(jax.random.split(rng, 2 + 8 * cfg.num_layers))

    def normal(shape):
        return (0.02 * jax.random.normal(next(keys), shape)) \
            .astype(jnp.float32)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    p = {"embed": normal((cfg.vocab_size, h)), "layers": [],
         "final_norm_g": ones(h), "head_w": normal((h, cfg.vocab_size))}
    for _ in range(cfg.num_layers):
        p["layers"].append({
            "ln1_g": ones(h),
            "q_w": normal((h, qkv)), "k_w": normal((h, qkv)),
            "v_w": normal((h, qkv)), "o_w": normal((qkv, h)),
            "q_norm_g": ones(qkv), "k_norm_g": ones(qkv),
            "ln2_g": ones(h),
            "router_w": normal((h, e)),
            "w_gate": normal((e, h, f)), "w_up": normal((e, h, f)),
            "w_down": normal((e, f, h)),
        })
    return p


def param_specs(cfg):
    """PartitionSpecs over ("model",): the attention projections split
    their heads' dim, the embedding its rows and the head its columns; the
    experts, the router and the gains are replicated."""
    layer = {
        "ln1_g": P(), "q_w": P(None, MODEL_AXIS), "k_w": P(None, MODEL_AXIS),
        "v_w": P(None, MODEL_AXIS), "o_w": P(MODEL_AXIS, None),
        "q_norm_g": P(MODEL_AXIS), "k_norm_g": P(MODEL_AXIS), "ln2_g": P(),
        "router_w": P(), "w_gate": P(), "w_up": P(), "w_down": P(),
    }
    return {"embed": P(MODEL_AXIS, None),
            "layers": [dict(layer) for _ in range(cfg.num_layers)],
            "final_norm_g": P(), "head_w": P(None, MODEL_AXIS)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Named scopes as models/bert.py (embed, attention, attention_core, ffn,
# layer_norm, loss; optimizer is in optimizer.py) plus rope, moe_router,
# moe_dispatch, moe_experts: chipbench's per-layer metrics key on them.
@jax.named_scope("attention")
def _attention(lp, x, rope, cfg, mesh=None):
    b, s, _ = x.shape
    dt = x.dtype
    q = blocks.rms_norm(x @ lp["q_w"].astype(dt), lp["q_norm_g"],
                        cfg.rms_eps)
    k = blocks.rms_norm(x @ lp["k_w"].astype(dt), lp["k_norm_g"],
                        cfg.rms_eps)
    v = x @ lp["v_w"].astype(dt)

    def heads(t):
        return t.reshape(b, s, cfg.num_heads, cfg.head_dim)

    q, k = (blocks.apply_rope(heads(t), *rope) for t in (q, k))
    ctx = blocks.causal_attention(q, k, heads(v), mesh=mesh)
    return ctx.reshape(b, s, -1) @ lp["o_w"].astype(dt)


def _block(lp, x, cfg, layer, rope, mesh=None):
    h = x + _attention(lp, blocks.rms_norm(x, lp["ln1_g"], cfg.rms_eps),
                       rope, cfg, mesh)
    normed = blocks.rms_norm(h, lp["ln2_g"], cfg.rms_eps)
    with jax.named_scope("ffn"):
        m, aux = moe.dropless_moe_ffn(lp, normed, cfg.experts_per_token,
                                      mesh=mesh)
    return h, h + m, aux


def _add_aux(cfg, ce, aux):
    """The loss: the cross-entropy plus ``balance_weight`` times the
    load-balancing loss and ``z_weight`` times the router z-loss (each the
    mean over the layers)."""
    return (ce + cfg.balance_weight * jnp.mean(aux["balance"])
            + cfg.z_weight * jnp.mean(aux["z"]))


# everything around the block is the skeleton's (``lm_trainer.Decoder``)
DECODER = lm_trainer.Decoder(
    init_params=init_params, param_specs=param_specs, block=_block,
    rotary=lambda cfg, positions: blocks.rope_angles(
        positions, cfg.head_dim, cfg.rope_theta),
    add_aux=_add_aux)
forward = DECODER.forward
lm_loss = DECODER.lm_loss
routing_stats = DECODER.routing_stats


def make_train_step(cfg, optimizer, mesh=None):
    """(init_fn, step_fn) of ``lm_trainer.make_train_step`` for this
    model: step(params, opt_state, batch) -> (loss, params, opt_state). No
    router has a selection bias to move, and the step hands no counts out."""
    return lm_trainer.make_train_step(cfg, optimizer, mesh, init_params,
                                      param_specs, lm_loss)


def synthetic_batch(cfg, batch_size, seq_len=None, seed=0):
    """``lm_trainer.synthetic_batch``; ``cfg.max_seq`` positions by default."""
    return lm_trainer.synthetic_batch(cfg, batch_size,
                                      seq_len or cfg.max_seq, seed)
