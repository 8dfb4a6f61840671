"""Nemotron-H decoders (``model_type`` ``nemotron_h``): a stack of layers
that are each **a mixer alone**, of three kinds that
``hybrid_override_pattern`` names a layer: ``M`` a Mamba-2 state-space
mixer, ``*`` attention, ``E`` a LatentMoE feed-forward; and a
multi-token-prediction module behind the last layer (the sizes here are
NVIDIA's ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``, 120B-A12B, 88 layers).

Every layer is ``x <- x + Mixer(RMSNorm(x))``: one pre-norm and one residual,
no feed-forward behind attention. Then a final RMSNorm and an untied head.

- **M, Mamba-2** (Dao & Gu 2024, arXiv:2405.21060): one input product cut
  into ``z | x | B | C | dt`` (``mamba_heads x mamba_head_dim`` channels of
  ``z`` and of ``x``, ``mamba_groups x state_size`` of ``B`` and of ``C``, a
  step a head); a causal depthwise convolution of ``conv_kernel`` taps with
  a bias over ``x | B | C``, then SiLU (scope ``short_conv``); ``dt =
  softplus(dt + dt_bias)``, the log-decay ``-exp(A_log) dt`` (scope
  ``ssd_gate``, float32); the state-space scan with the skip ``D x``
  (``ops/ssd.ssd_chunked`` in chunks of ``chunk_size``, scope ``ssd_core``:
  a state of ``[mamba_head_dim, state_size]`` a head, ``B`` and ``C`` shared
  by the heads of a group; the registry's kernel ``ssd``: on one chip the
  Mosaic kernels of ``ops/pallas/ssd.py``, which read ``x``, ``B`` and ``C``
  as the convolution leaves them, elsewhere the ``jax.numpy`` body); ``RMSNorm(y * silu(z))`` with the mean square
  taken over each group's channels (``ssd_gate`` again: the gate before the
  norm, Mamba-2's ``norm_before_gate`` false); the output product.
- **\\*, attention**: ``num_heads`` queries of ``head_dim`` over
  ``num_kv_heads`` keys and values, causal, no bias, **no positions** (the
  family's attention carries none), through ``blocks.causal_attention``.
- **E, LatentMoE**: a float32 sigmoid router over all ``num_experts`` behind
  a selection bias, ``experts_per_token`` a token, renormalised and scaled
  by ``routed_scale``; the routed experts are plain ``W2 act(W1 l)`` with
  ``act`` = ``expert_act`` (``relu2``: the square of the ReLU) on a latent
  ``l = x W_down`` of ``latent_size``, their weighted sum back through
  ``W_up``; one shared expert of the same form on the hidden, every token
  (``parallel/moe.dropless_moe_ffn``, which reads all of that from the
  parameters it is given and from ``activation``).
- **MTP**: ``mtp_pattern`` names the layers of the one module
  (``lm_trainer.Decoder._predict_further``); its layers are numbered on
  from the main ones, so ``kind`` finds them.

**The head counts are the chip's.** A configuration may give a share of the
mixers' heads (the benchmark's cell: a quarter, 32 of 128 Mamba heads with 2
of the 8 ``B``/``C`` groups, whole norm groups, and 8 of 32 query heads with
the key/value head they read) as it gives a share of the experts
(``experts_held``); the output products ``out_w`` and ``o_w`` then hold the
rows of the heads here, and what they give is this chip's part of the sum,
as the held experts' is. Nothing stands in for the absent heads.

Every M and ``*`` mixer is recomputed in the backward pass from its input
(``blocks.recomputed``, which keeps what the flash call and, where the
registry selects the scan's Mosaic kernels, ``ssd_fwd`` hand their backward
kernels: no forward kernel runs twice); the experts recompute their own
part. Built like ``models/deepseek_v3.py``: float32
master parameters, ``cfg.dtype`` (bfloat16) activations and matmul operands,
one jitted step (``models/lm_trainer.py``). No router, attention or trainer
code of its own.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import blocks, lm_trainer
from paddle_tpu.ops import ssd
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import MODEL_AXIS

__all__ = ["NemotronHConfig", "nemotron_3_super_120b_a12b",
           "nemotron_h_tiny", "init_params", "param_specs", "forward",
           "stages", "lm_loss", "routing_stats", "make_train_step",
           "synthetic_batch"]

#: the 88 layers as released
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)  # hashable: used as a jit-static arg
class NemotronHConfig:
    vocab_size: int = 131072
    hidden: int = 4096
    pattern: str = PUBLISHED_PATTERN     # a letter a layer: M, * or E
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_groups: int = 8                # B and C, and the gated norm's
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    num_experts: int = 512
    experts_per_token: int = 22
    latent_size: int = 1024
    expert_width: int = 2688
    shared_width: int = 5376
    expert_act: str = "relu2"
    routed_scale: float = 5.0
    bias_rate: float = 0.001             # the selection bias's step
    experts_held: tuple = None           # (first, n); None: all of them
    mtp_pattern: str = "*E"              # "" for no module
    mtp_weight: float = 0.3              # lambda on the second loss
    rms_eps: float = 1e-5
    dtype: object = jnp.bfloat16         # activation/compute dtype

    def __post_init__(self):
        if set(self.pattern + self.mtp_pattern) - set("M*E"):
            raise ValueError("a layer is M, * or E")
        if self.mamba_heads % self.mamba_groups \
                or self.num_heads % self.num_kv_heads:
            raise ValueError("the groups divide the heads")

    @property
    def num_layers(self):
        return len(self.pattern)

    def kind(self, layer):
        """M, * or E; the module's layers follow the main ones."""
        return (self.pattern + self.mtp_pattern)[layer]

    @property
    def scoring(self):
        return moe.Scoring("sigmoid", renormalize=True,
                           scale=self.routed_scale)

    @property
    def experts_here(self):
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def inner(self):
        """A Mamba mixer's channels, the heads held times their size."""
        return self.mamba_heads * self.mamba_head_dim


def nemotron_3_super_120b_a12b(**kw):
    """The published sizes: 120 B parameters, 12 B a token."""
    return NemotronHConfig(**kw)


def nemotron_h_tiny(**kw):
    """Small config for tests / dry runs: every kind of layer and the
    module, the widths in the published proportions where that is cheap."""
    for k, v in dict(vocab_size=512, hidden=64, pattern="EMEM*", mamba_heads=8,
                     mamba_head_dim=8, mamba_groups=2, state_size=16,
                     chunk_size=16, num_heads=4, num_kv_heads=2, head_dim=16,
                     num_experts=16, experts_per_token=4, latent_size=32,
                     expert_width=48, shared_width=96).items():
        kw.setdefault(k, v)
    return NemotronHConfig(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_params(rng, cfg):
    """fp32 master params as a nested dict pytree. Matrices N(0, 0.02),
    gains 1, the selection bias 0; a Mamba mixer as Mamba-2 starts it:
    ``dt_bias`` the inverse softplus of a step drawn log-uniformly in
    [0.001, 0.1] and floored at 1e-4, ``A_log`` the log of U(1, 16), ``D``
    1, the convolution's taps and bias U(-1/2, 1/2) (what PyTorch gives a
    ``Conv1d`` of 4 taps a channel). ``in_w``'s columns are ``z | x | B |
    C | dt`` in that order, the released ``in_proj``'s."""
    h = cfg.hidden
    kinds = cfg.pattern + cfg.mtp_pattern
    keys = iter(jax.random.split(rng, 4 + 10 * len(kinds)))

    def normal(*shape):
        return (0.02 * jax.random.normal(next(keys), shape)) \
            .astype(jnp.float32)

    def uniform(lo, hi, *shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def mamba():
        n, conv = cfg.mamba_heads, cfg.inner \
            + 2 * cfg.mamba_groups * cfg.state_size
        step = jnp.maximum(jnp.exp(uniform(jnp.log(0.001), jnp.log(0.1), n)),
                           1e-4)
        return {"in_w": normal(h, cfg.inner + conv + n),
                "conv_w": uniform(-0.5, 0.5, cfg.conv_kernel, conv),
                "conv_b": uniform(-0.5, 0.5, conv),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(uniform(1.0, 16.0, n)),
                "D": ones(n), "norm_g": ones(cfg.inner),
                "out_w": normal(cfg.inner, h)}

    def attention():
        q, kv = (n * cfg.head_dim for n in (cfg.num_heads, cfg.num_kv_heads))
        return {"q_w": normal(h, q), "k_w": normal(h, kv),
                "v_w": normal(h, kv), "o_w": normal(q, h)}

    def experts():
        e, lat, f = cfg.experts_here, cfg.latent_size, cfg.expert_width
        return {"router_w": normal(h, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "latent_down": normal(h, lat), "latent_up": normal(lat, h),
                "w_up": normal(e, lat, f), "w_down": normal(e, f, lat),
                "shared_up": normal(h, cfg.shared_width),
                "shared_down": normal(cfg.shared_width, h)}

    mixers = {"M": mamba, "*": attention, "E": experts}
    layers = [{"ln_g": ones(h), **mixers[kind]()} for kind in kinds]
    p = {"embed": normal(cfg.vocab_size, h),
         "layers": layers[:cfg.num_layers],
         "final_norm_g": ones(h), "head_w": normal(h, cfg.vocab_size)}
    if cfg.mtp_pattern:
        p["mtp"] = {"hnorm_g": ones(h), "enorm_g": ones(h),
                    "eh_w": normal(2 * h, h),
                    "layers": layers[cfg.num_layers:],
                    "final_norm_g": ones(h)}
    return p


def param_specs(cfg):
    """PartitionSpecs over ("model",): the attention projections split
    their heads, the embedding its rows and the head its columns; the Mamba
    mixers (whose one input product holds five column ranges), the experts,
    the router, the latent and everything small are replicated."""
    col, row = P(None, MODEL_AXIS), P(MODEL_AXIS, None)
    split = {"q_w": col, "o_w": row}
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))

    def of(layers):
        return [{name: split.get(name, P()) for name in lp} for lp in layers]

    specs = {"embed": row, "layers": of(shapes["layers"]),
             "final_norm_g": P(), "head_w": col}
    if "mtp" in shapes:
        specs["mtp"] = {**{name: P() for name in shapes["mtp"]},
                        "layers": of(shapes["mtp"]["layers"])}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Named scopes: embed, attention, attention_core, ffn, layer_norm, loss,
# moe_router, moe_dispatch, moe_experts, moe_shared, moe_latent as the other
# decoders', plus ssd_core, short_conv, ssd_gate (a Mamba mixer's parts,
# inside ``attention``) and mtp_merge (lm_trainer): chipbench's per-layer
# metrics key on them.
@jax.named_scope("short_conv")
def _causal_conv(x, taps, bias):
    """SiLU of the causal depthwise convolution of x [B, S, C] with ``taps``
    [K, C] and ``bias`` [C]: position t sees t - K + 1 to t, the last tap
    on t itself. Float32 sums, ``x.dtype`` out."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    y = bias.astype(jnp.float32) + sum(
        padded[:, i:i + s] * taps[i].astype(jnp.float32) for i in range(k))
    return jax.nn.silu(y).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _column_ranges(x, sizes):
    """``jnp.split`` of x [.., C] into column ranges of ``sizes``, whose
    gradient is the ranges' gradients side by side: one concatenation, where
    autodiff's is a padded copy a range and their sum. The scan's kernels
    read the ranges where they lie and write one array's gradient
    (``ops/pallas/ssd.py``); with this rule the compiler sees a
    concatenation of that array's own ranges and folds it away."""
    at = [sum(sizes[:i + 1]) for i in range(len(sizes) - 1)]
    return tuple(jnp.split(x, at, axis=-1))


_column_ranges.defvjp(
    lambda x, sizes: (_column_ranges(x, sizes), None),
    lambda sizes, _, parts: (jnp.concatenate(parts, axis=-1),))


@jax.named_scope("attention")
def _mamba(lp, x, cfg):
    b, s, _ = x.shape
    dt = x.dtype
    n, inner = cfg.mamba_heads, cfg.inner
    bc = cfg.mamba_groups * cfg.state_size
    z, xbc, step = jnp.split(x @ lp["in_w"].astype(dt),
                             [inner, 2 * inner + 2 * bc], axis=-1)
    xs, B, C = _column_ranges(_causal_conv(xbc, lp["conv_w"], lp["conv_b"]),
                              (inner, bc, bc))
    with jax.named_scope("ssd_gate"):
        step = jax.nn.softplus(step.astype(jnp.float32) + lp["dt_bias"])
        decay = -jnp.exp(lp["A_log"]) * step             # its log, <= 0
    with jax.named_scope("ssd_core"):
        y = ssd.ssd_chunked(
            xs.reshape(b, s, n, cfg.mamba_head_dim), step, decay,
            *(t.reshape(b, s, cfg.mamba_groups, cfg.state_size)
              for t in (B, C)), lp["D"], cfg.chunk_size)
    with jax.named_scope("ssd_gate"):
        gated = y.reshape(b, s, inner).astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        # the norm a group on the group's own columns of the rows-major
        # array: a [.., groups, channels] view of it is a relayout on the
        # chip wherever the scan's kernels, which write rows, produced y
        y = jnp.concatenate(
            [blocks.rms_normalize(part, gain, cfg.rms_eps)
             for part, gain in zip(
                 jnp.split(gated, cfg.mamba_groups, axis=-1),
                 jnp.split(lp["norm_g"], cfg.mamba_groups))],
            axis=-1).astype(dt)
    return y @ lp["out_w"].astype(dt)


@jax.named_scope("attention")
def _attention(lp, x, cfg, mesh=None):
    b, s, _ = x.shape
    dt = x.dtype
    q, k, v = ((x @ lp[name].astype(dt)).reshape(b, s, -1, cfg.head_dim)
               for name in ("q_w", "k_w", "v_w"))
    ctx = blocks.causal_attention(q, k, v, mesh=mesh)
    return ctx.reshape(b, s, -1) @ lp["o_w"].astype(dt)


def _block(lp, x, cfg, layer, rotary, mesh=None):
    """One layer, a mixer alone: (the stream after it, an expert layer's aux
    terms or None). ``rotary`` is None: no mixer takes positions. The M and
    ``*`` mixers are recomputed in the backward pass from their input, but
    for what their forward kernels (the flash call, the scan's) hand the
    backward ones; the experts recompute their own part."""
    kind = cfg.kind(layer)
    if kind == "E":
        with jax.named_scope("ffn"):
            m, aux = moe.dropless_moe_ffn(
                lp, blocks.rms_norm(x, lp["ln_g"], cfg.rms_eps),
                cfg.experts_per_token, mesh=mesh, scoring=cfg.scoring,
                held=cfg.experts_held, activation=cfg.expert_act)
        return x + m, aux

    def mix(lp, x):
        normed = blocks.rms_norm(x, lp["ln_g"], cfg.rms_eps)
        return x + (_mamba(lp, normed, cfg) if kind == "M"
                    else _attention(lp, normed, cfg, mesh))

    return blocks.recomputed(mix)(lp, x), None


# everything around the block is the skeleton's (``lm_trainer.Decoder``)
DECODER = lm_trainer.Decoder(init_params=init_params,
                             param_specs=param_specs, block=_block,
                             rotary=lambda cfg, positions: None)
forward = DECODER.forward
stages = DECODER.stages
lm_loss = DECODER.lm_loss
routing_stats = DECODER.routing_stats
make_train_step = DECODER.make_train_step
synthetic_batch = lm_trainer.synthetic_batch
