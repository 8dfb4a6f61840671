"""Pieces of a decoder block that more than one model can use: RMSNorm,
rotary positions, causal attention and the gated feed-forward. (A delta-rule
mixer's short convolution and L2 norm are ``ops/pallas/delta_glue.py``'s
since the two became one kernel.)

``models/olmoe.py``, ``models/kimi_linear.py``, ``models/laguna.py``,
``models/qwen3_next.py`` and ``models/lfm2.py`` are built from them, around
the one decoder skeleton of ``models/lm_trainer.py``. ``models/bert.py`` and
``models/transformer.py`` carry their own layer norm and attention and are
not moved here yet (ROADMAP C, "one trainer shape": their cells repeat to
0.004%, so a change to their HLO is a PR judged on its own). Every piece
enters the named scope a profile of the step is read by (``layer_norm``,
``rope``, ``attention_core`` and, inside it, ``attention_window`` where a
call has a window; the callers enter ``attention`` and ``ffn``).
"""

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.ops.pallas.flash_attention import KEPT as _FLASH_KEPT
from paddle_tpu.ops.pallas.kda import KEPT as _KDA_KEPT
from paddle_tpu.ops.pallas.ssd import KEPT as _SSD_KEPT
from paddle_tpu.ops.pallas.registry import mesh_scope, selected_body

__all__ = ["rms_norm", "rms_normalize", "yarn_inv_freq", "rope_angles",
           "apply_rope", "apply_rope_tail", "attention_body",
           "causal_attention", "latent_attention", "gated_ffn", "recomputed"]

#: from this many positions on, ``auto`` takes the flash kernels where the
#: Pallas body runs (one chip). Measured on a v5e at equal tokens a step
#: (PERF.md section 6, PR 29): the whole BERT-base step is 17% shorter with
#: the flash kernels at 512 positions (the cell ``mlm_s512``), 32% at 1024,
#: and at 2048 the dense scores no longer fit; ``mlm_s4096`` and
#: ``lm_s4096`` lie beyond. Below: flash still wins at 256 by 2.5% and
#: loses at 128 by 15%, but at 384 the step with the flash calls hung at
#: batch 88 and 96, unexplained, so the line stays at the lowest length
#: that was seen both to win and to run clean. ``causal_attention`` (16
#: heads of 128) is 3.4 times faster with the kernels at 512 already and
#: was not measured below, so it reads the same constant.
FLASH_FROM = 512
#: where the registry would hand "flash" its reference body (the CPU; a
#: mesh that splits more than the batch, or a batch its data axis does not
#: divide: GSPMD cannot partition a Mosaic call, and only over the batch can
#: the registry run one a shard at a time), ``auto`` keeps the callers'
#: inline dense code up to this many positions: the reference holds the
#: scores in float32 beside the head transposes, larger and slower than the
#: inline code (``mlm_s512_dp4`` before it ran the kernels a shard at a
#: time: 14.84 GiB a device against 12.61, compiler, PR 29). Beyond it the
#: reference, as ever: what ``lm_s4096`` would run on a ``model`` mesh.
REFERENCE_FLASH_BEYOND = 1024


def attention_body(positions, mesh=None, batch=None):
    """"flash" or "dense": what ``auto`` runs at ``positions`` keys a query
    under ``mesh`` (the sequence's length, or the window where that is
    shorter) on ``batch`` rows. It asks the registry which body a flash call
    would run here, so only what the code observes takes part: the length,
    the mesh and whether it splits only the batch, the platform."""
    if positions > REFERENCE_FLASH_BEYOND:
        return "flash"
    with mesh_scope(mesh):
        kernel_runs = selected_body("flash_attention", batch) != "reference"
    return "flash" if kernel_runs and positions >= FLASH_FROM else "dense"


def rms_normalize(x, gain, eps=1e-5):
    """``x / sqrt(mean(x^2) + eps) * gain`` over the last axis: statistics
    in float32, the result in ``x.dtype``. Under no scope of its own: for a
    norm that belongs to its caller's (a latent's, a head's)."""
    x32 = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (x32 * scale * gain.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("layer_norm")
def rms_norm(x, gain, eps=1e-5):
    """``rms_normalize`` under the scope ``layer_norm``: a block's norms."""
    return rms_normalize(x, gain, eps)


def yarn_inv_freq(rot, theta, factor, original_positions, beta_fast,
                  beta_slow):
    """YaRN's inverse frequencies [rot / 2] (Peng et al. 2023,
    arXiv:2309.00071, "NTK-by-parts"): with ``pos_i = theta^(2i / rot)``, the
    plain law ``1 / pos_i`` on the channels that turn more than ``beta_fast``
    times within ``original_positions``, the interpolated one ``1 / (factor
    pos_i)`` on those that turn fewer than ``beta_slow`` times, and a linear
    ramp between the two channel numbers (the correction range: floor and
    ceiling, clipped to the channels there are)."""
    pos = theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)

    def channel_of(turns):
        return rot * math.log(original_positions / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(channel_of(beta_fast)), 0)
    high = min(math.ceil(channel_of(beta_slow)), rot // 2 - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def rope_angles(positions, head_dim, theta=10000.0, inv_freq=None,
                factor=1.0):
    """(cos, sin), each [positions, head_dim / 2] float32, of the angles
    ``p * inv_freq_i``. ``head_dim`` is the rotated width (a head's, or the
    part of it ``apply_rope`` is to turn); the law is the plain one,
    ``theta^(-2i / head_dim)``, or the ``inv_freq`` [head_dim / 2] given
    (``yarn_inv_freq``). ``factor`` multiplies cos and sin both (YaRN's
    attention factor: it scales the scores by its square)."""
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim)
    angles = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv_freq
    if factor == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


@jax.named_scope("rope")
def apply_rope(x, cos, sin):
    """Rotary positions in the rotate-half convention on the FIRST ``rot``
    channels of x [B, S, N, D], ``rot`` twice the width of ``cos``: the pair
    (x_i, x_{i + rot/2}) is turned by the angle of position s and frequency
    i; the channels from ``rot`` on pass through. One entry of the one
    rotary pass (``_turn``; ``apply_rope_tail`` is the other): float32
    inside, ``x.dtype`` out, and the gradient is the same pass on the
    cotangent with the sine's sign turned; the angles take none."""
    return _turn(x, cos, sin, False, False)


@jax.named_scope("rope")
def apply_rope_tail(x, cos, sin, interleaved):
    """Rotary positions on the LAST ``rot`` channels of x [B, S, N, D],
    ``rot`` twice the width of ``cos``: the decoupled channels of a latent
    attention head, behind the ones that carry no position. ``interleaved``
    names the pairing: the neighbours (2i, 2i + 1) of the tail, turned where
    they lie (the DeepSeek-V3 checkpoints' ``rope_interleave``), or the
    halves (i, i + rot/2) as ``apply_rope`` pairs them; either by the angle
    of position s and frequency i. In place means a score needs no second
    operand laid out to match: queries and keys may be rotated apart. The
    other entry of the one rotary pass (``_turn``): float32 inside,
    ``x.dtype`` out, the gradient the same pass with the sine's sign turned;
    the angles take none."""
    return _turn(x, cos, sin, True, interleaved)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _turn(x, cos, sin, tail, interleaved):
    """The rotary pass both entries share: the span ``[0, rot)`` of a head,
    or ``[D - rot, D)`` with ``tail``, turned in halves or in interleaved
    pairs, as one product and one elementwise pass over whole heads, dense
    in the lanes: ``y = x cos' + (x M) sin'`` with M a constant D x D matrix
    of 0 and +-1 that is zero outside the span and cos' = 1, sin' = 0 there.
    No channel is sliced, gathered, shifted or concatenated. The transpose
    of that map is itself with -sin (M is antisymmetric and the tables are
    equal on the two channels of a pair, whatever scales them), which is the
    gradient rule: nothing of x's size is kept, and the backward pass is not
    the transposes of slices and pads in float32."""
    d = x.shape[-1]
    half = cos.shape[-1]
    rot = 2 * half
    start = d - rot if tail else 0
    # the channels ``first[i]`` and ``second[i]`` are pair i
    first = start + (np.arange(0, rot, 2) if interleaved else np.arange(half))
    second = first + (1 if interleaved else half)
    # a channel's partner with its sign, (-x_second, x_first): the head times
    # M on the MXU (exact in x.dtype: a sum of one term)
    swap = np.zeros((d, d), np.float32)
    swap[second, first], swap[first, second] = -1.0, 1.0

    def table(t, outside):
        """[S, D]: a pair's entry on both its channels, ``outside`` on the
        channels that pass through."""
        t = jnp.repeat(t, 2, axis=-1) if interleaved \
            else jnp.concatenate([t, t], axis=-1)
        return t if rot == d else jnp.pad(
            t, ((0, 0), (start, d - rot - start)), constant_values=outside)

    partner = jnp.dot(x, jnp.asarray(swap, x.dtype),
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    turned = x.astype(jnp.float32) * table(cos, 1.0)[None, :, None, :] \
        + partner * table(sin, 0.0)[None, :, None, :]
    return turned.astype(x.dtype)


def _turn_fwd(x, cos, sin, tail, interleaved):
    return _turn(x, cos, sin, tail, interleaved), (cos, sin)


def _turn_bwd(tail, interleaved, angles, dy):
    cos, sin = angles
    return (_turn(dy, cos, -sin, tail, interleaved), jnp.zeros_like(cos),
            jnp.zeros_like(sin))


_turn.defvjp(_turn_fwd, _turn_bwd)


def causal_attention(q, k, v, impl="auto", mesh=None, window=None):
    """Softmax of ``q k^T / sqrt(D)`` over the keys up to each query's own,
    times v; with ``window`` over the ``window`` keys that end at the
    query's own. q is [B, S, N, D], k [B, S, Nkv, D], v [B, S, Nkv, Dv] and
    the result [B, S, N, Dv]: the value heads may have a size of their own
    (latent attention: 192 for the scores, 128 for the values), D need be no
    multiple of the 128-lane grain, and N may be a multiple of Nkv: query
    head i then reads key/value head ``i // (N / Nkv)``. ``impl`` is "dense"
    (XLA, scores in float32), "flash" (the Pallas kernels through the
    registry, which runs them a shard of the batch at a time under a mesh
    that splits only the batch, and hands out the dense reference on the
    CPU and under a mesh that splits more) or "auto": ``attention_body``'s
    choice at the keys a query sees, ``min(S, window)``. A windowed call's
    core is under the scope ``attention_window`` inside
    ``attention_core``."""
    b, s, n, d = q.shape
    if window is not None and window >= s:
        window = None
    if impl == "auto":
        impl = attention_body(window or s, mesh, b)
    windowed = contextlib.nullcontext() if window is None \
        else jax.named_scope("attention_window")
    with jax.named_scope("attention_core"), windowed:
        if impl == "flash":
            from paddle_tpu.ops import pallas as _pk

            def heads(t):
                return t.transpose(0, 2, 1, 3)

            with mesh_scope(mesh):
                ctx = _pk.flash_attention(heads(q), heads(k), heads(v),
                                          causal=True, window=window)
            return ctx.transpose(0, 2, 1, 3).astype(q.dtype)
        if k.shape[2] != n:
            k, v = (jnp.repeat(t, n // t.shape[2], axis=2) for t in (k, v))
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(d)
        keep = jnp.tril(jnp.ones((s, s), bool))
        if window is not None:
            keep &= ~jnp.tril(jnp.ones((s, s), bool), -window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", probs.astype(q.dtype), v)


#: the ``checkpoint_name`` of a rotary latent-attention mixer's queries
LATENT_KEPT = "latent_attention_queries"


@jax.named_scope("attention")
def latent_attention(lp, x, heads, rank, nope, eps, rotary=None, mesh=None):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, sec.
    2.1) of the normed stream x [B, S, H], as a training step computes it:
    ``heads`` queries of ``nope`` channels and the decoupled ones behind
    them from ``q_w``; ``kva_w`` gives a latent of ``rank`` and one key of
    the decoupled width that every head shares; the latent, RMS-normed
    (``kv_norm_g``, ``eps``), is expanded a head by ``kvb_w`` into ``nope``
    key channels and the value; causal softmax over ``[k_nope | shared]``
    through ``causal_attention`` (the score head wider than the value head),
    then ``o_w``. ``rotary`` is None (the decoupled channels carry no
    position: Kimi Linear's ``mla_use_nope``) or (cos, sin, interleaved) as
    ``apply_rope_tail`` takes them: the queries' decoupled channels are
    turned a head, the shared key once, before it is copied to the heads. No
    weight absorption and no cache of the latent: those are serving forms.
    The expansion is under the scope ``mla_expand``, the rotation under
    ``rope``."""
    b, s, _ = x.shape
    dt = x.dtype
    q = (x @ lp["q_w"].astype(dt)).reshape(b, s, heads, -1)
    latent, k_shared = jnp.split(x @ lp["kva_w"].astype(dt), [rank], axis=-1)
    if rotary is not None:
        # the turned queries carry a name that ``recomputed`` keeps: the
        # widest projection and its rotation are not formed twice a step
        q = checkpoint_name(apply_rope_tail(q, *rotary), LATENT_KEPT)
        k_shared = apply_rope_tail(k_shared[:, :, None, :], *rotary)[:, :, 0]
    with jax.named_scope("mla_expand"):
        kv = (rms_normalize(latent, lp["kv_norm_g"], eps)
              @ lp["kvb_w"].astype(dt)).reshape(b, s, heads, -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_shared[:, :, None, :],
                              (b, s, heads, k_shared.shape[-1]))], axis=-1)
    ctx = causal_attention(q, k, kv[..., nope:], mesh=mesh)
    return ctx.reshape(b, s, -1) @ lp["o_w"].astype(dt)


def recomputed(mixer):
    """``mixer`` under a ``jax.checkpoint`` that keeps, beside the mixer's
    inputs, what its attention kernel's forward call hands the backward one
    and nothing but that kernel can make: the names the flash calls and the
    delta rule and the state-space scan put on those residuals inside their
    ``custom_vjp`` forward rules (``flash_attention.KEPT``: o and lse;
    ``kda.KEPT``: o, the state a unit starts from and its three tiles;
    ``ssd.KEPT``: y and the state a chunk starts from). The backward pass forms the cheap
    passes around the kernel again (norms, projections, rotation,
    convolutions, gates: the kernel's operands, which the weights' gradients
    need anyway) and the forward kernel, whose outputs are all kept, is not
    in the recomputation: no Mosaic forward kernel runs twice. One operand
    is kept too, where a mixer names it: a rotary latent-attention mixer's
    turned queries (``LATENT_KEPT``, 192 MiB a layer at 16 384 positions:
    its widest projection and rotation, which the step has room for where
    it has none for the expanded keys and values beside them). Where no
    such name is in the trace (the reference bodies of a mixer that names
    no operand; a mixer with no kernel) it is the plain
    ``jax.checkpoint``."""
    return jax.checkpoint(
        mixer, policy=jax.checkpoint_policies.save_only_these_names(
            _FLASH_KEPT, _KDA_KEPT, _SSD_KEPT, LATENT_KEPT))


def gated_ffn(x, w_gate, w_up, w_down, matmul=jnp.matmul):
    """``(silu(x w_gate) * (x w_up)) w_down``, no biases: the feed-forward
    of the Llama family. ``matmul(rows, weights)`` is the plain product, or
    the grouped one where the three weights are stacks of experts' matrices
    and the rows are sorted by expert (``parallel/moe.dropless_moe_ffn``)."""
    dt = x.dtype
    h = jax.nn.silu(matmul(x, w_gate.astype(dt))) \
        * matmul(x, w_up.astype(dt))
    return matmul(h, w_down.astype(dt))
