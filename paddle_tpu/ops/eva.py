"""EVA attention (Zheng, Yuan, Wang and Kong 2023, "Efficient Attention via
Control Variates", arXiv:2302.04542, sec. 4) in the deterministic form the
EvaByte decoder trains with: a query attends to the **tokens of its own
window** and to the **chunk summaries of every earlier window**, under ONE
softmax.

Positions are cut into windows of ``window`` and chunks of ``chunk``
(``chunk`` divides ``window``); position i lies in window ``i // window``.
Per head, with learned vectors ``mu`` and ``phi`` of the head's size:

- ``eva_summaries``: for each chunk c, ``ksum_c = sum_j a_j k_j`` with ``a =
  softmax_{j in c}(k_j . mu)`` and ``vsum_c = sum_j b_j v_j`` with ``b =
  softmax_{j in c}(k_j . phi)``: the EVA paper's pooled key and value of a
  chunk, its random proposal replaced by the two vectors (the released
  model's ``adaptive_mu_k`` and ``adaptive_phi``). No scale and no ``-|k|^2 /
  2`` term inside the chunk softmax. Scores, softmax and sums in float32.
- ``eva_attention``: for query i, the scores ``q_i . k_j / sqrt(D)`` over the
  keys j of its own window with ``j <= i``, and ``q_i . ksum_c / sqrt(D)``
  over every chunk c of an earlier window (none of its own); one softmax over
  the union; ``o_i = sum_j p_ij v_j + sum_c p_ic vsum_c``.

Two identities the tests hold the op to: with ``window >= S`` no summary is
visible and the op is plain causal attention; with ``chunk = 1`` every
summary is its token and the op is plain causal attention over the whole
sequence, whatever ``mu`` and ``phi``.

The summaries are plain ``jax.numpy`` under autodiff: they are 1/16 of the
keys and XLA fuses the reshape, the softmax and the sum into one pass over k
and one over v. The aggregation has two bodies, chosen by the kernel registry
(``ops/pallas/registry.py``: platform and mesh, nothing a user sets), kernel
``eva_attention``: the ``jax.numpy`` body here, a window at a time with a
mask over that window's keys and the summaries (the CPU's, and a mesh's that
splits more than the batch), and on one chip the Mosaic kernels
``flash_fwd_eva`` and ``flash_bwd_eva`` of ``ops/pallas/eva.py``.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.pallas import registry as _registry

__all__ = ["eva_summaries", "eva_attention"]

_NEG_INF = -1e30


def eva_summaries(k, v, mu, phi, chunk):
    """(ksum, vsum), each [B, S / chunk, H, D] in ``k.dtype``: the two
    softmax-weighted sums over each chunk of k and v [B, S, H, D] (k after
    its rotation), scored by ``mu`` and ``phi`` [H, D]. Float32 inside."""
    b, s, h, d = k.shape
    if s % chunk:
        raise ValueError(f"{s} positions are no whole chunks of {chunk}")
    f32 = jnp.float32
    kc = k.astype(f32).reshape(b, s // chunk, chunk, h, d)
    vc = v.astype(f32).reshape(b, s // chunk, chunk, h, v.shape[-1])

    def pooled(values, by):
        scores = jnp.einsum("bnchd,hd->bnch", kc, by.astype(f32),
                            precision=lax.Precision.HIGHEST)
        return jnp.sum(jax.nn.softmax(scores, axis=2)[..., None] * values,
                       axis=2)

    return pooled(kc, mu).astype(k.dtype), pooled(vc, phi).astype(v.dtype)


def _windows(s, window):
    """(the window the op runs at, how many): one window where ``window``
    reaches the whole sequence."""
    if window >= s:
        return s, 1
    return window, -(-s // window)


def _eva_attention_reference(q, k, v, ksum, vsum, window, chunk):
    """The aggregation in stock ``jax.numpy``, heads-major: q, k, v [B, H, S,
    D], ksum and vsum [B, H, S / chunk, D]; returns [B, H, S, D] in
    ``q.dtype``. A window of queries at a time (``lax.map``): its scores on
    the window's own keys [W, W] and on all the summaries [W, S / chunk],
    masked, in float32."""
    b, h, s, d = q.shape
    f32 = jnp.float32
    window, nw = _windows(s, window)
    pad = nw * window - s

    def by_window(t):
        t = jnp.pad(t.astype(f32), ((0, 0), (0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(t.reshape(b, h, nw, window, t.shape[-1]), 2, 0)

    ks, vs = ksum.astype(f32), vsum.astype(f32)
    causal = jnp.tril(jnp.ones((window, window), bool))
    chunk_of = jnp.arange(ks.shape[2])
    per_window = window // chunk

    def one(args):
        w, q_w, k_w, v_w = args
        tokens = jnp.einsum("bhqd,bhkd->bhqk", q_w, k_w) / math.sqrt(d)
        tokens = jnp.where(causal, tokens, _NEG_INF)
        pooled = jnp.einsum("bhqd,bhcd->bhqc", q_w, ks) / math.sqrt(d)
        pooled = jnp.where(chunk_of < w * per_window, pooled, _NEG_INF)
        p = jax.nn.softmax(jnp.concatenate([tokens, pooled], axis=-1),
                           axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p[..., :window], v_w) \
            + jnp.einsum("bhqc,bhcd->bhqd", p[..., window:], vs)

    out = lax.map(one, (jnp.arange(nw), by_window(q), by_window(k),
                        by_window(v)))
    return jnp.moveaxis(out, 0, 2).reshape(b, h, nw * window, -1)[:, :, :s] \
        .astype(q.dtype)


def eva_attention(q, k, v, ksum, vsum, window, chunk, mesh=None):
    """The aggregation (module docstring) of q, k, v [B, S, H, D] and the
    summaries ksum, vsum [B, S / chunk, H, D]; returns [B, S, H, D] in
    ``q.dtype``, by the body the registry selects (kernel ``eva_attention``).
    The calls are under the scope ``eva_core`` inside ``attention_core``,
    the head transposes around them under ``attention_core`` alone."""
    s = q.shape[1]
    if window < s and window % chunk:
        raise ValueError(f"a window of {window} is no whole chunks of "
                         f"{chunk}")
    if ksum.shape[1] * chunk != s or vsum.shape[1] * chunk != s:
        raise ValueError(f"{ksum.shape[1]} summaries of {chunk} positions "
                         f"are not {s} positions")

    def heads(t):
        return t.transpose(0, 2, 1, 3)

    # the head transposes are ``attention_core``'s own time, the calls
    # ``eva_core``'s inside it
    with jax.named_scope("attention_core"):
        operands = [heads(t) for t in (q, k, v, ksum, vsum)]
        with jax.named_scope("eva_core"), _registry.mesh_scope(mesh):
            ctx = _registry.dispatch("eva_attention", *operands,
                                     window=window, chunk=chunk)
        return heads(ctx).astype(q.dtype)

