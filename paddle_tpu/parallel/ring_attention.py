"""Ring attention + Ulysses (all-to-all) sequence/context parallelism.

The reference framework (2019-era) has no sequence parallelism — its
longest-sequence story is LoD variable-length batching (ref:
SURVEY §5.7; lod_tensor.h:110). This module is the TPU-native
long-context design the rebuild treats as first-class:

* ``ring_attention`` — blockwise attention with online-softmax
  accumulation; K/V blocks rotate around the "seq" mesh axis via
  ``lax.ppermute`` (ICI neighbor exchange), so the full sequence is never
  materialised on one chip. Memory per chip is O(S/n), compute overlaps
  the permute. (Liu et al., Ring Attention, 2023 — blockwise pattern.)
* ``ulysses_attention`` — DeepSpeed-Ulysses style: ``all_to_all``
  re-shards [B, S/n, H, D] -> [B, S, H/n, D], runs ordinary attention
  on full sequence with a head subset, and all-to-alls back. Cheaper at
  moderate S, needs H % n == 0.

Both are written for ``shard_map`` over a mesh carrying a "seq" axis
(see parallel/mesh.py) and are exact (up to fp error) vs full softmax
attention — tests compare against the dense reference on an 8-device
CPU mesh.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from .mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS

_NEG_INF = -1e30


def _block_attn(q, k, v, bias, scale):
    """One (q-block, kv-block) partial attention step.

    q: [B, Sq, H, D]; k,v: [B, Sk, H, D] — any dtype (bf16 stays bf16 on
    the MXU; accumulation and softmax stats are fp32 via
    preferred_element_type). bias: broadcastable to [B, H, Sq, Sk] or
    None. Returns (o_unnorm fp32 [B,Sq,H,D], m fp32 [B,H,Sq],
    l fp32 [B,H,Sq]) — unnormalised output, row max, row sum-exp.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def _combine(carry, o, m, l):
    """Online-softmax merge of a new partial block into the running
    (o_acc, m_acc, l_acc)."""
    o_acc, m_acc, l_acc = carry
    m_new = jnp.maximum(m_acc, m)
    alpha = jnp.exp(m_acc - m_new)   # rescale old
    beta = jnp.exp(m - m_new)        # rescale new
    l_new = l_acc * alpha + l * beta
    o_new = (o_acc * alpha[..., None].swapaxes(1, 2)
             + o * beta[..., None].swapaxes(1, 2))
    return o_new, m_new, l_new


def ring_attention_local(q, k, v, *, axis_name=SEQ_AXIS, causal=False,
                         key_padding_mask=None, scale=None):
    """Ring attention body — call INSIDE shard_map.

    q, k, v: [B, S_local, H, D] — the local sequence shard.
    key_padding_mask: [B, S_local] bool/0-1, True/1 = attend (rotates
      with K/V). causal: mask by absolute positions across shards.
    Returns [B, S_local, H, D].
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    q_pos = idx * s_local + jnp.arange(s_local)           # absolute q rows
    perm = [(i, (i + 1) % n) for i in range(n)]           # shift kv right

    # Derive initial carries FROM q so they inherit q's varying mesh axes
    # (jax>=0.7 shard_map rejects fori_loop carries whose varying-axis
    # sets change between input and output). Accumulators are fp32
    # regardless of q's dtype (online-softmax stats need the range).
    masked = key_padding_mask is not None
    if masked:
        zero_bs = (q[:, :, 0, 0] * 0.0).astype(jnp.float32)  # [B, S_local]
        kpm = key_padding_mask.astype(jnp.float32) + zero_bs

    o_acc = (q * 0.0).astype(jnp.float32)
    zero_bhs = (jnp.moveaxis(q[..., 0], -1, 1) * 0.0       # [B, H, S_local]
                ).astype(jnp.float32)
    m_acc = zero_bhs + _NEG_INF
    l_acc = zero_bhs

    def block_bias(i, kpm_cur):
        # kv block currently held arrived from device (idx - i); its
        # absolute positions are ((idx - i) mod n) * s_local + arange.
        bias = None
        if kpm_cur is not None:
            bias = jnp.where(kpm_cur[:, None, None, :] > 0, 0.0, _NEG_INF)
        if causal:
            src = (idx - i) % n
            k_pos = src * s_local + jnp.arange(s_local)
            cmask = q_pos[:, None] >= k_pos[None, :]       # [Sq, Sk]
            cbias = jnp.where(cmask[None, None], 0.0, _NEG_INF)
            bias = cbias if bias is None else bias + cbias
        return bias

    if masked:
        def step(i, carry):
            o_acc, m_acc, l_acc, k, v, kpm = carry
            o, m, l = _block_attn(q, k, v, block_bias(i, kpm), scale)
            o_acc, m_acc, l_acc = _combine((o_acc, m_acc, l_acc), o, m, l)
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
            kpm = lax.ppermute(kpm, axis_name, perm)
            return o_acc, m_acc, l_acc, k, v, kpm

        o_acc, m_acc, l_acc, _, _, _ = lax.fori_loop(
            0, n, step, (o_acc, m_acc, l_acc, k, v, kpm))
    else:
        # maskless: no mask carry, no per-step mask permute or bias build
        def step(i, carry):
            o_acc, m_acc, l_acc, k, v = carry
            o, m, l = _block_attn(q, k, v, block_bias(i, None), scale)
            o_acc, m_acc, l_acc = _combine((o_acc, m_acc, l_acc), o, m, l)
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
            return o_acc, m_acc, l_acc, k, v

        o_acc, m_acc, l_acc, _, _ = lax.fori_loop(
            0, n, step, (o_acc, m_acc, l_acc, k, v))
    return (o_acc / l_acc[..., None].swapaxes(1, 2)).astype(q.dtype)


def ring_attention(mesh, q, k, v, *, causal=False, key_padding_mask=None,
                   scale=None, seq_axis=SEQ_AXIS, data_axis=DATA_AXIS,
                   model_axis=MODEL_AXIS):
    """shard_map wrapper: q,k,v are global [B, S, H, D] arrays; batch
    sharded over "data", sequence over "seq", heads over "model"."""
    qkv_spec = P(data_axis, seq_axis, model_axis, None)
    mask_spec = P(data_axis, seq_axis)
    body = functools.partial(ring_attention_local, causal=causal,
                             scale=scale, axis_name=seq_axis)

    if key_padding_mask is None:
        def f(q, k, v):
            return body(q, k, v)
        return shard_map(f, mesh=mesh,
                         in_specs=(qkv_spec, qkv_spec, qkv_spec),
                         out_specs=qkv_spec)(q, k, v)

    def f(q, k, v, kpm):
        return body(q, k, v, key_padding_mask=kpm)
    return shard_map(f, mesh=mesh,
                     in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
                     out_specs=qkv_spec)(q, k, v, key_padding_mask)


def ulysses_attention_local(q, k, v, *, axis_name=SEQ_AXIS, causal=False,
                            key_padding_mask=None, scale=None):
    """Ulysses body — call INSIDE shard_map.

    q,k,v: [B, S_local, H, D] with H % axis_size == 0. all_to_all to
    [B, S, H_local, D], dense attention, all_to_all back.
    """
    n = lax.psum(1, axis_name)
    b, s_local, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    def seq2head(t):   # [B, S/n, H, D] -> [B, S, H/n, D]
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def head2seq(t):   # [B, S, H/n, D] -> [B, S/n, H, D]
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = seq2head(q), seq2head(k), seq2head(v)
    s_full = s_local * n
    bias = None
    if key_padding_mask is not None:
        kpm = lax.all_gather(key_padding_mask.astype(jnp.float32),
                             axis_name, axis=1, tiled=True)  # [B, S]
        bias = jnp.where(kpm[:, None, None, :] > 0, 0.0, _NEG_INF)
    if causal:
        pos = jnp.arange(s_full)
        cmask = pos[:, None] >= pos[None, :]
        cbias = jnp.where(cmask[None, None], 0.0, _NEG_INF)
        bias = cbias if bias is None else bias + cbias

    s = jnp.einsum("bqhd,bkhd->bhqk", qg, kg) * scale
    if bias is not None:
        s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vg)
    return head2seq(o)


def ulysses_attention(mesh, q, k, v, *, causal=False, key_padding_mask=None,
                      scale=None, seq_axis=SEQ_AXIS, data_axis=DATA_AXIS):
    """shard_map wrapper for Ulysses; the seq-axis size must divide the
    head count (H % n_seq == 0 — all_to_all splits the head dim). Heads
    are NOT simultaneously sharded over "model" here (Ulysses uses the
    head dim as its transport dim)."""
    qkv_spec = P(data_axis, seq_axis, None, None)
    mask_spec = P(data_axis, seq_axis)
    body = functools.partial(ulysses_attention_local, causal=causal,
                             scale=scale, axis_name=seq_axis)
    if key_padding_mask is None:
        def f(q, k, v):
            return body(q, k, v)
        return shard_map(f, mesh=mesh,
                         in_specs=(qkv_spec, qkv_spec, qkv_spec),
                         out_specs=qkv_spec)(q, k, v)

    def f(q, k, v, kpm):
        return body(q, k, v, key_padding_mask=kpm)
    return shard_map(f, mesh=mesh,
                     in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
                     out_specs=qkv_spec)(q, k, v, key_padding_mask)


def full_attention_reference(q, k, v, *, causal=False,
                             key_padding_mask=None, scale=None):
    """Dense softmax attention on one device — the correctness oracle."""
    b, s, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if key_padding_mask is not None:
        att = att + jnp.where(
            key_padding_mask[:, None, None, :] > 0, 0.0, _NEG_INF)
    if causal:
        pos = jnp.arange(s)
        att = att + jnp.where(pos[:, None] >= pos[None, :],
                              0.0, _NEG_INF)[None, None]
    p = jax.nn.softmax(att, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
