"""The Transformer's forward pass and label-smoothed loss, plainly:
``jax.numpy``, float32, matmuls at ``highest`` precision, no kernels.

Written from Vaswani et al. 2017: post-layer-norm encoder and decoder
layers, ReLU feed-forward, embeddings scaled by sqrt(d_model), sinusoidal
positions, a causal mask on the decoder's self-attention, the output
projection tied to the target embedding, label smoothing (Szegedy et al.).
It shares no code with ``paddle_tpu/models``; it reads the program's
parameter tree by its key names. Departures of the program from the paper
are in ``configs/transformer_big.json`` (a layer norm after the last layer of
each stack, sines then cosines, epsilon spread over all classes); this file
follows the program in them.
"""

import math

import jax
import jax.numpy as jnp

#: Largest relative error (Frobenius norm, float32) at which the program
#: still agrees with this file. ``outputs`` are the logits over the target
#: vocabulary, computed by the program in float32 from bfloat16 hidden states
#: that passed 12 layers: 0.72% to 0.75% from this file on the chip (my chip
#: run, PR 22). See ``reference/bert.py`` for the argument: 4 stored bits of
#: mantissa on one tensor add 1.3% in quadrature (1.5% together), so 1%
#: separates the two with a third of room. ``loss``: 2e-6 to 1.5e-5 measured.
TOLERANCE = {"outputs": 1.0e-2, "loss": 2e-4}


def _layer_norm(x, ln, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * ln["g"] + ln["b"]


def _positions(length, width):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    rate = 10000.0 ** (2.0 * jnp.arange(width // 2, dtype=jnp.float32)
                       / width)
    return jnp.concatenate([jnp.sin(pos / rate), jnp.cos(pos / rate)], -1)


def _attend(ap, queries, memory, bias, heads):
    """queries [B, T, H] attend to memory [B, S, H]; bias [B, 1, T|1, S]."""
    def split(t):
        b, n, _ = t.shape
        return t.reshape(b, n, heads, -1).transpose(0, 2, 1, 3)
    q = split(queries @ ap["q_w"] + ap["q_b"])
    k = split(memory @ ap["k_w"] + ap["k_b"])
    v = split(memory @ ap["v_w"] + ap["v_b"])
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(q.shape[-1])
    ctx = jax.nn.softmax(scores + bias, axis=-1) @ v
    b, _, t, _ = ctx.shape
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, -1) @ ap["o_w"] \
        + ap["o_b"]


def _feed_forward(fp, x):
    return jax.nn.relu(x @ fp["w1"] + fp["b1"]) @ fp["w2"] + fp["b2"]


def _logits(params, config, batch):
    heads, eps = config["num_attention_heads"], config["layer_norm_eps"]
    width = config["d_model"]
    src, tgt = batch["src_ids"], batch["tgt_in"]
    src_bias = jnp.where(batch["src_mask"] > 0, 0.0, -1e9)[:, None, None, :]
    x = params["src_embed"][src] * math.sqrt(width) \
        + _positions(src.shape[1], width)
    for lp in params["enc"]:
        x = _layer_norm(x + _attend(lp["attn"], x, x, src_bias, heads),
                        lp["ln1"], eps)
        x = _layer_norm(x + _feed_forward(lp["ffn"], x), lp["ln2"], eps)
    memory = _layer_norm(x, params["enc_ln"], eps)

    t = tgt.shape[1]
    causal = jnp.tril(jnp.ones((t, t)))[None, None]
    self_bias = jnp.where(
        causal * batch["tgt_mask"][:, None, None, :] > 0, 0.0, -1e9)
    y = params["tgt_embed"][tgt] * math.sqrt(width) + _positions(t, width)
    for lp in params["dec"]:
        y = _layer_norm(
            y + _attend(lp["self_attn"], y, y, self_bias, heads),
            lp["ln1"], eps)
        y = _layer_norm(
            y + _attend(lp["cross_attn"], y, memory, src_bias, heads),
            lp["ln2"], eps)
        y = _layer_norm(y + _feed_forward(lp["ffn"], y), lp["ln3"], eps)
    return _layer_norm(y, params["dec_ln"], eps) @ params["tgt_embed"].T


def loss_and_outputs(params, config, batch):
    """(label-smoothed loss over the batch, logits [B, T, V])."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    @jax.jit
    def run(params, batch):
        logits = _logits(params, config, batch)
        logp = jax.nn.log_softmax(logits, axis=-1)
        eps = config["label_smoothing"]
        picked = jnp.take_along_axis(
            logp, batch["tgt_out"][..., None], axis=-1)[..., 0]
        smooth = jnp.mean(logp, axis=-1)
        w = batch["tgt_mask"].astype(jnp.float32)
        nll = -((1.0 - eps) * picked + eps * smooth)
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0), logits

    with jax.default_matmul_precision("highest"):
        return run(params, batch)
