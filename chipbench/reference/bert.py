"""BERT's forward pass and masked-LM loss, plainly: ``jax.numpy``, float32,
matmuls at ``highest`` precision, one sequence at a time, no kernels.

Written from Devlin et al. 2018 (and Vaswani et al. 2017 for the encoder
layer): post-layer-norm blocks, learned positions, GELU in its tanh form as
the released ``modeling.py`` computes it, layer-norm epsilon 1e-12, the
masked-LM head (dense, GELU, layer norm, output embedding tied to the word
embedding, plus a bias). It shares no code with ``paddle_tpu/models``; it
reads the program's parameter tree by its key names, which is the one thing
it has to know about the program. Departures of the program from the paper
are in ``configs/bert_base.json``; this file follows the program in them.
"""

import math

import jax
import jax.numpy as jnp

#: Largest relative error (Frobenius norm over everything compared, in
#: float32) at which the program still agrees with this file.
#:
#: - ``outputs``: the final hidden states. The program keeps activations in
#:   bfloat16 (7 stored bits of mantissa) through 12 layers, which on the chip
#:   puts it 1.12% to 1.18% from this file with the Pallas bodies, the same at
#:   512 and 4096 positions, and 0.93% with the reference bodies of the
#:   four-chip mesh (my chip run, PR 22; five seeds). Rounding one tensor to 4
#:   stored bits of mantissa (fp8 e4m3 stores 3) puts 1.3% on it (spacing 1/32
#:   to 1/16, over the square root of 12), 1.7% together with bfloat16's own,
#:   and a network computed that way far more. 1.3% is five standard
#:   deviations above what bfloat16 measures and below both.
#: - ``loss``: a mean over hundreds of log-probabilities near ln(vocabulary),
#:   in float32 from the head's layer norm on: 2e-6 to 4.5e-5 measured, four
#:   times that allowed. At random weights the loss is a weak detector (the
#:   logits are small), which is why the hidden states are compared too.
TOLERANCE = {"outputs": 1.3e-2, "loss": 2e-4}


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _encode(params, config, ids, type_ids, mask):
    """One sequence: ids, type_ids, mask are [S]; returns [S, H]."""
    heads = config["num_attention_heads"]
    eps = config["layer_norm_eps"]
    e = params["embed"]
    s = ids.shape[0]
    x = e["word"][ids] + e["pos"][:s] + e["type"][type_ids]
    x = _layer_norm(x, e["ln_g"], e["ln_b"], eps)
    key_bias = jnp.where(mask > 0, 0.0, -1e9)[None, None, :]
    for lp in params["layers"]:
        q, k, v = jnp.split(x @ lp["qkv_w"] + lp["qkv_b"], 3, axis=-1)
        q, k, v = (t.reshape(s, heads, -1).transpose(1, 0, 2)
                   for t in (q, k, v))                      # [A, S, D]
        scores = q @ k.transpose(0, 2, 1) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(scores + key_bias, axis=-1)
        ctx = (probs @ v).transpose(1, 0, 2).reshape(s, -1)
        x = _layer_norm(x + ctx @ lp["out_w"] + lp["out_b"],
                        lp["ln1_g"], lp["ln1_b"], eps)
        ffn = _gelu(x @ lp["fc1_w"] + lp["fc1_b"]) @ lp["fc2_w"] \
            + lp["fc2_b"]
        x = _layer_norm(x + ffn, lp["ln2_g"], lp["ln2_b"], eps)
    return x


def _masked_lm(params, config, hidden, positions, labels, weights):
    """One sequence: the summed negative log-likelihood and the weight."""
    m = params["mlm"]
    h = _gelu(hidden[positions] @ m["dense_w"] + m["dense_b"])
    h = _layer_norm(h, m["ln_g"], m["ln_b"], config["layer_norm_eps"])
    logp = jax.nn.log_softmax(h @ params["embed"]["word"].T + m["bias"])
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked * weights), jnp.sum(weights)


def loss_and_outputs(params, config, batch):
    """(masked-LM loss over the batch, final hidden states [B, S, H])."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    @jax.jit
    def one(params, row):
        hidden = _encode(params, config, row["input_ids"],
                         row["token_type_ids"], row["attention_mask"])
        nll, weight = _masked_lm(
            params, config, hidden, row["masked_positions"],
            row["masked_labels"], row["masked_weights"])
        return hidden, nll, weight

    with jax.default_matmul_precision("highest"):
        rows = [one(params, jax.tree.map(lambda a: a[i], batch))
                for i in range(batch["input_ids"].shape[0])]
    hidden, nll, weight = (jnp.stack(t) for t in zip(*rows))
    return jnp.sum(nll) / jnp.maximum(jnp.sum(weight), 1.0), hidden
