"""OLMoE's forward pass and training loss, plainly: ``jax.numpy``, float32,
matmuls at ``highest`` precision, one sequence at a time, no kernels, every
expert applied to every token and masked by the router's choice.

Written from Muennighoff et al. 2024 (arXiv:2409.02060) and the released
``modeling_olmoe.py``. A block is ``h = x + Attn(RMSNorm(x))``, ``y = h +
MoE(RMSNorm(h))``. Attention has no bias; the query and key projections are
RMS-normalised over their whole width before they are split into heads, then
turned by rotary positions (pairs ``(i, i + d/2)``, angle ``p * theta^(-2i/d)``)
and the softmax is causal. The router is a softmax over all experts; a token
takes its k most probable experts with those probabilities as they are (not
renormalised), and an expert is ``W_down (silu(W_gate x) * W_up x)``. After
the last block a final RMSNorm, then an untied head. The loss is the mean
next-token cross-entropy over every position, plus the load-balancing loss
``E * sum_e f_e P_e`` (``f_e`` the share of the (token, choice) assignments
that went to e, ``P_e`` the mean probability of e) and the router z-loss
``mean(logsumexp(router logits)^2)``, each taken over the batch's tokens and
averaged over the layers, times the configuration's two weights. It shares
no code with ``paddle_tpu``; it reads the program's parameter tree by its key
names. Departures of the program from the paper are in
``configs/olmoe_1b_7b.json``; this file follows the program in them.

**A choice is discrete, so it is checked as one.** The program keeps its
activations in bfloat16, so the probabilities its router sees are this file's
to within a few parts in a thousand, and where a token's 8th and 9th
probabilities are closer than that, its 8th expert can be the other one. At
random weights that is one token in 25 (0.5% of the assignments), and the
swap of one expert moves that token's hidden state by a tenth: 1.8% to 2.4%
of the whole in the Frobenius norm, more than rounding every state to 4 bits
of mantissa does (1.3%), with nothing wrong anywhere (my chip run, PR 26). A
tolerance wide enough for that would let a lower precision through. So the
runner's probe leaves the experts the program chose on the sample
(``program_choice``), and this file (1) holds each choice to its own float32
probabilities: no expert the program used may be less probable than one it
left out by more than ``ROUTER_MARGIN``; a router that ranks by anything else
fails there, by a wide margin; then (2) computes with those experts, so that
the hidden states can be held to bfloat16's own error. Without
``program_choice`` it uses its own k most probable, which is what the
float32 tests on the CPU compare with.
"""

import math

import jax
import jax.numpy as jnp

#: Largest relative error (Frobenius norm over everything compared, in
#: float32) at which the program still agrees with this file.
#:
#: - ``outputs``: the final normed hidden states, under the program's own
#:   admissible routing. The program keeps activations in bfloat16 (7 stored
#:   bits of mantissa) through one block whose residual stream is small
#:   beside what the attention and the experts add to it (embeddings of
#:   N(0, 0.02)): on the chip that puts it 0.761% to 0.894% from this file
#:   (my chip run, PR 26; sixteen seeds, mean 0.81%, deviation 0.04%). Rounding
#:   the states to 4 stored bits of mantissa (fp8 e4m3 stores 3) reads 1.556%
#:   and 1.564% on two seeds, 3 bits 2.78%. 1.1% is seven deviations above
#:   what bfloat16 measures and well under both. Under this file's own
#:   routing the same runs read 1.81% to 2.37%: the discrete flips, not the
#:   arithmetic (module docstring).
#: - ``loss``: float32 from the head's logits on, a mean over 8192
#:   log-probabilities near ln(vocabulary) plus the two router terms: 1.1e-5
#:   to 6.9e-5 measured, four times that allowed; the loss rounded to 4 bits
#:   reads 1.3e-2. At random weights the loss is a weak detector, which is why
#:   the hidden states are compared too.
TOLERANCE = {"outputs": 1.1e-2, "loss": 3e-4}

#: How far under the most probable expert it left out the least probable
#: expert the program used may lie, as a share of probability
#: (``routing_check``). On the chip the largest shortfall of a sample of 8192
#: tokens is 0.017 to 0.027 (seventeen seeds; 255 to 407 of 65536 choices
#: differ, 0.39% to 0.62%), and the count of flips falls by a factor of e every 0.004
#: of margin, so 0.1 is out of bfloat16's reach and far inside what a router
#: that ranks by anything else does (probabilities of rank 9 and below
#: spread over a factor of 20).
ROUTER_MARGIN = 0.1


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x [S, heads, d]: rotate the pair (x_i, x_{i+d/2}) of position p by
    p * theta^(-2i/d)."""
    s, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq   # [S, d/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + turned * sin


def _attention(lp, x, config):
    s = x.shape[0]
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    q = _rms_norm(x @ lp["q_w"], lp["q_norm_g"], eps).reshape(s, heads, -1)
    k = _rms_norm(x @ lp["k_w"], lp["k_norm_g"], eps).reshape(s, heads, -1)
    v = (x @ lp["v_w"]).reshape(s, heads, -1)
    q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(q.shape[-1])
    visible = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return jnp.einsum("nqk,knd->qnd", probs, v).reshape(s, -1) @ lp["o_w"]


def _experts(lp, x, config, choice=None):
    """(output [S, H], router logits [S, E], the experts used [S, E] of 0/1,
    this file's own top-k [S, E] of 0/1). ``choice`` [S, k], where given,
    names the experts to use in place of this file's own k most probable."""
    k = config["num_experts_per_tok"]
    logits = x @ lp["router_w"]
    probs = jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -k][:, None]
    own = (probs >= kth).astype(jnp.float32)
    used = own if choice is None else jnp.sum(
        jax.nn.one_hot(choice, probs.shape[-1], dtype=jnp.float32), axis=-2)

    def expert(e):
        w_gate, w_up, w_down, weight = e
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return weight[:, None] * y

    # every expert on every token, one expert at a time, masked by the choice
    out = jnp.sum(jax.lax.map(expert, (lp["w_gate"], lp["w_up"], lp["w_down"],
                                       (probs * used).T)), axis=0)
    return out, logits, used, own


def _sequence(params, config, ids, labels, choice=None):
    """One sequence: hidden [S, H], summed negative log-likelihood, and per
    layer the router logits, the experts used and this file's own choice."""
    eps = config["rms_norm_eps"]
    x = params["embed"][ids]
    routed = []
    for i, lp in enumerate(params["layers"]):
        x = x + _attention(lp, _rms_norm(x, lp["ln1_g"], eps), config)
        moe, *router = _experts(lp, _rms_norm(x, lp["ln2_g"], eps), config,
                                None if choice is None else choice[i])
        x = x + moe
        routed.append(router)
    hidden = _rms_norm(x, params["final_norm_g"], eps)
    logp = jax.nn.log_softmax(hidden @ params["head_w"], axis=-1)
    nll = -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return hidden, nll, routed


def _all(params, config, batch):
    """Over the batch's sequences: hidden [B, S, H], the summed negative
    log-likelihood, and [L, B*S, E] each of router logits, experts used and
    own choices."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    one = jax.jit(lambda p, *row: _sequence(p, config, *row))
    rows = [batch["input_ids"], batch["labels"]]
    if "program_choice" in batch:                  # [L, B, S, k] -> by row
        rows.append(jnp.swapaxes(jnp.asarray(batch["program_choice"]), 0, 1))
    with jax.default_matmul_precision("highest"):
        done = [one(params, *(r[i] for r in rows))
                for i in range(batch["input_ids"].shape[0])]
    hidden = jnp.stack([d[0] for d in done])
    nll = sum(d[1] for d in done)
    logits, used, own = (
        jnp.stack([jnp.concatenate([d[2][layer][j] for d in done])
                   for layer in range(len(params["layers"]))])
        for j in range(3))
    return hidden, nll, logits, used, own


def routing_check(probs, used, own):
    """How the experts used differ from this file's own choice: (the number
    of (token, expert) pairs used that are not among its own k most
    probable, the largest shortfall). A token's shortfall is how far the
    least probable expert used lies under the most probable one left out,
    ``p_out / p_used - 1``, in this file's float32 probabilities: 0 or less
    where the experts used are the k most probable."""
    least_used = jnp.min(jnp.where(used > 0, probs, jnp.inf), axis=-1)
    most_out = jnp.max(jnp.where(used > 0, 0.0, probs), axis=-1)
    return (int(jnp.sum((used > 0) & (own == 0))),
            float(jnp.max(most_out / least_used - 1.0)))


def loss_and_outputs(params, config, batch):
    """(training loss over the batch, final normed hidden states [B, S, H]).

    Where the batch carries ``program_choice`` [L, B, S, k], the experts the
    program chose for each token, they are first held to this file's own
    probabilities (``routing_check`` against ``ROUTER_MARGIN``; hidden states
    of NaN, which agree with nothing, where they fail) and then used in
    place of this file's own choice."""
    hidden, nll, logits, used, own = _all(params, config, batch)
    probs = jax.nn.softmax(logits, axis=-1)
    if "program_choice" in batch:
        differ, shortfall = routing_check(probs, used, own)
        ok, total = shortfall <= ROUTER_MARGIN, int(jnp.sum(used))
        print(f"[reference] routing: {differ} of {total} (token, expert) "
              f"choices of the program are not among this file's own top-k "
              f"({100 * differ / total:.3f}%); largest shortfall "
              f"{shortfall:.5f} in probability, {ROUTER_MARGIN} allowed: "
              f"{'admissible' if ok else 'A WRONG ROUTER'}", flush=True)
        if not ok:
            hidden = jnp.full_like(hidden, jnp.nan)
    experts = config["num_experts"]
    share = jnp.sum(used, axis=1) / jnp.sum(used, axis=(1, 2))[:, None]
    balance = experts * jnp.sum(share * jnp.mean(probs, axis=1), axis=-1)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2, axis=-1)
    loss = nll / batch["input_ids"].size \
        + config["router_aux_loss_coef"] * jnp.mean(balance) \
        + config["router_z_loss_coef"] * jnp.mean(z)
    return loss, hidden
