"""LFM2's forward pass and training loss, plainly: ``jax.numpy``, float32,
matmuls at ``highest`` precision, one sequence at a time, no kernels: the
convolution is three shifted products, the scores of the attention layer are
the dense [S, S] ones, a block of queries at a time.

Written from the released ``LiquidAI/LFM2-24B-A2B`` config (the catalog row)
and the issue's equations. A block is ``h = x + Op(norm(x; w1))``, ``y = h +
FF(norm(h; w2))`` with ``norm(x; w) = x / sqrt(mean x^2 + norm_eps) * w``;
the stage runs ``num_hidden_layers`` entries of ``layer_types`` from
``first_layer`` on, the first ``num_dense_layers`` of them with the dense
feed-forward.

**Short convolution** (``conv``), from the normed input ``x`` [S, H]: ``[B |
C | u] = x W_in`` (three ranges of H columns), ``z = B * u``, ``c_t = sum_j
w_j z_{t - K + 1 + j}`` a channel with ``K = conv_L_cache`` taps (causal,
zeros before the sequence, no bias, no activation), ``(C * c) W_out``.

**Attention** (``full_attention``): ``q = x W_q`` as ``num_attention_heads``
heads of ``hidden_size / num_attention_heads``, ``k = x W_k`` and ``v = x
W_v`` as ``num_key_value_heads`` heads; q and k normed a head (``norm`` over
the head's channels, one gain vector each); rotary positions in the
rotate-half convention on the whole head, the pair (i, i + d/2) turned by ``p
* theta^(-2i / d)``; query head i reads key/value head ``i // group``; scores
``q . k / sqrt(d)`` over the keys ``j <= i``, softmax, times v, then ``W_o``.

**Feed-forward**: a dense ``W_2 (silu(W_1 x) * W_3 x)`` in the leading
layers; else ``s = sigmoid(x W_r)`` over all ``router_width`` experts, a token
takes the ``num_experts_per_tok`` largest of ``s + bias`` with the weights
``routed_scaling_factor * s_e / (sum of the chosen s + 1e-6)`` on the
experts' outputs (the published 1e-6; the program's ``moe.route`` has 1e-20:
``configs/lfm2_24b_a2b.json``, departures); the experts ``experts_held`` =
[first, n] are the ones this chip holds and the only ones computed, here as in
the program; no shared expert.

After the last block a final norm and the tied head, the embedding's
transpose, over the slice of the vocabulary; the loss is the mean next-token
cross-entropy. It shares no code with ``paddle_tpu``; it reads the program's
parameter tree by its key names.

**A choice is discrete, so it is checked as one**, and **a part is held to
float32 on its own input**: both as ``reference/kimi_linear.py`` does and for
its reasons. The runner's probe leaves the experts the program chose on the
sample (``program_choice``) and what every part of its forward pass handed on
(``program_stream``, in the program's bfloat16); this file holds each choice
to its own float32 scores (``ROUTER_MARGIN``), computes with those experts,
and computes every part from the program's state before it.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

#: Largest relative error (Frobenius norm over everything compared, in
#: float32) at which the program still agrees with this file. The readings
#: are PERF.md's (section 6, PR 40).
#:
#: - ``outputs``: what every part of the forward pass hands on (the
#:   embedding, the stream after each operator and each feed-forward, the
#:   final normed hidden states: 12 parts for 5 layers), each computed here
#:   from the PROGRAM's state before it and each over its norm. A part that
#:   only stores its result in bfloat16 reads 0.166%; the program's parts
#:   together read 0.2655 to 0.2664% on the chip over four seeds (the first
#:   convolution 0.58%, the dense feed-forward 0.46%: bfloat16 matmul
#:   operands at 2048 and 11 776 terms a sum). What every part hands on in 4
#:   stored bits of mantissa (``state_bits``) reads 1.33%, a convolution
#:   summed in 4 bits (``conv_bits``) 0.84%; bfloat16's 7 bits on the states
#:   pass, as they should: that is the program's own precision. 0.5% is 1.9
#:   times the program's largest reading and 1.7 times under the lower
#:   control. (A convolution summed in bfloat16 reads 0.106%, under the
#:   program's own distance: no limit can lie between: PERF.md section 6.)
#: - ``loss``: float32 from the head's logits on, a mean over 8192
#:   log-probabilities near ln(8192), this file's own pass from the ids on
#:   (the one end-to-end number): 8e-6 to 2.1e-5 on the chip, so the
#:   accepted cells' 3e-4 leaves fourteen times of room.
TOLERANCE = {"outputs": 5e-3, "loss": 3e-4}

#: How far under the best-scoring expert it left out the worst-scoring expert
#: the program used may lie, as a share of the score (``routing_check``), in
#: this file's scores of the program's own input to each router. The program
#: rounds the normed input to bfloat16 before its float32 router: 0.00098 to
#: 0.00196 on the chip over four seeds (0.17 to 0.19% of the 131 072 choices
#: differ). A router whose scores are rounded to bfloat16 before the choice
#: (``router_bits`` = 7, the control) reads 0.00471 and must read over it;
#: 10 stored bits read 0.0005. Laguna's margin, for the same sigmoid router.
ROUTER_MARGIN = 0.004

QUERY_BLOCK = 128


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def round_mantissa(x, bits):
    """x rounded to ``bits`` stored bits of mantissa (bfloat16 stores 7, fp8
    e4m3 stores 3)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 2.0 ** (bits + 1)) / 2.0 ** (bits + 1), e)


def _rounded(x, bits):
    return x if bits is None else round_mantissa(x, bits)


def layer_kinds(config):
    """[(operator, "dense" or "sparse")] of the layers the stage runs."""
    first, n = config["first_layer"], config["num_hidden_layers"]
    return [(kind, "dense" if i < config["num_dense_layers"] else "sparse")
            for i, kind in enumerate(config["layer_types"][first:first + n])]


def _short_conv(lp, x, conv_bits=None):
    """``conv_bits``: the gated signal, every tap's product and the running
    sum kept in that many stored bits (a convolution summed in that
    precision: the control)."""
    s, h = x.shape
    taps = lp["conv"]
    k = taps.shape[0]
    gate_b, gate_c, u = jnp.split(x @ lp["in_w"], 3, axis=-1)
    z = jnp.pad(_rounded(gate_b * u, conv_bits), ((k - 1, 0), (0, 0)))
    conv = jnp.zeros((s, h), jnp.float32)
    for j in range(k):                     # three shifted products
        conv = _rounded(conv + _rounded(taps[j] * z[j:j + s], conv_bits),
                        conv_bits)
    return (gate_c * conv) @ lp["out_w"]


def _rotate(x, cos, sin):
    """x [S, n, d]: the pairs (i, i + d/2) of the whole head."""
    half = cos.shape[-1]
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(lp, x, config, softmax_bits=None):
    s = x.shape[0]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // n
    eps = config["norm_eps"]
    theta = float(config["rope_parameters"]["rope_theta"])
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    q = _rotate(_rms_norm((x @ lp["q_w"]).reshape(s, n, d), lp["q_norm_g"],
                          eps), cos, sin)
    k = _rotate(_rms_norm((x @ lp["k_w"]).reshape(s, kv, d), lp["k_norm_g"],
                          eps), cos, sin)
    v = (x @ lp["v_w"]).reshape(s, kv, d)
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    # [blocks, block, kv, group, d]: query head i = (i // group, i % group)
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, kv, n // kv, d)
    # a padded query stands at the last position: it sees keys, so nothing
    # of it is NaN on the way back, and its row is cut off below
    at = jnp.minimum(jnp.arange(s + pad), s - 1).reshape(-1, block)

    def queries(args):
        q_blk, at_blk = args
        scores = jnp.einsum("qhgd,khd->hgqk", q_blk, k) / math.sqrt(d)
        seen = jnp.arange(s)[None, :] <= at_blk[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        lse = _rounded(jax.nn.logsumexp(scores, axis=-1, keepdims=True),
                       softmax_bits)
        return jnp.einsum("hgqk,khd->qhgd", jnp.exp(scores - lse), v)

    ctx = jax.lax.map(queries, (q, at)).reshape(s + pad, n, d)[:s]
    return ctx.reshape(s, n * d) @ lp["o_w"]


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _experts(lp, x, config, choice=None, router_bits=None):
    """(output [S, H], the scores the choice is made on [S, E], the experts
    used [S, E] of 0/1, this file's own top-k [S, E] of 0/1). ``choice``
    [S, k], where given, names the experts to use in place of this file's
    own k best; ``router_bits`` instead uses the k best of the scores
    rounded to that many bits (a router of that precision: the control)."""
    k = config["num_experts_per_tok"]
    first, held = config["experts_held"]
    scores = jax.nn.sigmoid(x @ lp["router_w"])
    ranked = scores + lp["router_bias"]

    def k_best(of):
        return jnp.sum(jax.nn.one_hot(jax.lax.top_k(of, k)[1], of.shape[-1],
                                      dtype=jnp.float32), axis=-2)

    own = k_best(ranked)
    if router_bits is not None:
        used = k_best(round_mantissa(scores, router_bits)
                      + lp["router_bias"])
    elif choice is not None:
        used = jnp.sum(jax.nn.one_hot(choice, scores.shape[-1],
                                      dtype=jnp.float32), axis=-2)
    else:
        used = own
    weights = config["routed_scaling_factor"] * scores * used \
        / (jnp.sum(scores * used, axis=-1, keepdims=True) + 1e-6)

    def expert(e):
        w_gate, w_up, w_down, weight = e
        return weight[:, None] * _gated(x, w_gate, w_up, w_down)

    # the experts held here on every token, one at a time, masked by the
    # choice; the others' part is another chip's and is left out
    out, _ = jax.lax.scan(
        lambda total, e: (total + expert(e), None), jnp.zeros_like(x),
        (lp["w_gate"], lp["w_up"], lp["w_down"],
         weights[:, first:first + held].T))
    return out, ranked, used, own


def _mixer(lp, x, config, kind, conv_bits=None, softmax_bits=None):
    normed = _rms_norm(x, lp["ln1_g"], config["norm_eps"])
    if kind == "full_attention":
        return x + _attention(lp, normed, config, softmax_bits)
    return x + _short_conv(lp, normed, conv_bits)


def _feed(lp, x, config, mlp, choice=None, router_bits=None):
    """(the stream after the layer's feed-forward, the router's (ranked
    scores, experts used, own choice) or None for a dense layer)."""
    normed = _rms_norm(x, lp["ln2_g"], config["norm_eps"])
    if mlp == "dense":
        return x + _gated(normed, lp["ffn_gate"], lp["ffn_up"],
                          lp["ffn_down"]), None
    out, *router = _experts(lp, normed, config, choice, router_bits)
    return x + out, router


def _head(params, x, labels, eps):
    """(the final normed hidden states, the summed negative
    log-likelihood of ``labels``)."""
    hidden = _rms_norm(x, params["final_norm_g"], eps)
    logp = jax.nn.log_softmax(hidden @ params["embed"].T, axis=-1)
    return hidden, -jnp.sum(jnp.take_along_axis(logp, labels[:, None],
                                                axis=-1))


def loss(params, config, batch):
    """The training loss alone, from the ids on, in one traceable piece:
    what the float32 tests differentiate."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def nll(ids, labels):
        x = params["embed"][ids]
        for lp, (kind, mlp) in zip(params["layers"], layer_kinds(config)):
            x = _feed(lp, _mixer(lp, x, config, kind), config, mlp)[0]
        return _head(params, x, labels, config["norm_eps"])[1]

    with jax.default_matmul_precision("highest"):
        return sum(nll(ids, labels) for ids, labels
                   in zip(batch["input_ids"], batch["labels"])) \
            / batch["input_ids"].size


#: the keys of a configuration this file reads
_READ = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "layer_types", "first_layer", "num_hidden_layers",
         "num_dense_layers", "rope_parameters", "norm_eps",
         "num_experts_per_tok", "experts_held", "routed_scaling_factor")


@functools.lru_cache(maxsize=8)
def _compiled_parts(frozen, conv_bits, softmax_bits, router_bits):
    """The parts as jitted functions of the configuration ``frozen`` (its
    ``_READ`` keys as JSON), made once for every row, every seed and every
    control that shares them: (mixer(lp, x, kind), feed(lp, x, chosen,
    mlp), head(params, x, labels))."""
    config = json.loads(frozen)
    mixer = jax.jit(lambda lp, x, kind: (_mixer(
        lp, x, config, kind, conv_bits, softmax_bits),), static_argnums=2)
    feed = jax.jit(lambda lp, x, chosen, mlp: _feed(
        lp, x, config, mlp, chosen, router_bits), static_argnums=3)
    head = jax.jit(lambda p, x, labels: _head(p, x, labels,
                                              config["norm_eps"]))
    return mixer, feed, head


def _sequence(params, config, parts, ids, labels, choice=None, program=None,
              state_bits=None):
    """One sequence, a part at a time: (what every part hands on [2 layers +
    2, S, H] on the host, each part over its norm: the embedding, the stream
    after each operator and each feed-forward, the final normed hidden states;
    those norms; the summed negative log-likelihood; per expert layer the
    ranked scores, the experts used and this file's own choice; how far each
    of the program's parts lies from this file's, over its norm).

    The loss is this file's own from the ids on. ``program`` [2 layers + 2,
    S, H] on the host, where given, is what the program's parts handed on:
    each part after the embedding is then computed from the program's state
    before it and divided by the norm of the program's state after it, so a
    part is held to float32 on its own input. ``parts`` are
    ``_compiled_parts``'; ``state_bits`` rounds what every part hands on to
    that many stored bits of mantissa.

    Every part is a call of its own and its result goes to the host at
    once, the program's stream comes from the host a part at a time: at 8192
    positions a part is 64 MB in float32, and the device holds the weights
    and Adam's moments of the step beside whatever this file keeps there."""
    mixer, feed, head = parts
    handed, norms, apart, routed = [], [], [], []

    def theirs(index):
        return jnp.asarray(program[index], jnp.float32)

    @jax.jit
    def settle(x, of):
        norm = jnp.maximum(jnp.linalg.norm(of), 1e-30)
        return x / norm, norm, jnp.linalg.norm(x - of) / norm

    def hand(compared):
        """Note one part: over the program's norm where there is one."""
        of = compared if program is None else theirs(len(handed))
        over, norm, far = settle(compared, of)
        handed.append(np.asarray(over))
        norms.append(float(norm))
        apart.append(float(far))

    def both(step, own):
        """``step`` on this file's own stream and, where the program's is
        given, on its state before this part: (own stream after, the result
        for the stream compared, whatever ``step`` returns beside it)."""
        after, *rest = step(own)
        after = _rounded(after, state_bits)
        if program is None:
            return after, after, rest
        compared, *rest = step(theirs(len(handed) - 1))
        return after, _rounded(compared, state_bits), rest

    x = _rounded(params["embed"][ids], state_bits)
    hand(x)
    for lp, (kind, mlp) in zip(params["layers"], layer_kinds(config)):
        # one compiled operator a type, one feed-forward a type
        x, compared, _ = both(lambda x: mixer(lp, x, kind), x)
        hand(compared)
        dense = mlp == "dense"
        chosen = None if dense or choice is None else choice[len(routed)]
        x, compared, (router,) = both(lambda x: feed(lp, x, chosen, mlp), x)
        hand(compared)
        if not dense:
            routed.append(router)
    slim = {k: params[k] for k in ("final_norm_g", "embed")}
    hidden, nll = head(slim, x, labels)
    hand(_rounded(hidden if program is None else head(
        slim, theirs(len(handed) - 1), labels)[0], state_bits))
    return (np.stack(handed), np.asarray(norms), nll, routed,
            np.asarray(apart))


def routing_check(ranked, used, own):
    """How the experts used differ from this file's own choice: (the number
    of (token, expert) pairs used that are not among its own k best, the
    largest shortfall). A token's shortfall is how far the worst expert used
    lies under the best one left out, ``s_out / s_used - 1``, in this file's
    float32 scores: 0 or less where the experts used are the k best."""
    least_used = jnp.min(jnp.where(used > 0, ranked, jnp.inf), axis=-1)
    most_out = jnp.max(jnp.where(used > 0, 0.0, ranked), axis=-1)
    return (int(jnp.sum((used > 0) & (own == 0))),
            float(jnp.max(most_out / least_used - 1.0)))


def loss_and_outputs(params, config, batch, state_bits=None, conv_bits=None,
                     softmax_bits=None, router_bits=None):
    """(training loss over the batch, what every part of the forward pass
    hands on [2 layers + 2, B, S, H], each part over its norm).

    The loss is this file's own pass from the ids on. Where the batch
    carries ``program_stream`` [2 layers + 2, B, S, H], what the program's
    parts handed on, each part here is computed in float32 from the
    program's state before it and divided by the norm of the program's state
    after it; without it the parts are this file's own stream over its own
    norms. Where it carries ``program_choice`` [expert layers, B, S, k], the
    experts the program chose for each token, they are first held to this
    file's own scores (``routing_check`` against ``ROUTER_MARGIN``; parts of
    NaN, which agree with nothing, where they fail) and then used in place
    of this file's own choice.

    The ``*_bits`` are the controls: the same pass with what every part
    hands on (``state_bits``), the convolution's gated signal, products and
    sum (``conv_bits``) or the softmax's logsumexp (``softmax_bits``) kept in
    that many stored bits of mantissa, with the program's own choice of experts so that only the
    arithmetic differs; and (``router_bits``) with the experts a router of
    that precision would choose in place of the program's, held to the same
    check: what a precision below the configuration's reads."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rows = batch["input_ids"].shape[0]
    given = [{} for _ in range(rows)]
    for name, key in (("choice", "program_choice"),
                      ("program", "program_stream")):
        if key in batch:                           # [L | P, B, ...] by row
            for i in range(rows):
                given[i][name] = np.asarray(batch[key])[:, i]
    parts = _compiled_parts(
        json.dumps({k: config[k] for k in _READ}, sort_keys=True),
        conv_bits, softmax_bits, router_bits)
    with jax.default_matmul_precision("highest"):
        done = [_sequence(params, config, parts,
                          jnp.asarray(batch["input_ids"][i]),
                          jnp.asarray(batch["labels"][i]),
                          state_bits=state_bits, **given[i])
                for i in range(rows)]
    # a part is compared over its norm in the whole batch, as the runner's
    # probe divides it: [parts, rows] -> each row's share
    norms = np.stack([d[1] for d in done], axis=1)
    share = norms / np.sqrt(np.sum(np.square(norms), axis=1, keepdims=True))
    outputs = np.stack([d[0] for d in done], axis=1)
    if rows > 1:
        outputs = outputs * share[:, :, None, None].astype(np.float32)
    ranked, used, own = (
        jnp.stack([jnp.concatenate([d[3][layer][j] for d in done])
                   for layer in range(len(done[0][3]))])
        for j in range(3))
    if "program_choice" in batch or router_bits is not None:
        differ, shortfall = routing_check(ranked, used, own)
        ok, total = shortfall <= ROUTER_MARGIN, int(jnp.sum(used))
        print(f"[reference] routing: {differ} of {total} (token, expert) "
              f"choices of the program are not among this file's own top-k "
              f"({100 * differ / total:.3f}%); largest shortfall "
              f"{shortfall:.5f} of the score, {ROUTER_MARGIN} allowed: "
              f"{'admissible' if ok else 'A WRONG ROUTER'}", flush=True)
        if not ok:
            outputs = np.full_like(outputs, np.nan)
    if "program_stream" in batch:
        each = np.sqrt(np.mean(np.square(np.stack([d[4] for d in done])),
                               axis=0))
        print("[reference] the program's parts, each on its own input, are "
              + " ".join(f"{100 * float(e):.3f}%" for e in each)
              + " from float32", flush=True)
    return sum(d[2] for d in done) / batch["input_ids"].size, outputs
