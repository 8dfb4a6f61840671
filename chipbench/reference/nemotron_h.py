"""A Nemotron-H decoder's forward pass and two-term training loss, plainly:
``jax.numpy``, float32, matmuls at ``highest`` precision, one sequence at a
time, no kernels and no chunked form: **the state-space layer is the
recurrence itself, one position a step** (``lax.scan``), the scores of an
attention layer are the dense [S, S] ones, a block of queries at a time.

Written from the released ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` config
(the catalog row, ``model_type`` ``nemotron_h``) and the issue's equations.
Every layer is a mixer alone, ``x <- x + Mixer(norm(x; w))`` with ``norm(x;
w) = x / sqrt(mean x^2 + layer_norm_epsilon) * w``; the letter of
``hybrid_override_pattern`` names the mixer.

**M, Mamba-2.** ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``,
a causal depthwise convolution of ``conv_kernel`` taps (position t sees t - 3
to t); ``x [S, H, P], B [S, G, N], C [S, G, N]`` its parts, head h reads
group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log)
dt)``; ``h_t = a_t h_{t-1} + dt_t x_t B_t^T`` from ``h_0 = 0``, ``y_t = h_t
C_t + D x_t``; ``y = norm_group(y * silu(z)) * g`` with the mean square over
each group's channels; ``out = y W_out``.

**\\*, attention.** ``num_attention_heads`` queries of ``head_dim`` over
``num_key_value_heads`` keys and values, query head i reads key/value head
``i // (heads / kv heads)``, no positions, scores ``/ sqrt(head_dim)`` over
the keys ``j <= i``, softmax, times v, then ``W_o``.

**E, LatentMoE.** ``s = sigmoid(u W_r)`` over all the router's experts, a
token takes the ``num_experts_per_tok`` largest of ``s + bias`` with the
weights ``routed_scaling_factor s_e / (sum of the chosen s + 1e-20)``; ``l =
u W_down``; ``r = sum_e w_e W2_e relu(W1_e l)^2`` over the experts
``experts_held`` = [first, n], the ones this chip holds and the only ones
computed, here as in the program; ``out = r W_up + Ws2 relu(Ws1 u)^2``.

**MTP.** ``h' = [norm(h; w_h) ; norm(Emb(id_{t+1}); w_e)] W_eh`` with h the
stream behind the last layer (before the final norm), then the layers
``mtp_hybrid_override_pattern`` names, a final norm of its own and the main
model's head; ``loss = mean CE(logits_t, id_{t+1})`` over the S positions ``+
mtp_loss_weight x mean CE(logits'_t, id_{t+2})`` over the S - 1 that have one.

**Departures from the published description**, each also in the
configuration file: the head counts, the experts and the vocabulary are the
chip's share (a mixer's output is the part of the heads here through their
rows of ``W_out`` / ``W_o``: the absent heads' part is left out, as the
absent experts' is); what the config does not settle is ``assumed`` there
(no rotary positions, the gate before the norm, ``dt`` not clamped, the
router's rule, no norm on the latent, the shared expert on the hidden, the
module's form and weight); the divisor's 1e-20.

It shares no code with ``paddle_tpu``; it reads the program's parameter tree
by its key names.

**A choice is discrete, so it is checked as one**, and **a part is held to
float32 on its own input**: both as ``reference/deepseek_v3.py`` does and for
its reasons. The runner's probe leaves the experts the program chose on the
sample (``program_choice``) and what every part of its forward pass handed on
(``program_stream``, in the program's bfloat16: the embedding, the stream
after each layer, the final normed hidden states, then the module's merged
state, the stream after each of its layers and its own final normed hidden
states, the two heads' inputs among them); this file holds each choice to its
own float32 scores (``ROUTER_MARGIN``), computes with those experts, and
computes every part from the program's state before it. **The parameters are
held to float32 as parameters** (``parameters_are_float32``).
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

#: Largest relative error (Frobenius norm over everything compared, in
#: float32) at which the program still agrees with this file. The readings
#: are PERF.md's (section 6, PR 48).
#:
#: - ``outputs``: what every part of the forward pass hands on (17 parts for
#:   11 layers and the module: both heads' inputs among them), each computed
#:   here from the PROGRAM's state before it and each over its norm. A part
#:   that only stores its result in bfloat16 reads 0.166%; the program's
#:   parts together read 0.2584 to 0.2596% on the chip over eleven seeds (an
#:   M layer 0.33 to 0.43%, an E layer 0.18 to 0.25%: bfloat16 matmul
#:   operands at 4096 terms a sum). What every part hands on in 4 stored
#:   bits of mantissa (``state_bits``) reads 1.326% and must fail; bfloat16's
#:   7 bits there read 0.166% and pass, as they should: that is the
#:   program's own precision. 0.7% is 2.7 times the program's largest
#:   reading and 1.9 times under the lower control.
#: - ``loss``: float32 from the heads' logits on, two means over 8192 and
#:   8191 log-probabilities near ln(16384), this file's own pass from the ids
#:   on (the one end-to-end number): 1.4e-6 to 4.2e-5 on the chip, the first
#:   reading 4.0e-5; the accepted cells' 3e-4 leaves seven times of room over
#:   the largest. The loss itself rounded to bfloat16 reads 2.3e-3.
TOLERANCE = {"outputs": 7e-3, "loss": 3e-4}

#: How far under the best-scoring expert it left out the worst-scoring expert
#: the program used may lie, as a share of the score (``routing_check``), in
#: this file's scores of the program's own input to each router. The program
#: rounds the normed input to bfloat16 before its float32 router: 0.00147 to
#: 0.00232 on the chip over eleven seeds (0.9 to 1.9% of the 1 081 344
#: choices differ: 22 of 512 is a crowded boundary). A router whose scores
#: are rounded to bfloat16 before the choice (``router_bits`` = 7, the
#: control) reads 0.00438 and must read over it (8 bits: 0.00210). 1.5 times
#: the program's largest, 1.25 times under the control.
ROUTER_MARGIN = 0.0035

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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _mamba(lp, u, config):
    """The Mamba-2 mixer of the normed input u [S, hidden]: the recurrence,
    one position a step."""
    s = u.shape[0]
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    taps = config["conv_kernel"]
    inner, bc = heads * p, groups * n
    proj = u @ lp["in_w"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                  proj[:, 2 * inner + 2 * bc:])
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(lp["conv_b"] + sum(
        padded[i:i + s] * lp["conv_w"][i] for i in range(taps)))
    x = xbc[:, :inner].reshape(s, heads, p)
    B = jnp.repeat(xbc[:, inner:inner + bc].reshape(s, groups, n),
                   heads // groups, axis=1)
    C = jnp.repeat(xbc[:, inner + bc:].reshape(s, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                # [S, heads]
    decay = jnp.exp(-jnp.exp(lp["A_log"]) * dt)             # a_t <= 1

    def position(state, at):
        x_t, b_t, c_t, dt_t, a_t = at
        state = a_t[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(position, jnp.zeros((heads, p, n), jnp.float32),
                        (x, B, C, dt, decay))
    y = (y + lp["D"][:, None] * x).reshape(s, inner) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(s, groups, -1), lp["norm_g"].reshape(groups, -1),
                  config["layer_norm_epsilon"]).reshape(s, inner)
    return y @ lp["out_w"]


def _attention(lp, x, config, softmax_bits=None):
    s = x.shape[0]
    n, kv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    q = (x @ lp["q_w"]).reshape(s, n, d)
    k, v = (jnp.repeat((x @ lp[name]).reshape(s, kv, d), n // kv, axis=1)
            for name in ("k_w", "v_w"))
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, n, d)
    # a padded query stands at the last position: it sees keys, so nothing
    # of it is NaN, and its row is cut off below
    at = jnp.minimum(jnp.arange(s + pad), s - 1).reshape(-1, block)

    def queries(args):
        q_blk, at_blk = args
        scores = jnp.einsum("qnd,knd->nqk", q_blk, k) / math.sqrt(d)
        seen = jnp.arange(s)[None, :] <= at_blk[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        lse = _rounded(jax.nn.logsumexp(scores, axis=-1, keepdims=True),
                       softmax_bits)
        return jnp.einsum("nqk,knd->qnd", jnp.exp(scores - lse), v)

    ctx = jax.lax.map(queries, (q, at)).reshape(s + pad, -1)[:s]
    return ctx @ lp["o_w"]


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
        / (jnp.sum(scores * used, axis=-1, keepdims=True) + 1e-20)
    latent = x @ lp["latent_down"]

    def expert(e):
        w_up, w_down, weight = e
        return weight[:, None] * (_relu2(latent @ w_up) @ w_down)

    # the experts held here on every token, one at a time, masked by the
    # choice; the others' part is another chip's and is left out
    routed, _ = jax.lax.scan(
        lambda total, e: (total + expert(e), None), jnp.zeros_like(latent),
        (lp["w_up"], lp["w_down"], weights[:, first:first + held].T))
    shared = _relu2(x @ lp["shared_up"]) @ lp["shared_down"]
    return routed @ lp["latent_up"] + shared, ranked, used, own


def _layer(lp, x, config, kind, choice=None, router_bits=None,
           softmax_bits=None):
    """(the stream after a layer of ``kind``, the router's (ranked scores,
    experts used, own choice) or None)."""
    normed = _rms_norm(x, lp["ln_g"], config["layer_norm_epsilon"])
    if kind == "E":
        out, *router = _experts(lp, normed, config, choice, router_bits)
        return x + out, router
    if kind == "M":
        return x + _mamba(lp, normed, config), None
    return x + _attention(lp, normed, config, softmax_bits), None


def _merge(mp, h, e, eps):
    """The module's merged state: the stream's rows of ``eh_w`` first."""
    return jnp.concatenate([_rms_norm(h, mp["hnorm_g"], eps),
                            _rms_norm(e, mp["enorm_g"], eps)],
                           axis=-1) @ mp["eh_w"]


def _head(gain, head_w, x, labels, eps):
    """(the final normed hidden states, the negative log-likelihood of
    ``labels`` a position [S])."""
    hidden = _rms_norm(x, gain, eps)
    logp = jax.nn.log_softmax(hidden @ head_w, axis=-1)
    return hidden, -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def _kinds(config):
    """(the main layers' letters, the module's)."""
    return (config["hybrid_override_pattern"][:config["num_hidden_layers"]],
            config["mtp_hybrid_override_pattern"]
            if config["num_nextn_predict_layers"] else "")


def _two_terms(nll, nll_further):
    """One sequence's two rows of negative log-likelihoods as (sum of the
    first, sum of the second over the positions that have a token after
    next)."""
    return jnp.sum(nll), jnp.sum(nll_further[:-1])


def _total(config, sums, rows, positions):
    first, further = (sum(s[i] for s in sums) for i in range(2))
    return first / (rows * positions) \
        + config["mtp_loss_weight"] * further / (rows * (positions - 1))


def loss(params, config, batch):
    """The training loss alone, from the ids on, in one traceable piece:
    what the float32 tests differentiate."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    eps = config["layer_norm_epsilon"]
    main, module = _kinds(config)

    def sequence(ids, labels):
        x = params["embed"][ids]
        for lp, kind in zip(params["layers"], main):
            x = _layer(lp, x, config, kind)[0]
        nll = _head(params["final_norm_g"], params["head_w"], x, labels,
                    eps)[1]
        if not module:
            return jnp.sum(nll), 0.0
        mp = params["mtp"]
        x = _merge(mp, x, params["embed"][labels], eps)
        for lp, kind in zip(mp["layers"], module):
            x = _layer(lp, x, config, kind)[0]
        further = _head(mp["final_norm_g"], params["head_w"], x,
                        jnp.roll(labels, -1), eps)[1]
        return _two_terms(nll, further)

    with jax.default_matmul_precision("highest"):
        sums = [sequence(ids, labels) for ids, labels
                in zip(batch["input_ids"], batch["labels"])]
    return _total(config, sums, *batch["input_ids"].shape)


#: the keys of a configuration this file reads
_READ = ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
         "ssm_state_size", "conv_kernel", "num_attention_heads",
         "num_key_value_heads", "head_dim", "layer_norm_epsilon",
         "num_hidden_layers", "hybrid_override_pattern",
         "mtp_hybrid_override_pattern", "num_nextn_predict_layers",
         "num_experts_per_tok", "experts_held", "routed_scaling_factor",
         "mtp_loss_weight")


@functools.lru_cache(maxsize=8)
def _compiled_parts(frozen, softmax_bits, router_bits):
    """The parts as jitted functions of the configuration ``frozen`` (its
    ``_READ`` keys as JSON), made once for every row, every seed and every
    control that shares them: (layer(lp, x, chosen, kind), merge(mp, h, e),
    head(gain, head_w, x, labels))."""
    config = json.loads(frozen)
    eps = config["layer_norm_epsilon"]
    layer = jax.jit(lambda lp, x, chosen, kind: _layer(
        lp, x, config, kind, chosen, router_bits, softmax_bits),
        static_argnums=3)
    merge = jax.jit(lambda mp, h, e: _merge(mp, h, e, eps))
    head = jax.jit(lambda gain, head_w, x, labels: _head(
        gain, head_w, x, labels, eps))
    return layer, merge, head


def _sequence(params, config, parts, ids, labels, choice=None, program=None,
              state_bits=None):
    """One sequence, a part at a time: (what every part hands on [layers +
    6, S, H] on the host, each part over its norm; those norms; the two sums
    of negative log-likelihoods; per expert layer the ranked scores, the
    experts used and this file's own choice; how far each of the program's
    parts lies from this file's, over its norm).

    The loss is this file's own from the ids on. ``program`` [layers + 6, S,
    H] on the host, where given, is what the program's parts handed on: each
    part after the embedding is then computed from the program's state
    before it and divided by the norm of the program's state after it, so a
    part is held to float32 on its own input. ``state_bits`` rounds what
    every part hands on to that many stored bits of mantissa.

    Every part is a call of its own and its result goes to the host at
    once, the program's stream comes from the host a part at a time: at 8192
    positions a part is 128 MB in float32, and the device holds the weights
    and Adam's moments of the step beside whatever this file keeps there."""
    layer, merge, head = parts
    main, module = _kinds(config)
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

    def both(step, own, before):
        """``step`` on this file's own state and, where the program's is
        given, on its part number ``before``: (own result, the result
        compared, whatever ``step`` returns beside it)."""
        after, *rest = step(own)
        after = _rounded(after, state_bits)
        if program is None:
            return after, after, rest
        compared, *rest = step(theirs(before))
        return after, _rounded(compared, state_bits), rest

    def through(layers, kinds, x):
        for lp, kind in zip(layers, kinds):
            chosen = None if kind != "E" or choice is None \
                else choice[len(routed)]
            x, compared, (router,) = both(
                lambda x: layer(lp, x, chosen, kind), x, len(handed) - 1)
            hand(compared)
            if kind == "E":
                routed.append(router)
        return x

    x = _rounded(params["embed"][ids], state_bits)
    hand(x)
    x = through(params["layers"], main, x)
    last = len(handed) - 1              # the stream behind the last layer
    (hidden, nll), (compared, _) = (
        head(params["final_norm_g"], params["head_w"], state, labels)
        for state in (x, x if program is None else theirs(last)))
    hand(_rounded(compared, state_bits))
    sums = (jnp.sum(nll), 0.0)
    if module:
        mp = params["mtp"]
        slim = {k: mp[k] for k in ("hnorm_g", "enorm_g", "eh_w")}
        e = params["embed"][labels]
        x, compared, _ = both(lambda h: (merge(slim, h, e),), x, last)
        hand(compared)
        x = through(mp["layers"], module, x)
        (hidden, further), (compared, _) = (
            head(mp["final_norm_g"], params["head_w"], state,
                 jnp.roll(labels, -1))
            for state in (x, x if program is None
                          else theirs(len(handed) - 1)))
        hand(_rounded(compared, state_bits))
        sums = _two_terms(nll, further)
    return (np.stack(handed), np.asarray(norms), sums, routed,
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


def parameters_are_float32(params):
    """Whether every matrix of the tree is float32 and holds a value that
    bfloat16's 7 stored bits cannot: what float32 master parameters look
    like, whatever dtype carries them."""
    @jax.jit
    def beyond_bfloat16(a):
        # the low 16 bits of a float32 are what bfloat16 drops (read as
        # bits: a round trip through bfloat16 is one the compiler may skip)
        return jnp.any(jax.lax.bitcast_convert_type(a, jnp.uint32)
                       & jnp.uint32(0xFFFF))

    return all(a.dtype == jnp.float32 and bool(beyond_bfloat16(a))
               for a in jax.tree.leaves(params) if a.ndim >= 2)


def loss_and_outputs(params, config, batch, state_bits=None,
                     softmax_bits=None, router_bits=None):
    """(training loss over the batch, what every part of the forward pass
    hands on [layers + 6, B, S, H], each part over its norm; an array of
    jax's CPU device where the sample's ``program_stream`` came with one,
    so that the harness's comparison runs beside the host's memory).

    The loss is this file's own pass from the ids on. Where the batch
    carries ``program_stream`` [layers + 6, B, S, H], what the program's
    parts handed on, each part here is computed in float32 from the
    program's state before it and divided by the norm of the program's state
    after it; without it the parts are this file's own stream over its own
    norms. Where it carries ``program_choice`` [expert layers, B, S, k], the
    experts the program chose for each token, they are first held to this
    file's own scores (``routing_check`` against ``ROUTER_MARGIN``; parts of
    NaN, which agree with nothing, where they fail) and then used in place
    of this file's own choice. Parameters that are not float32 masters
    (``parameters_are_float32``) give parts of NaN too.

    The controls: the same pass with what every part hands on
    (``state_bits``) or the softmax's logsumexp (``softmax_bits``) kept in
    that many stored bits of mantissa, with the program's own choice of
    experts so that only the arithmetic differs; (``router_bits``) with the
    experts a router of that precision would choose in place of the
    program's, held to the same check: what a precision below the
    configuration's reads."""
    masters = parameters_are_float32(params)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rows, positions = batch["input_ids"].shape
    given = [{} for _ in range(rows)]
    for name, key in (("choice", "program_choice"),
                      ("program", "program_stream")):
        if key in batch:                           # [L | P, B, ...] by row
            for i in range(rows):
                given[i][name] = np.asarray(batch[key])[:, i]
    parts = _compiled_parts(
        json.dumps({k: config[k] for k in _READ}, sort_keys=True),
        softmax_bits, router_bits)
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
    if not masters:
        print("[reference] a parameter matrix holds nothing beyond "
              "bfloat16's 7 stored bits, or is not float32: NOT THE "
              "CONFIGURATION'S float32 PARAMETERS", flush=True)
        outputs = np.full_like(outputs, np.nan)
    if "program_stream" in batch:
        each = np.sqrt(np.mean(np.square(np.stack([d[4] for d in done])),
                               axis=0))
        print("[reference] the program's parts, each on its own input, are "
              + " ".join(f"{100 * float(e):.3f}%" for e in each)
              + " from float32", flush=True)
    try:        # beside the host's memory, as the runner's probe hands its
        outputs = jax.device_put(outputs, jax.devices("cpu")[0])
    except RuntimeError:
        pass
    return _total(config, [d[2] for d in done], rows, positions), outputs
