"""A DeepSeek-V3-shaped decoder's forward pass and training loss, plainly:
``jax.numpy``, float32, matmuls at ``highest`` precision, one sequence at a
time, no kernels: the scores of a layer are the dense [S, S] ones, a block of
queries at a time, and the interleaved pairs are turned directly.

Written from the released ``kakaocorp/kanana-2-30b-a3b-instruct-2601`` config
(the catalog row, ``model_type`` ``deepseek_v3``) and the issue's equations. A
block is ``h = x + Mix(norm(x; w1))``, ``y = h + FF(norm(h; w2))`` with
``norm(x; w) = x / sqrt(mean x^2 + rms_norm_eps) * w``.

**Mixer**, every layer, from the normed input ``x`` [S, H]: ``q = x W_q`` as
``num_attention_heads`` heads of ``[q_nope (qk_nope_head_dim) | q_pe
(qk_rope_head_dim)]``; ``x W_kva`` = ``[c (kv_lora_rank) | k_pe
(qk_rope_head_dim)]``, one ``k_pe`` for all heads; ``norm(c; w_kv) W_kvb`` as
heads of ``[k_nope | v (v_head_dim)]``. ``q_pe`` of every head and ``k_pe``
are turned by position: with ``rope_interleave`` the pair ``(2i, 2i + 1)``,
otherwise ``(i, i + rot/2)``, by the angle ``p * theta^(-2i / rot)``
(``rope_scaling`` null: no other factor). Scores ``(q_nope . k_nope + q_pe .
k_pe) / sqrt(qk_nope + qk_rope)`` over the keys ``j <= i``, softmax, times v,
then ``W_o``. No weight absorption, no query latent.

**Feed-forward**: a dense ``W_2 (silu(W_1 x) * W_3 x)`` in the first
``first_k_dense_replace`` layers; else ``s = sigmoid(x W_r)`` over all the
router's experts, a token takes the ``num_experts_per_tok`` largest of ``s +
bias`` with the weights ``routed_scaling_factor * s_e / (sum of the chosen s
+ 1e-20)`` on the experts' outputs; the experts ``experts_held`` = [first, n]
are the ones this chip holds and the only ones computed, here as in the
program (``configs/kanana_2_30b_a3b.json``: the deployment); plus the shared
feed-forward (``n_shared_experts`` experts side by side) on every token.

After the last block a final norm and an untied head over the slice of the
vocabulary; the loss is the mean next-token cross-entropy. It shares no code
with ``paddle_tpu``; it reads the program's parameter tree by its key names.

**A choice is discrete, so it is checked as one**, and **a part is held to
float32 on its own input**: both as ``reference/kimi_linear.py`` does and for
its reasons. The runner's probe leaves the experts the program chose on the
sample (``program_choice``) and what every part of its forward pass handed on
(``program_stream``, in the program's bfloat16); this file holds each choice
to its own float32 scores (``ROUTER_MARGIN``), computes with those experts,
and computes every part from the program's state before it. **The parameters
are held to float32 as parameters**: the program rounds them to bfloat16
matmul operands itself, so no forward pass tells float32 masters from
bfloat16 ones; a tree in which a matrix holds no value beyond bfloat16's 7
stored bits is refused (``parameters_are_float32``).
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

#: Largest relative error (Frobenius norm over everything compared, in
#: float32) at which the program still agrees with this file. The readings
#: are PERF.md's (section 6, PR 44).
#:
#: - ``outputs``: what every part of the forward pass hands on (the
#:   embedding, the stream after each mixer and each feed-forward, the final
#:   normed hidden states: 12 parts for 5 layers), each computed here from
#:   the PROGRAM's state before it and each over its norm. A part that only
#:   stores its result in bfloat16 reads 0.166%; the program's parts
#:   together read 0.2789 to 0.2863% on the chip over sixteen seeds (the
#:   first mixer 0.52 to 0.54%, the dense feed-forward 0.48%: bfloat16 matmul
#:   operands at 2048 and 6144 terms a sum; the others 0.20 to 0.25%). What
#:   every part hands on in 4 stored bits of mantissa (``state_bits``) reads
#:   1.327% and must fail; bfloat16's 7 bits there read 0.166% and pass, as
#:   they should: that is the program's own precision. 0.7% is 2.4 times the
#:   program's largest reading and 1.9 times under the lower control. What
#:   another model reads: the decoupled channels not turned (``rotation``
#:   "none") 9.3%, turned in the other pairing 8.0%, the scores not divided
#:   by the root of 192 (``score_scale`` 1) 153%.
#: - ``loss``: float32 from the head's logits on, a mean over 16 384
#:   log-probabilities near ln(16128), this file's own pass from the ids on
#:   (the one end-to-end number): 1.7e-5 to 2.3e-4 on the chip, the first
#:   reading 2.1e-5; the 4-bit control reads 3.6e-4. The accepted cells'
#:   3e-4 lies between them with 1.3 times of room to the largest (PERF.md
#:   section 7 asks the next ``benchmark`` PR for the repair).
TOLERANCE = {"outputs": 7e-3, "loss": 3e-4}

#: How far under the best-scoring expert it left out the worst-scoring expert
#: the program used may lie, as a share of the score (``routing_check``), in
#: this file's scores of the program's own input to each router. The program
#: rounds the normed input to bfloat16 before its float32 router: 0.00156 to
#: 0.00256 on the chip over sixteen seeds (0.35 to 0.40% of the 393 216
#: choices differ). A router whose scores are rounded to bfloat16 before the
#: choice (``router_bits`` = 7, the control) reads 0.00537 and must read over
#: it. Laguna's margin, for the same sigmoid router.
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


def rotate(x, positions, theta, interleaved):
    """x [S, n, rot] turned by position: the pair ``(2i, 2i + 1)``
    (``interleaved``) or ``(i, i + rot/2)`` by ``p * theta^(-2i / rot)``."""
    rot = x.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] \
        * theta ** (-2.0 * jnp.arange(rot // 2, dtype=jnp.float32) / rot)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(lp, x, config, softmax_bits=None, rotation="config",
               score_scale=None):
    """``rotation``: "config" (the pairing ``rope_interleave`` names),
    "other" (the pairing it does not name) or "none"; ``score_scale``
    replaces ``1 / sqrt(qk_nope + qk_rope)``: the structural controls."""
    s = x.shape[0]
    n, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    q = (x @ lp["q_w"]).reshape(s, n, nope + rope)
    kva = x @ lp["kva_w"]
    latent, k_pe = kva[:, :rank], kva[:, None, rank:]   # one key [S, 1, rope]
    kv = (_rms_norm(latent, lp["kv_norm_g"], config["rms_norm_eps"])
          @ lp["kvb_w"]).reshape(s, n, -1)
    q_pe = q[..., nope:]
    if rotation != "none":
        interleaved = bool(config["rope_interleave"]) == (rotation == "config")
        at, theta = jnp.arange(s), float(config["rope_theta"])
        q_pe = rotate(q_pe, at, theta, interleaved)
        k_pe = rotate(k_pe, at, theta, interleaved)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (s, n, rope))], axis=-1)
    v = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope) if score_scale is None \
        else score_scale
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, n,
                                                       nope + rope)
    # a padded query stands at the last position: it sees keys, so nothing
    # of it is NaN on the way back, and its row is cut off below
    at = jnp.minimum(jnp.arange(s + pad), s - 1).reshape(-1, block)

    def queries(args):
        q_blk, at_blk = args
        scores = jnp.einsum("qnd,knd->nqk", q_blk, k) * scale
        seen = jnp.arange(s)[None, :] <= at_blk[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        lse = _rounded(jax.nn.logsumexp(scores, axis=-1, keepdims=True),
                       softmax_bits)
        return jnp.einsum("nqk,knd->qnd", jnp.exp(scores - lse), v)

    ctx = jax.lax.map(queries, (q, at)).reshape(s + pad, -1)[:s]
    return ctx @ lp["o_w"]


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
        / (jnp.sum(scores * used, axis=-1, keepdims=True) + 1e-20)

    def expert(e):
        w_gate, w_up, w_down, weight = e
        return weight[:, None] * _gated(x, w_gate, w_up, w_down)

    # the experts held here on every token, one at a time, masked by the
    # choice; the others' part is another chip's and is left out
    out, _ = jax.lax.scan(
        lambda total, e: (total + expert(e), None), jnp.zeros_like(x),
        (lp["w_gate"], lp["w_up"], lp["w_down"],
         weights[:, first:first + held].T))
    shared = _gated(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return out + shared, ranked, used, own


def _mixer(lp, x, config, **controls):
    normed = _rms_norm(x, lp["ln1_g"], config["rms_norm_eps"])
    return x + _attention(lp, normed, config, **controls)


def _feed(lp, x, config, dense, choice=None, router_bits=None):
    """(the stream after the layer's feed-forward, the router's (ranked
    scores, experts used, own choice) or None for a ``dense`` layer)."""
    normed = _rms_norm(x, lp["ln2_g"], config["rms_norm_eps"])
    if dense:
        return x + _gated(normed, lp["ffn_gate"], lp["ffn_up"],
                          lp["ffn_down"]), None
    out, *router = _experts(lp, normed, config, choice, router_bits)
    return x + out, router


def _head(params, x, labels, eps):
    """(the final normed hidden states, the summed negative
    log-likelihood of ``labels``)."""
    hidden = _rms_norm(x, params["final_norm_g"], eps)
    logp = jax.nn.log_softmax(hidden @ params["head_w"], axis=-1)
    return hidden, -jnp.sum(jnp.take_along_axis(logp, labels[:, None],
                                                axis=-1))


def _layers(params, config):
    """[(a layer's parameters, whether its feed-forward is the dense one)]
    of the layers the configuration runs."""
    return [(lp, layer < config["first_k_dense_replace"]) for layer, lp
            in enumerate(params["layers"][:config["num_hidden_layers"]])]


def loss(params, config, batch):
    """The training loss alone, from the ids on, in one traceable piece:
    what the float32 tests differentiate."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def nll(ids, labels):
        x = params["embed"][ids]
        for lp, dense in _layers(params, config):
            x = _feed(lp, _mixer(lp, x, config), config, dense)[0]
        return _head(params, x, labels, config["rms_norm_eps"])[1]

    with jax.default_matmul_precision("highest"):
        return sum(nll(ids, labels) for ids, labels
                   in zip(batch["input_ids"], batch["labels"])) \
            / batch["input_ids"].size


#: the keys of a configuration this file reads
_READ = ("hidden_size", "num_attention_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
         "rope_interleave", "rms_norm_eps", "num_hidden_layers",
         "first_k_dense_replace", "num_experts_per_tok", "experts_held",
         "routed_scaling_factor")


@functools.lru_cache(maxsize=8)
def _compiled_parts(frozen, softmax_bits, router_bits, rotation,
                    score_scale):
    """The parts as jitted functions of the configuration ``frozen`` (its
    ``_READ`` keys as JSON), made once for every row, every seed and every
    control that shares them: (mixer(lp, x), feed(lp, x, chosen, dense),
    head(params, x, labels))."""
    config = json.loads(frozen)
    mixer = jax.jit(lambda lp, x: (_mixer(
        lp, x, config, softmax_bits=softmax_bits, rotation=rotation,
        score_scale=score_scale),))
    feed = jax.jit(lambda lp, x, chosen, dense: _feed(
        lp, x, config, dense, chosen, router_bits), static_argnums=3)
    head = jax.jit(lambda p, x, labels: _head(p, x, labels,
                                              config["rms_norm_eps"]))
    return mixer, feed, head


def _sequence(params, config, parts, ids, labels, choice=None, program=None,
              state_bits=None):
    """One sequence, a part at a time: (what every part hands on [2 layers +
    2, S, H] on the host, each part over its norm: the embedding, the stream
    after each mixer and each feed-forward, the final normed hidden states;
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
    once, the program's stream comes from the host a part at a time: at
    16 384 positions a part is 128 MB in float32, and the device holds the
    weights and Adam's moments of the step beside whatever this file keeps
    there."""
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
    for lp, dense in _layers(params, config):
        x, compared, _ = both(lambda x: mixer(lp, x), x)
        hand(compared)
        chosen = None if dense or choice is None else choice[len(routed)]
        x, compared, (router,) = both(lambda x: feed(lp, x, chosen, dense), x)
        hand(compared)
        if not dense:
            routed.append(router)
    slim = {k: params[k] for k in ("final_norm_g", "head_w")}
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
                     softmax_bits=None, router_bits=None, rotation="config",
                     score_scale=None):
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
    of this file's own choice. Parameters that are not float32 masters
    (``parameters_are_float32``) give parts of NaN too.

    The controls: the same pass with what every part hands on
    (``state_bits``) or the softmax's logsumexp (``softmax_bits``) kept in
    that many stored bits of mantissa, with the program's own choice of
    experts so that only the arithmetic differs; (``router_bits``) with the
    experts a router of that precision would choose in place of the
    program's, held to the same check: what a precision below the
    configuration's reads; and what a different model reads: ``rotation``
    "none" (the decoupled channels not turned) or "other" (turned in the
    pairing the configuration does not name), ``score_scale`` in place of
    ``1 / sqrt(192)``."""
    masters = parameters_are_float32(params)
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
        softmax_bits, router_bits, rotation, score_scale)
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
    return sum(d[2] for d in done) / batch["input_ids"].size, outputs
