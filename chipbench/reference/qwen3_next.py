"""Qwen3-Next's forward pass and training loss, plainly: ``jax.numpy``,
float32, matmuls at ``highest`` precision, one sequence at a time, no kernels
and no chunks: the delta rule is run one position a step, the scores of the
attention layer are the dense [S, S] ones, a block of queries at a time.

Written from the released ``Qwen/Qwen3-Next-80B-A3B-Instruct`` config (the
catalog row) and the issue's equations. ``norm(x; w) = x / sqrt(mean x^2 +
eps) * (1 + w)``. A block is ``h = x + Mix(norm(x; w1))``, ``y = h +
MoE(norm(h; w2))``; layer l has full attention where ``(l + 1) %
full_attention_interval == 0``, else Gated DeltaNet.

**Gated DeltaNet**, from the normed input ``x`` [S, H]: ``[q | k | v | z] = x
W_qkvz`` (16 x 128, 16 x 128, 32 x 128, 32 x 128 channels), ``[b | a] = x
W_ba`` (32 each); ``[q | k | v]`` through a causal depthwise convolution
(``y_t = sum_j taps_j x_{t-K+1+j}``) and SiLU; value head h reads key head
``h // 2``; q and k divided by their L2 norm over the head, q also by
sqrt(128); ``beta_t = sigmoid(b_t)`` and ``g_t = -exp(A_log) softplus(a_t +
dt_bias)``, one number a value head. The state starts at zero and, position by
position, ``S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t
v_t^T``, ``o_t = S_t^T q_t``. The output is ``(o_t / rms(o_t) * w_o *
silu(z_t)) W_out``, the norm a head with a plain gain.

**Gated attention**: ``[q | gate] = x W_q`` as 16 heads of 256 + 256, ``k = x
W_k`` and ``v = x W_v`` as 2 heads of 256; ``q = norm(q; w_q)``, ``k = norm(k;
w_k)`` a head; rotary positions in the rotate-half convention on the first 64
channels of every query and key head, the pair (i, i + 32) turned by ``p *
theta^(-2i / 64)``; query head i reads key/value head ``i // 8``; scores ``q .
k / 16`` over the keys ``j <= i``, softmax, times v; the context times
``sigmoid(gate)`` a channel; then ``W_o``.

**Experts**: ``p = softmax(x W_r)`` over all ``router_width`` experts; a token
takes the ``num_experts_per_tok`` largest with the weights ``p_e / (sum of the
chosen p + 1e-20)``; the experts ``experts_held`` = [first, n] are the ones
this chip holds and the only ones computed, here as in the program
(``configs/qwen3_next_80b_a3b.json``: the deployment); one shared expert on
every token, its output times ``sigmoid(x . w_s)``.

After the last block a final norm and an untied head over the slice of the
vocabulary; the loss is the mean next-token cross-entropy plus
``router_aux_loss_coef`` times the mean over the layers of ``E sum_e f_e
P_e`` (``f_e`` the share of the assignments expert e took, ``P_e`` its mean
probability, over all E). It shares no code with ``paddle_tpu``; it reads the
program's parameter tree by its key names.

**A choice is discrete, so it is checked as one**, and **a part is held to
float32 on its own input**: both as ``reference/laguna.py`` does and for its
reasons (its ``round_mantissa`` and ``routing_check`` are used here). The
runner's probe leaves the experts the program chose on the sample
(``program_choice``) and what every part of its forward pass handed on
(``program_stream``, in the program's bfloat16); this file holds each choice
to its own float32 probabilities (``ROUTER_MARGIN``), computes with those
experts, and computes every part from the program's state before it.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.laguna import (_gated, _rounded, round_mantissa,
                                        routing_check)

#: Largest relative error (Frobenius norm over everything compared, in
#: float32) at which the program still agrees with this file. The readings
#: are my chip runs', PR 38 (PERF.md section 6).
#:
#: - ``outputs``: what every part of the forward pass hands on (the
#:   embedding, the stream after each mixer and each expert layer, the final
#:   normed hidden states: 10 parts for 4 layers), each computed here from
#:   the PROGRAM's state before it and each over its norm. A part that only
#:   stores its result in bfloat16 reads 0.166%; the expert layers 0.17% to
#:   0.19%, the attention mixer 0.169%, the three delta-rule mixers 0.78%,
#:   0.56% and 0.46% (the first starts from the embedding alone); together
#:   the program reads 0.3656% to 0.3691% over thirteen seeds, an aggregate
#:   over 3e8 numbers that hardly moves with the seed. The precisions below
#:   the configuration's: the delta rule's log decay summed in bfloat16
#:   inside blocks of 64 positions (``decay_bits`` = 7) reads 0.535% and
#:   0.623% on the two seeds tried (it follows the decays the seed drew),
#:   what every part hands on in 4 stored bits of mantissa (``state_bits``)
#:   1.328% on both; bfloat16's 7 bits on what the parts hand on read 0.166%
#:   and pass, as they should: that is the program's own precision. 0.45% is
#:   1.22 times the program's largest reading and 1.19 times under the lowest
#:   control's.
#: - ``loss``: float32 from the head's logits on, a mean over 16 384
#:   log-probabilities near ln(19072) with the balancing term, this file's
#:   own pass from the ids on (the one end-to-end number): 3.7e-7 to 3.7e-5
#:   measured; the accepted cells' 3e-4 leaves eight times that. The loss
#:   rounded to 4 bits reads 2.2e-2.
TOLERANCE = {"outputs": 4.5e-3, "loss": 3e-4}

#: How far under the best-scoring expert it left out the worst-scoring expert
#: the program used may lie, as a share of the probability
#: (``routing_check``), in this file's probabilities of the program's own
#: input to each router. The program rounds the normed input to bfloat16
#: before its float32 router, and ten of 512 by a softmax meet closer pairs
#: than eight of 256 by a sigmoid: 0.217% to 0.228% of the 655 360 choices
#: of a sample differ, with a largest shortfall of 0.00606 to 0.00845 over
#: thirteen seeds. A router whose logits are kept in bfloat16 (``router_bits``
#: = 7, the control) reads 0.01556 and 0.01570, the step of bfloat16 between
#: 2 and 4, where the chosen experts' logits lie; with 10 stored bits it
#: reads 0.0017 and 0.0018. 0.012 is 1.4 times the program's largest reading
#: and 1.3 times under the control's.
ROUTER_MARGIN = 0.012

QUERY_BLOCK = 128
#: positions over which the control ``decay_bits`` sums the log decay before
#: it rounds the sum: a chunked delta rule's chunk
DECAY_BLOCK = 64


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _conv_silu(x, taps):
    """x [S, C], taps [K, C]: y_t = sum_j taps_j x_{t-K+1+j}, then SiLU."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(k)))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _summed_in(g, bits):
    """The control: the log decay g [S, n] as a rule that sums it inside
    blocks of ``DECAY_BLOCK`` positions and keeps the running sum in ``bits``
    stored bits of mantissa would see it: the differences of the rounded
    sums."""
    s, n = g.shape
    pad = (-s) % DECAY_BLOCK
    blocks = jnp.pad(g, ((0, pad), (0, 0))).reshape(-1, DECAY_BLOCK, n)
    sums = round_mantissa(jnp.cumsum(blocks, axis=1), bits)
    steps = jnp.diff(sums, axis=1, prepend=jnp.zeros_like(sums[:, :1]))
    return steps.reshape(-1, n)[:s]


def _delta_net(lp, x, config, decay_bits=None):
    s = x.shape[0]
    nk, nv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    kw, vw = nk * dk, nv * dv
    qkvz = x @ lp["qkvz_w"]
    mixed = _conv_silu(qkvz[:, :2 * kw + vw], lp["conv"])
    z = qkvz[:, 2 * kw + vw:].reshape(s, nv, dv)
    # value head h reads key head h // (nv / nk)
    q, k = (jnp.repeat(_l2(t.reshape(s, nk, dk)), nv // nk, axis=1)
            for t in (mixed[:, :kw], mixed[:, kw:2 * kw]))
    q = q / math.sqrt(dk)
    v = mixed[:, 2 * kw:].reshape(s, nv, dv)
    ba = x @ lp["ba_w"]
    beta = jax.nn.sigmoid(ba[:, :nv])                           # [S, nv]
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, nv:] + lp["dt_bias"])
    if decay_bits is not None:
        g = _summed_in(g, decay_bits)

    def position(state, row):                # state [nv, d_k, d_v]
        q_t, k_t, v_t, g_t, b_t = row
        state = jnp.exp(g_t)[:, None, None] * state
        kept = jnp.einsum("nk,nkv->nv", k_t, state)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - kept)[:, None, :]
        return state, jnp.einsum("nk,nkv->nv", q_t, state)

    _, o = jax.lax.scan(position, jnp.zeros((nv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + config["rms_norm_eps"]) * lp["o_norm_g"]
    return (o * jax.nn.silu(z)).reshape(s, vw) @ lp["out_w"]


def _rotate(x, cos, sin):
    """x [S, n, d]: the pairs (i, i + rot/2) of the first rot channels."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _attention(lp, x, config):
    s = x.shape[0]
    d, kv = config["head_dim"], config["num_key_value_heads"]
    n, eps = config["num_attention_heads"], config["rms_norm_eps"]
    rot = int(d * config["partial_rotary_factor"])
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * float(
        config["rope_theta"]) ** (-jnp.arange(0, rot, 2, dtype=jnp.float32)
                                  / rot)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    q_gate = (x @ lp["q_w"]).reshape(s, n, 2 * d)
    q = _rotate(_norm(q_gate[..., :d], lp["q_norm_w"], eps), cos, sin)
    k = _rotate(_norm((x @ lp["k_w"]).reshape(s, kv, d), lp["k_norm_w"],
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
        scores = jnp.where(jnp.arange(s)[None, :] <= at_blk[:, None], scores,
                           -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, axis=-1),
                          v)

    ctx = jax.lax.map(queries, (q, at)).reshape(s + pad, n, d)[:s]
    return (ctx * jax.nn.sigmoid(q_gate[..., d:])).reshape(s, n * d) \
        @ lp["o_w"]


def _experts(lp, x, config, choice=None, router_bits=None):
    """(output [S, H], the probabilities the choice is made on [S, E], the
    experts used [S, E] of 0/1, this file's own top-k [S, E] of 0/1).
    ``choice`` [S, k], where given, names the experts to use in place of this
    file's own k best; ``router_bits`` instead uses the k best of the
    router's logits rounded to that many bits (a router whose product is kept
    in that precision: the control)."""
    k = config["num_experts_per_tok"]
    first, held = config["experts_held"]
    logits = x @ lp["router_w"]
    probs = jax.nn.softmax(logits, axis=-1)

    def k_best(of):
        return jnp.sum(jax.nn.one_hot(jax.lax.top_k(of, k)[1], of.shape[-1],
                                      dtype=jnp.float32), axis=-2)

    own = k_best(probs)
    if router_bits is not None:
        used = k_best(round_mantissa(logits, router_bits))
    elif choice is not None:
        used = jnp.sum(jax.nn.one_hot(choice, probs.shape[-1],
                                      dtype=jnp.float32), axis=-2)
    else:
        used = own
    weights = probs * used \
        / (jnp.sum(probs * used, axis=-1, keepdims=True) + 1e-20)

    def expert(e):
        w_gate, w_up, w_down, weight = e
        return weight[:, None] * _gated(x, w_gate, w_up, w_down)

    # the experts held here on every token, one at a time, masked by the
    # choice; the others' part is another chip's and is left out
    shared = jax.nn.sigmoid(x @ lp["shared_scale_w"])[:, None] * _gated(
        x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    out, _ = jax.lax.scan(
        lambda total, e: (total + expert(e), None), shared,
        (lp["w_gate"], lp["w_up"], lp["w_down"],
         weights[:, first:first + held].T))
    return out, probs, used, own


def _mixer(lp, x, config, full, decay_bits=None):
    normed = _norm(x, lp["ln1_w"], config["rms_norm_eps"])
    return x + (_attention(lp, normed, config) if full
                else _delta_net(lp, normed, config, decay_bits))


def _feed(lp, x, config, choice=None, router_bits=None):
    """(the stream after the layer's experts, the router's (probabilities,
    experts used, own choice))."""
    normed = _norm(x, lp["ln2_w"], config["rms_norm_eps"])
    out, *router = _experts(lp, normed, config, choice, router_bits)
    return x + out, router


def _head(params, x, labels, eps):
    """(the final normed hidden states, the summed negative
    log-likelihood of ``labels``)."""
    hidden = _norm(x, params["final_norm_w"], eps)
    logp = jax.nn.log_softmax(hidden @ params["head_w"], axis=-1)
    return hidden, -jnp.sum(jnp.take_along_axis(logp, labels[:, None],
                                                axis=-1))


def _is_full(config, layer):
    return (layer + 1) % config["full_attention_interval"] == 0


def _balance(routers):
    """``E sum_e f_e P_e`` of one layer from its routers a sequence, each
    (probabilities [S, E], experts used [S, E], ...)."""
    counts = sum(jnp.sum(r[1], axis=0) for r in routers)
    mean_p = sum(jnp.mean(r[0], axis=0) for r in routers) / len(routers)
    return counts.shape[-1] * jnp.sum(counts / jnp.sum(counts) * mean_p)


def loss(params, config, batch):
    """The training loss alone, from the ids on, in one traceable piece:
    what the float32 tests differentiate."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def sequence(ids, labels):
        x, routers = params["embed"][ids], []
        for i, lp in enumerate(params["layers"]):
            x, router = _feed(lp, _mixer(lp, x, config, _is_full(config, i)),
                              config)
            routers.append(router)
        return _head(params, x, labels, config["rms_norm_eps"])[1], routers

    with jax.default_matmul_precision("highest"):
        done = [sequence(ids, labels) for ids, labels
                in zip(batch["input_ids"], batch["labels"])]
        balance = sum(_balance([d[1][i] for d in done])
                      for i in range(len(params["layers"]))) \
            / len(params["layers"])
        return sum(d[0] for d in done) / batch["input_ids"].size \
            + config["router_aux_loss_coef"] * balance


#: the keys of a configuration this file reads
_READ = ("head_dim", "num_key_value_heads", "num_attention_heads",
         "partial_rotary_factor", "rope_theta", "rms_norm_eps",
         "full_attention_interval", "linear_num_key_heads",
         "linear_num_value_heads", "linear_key_head_dim",
         "linear_value_head_dim", "num_experts_per_tok", "experts_held",
         "router_aux_loss_coef")


@functools.lru_cache(maxsize=8)
def _compiled_parts(frozen, decay_bits, router_bits):
    """The parts as jitted functions of the configuration ``frozen`` (its
    ``_READ`` keys as JSON), made once for every row, every seed and every
    control that shares them: (mixer(lp, x, full), feed(lp, x, chosen),
    head(params, x, labels))."""
    config = json.loads(frozen)
    mixer = jax.jit(lambda lp, x, full: (_mixer(lp, x, config, full,
                                                decay_bits),),
                    static_argnums=2)
    feed = jax.jit(lambda lp, x, chosen: _feed(lp, x, config, chosen,
                                               router_bits))
    head = jax.jit(lambda p, x, labels: _head(p, x, labels,
                                              config["rms_norm_eps"]))
    return mixer, feed, head


def _sequence(params, config, parts, ids, labels, choice=None, program=None,
              state_bits=None):
    """One sequence, a part at a time, as ``reference/laguna.py``'s
    ``_sequence``: (what every part hands on [2 layers + 2, S, H] on the
    host, each part over its norm; those norms; the summed negative
    log-likelihood; per layer the router's probabilities, the experts used
    and this file's own choice; how far each of the program's parts lies from
    this file's, over its norm). The loss is this file's own from the ids on;
    with ``program`` [2 layers + 2, S, H] each part after the embedding is
    computed from the program's state before it and divided by the norm of
    the program's state after it. Every part is a call of its own and its
    result goes to the host at once: the device holds the step's weights and
    Adam's moments beside whatever this file keeps there."""
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
    for i, lp in enumerate(params["layers"]):
        full = _is_full(config, i)
        x, compared, _ = both(lambda x: mixer(lp, x, full), x)
        hand(compared)
        chosen = None if choice is None else choice[i]
        x, compared, (router,) = both(lambda x: feed(lp, x, chosen), x)
        hand(compared)
        routed.append(router)
    slim = {k: params[k] for k in ("final_norm_w", "head_w")}
    hidden, nll = head(slim, x, labels)
    hand(_rounded(hidden if program is None else head(
        slim, theirs(len(handed) - 1), labels)[0], state_bits))
    return (np.stack(handed), np.asarray(norms), nll, routed,
            np.asarray(apart))


def loss_and_outputs(params, config, batch, state_bits=None,
                     decay_bits=None, router_bits=None):
    """(training loss over the batch, what every part of the forward pass
    hands on [2 layers + 2, B, S, H], each part over its norm).

    The loss is this file's own pass from the ids on, the balancing term on
    its own choices. Where the batch carries ``program_stream`` [2 layers +
    2, B, S, H], what the program's parts handed on, each part here is
    computed in float32 from the program's state before it and divided by the
    norm of the program's state after it; without it the parts are this
    file's own stream over its own norms. Where it carries ``program_choice``
    [layers, B, S, k], the experts the program chose for each token, they are
    first held to this file's own probabilities (``routing_check`` against
    ``ROUTER_MARGIN``; parts of NaN, which agree with nothing, where they
    fail) and then used in place of this file's own choice.

    The ``*_bits`` are the controls: the same pass with what every part hands
    on (``state_bits``) kept in that many stored bits of mantissa, or with
    the delta rule's log decay summed in that precision (``decay_bits``),
    with the program's own choice of experts so that only the arithmetic
    differs; and (``router_bits``) with the experts a router of that
    precision would choose in place of the program's, held to the same check:
    what a precision below the configuration's reads."""
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
        decay_bits, router_bits)
    layers = len(params["layers"])
    with jax.default_matmul_precision("highest"):
        done = [_sequence(params, config, parts,
                          jnp.asarray(batch["input_ids"][i]),
                          jnp.asarray(batch["labels"][i]),
                          state_bits=state_bits, **given[i])
                for i in range(rows)]
        # the balancing term on this file's own choices (a router's own k
        # best stand third in what ``_experts`` returns)
        balance = sum(float(_balance([(d[3][layer][0], d[3][layer][2])
                                      for d in done]))
                      for layer in range(layers)) / layers
    # a part is compared over its norm in the whole batch, as the runner's
    # probe divides it: [parts, rows] -> each row's share
    norms = np.stack([d[1] for d in done], axis=1)
    share = norms / np.sqrt(np.sum(np.square(norms), axis=1, keepdims=True))
    outputs = np.stack([d[0] for d in done], axis=1)
    if rows > 1:
        outputs = outputs * share[:, :, None, None].astype(np.float32)
    ranked, used, own = (
        jnp.stack([jnp.concatenate([d[3][layer][j] for d in done])
                   for layer in range(layers)])
        for j in range(3))
    if "program_choice" in batch or router_bits is not None:
        differ, shortfall = routing_check(ranked, used, own)
        ok, total = shortfall <= ROUTER_MARGIN, int(jnp.sum(used))
        print(f"[reference] routing: {differ} of {total} (token, expert) "
              f"choices of the program are not among this file's own top-k "
              f"({100 * differ / total:.3f}%); largest shortfall "
              f"{shortfall:.5f} of the probability, {ROUTER_MARGIN} allowed: "
              f"{'admissible' if ok else 'A WRONG ROUTER'}", flush=True)
        if not ok:
            outputs = np.full_like(outputs, np.nan)
    if "program_stream" in batch:
        each = np.sqrt(np.mean(np.square(np.stack([d[4] for d in done])),
                               axis=0))
        print("[reference] the program's parts, each on its own input, are "
              + " ".join(f"{100 * float(e):.3f}%" for e in each)
              + " from float32", flush=True)
    return (sum(d[2] for d in done) / batch["input_ids"].size
            + config["router_aux_loss_coef"] * balance), outputs
