"""Kimi Linear's forward pass and training loss, plainly: ``jax.numpy``,
float32, matmuls at ``highest`` precision, one sequence at a time, no
kernels, no chunks: the delta rule is run one position a step.

Written from the Kimi Linear technical report (Moonshot AI 2025,
arXiv:2510.26692) and the released config. A block is ``h = x +
Mix(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``; the published layer numbers in
``linear_attn_config`` say which mixer a layer has (1-based), and the first
``first_k_dense_replace`` layers have a dense feed-forward.

**KDA layer**, per head of size d, from the normed input ``x``: q, k, v =
SiLU(Conv(x W)) with a causal depthwise convolution over positions (``y_t =
sum_j taps_j x_{t-K+1+j}``); q and k divided by their L2 norm over the head,
q also by sqrt(d); log decay per channel ``g_t = -exp(A_log) softplus((x
W_fa) W_fb + dt_bias)``; ``beta_t = sigmoid(x w_beta)`` a head. The state
starts at zero and, position by position, ``S_t = (I - beta_t k_t k_t^T)
Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``. The output
is ``(RMSNorm_head(o_t) * sigmoid((x W_ga) W_gb)) W_o``.

**MLA layer** without positions: ``q = x W_q`` as heads of 128 + 64; ``[c,
k_shared] = x W_kva`` (512 + 64); ``[k_nope | v]`` a head = ``RMSNorm(c)
W_kvb`` (128 + 128); a head's key is ``[k_nope ; k_shared]``, the 64 shared
channels the same for every head and, as the query's, not rotated; causal
``softmax(q k^T / sqrt(192)) v``, the scores dense, a block of queries at a
time; then ``W_o``.

**Experts**: ``s = sigmoid(x W_r)`` over all ``router_width`` experts; a
token takes the ``num_experts_per_token`` largest of ``s + bias`` with the
weights ``routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)``; the
experts ``experts_held`` = [first, n] are the ones this chip holds and the
only ones computed: what the others would add is left out, here as in the
program (``configs/kimi_linear_48b_a3b.json``: the deployment). One shared
expert on every token. An expert is ``W_down (silu(W_gate x) * W_up x)``.

After the last block a final RMSNorm and an untied head over the slice of the
vocabulary; the loss is the mean next-token cross-entropy. It shares no code
with ``paddle_tpu``; it reads the program's parameter tree by its key names.

**A choice is discrete, so it is checked as one** (as ``reference/olmoe.py``
does, and for its reason): the runner's probe leaves the experts the program
chose on the sample (``program_choice``), and this file (1) holds each choice
to its own float32 scores: no expert the program used may score less than one
it left out by more than ``ROUTER_MARGIN`` (relative, on ``s + bias``); then
(2) computes with those experts. Without ``program_choice`` it uses its own
top-k, which is what the float32 tests on the CPU compare with.
"""

import math

import jax
import jax.numpy as jnp

#: Largest relative error (Frobenius norm over everything compared, in
#: float32) at which the program still agrees with this file (my chip run,
#: PR 30; PERF.md section 6 has the readings).
#:
#: - ``outputs``: what every part of the forward pass hands on (the
#:   embedding, the stream after each mixer and each feed-forward, the final
#:   normed hidden states: 12 parts for 5 layers), each part computed here
#:   from the PROGRAM's state before it and each over its norm, so that one
#:   part's arithmetic is what is read and every part weighs the same. End
#:   to end the program is 1.85% to 2.2% from this file (five blocks of
#:   bfloat16 operands in a row), and one rounding of the final states to 4
#:   stored bits adds 1.556% to that in quadrature (2.3% to 2.6%): no limit
#:   stands between the two. A part at a time the program reads 0.299% to
#:   0.301% on the chip over nine seeds: 0.166% for a part that only stores
#:   its result in bfloat16 (the embedding, the final norm; the MLA mixer
#:   0.170%), 0.19% to 0.44% for the others, the first KDA mixer, which
#:   starts from the embedding alone, 0.64%. The precisions below the
#:   program's: what every part hands on in 4 stored bits of mantissa
#:   (``state_bits``) reads 1.326% on both seeds tried, 4.4 times the
#:   program; the delta rule's state in 4 stored bits after every position
#:   (``kda_state_bits``) reads 3.36% and 3.85%; bfloat16's 7 bits read
#:   0.166% and 0.276% in those places and pass, as they should: that is
#:   the program's own precision. 0.7% is 2.3 times the program's largest
#:   reading and 1.9 times under the lowest control's.
#: - ``loss``: float32 from the head's logits on, a mean over 8192
#:   log-probabilities near ln(20480), this file's own pass from the ids on
#:   (the one end-to-end number): 1.8e-6 to 5.4e-5 measured; the accepted
#:   cells' 3e-4 leaves five times that. The loss rounded to 4 bits reads
#:   1e-2; 4-bit states move it by 1e-4 only, so they fail by the outputs.
TOLERANCE = {"outputs": 7e-3, "loss": 3e-4}

#: How far under the best-scoring expert it left out the worst-scoring expert
#: the program used may lie, as a share of the score (``routing_check``), in
#: this file's scores of the program's own input to each router. On the chip
#: the largest shortfall of a sample of 8192 tokens in four expert layers is
#: 0.00086 to 0.00130 over nine seeds (0.20% to 0.22% of the 262 144 choices
#: differ: the program rounds the normed input to bfloat16 before its
#: float32 router); a router that ranks by
#: anything else leaves out experts that score twice what it used (shortfall
#: 1 and more). 0.01 is seven times the largest reading. The choices have to
#: be those of the pass whose states are compared (``kimi_linear.stages``
#: returns both): two separately compiled passes of the same program differ
#: in the experts of 2% to 6% of the tokens by the third expert layer, and
#: the choices of one read shortfalls of 0.03 to 0.05 on the states of the
#: other (my chip run, PR 30).
ROUTER_MARGIN = 0.01

QUERY_BLOCK = 512


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _conv_silu(x, taps):
    """x [S, C], taps [K, C]: y_t = sum_j taps_j x_{t-K+1+j}, then SiLU."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(k)))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(lp, x, config, state_bits=None):
    linear = config["linear_attn_config"]
    n, d = linear["num_heads"], linear["head_dim"]
    s = x.shape[0]
    q, k, v = (_conv_silu(x @ lp[f"{name}_w"], lp[f"{name}_conv"])
               .reshape(s, n, d) for name in "qkv")
    q, k = _l2(q) / math.sqrt(d), _l2(k)
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        (x @ lp["f_a"]) @ lp["f_b"] + lp["dt_bias"]).reshape(s, n, d)
    beta = jax.nn.sigmoid(x @ lp["beta_w"])                   # [S, n]

    def position(state, row):                # state [n, d_k, d_v]
        q_t, k_t, v_t, g_t, b_t = row
        state = jnp.exp(g_t)[:, :, None] * state
        kept = jnp.einsum("nk,nkv->nv", k_t, state)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - kept)[:, None, :]
        if state_bits is not None:
            state = round_mantissa(state, state_bits)
        return state, jnp.einsum("nk,nkv->nv", q_t, state)

    _, o = jax.lax.scan(position, jnp.zeros((n, d, d), jnp.float32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid((x @ lp["g_a"]) @ lp["g_b"]).reshape(s, n, d)
    o = _rms_norm(o, lp["o_norm_g"], config["rms_norm_eps"]) * gate
    return o.reshape(s, n * d) @ lp["o_w"]


def _mla(lp, x, config):
    s = x.shape[0]
    n, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope = config["qk_nope_head_dim"]
    q = (x @ lp["q_w"]).reshape(s, n, -1)
    kva = x @ lp["kva_w"]
    latent, shared = kva[:, :rank], kva[:, rank:]
    kv = (_rms_norm(latent, lp["kv_norm_g"], config["rms_norm_eps"])
          @ lp["kvb_w"]).reshape(s, n, -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(shared[:, None, :],
                                          (s, n, shared.shape[-1]))], axis=-1)
    v = kv[..., nope:]
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    at = jnp.arange(s + pad).reshape(-1, block)

    def queries(args):
        q_blk, at_blk = args
        scores = jnp.einsum("qnd,knd->nqk", q_blk, k) / math.sqrt(q.shape[-1])
        visible = jnp.arange(s)[None, :] <= at_blk[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", probs, v)

    ctx = jax.lax.map(queries, (q.reshape(-1, block, *q.shape[1:]), at))
    return ctx.reshape(s + pad, -1)[:s] @ lp["o_w"]


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _experts(lp, x, config, choice=None):
    """(output [S, H], the scores the choice is made on [S, E], the experts
    used [S, E] of 0/1, this file's own top-k [S, E] of 0/1). ``choice``
    [S, k], where given, names the experts to use in place of this file's
    own k best."""
    k = config["num_experts_per_token"]
    first, held = config["experts_held"]
    scores = jax.nn.sigmoid(x @ lp["router_w"])
    ranked = scores + lp["router_bias"]
    kth = jnp.sort(ranked, axis=-1)[:, -k][:, None]
    own = (ranked >= kth).astype(jnp.float32)
    used = own if choice is None else jnp.sum(
        jax.nn.one_hot(choice, scores.shape[-1], dtype=jnp.float32), axis=-2)
    weights = config["routed_scaling_factor"] * scores * used \
        / (jnp.sum(scores * used, axis=-1, keepdims=True) + 1e-20)

    def expert(e):
        w_gate, w_up, w_down, weight = e
        return weight[:, None] * _gated(x, w_gate, w_up, w_down)

    # the experts held here on every token, one at a time, masked by the
    # choice; the others' part is another chip's and is left out
    out = jnp.sum(jax.lax.map(expert, (
        lp["w_gate"], lp["w_up"], lp["w_down"],
        weights[:, first:first + held].T)), axis=0)
    out = out + _gated(x, lp["shared_gate"], lp["shared_up"],
                       lp["shared_down"])
    return out, ranked, used, own


def round_mantissa(x, bits):
    """x rounded to ``bits`` stored bits of mantissa (bfloat16 stores 7, fp8
    e4m3 stores 3)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 2.0 ** (bits + 1)) / 2.0 ** (bits + 1), e)


def _sequence(params, config, ids, labels, choice=None, program=None,
              state_bits=None, kda_state_bits=None):
    """One sequence: (what every part hands on [2 layers + 2, S, H]: the
    embedding, the stream after each mixer and each feed-forward, the final
    normed hidden states; the summed negative log-likelihood; per expert
    layer the ranked scores, the experts used and this file's own choice).

    The loss is this file's own from the ids on. ``program`` [2 layers + 2,
    S, H], where given, is what the program's parts handed on: each part
    after the embedding is then computed from the program's state before it
    and not from this file's own, so a part is held to float32 on its own
    input and the parts before it add nothing to its error; the scores the
    routing is checked on are those of the program's own input to each
    router. ``state_bits`` rounds what every part hands on to that many
    stored bits of mantissa, ``kda_state_bits`` the delta rule's state after
    every position."""
    eps = config["rms_norm_eps"]
    linear = config["linear_attn_config"]

    def hand_on(x):
        return x if state_bits is None else round_mantissa(x, state_bits)

    def part(f, gain, own):
        """``x + f(RMSNorm(x))`` on this file's own stream and, where the
        program's is given, on its state before this part: (own stream, what
        ``f`` returns beside the contribution, for the stream compared)."""
        before = own[None] if program is None \
            else jnp.stack([own, program[len(handed) - 1]])
        add, *rest = jax.vmap(lambda x: f(_rms_norm(x, gain, eps)))(before)
        after = hand_on(before + add)
        handed.append(after[-1])
        return after[0], [r[-1] for r in rest]

    x = hand_on(params["embed"][ids])
    handed, routed = [x], []
    for i, lp in enumerate(params["layers"]):
        if i + 1 in linear["kda_layers"]:
            x, _ = part(lambda n: (_kda(lp, n, config, kda_state_bits),),
                        lp["ln1_g"], x)
        elif i + 1 in linear["full_attn_layers"]:
            x, _ = part(lambda n: (_mla(lp, n, config),), lp["ln1_g"], x)
        else:
            raise ValueError(f"layer {i + 1} is in neither list of mixers")
        if i < config["first_k_dense_replace"]:
            x, _ = part(lambda n: (_gated(n, lp["ffn_gate"], lp["ffn_up"],
                                          lp["ffn_down"]),), lp["ln2_g"], x)
        else:
            chosen = None if choice is None else choice[len(routed)]
            x, router = part(lambda n: _experts(lp, n, config, chosen),
                             lp["ln2_g"], x)
            routed.append(router)
    hidden = _rms_norm(x, params["final_norm_g"], eps)
    handed.append(hand_on(hidden if program is None else _rms_norm(
        program[-2], params["final_norm_g"], eps)))
    logp = jax.nn.log_softmax(hidden @ params["head_w"], axis=-1)
    nll = -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return jnp.stack(handed), nll, routed


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


def over_norms(handed, of=None):
    """``handed`` [parts, ...] with each part divided by the Frobenius norm
    of that part of ``of`` (default: its own), so that every part weighs the
    same in one relative error over all of them."""
    of = handed if of is None else of
    norms = jnp.sqrt(jnp.sum(jnp.square(of.astype(jnp.float32)),
                             axis=tuple(range(1, of.ndim)), keepdims=True))
    return handed.astype(jnp.float32) / jnp.maximum(norms, 1e-30)


def loss_and_outputs(params, config, batch, state_bits=None,
                     kda_state_bits=None):
    """(training loss over the batch, what every part of the forward pass
    hands on [2 layers + 2, B, S, H], each part over its norm).

    The loss is this file's own pass from the ids on. Where the batch
    carries ``program_stream`` [2 layers + 2, B, S, H], what the program's
    parts handed on (the embedding, the stream after every mixer and
    feed-forward, the final normed hidden states), each part here is
    computed in float32 from the program's state before it and divided by
    the norm of the program's state after it (``_sequence`` says why);
    without it the parts are this file's own stream over its own norms.

    ``state_bits``, ``kda_state_bits``: the same pass with what every part
    hands on, or the delta rule's state after every position, kept in that
    many stored bits of mantissa: what a precision below the program's
    reads, which ``TOLERANCE`` has to refuse (4 bits, with the program's own
    choice of experts, so that only the arithmetic differs).

    Where the batch carries ``program_choice`` [expert layers, B, S, k], the
    experts the program chose for each token, they are first held to this
    file's own scores (``routing_check`` against ``ROUTER_MARGIN``; parts of
    NaN, which agree with nothing, where they fail) and then used in place
    of this file's own choice."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    given = {}
    if "program_choice" in batch:                  # [L, B, S, k] -> by row
        given["choice"] = jnp.swapaxes(
            jnp.asarray(batch["program_choice"]), 0, 1)
    if "program_stream" in batch:                  # [P, B, S, H] -> by row
        given["program"] = jnp.swapaxes(
            jnp.asarray(batch["program_stream"], jnp.float32), 0, 1)
    one = jax.jit(lambda p, ids, labels, given: _sequence(
        p, config, ids, labels, state_bits=state_bits,
        kda_state_bits=kda_state_bits, **given))
    with jax.default_matmul_precision("highest"):
        done = [one(params, batch["input_ids"][i], batch["labels"][i],
                    {k: v[i] for k, v in given.items()})
                for i in range(batch["input_ids"].shape[0])]
    handed = jnp.stack([d[0] for d in done], axis=1)
    ranked, used, own = (
        jnp.stack([jnp.concatenate([d[2][layer][j] for d in done])
                   for layer in range(len(done[0][2]))])
        for j in range(3))
    if "program_choice" in batch:
        differ, shortfall = routing_check(ranked, used, own)
        ok, total = shortfall <= ROUTER_MARGIN, int(jnp.sum(used))
        print(f"[reference] routing: {differ} of {total} (token, expert) "
              f"choices of the program are not among this file's own top-k "
              f"({100 * differ / total:.3f}%); largest shortfall "
              f"{shortfall:.5f} of the score, {ROUTER_MARGIN} allowed: "
              f"{'admissible' if ok else 'A WRONG ROUTER'}", flush=True)
        if not ok:
            handed = jnp.full_like(handed, jnp.nan)
    if "program" not in given:
        outputs = over_norms(handed)
    else:
        program = jnp.swapaxes(given["program"], 0, 1)
        outputs = over_norms(handed, program)
        each = jnp.sqrt(jnp.sum(jnp.square(outputs - over_norms(program)),
                                axis=(1, 2, 3)))
        print("[reference] the program's parts, each on its own input, are "
              + " ".join(f"{100 * float(e):.3f}%" for e in each)
              + " from float32", flush=True)
    return sum(d[1] for d in done) / batch["input_ids"].size, outputs
