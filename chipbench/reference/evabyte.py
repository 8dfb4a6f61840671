"""An EvaByte decoder's forward pass and eight-head training loss, plainly:
``jax.numpy``, float32, matmuls at ``highest`` precision, one sequence at a
time, no kernels, no online softmax and no window loop: **the visibility of
EVA attention is a dense mask over [S + S / chunk] keys**, a block of
queries at a time.

Written from the released ``EvaByte/EvaByte`` config (the catalog row,
``model_type`` ``evabyte``, ``attention_class`` ``eva``) and the equations of
ISSUE 53 (EVA: Zheng, Yuan, Wang and Kong 2023, arXiv:2302.04542, sec. 4).
With H heads of D = hidden / H, a window of W and a chunk of C positions,
position i in window ``i // W``:

**Block.** ``x <- x + Attn(norm(x; w1))``, ``x <- x + FFN(norm(x; w2))``,
``norm(x; w) = x / sqrt(mean x^2 + rms_norm_eps) * (1 + w)``
(``norm_add_unit_offset``), ``FFN(h) = (silu(h W_gate) * (h W_up)) W_down``.

**Attention.** ``q = h W_q``, ``k = h W_k``, ``v = h W_v`` in H heads; q and
k rotated over all D channels in the halves convention (channel i with i + D
/ 2, angle ``p * rope_theta^(-2 i / D)`` at position p). Per head with its
vectors ``mu`` and ``phi``, for each chunk c: ``ksum_c = sum_j a_j k_j``, ``a
= softmax_{j in c}(k_j . mu)``; ``vsum_c = sum_j b_j v_j``, ``b = softmax_{j
in c}(k_j . phi)`` (k after rotation). Query i scores the keys j of its own
window with ``j <= i`` and the summaries of every chunk ``c < (W / C) (i //
W)`` (all chunks of earlier windows, none of its own), each ``/ sqrt(D)``;
ONE softmax over the union; ``o_i = sum_j p_ij v_j + sum_c p_ic vsum_c``;
then ``W_o``.

**Head.** ``logits = norm(x; w_f) W_head`` viewed [S, heads, vocab]; head i
at position t is scored against ``labels[t + i]``, the id i + 1 positions
after the input's, over the S - i positions that have one; the loss is the
mean of the heads' mean cross-entropies.

**Departures from the published description**, each also in the
configuration file: what the config does not settle is ``assumed`` there
(the summaries' form, every initial value, the heads' equal weights, the
rotation's convention).

It shares no code with ``paddle_tpu``; it reads the program's parameter tree
by its key names.

**A part is held to float32 on its own input, over the norm of what it
adds.** The runner's probe leaves what every part of the program's forward
pass handed on (``program_stream``: the embedding, the float32 stream after
each layer's mixer and after its feed-forward, the final normed hidden
states) and the eight heads' logits (``program_logits``). This file computes
every part in float32 from the PROGRAM's state before it, and both sides
divide a stream part by the norm of the program's *update* (the part less
the part before it): a float32 residual stream is there to carry a small
update on a large stream, and an error is read against what the layer added,
not against what it passed through. The embedding, the hidden states and the
logits are over their own norms. The loss is this file's own pass from the
ids on. **The parameters are held to float32 as parameters**
(``parameters_are_float32``).
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

#: Largest relative error (Frobenius norm over everything compared, in
#: float32) at which the program still agrees with this file. The readings
#: are PERF.md's (section 6, PR 53).
#:
#: - ``outputs``: the embedding, the stream after each of the 2 x layers
#:   parts (each computed here from the PROGRAM's state before it, each over
#:   the norm of the program's update), the final normed hidden states and
#:   the eight heads' logits (over their norms): 11 parts for 4 layers, one
#:   norm over all of them. The program's parts together read 0.1919 to
#:   0.2024% on the chip over thirteen seeds (a mixer 0.28 to 0.39% of what it
#:   added, a feed-forward 0.45 to 0.47%: bfloat16 matmul operands at 4096
#:   and 11 008 terms a sum; the hidden states and the logits 0.17%, the
#:   head's bfloat16 operand). What every part hands on in 4 stored bits of
#:   mantissa (``state_bits`` = 4) is 1.32% of each part's own norm and so of
#:   the whole, and summaries left out of the softmax's denominator read
#:   some 770%. 0.6% is three times the program's largest reading and 2.2
#:   times under the lower control. The tight limits on what this norm is
#:   made of are ``MIXER_TOLERANCE`` and ``stream_is_float32`` below.
#: - ``loss``: float32 from the logits on, eight means over 16384 - i
#:   log-probabilities near 6.07, this file's own pass from the ids on (the
#:   one end-to-end number): 0 to 1.59e-4 on the chip over thirteen seeds
#:   (the median 5.2e-5, the first reading 9.0e-6: the weights' rounding to
#:   bfloat16 moves every position's loss the same way, so the mean over
#:   131 044 terms does not average it out); the accepted cells' 3e-4 leaves
#:   1.9 times of room over the largest, as it leaves Laguna's 1.7. A loss kept in bfloat16 (steps of 1/32 at 6.07) is off
#:   by up to 2.6e-3.
TOLERANCE = {"outputs": 6e-3, "loss": 3e-4}

#: How far a mixer's part of the program's stream may lie from this file's
#: float32 mixer of the same input, over the norm of what the mixer added
#: (``loss_and_outputs`` hands out parts of NaN beyond it). The program's
#: bfloat16 operands read 0.373 to 0.388% there in their largest mixer (the
#: first layer's; the others 0.28 to 0.30%) on the chip over thirteen seeds; a
#: softmax whose logsumexp keeps bfloat16's 7 stored bits (``softmax_bits``
#: = 7: steps of 1/32 on a logsumexp between 4 and 8, every probability of a
#: row off by the same factor) reads 1.06 to 1.12% in every mixer, which the
#: one norm over all the parts would hide behind the feed-forwards' 0.46%.
#: 0.7% is 1.8 times the program's largest reading and 1.5 times under the
#: control's smallest.
MIXER_TOLERANCE = 7e-3

QUERY_BLOCK = 128
#: positions a block of the feed-forward at ``highest`` precision: its three
#: [block, intermediate] float32 intermediates beside the step's state
FFN_BLOCK = 4096


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def round_mantissa(x, bits):
    """x rounded to ``bits`` stored bits of mantissa (bfloat16 stores 7)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 2.0 ** (bits + 1)) / 2.0 ** (bits + 1), e)


def _rounded(x, bits):
    return x if bits is None else round_mantissa(x, bits)


def _sizes(config):
    heads = config["num_attention_heads"]
    return heads, config["hidden_size"] // heads


def _rotated(x, theta):
    """x [S, H, D] turned in the halves convention at positions 0 .. S - 1."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # [S, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _summaries(k, v, mu, phi, chunk):
    """(ksum, vsum) [S / chunk, H, D] of k, v [S, H, D]."""
    s, h, d = k.shape
    kc, vc = (t.reshape(s // chunk, chunk, h, d) for t in (k, v))
    a = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, mu), axis=1)
    b = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi), axis=1)
    return (jnp.sum(a[..., None] * kc, axis=1),
            jnp.sum(b[..., None] * vc, axis=1))


def _attention(lp, x, config, softmax_bits=None, summaries_in_sum=True):
    """EVA attention of the normed input x [S, hidden]. The controls:
    ``softmax_bits`` keeps the softmax's logsumexp in that many stored bits
    of mantissa; ``summaries_in_sum`` false leaves the summaries out of the
    softmax's denominator (their weights are then not normalised with the
    tokens')."""
    s = x.shape[0]
    n, d = _sizes(config)
    window, chunk = config["window_size"], config["chunk_size"]
    if s % chunk or window % chunk:
        raise ValueError(f"{s} positions or a window of {window} are no "
                         f"whole chunks of {chunk}")
    q, k, v = ((x @ lp[name]).reshape(s, n, d)
               for name in ("q_w", "k_w", "v_w"))
    q, k = (_rotated(t, float(config["rope_theta"])) for t in (q, k))
    ksum, vsum = _summaries(k, v, lp["mu"], lp["phi"], chunk)
    keys = jnp.concatenate([k, ksum])                # [S + S / chunk, H, D]
    values = jnp.concatenate([v, vsum])
    block = min(QUERY_BLOCK, s)
    pad = (-s) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, n, d)
    # a padded query stands at the last position: it sees keys, so nothing
    # of it is NaN, and its row is cut off below
    at = jnp.minimum(jnp.arange(s + pad), s - 1).reshape(-1, block)
    token, chunk_of = jnp.arange(s), jnp.arange(s // chunk)

    def queries(args):
        q_blk, i = args
        i = i[:, None]
        seen = jnp.concatenate(
            [(token <= i) & (token // window == i // window),
             chunk_of < (window // chunk) * (i // window)], axis=1)
        scores = jnp.einsum("qnd,knd->nqk", q_blk, keys) / math.sqrt(d)
        scores = jnp.where(seen, scores, -jnp.inf)
        summed = scores if summaries_in_sum else scores[..., :s]
        lse = _rounded(jax.nn.logsumexp(summed, axis=-1, keepdims=True),
                       softmax_bits)
        return jnp.einsum("nqk,knd->qnd", jnp.exp(scores - lse), values)

    ctx = jax.lax.map(queries, (q, at)).reshape(s + pad, -1)[:s]
    return ctx @ lp["o_w"]


def _ffn(lp, x):
    def rows(h):
        return (jax.nn.silu(h @ lp["ffn_gate"]) * (h @ lp["ffn_up"])) \
            @ lp["ffn_down"]

    s = x.shape[0]
    if s <= FFN_BLOCK or s % FFN_BLOCK:
        return rows(x)
    return jax.lax.map(rows, x.reshape(-1, FFN_BLOCK, x.shape[-1])) \
        .reshape(x.shape)


def _mixer(lp, x, config, **controls):
    eps = config["rms_norm_eps"]
    return x + _attention(lp, _rms_norm(x, lp["ln1_w"], eps), config,
                          **controls)


def _feed(lp, x, config):
    return x + _ffn(lp, _rms_norm(x, lp["ln2_w"], config["rms_norm_eps"]))


def _logits(params, hidden, config):
    """[S, heads, vocab] of the final normed hidden states."""
    return (hidden @ params["head_w"]).reshape(
        hidden.shape[0], config["num_pred_heads"], config["vocab_size"])


def _head_sums(logits, labels):
    """[heads] the sum of head i's negative log-likelihoods of
    ``labels[t + i]`` over the S - i positions t that have one."""
    s, heads, _ = logits.shape
    logp = jax.nn.log_softmax(logits, axis=-1)
    sums = []
    for i in range(heads):
        picked = jnp.take_along_axis(logp[:s - i, i], labels[i:, None],
                                     axis=-1)[:, 0]
        sums.append(-jnp.sum(picked))
    return jnp.stack(sums)


def _head_means(sums, rows, positions):
    """The heads' cross-entropies [heads] of the rows' sums [rows, heads]."""
    heads = sums.shape[-1]
    return jnp.sum(sums, axis=0) / (rows * (positions - jnp.arange(heads)))


def head_losses(params, config, batch):
    """[heads] each head's mean cross-entropy, from the ids on, in one
    traceable piece; ``loss`` is their mean."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    eps = config["rms_norm_eps"]

    def sequence(ids, labels):
        x = params["embed"][ids]
        for lp in params["layers"]:
            x = _feed(lp, _mixer(lp, x, config), config)
        hidden = _rms_norm(x, params["final_norm_w"], eps)
        return _head_sums(_logits(params, hidden, config), labels)

    with jax.default_matmul_precision("highest"):
        sums = jnp.stack([sequence(ids, labels) for ids, labels
                          in zip(batch["input_ids"], batch["labels"])])
    return _head_means(sums, *batch["input_ids"].shape)


def loss(params, config, batch):
    """The training loss alone: what the float32 tests differentiate."""
    return jnp.mean(head_losses(params, config, batch))


#: the keys of a configuration this file reads
_READ = ("hidden_size", "num_attention_heads", "window_size", "chunk_size",
         "num_pred_heads", "vocab_size", "rope_theta", "rms_norm_eps",
         "num_hidden_layers")


@functools.lru_cache(maxsize=8)
def _compiled_parts(frozen, softmax_bits, summaries_in_sum):
    """The parts as jitted functions of the configuration ``frozen`` (its
    ``_READ`` keys as JSON), made once for every row, every seed and every
    control that shares them: (mixer(lp, x), feed(lp, x), head(params,
    x) -> (hidden, logits), sums(logits, labels))."""
    config = json.loads(frozen)
    eps = config["rms_norm_eps"]
    mixer = jax.jit(lambda lp, x: _mixer(
        lp, x, config, softmax_bits=softmax_bits,
        summaries_in_sum=summaries_in_sum))
    feed = jax.jit(lambda lp, x: _feed(lp, x, config))
    final = jax.jit(lambda w, x: _rms_norm(x, w, eps))
    logits = jax.jit(lambda head_w, hidden: _logits({"head_w": head_w},
                                                    hidden, config))
    return mixer, feed, final, logits, jax.jit(_head_sums)


def _sequence(params, parts, ids, labels, program=None, state_bits=None):
    """One sequence, a part at a time: (what every part hands on, a list of
    host arrays: the embedding, the stream after each layer's mixer and
    feed-forward, the final normed hidden states [S, hidden] each, then the
    logits [S, heads, vocab]; the heads' sums of negative log-likelihoods).

    The sums are this file's own from the ids on. ``program``, where given,
    is what the program's parts handed on, the same list: each part after
    the embedding is then computed from the program's state before it, so a
    part is held to float32 on its own input. ``state_bits`` rounds what
    every part hands on to that many stored bits of mantissa.

    Every part is a call of its own and its result goes to the host at
    once: at 16384 positions a part is 256 MB in float32, and the device
    holds the weights and Adam's moments of the step beside whatever this
    file keeps there."""
    mixer, feed, final, logits, sums = parts
    handed = []

    def theirs(index):
        return jnp.asarray(program[index], jnp.float32)

    def both(step, own):
        """``step`` on this file's own state and, where the program's is
        given, on its part before the one being made."""
        after = _rounded(step(own), state_bits)
        compared = after if program is None else _rounded(
            step(theirs(len(handed) - 1)), state_bits)
        handed.append(np.asarray(compared))
        return after

    x = _rounded(params["embed"][ids], state_bits)
    handed.append(np.asarray(x))
    for lp in params["layers"]:
        x = both(lambda x: mixer(lp, x), x)
        x = both(lambda x: feed(lp, x), x)
    hidden = both(lambda x: final(params["final_norm_w"], x), x)
    own = both(lambda h: logits(params["head_w"], h), hidden)
    return handed, sums(own, labels)


def norms(of):
    """What each part of ``of`` (a list: the embedding, the stream's parts,
    the hidden states, the logits; each with the batch leading) is divided
    by: a stream part by the norm of its update, the part less the part
    before it; the first and the last two by their own norm."""
    def norm(a):
        return max(float(np.sqrt(np.sum(np.square(a, dtype=np.float64)))),
                   1e-30)

    of = [np.asarray(part, np.float32) for part in of]
    return [norm(part - of[i - 1]) if 0 < i < len(of) - 2 else norm(part)
            for i, part in enumerate(of)]


def over_norms(parts, by):
    """``parts`` as one flat float32 array, each part over its entry of
    ``by`` (``norms`` of the PROGRAM's parts, for both sides of the
    comparison); written part by part into the one array, 2.8 GB at the
    cell's size."""
    sizes = [int(np.size(part)) for part in parts]
    out = np.empty(sum(sizes), np.float32)
    at = 0
    for part, n, size in zip(parts, by, sizes):
        np.divide(np.asarray(part, np.float32).ravel(), np.float32(n),
                  out=out[at:at + size])
        at += size
    return out


def _beyond_bfloat16(a):
    """Whether the float32 host array ``a`` holds a value that bfloat16's 7
    stored bits cannot: the low 16 bits of a float32 are what bfloat16
    drops."""
    a = np.ascontiguousarray(a)
    return a.dtype == np.float32 and bool(
        np.any(a.view(np.uint32) & np.uint32(0xFFFF)))


def parameters_are_float32(params):
    """Whether every matrix of the tree is float32 and holds a value that
    bfloat16's 7 stored bits cannot: what float32 master parameters look
    like, whatever dtype carries them."""
    return all(a.dtype == jnp.float32 and _beyond_bfloat16(np.asarray(a))
               for a in jax.tree.leaves(params) if a.ndim >= 2)


def stream_is_float32(stream):
    """Whether every part of a residual stream (host arrays: the embedding
    and what each mixer and feed-forward handed on) is float32 and holds a
    value beyond bfloat16's bits: what ``fp32_skip_add`` looks like. At this
    depth, from fresh weights, no norm tells the two apart: a layer adds as
    much as it is handed (the parts' norms over their updates' read 1.00 to
    2.99 on the chip), so a bfloat16 stream's rounding, 0.17% of a part,
    lies under the 0.28 to 0.47% that the layers' bfloat16 operands put on
    what they add (``state_bits`` = 7 reads 0.36 to 0.47% in the mixers and
    0.48 to 0.68% in the feed-forwards: inside every limit a norm could
    set). What such a stream loses, the small update on a large state of a
    deep trained model, this cell cannot see; that the state is float32, it
    can."""
    return all(_beyond_bfloat16(part) for part in stream)


def loss_and_outputs(params, config, batch, state_bits=None,
                     softmax_bits=None, summaries_in_sum=True):
    """(training loss over the batch, what every part of the forward pass
    hands on as one flat array, each part over its norm (``norms``); an
    array of jax's CPU device where that backend is there, so that the
    harness's comparison runs beside the host's memory).

    The loss is this file's own pass from the ids on. Where the batch
    carries ``program_stream`` (2 layers + 2 arrays [B, S, hidden]) and
    ``program_logits`` [B, S, heads x vocab], what the program's parts
    handed on, each part here is computed in float32 from the program's
    state before it and divided by the program's norm, and each mixer's
    part is held to ``MIXER_TOLERANCE`` on its own; without them the parts
    are this file's own over its own norms. Parameters that are not float32
    masters (``parameters_are_float32``), a residual stream that is not
    float32 (``stream_is_float32``: the program's, or this pass's own under
    ``state_bits``) and a mixer beyond its limit give parts of NaN, which
    agree with nothing.

    The controls: the same pass with what every part hands on
    (``state_bits``: 7 is a bfloat16 residual stream) or the softmax's
    logsumexp (``softmax_bits``) kept in that many stored bits of mantissa,
    or with the summaries left out of the softmax's denominator
    (``summaries_in_sum`` false): what a precision below the
    configuration's, or a softmax that is not one, reads."""
    masters = parameters_are_float32(params)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rows, positions = batch["input_ids"].shape
    program = None
    if "program_stream" in batch:
        logits = np.asarray(batch["program_logits"], np.float32)
        program = [np.asarray(part) for part in batch["program_stream"]] + [
            logits.reshape(rows, positions, config["num_pred_heads"], -1)]
    parts = _compiled_parts(
        json.dumps({k: config[k] for k in _READ}, sort_keys=True),
        softmax_bits, summaries_in_sum)
    with jax.default_matmul_precision("highest"):
        done = [_sequence(
            params, parts, jnp.asarray(batch["input_ids"][i]),
            jnp.asarray(batch["labels"][i]),
            None if program is None else [p[i] for p in program],
            state_bits) for i in range(rows)]
    handed = [np.stack([d[0][p] for d in done])
              for p in range(len(done[0][0]))]
    by = norms(handed if program is None else program)
    outputs = over_norms(handed, by)
    held = masters
    if not masters:
        print("[reference] a parameter matrix holds nothing beyond "
              "bfloat16's 7 stored bits, or is not float32: NOT THE "
              "CONFIGURATION'S float32 PARAMETERS", flush=True)
    for whose, stream in (("this pass's", handed[:-2]),
                          ("the program's", (program or handed)[:-2])):
        if not stream_is_float32(stream):
            print(f"[reference] a part of {whose} residual stream holds "
                  "nothing beyond bfloat16's 7 stored bits, or is not "
                  "float32: NOT THE CONFIGURATION'S float32 STREAM",
                  flush=True)
            held = False
    if program is not None:
        each = [float(np.linalg.norm(np.asarray(ours, np.float32)
                                     - np.asarray(theirs, np.float32)) / n)
                for ours, theirs, n in zip(handed, program, by)]
        mixers = max(each[1:-2:2])
        ok = mixers <= MIXER_TOLERANCE
        print("[reference] the program's parts, each on its own input and "
              "over its norm, are "
              + " ".join(f"{100 * e:.3f}%" for e in each)
              + f" from float32; the mixers' largest {100 * mixers:.3f}%, "
              f"{100 * MIXER_TOLERANCE:.3f}% allowed: "
              f"{'admissible' if ok else 'A MIXER OF ANOTHER PRECISION'}",
              flush=True)
        held = held and ok
    if not held:
        outputs = np.full_like(outputs, np.nan)
    try:        # beside the host's memory, as the runner's probe hands its
        outputs = jax.device_put(outputs, jax.devices("cpu")[0])
    except RuntimeError:
        pass
    means = _head_means(jnp.stack([d[1] for d in done]), rows, positions)
    return jnp.mean(means), outputs

