"""Operations and bytes the EVA aggregation's kernels (``flash_fwd_eva``,
``flash_bwd_eva``: ``paddle_tpu/ops/pallas/eva.py``, scope ``eva_core``) of a
training step must do, from shapes: ``num_attention_heads`` heads of
``hidden_size / num_attention_heads`` channels, a window of ``window_size``
and chunks of ``chunk_size`` positions.

Only the visible (query, key) pairs are counted, and they are counted
exactly: the token pairs inside each window up to the diagonal, ``W (W + 1)
/ 2`` a whole window, and the summary pairs of earlier windows, ``w W / C``
summaries for each query of window w. At 16384 positions, a window of 2048
and chunks of 16 that is 16.8 M token pairs and 7.3 M summary pairs a head,
24.1 M against the 134 M of full causal attention. A pair is six matmuls of
``2 x head_dim`` operations, as ``flops/flash.py`` and ``flops/swa_flash.py``
count them; the scores the backward call forms again are recomputation and
are not counted, nor is a forward call the backward pass would repeat (the
time the reader divides by holds whatever runs, so the share reads low there
and never high), nor the masked half of a diagonal tile.

The bytes are the algorithm's, each array once a call: forward q, k, v and
the two summaries in and o out; backward q, k, v, the summaries, o and dO in
and the five gradients out; the operands' two bytes a value.
"""


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def visible_pairs(seq_len, window, chunk):
    """(token pairs, summary pairs) a head's ``seq_len`` queries see."""
    window = min(window, seq_len)
    whole, last = divmod(seq_len, window)
    tokens = whole * window * (window + 1) // 2 + last * (last + 1) // 2
    # window w's queries each see the w (window / chunk) summaries before it
    summaries = (window // chunk) * (
        window * whole * (whole - 1) // 2 + last * whole)
    return tokens, summaries


def _heads(config, traffic):
    return (config["num_hidden_layers"] * traffic["batch"]
            * config["num_attention_heads"])


def flops_per_step(config, traffic):
    pairs = sum(visible_pairs(traffic["seq_len"], config["window_size"],
                              config["chunk_size"]))
    return _heads(config, traffic) * pairs * 6 * 2 * head_dim(config)


def bytes_per_step(config, traffic):
    s = traffic["seq_len"]
    return _heads(config, traffic) * head_dim(config) * 2 \
        * (12 * s + 6 * (s // config["chunk_size"]))
