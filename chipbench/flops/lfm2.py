"""Operations the LFM2 language-model step requires, from shapes.

Matmul operations only, a multiply-add is 2, forward + backward = 3 x
forward, nothing counted for recomputation (PERF.md section 4 says what the
program forms again in its backward pass). Per token, over the layers the
stage runs (``num_hidden_layers`` entries of ``layer_types`` from
``first_layer`` on):

- a short-convolution operator: the fused ``[B | C | u]`` projection and the
  output projection; the 3 taps and the two gates count nothing;
- the attention operator: the query, key, value and output projections, and
  the causal scores and context at half the square with 64 channels;
- the dense feed-forward of the first ``num_dense_layers``: three matmuls of
  ``intermediate_size``;
- an expert layer: the router over all ``router_width`` experts and the
  assignments that fell on the experts held here, as the runner's probe
  counted them on the reference sample (it leaves them in
  ``config["probe"]``); before any probe, their expectation under a uniform
  router, ``experts per token x held / router_width``; no shared expert;
- the tied head over the slice of the vocabulary, on every position. The
  embedding lookup, the norms and the rotary arithmetic count nothing.
"""


def layer_kinds(config):
    """[(operator, dense?)] of the layers the stage runs."""
    first, n = config["first_layer"], config["num_hidden_layers"]
    return [(kind, i < config["num_dense_layers"]) for i, kind
            in enumerate(config["layer_types"][first:first + n])]


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h, s = config["hidden_size"], traffic["seq_len"]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = h // n
    conv_op = 2 * h * 3 * h + 2 * h * h
    attn_op = 2 * h * (n * d + 2 * kv * d) + 2 * n * d * h \
        + n * (s // 2) * 4 * d

    expert = 3 * 2 * h * config["moe_intermediate_size"]
    probe = config.get("probe")
    if probe:
        held = sum(probe["held_rows"]) / len(probe["held_rows"]) \
            / probe["tokens"]
    else:
        held = config["num_experts_per_tok"] * config["experts_held"][1] \
            / config["router_width"]
    moe_layer = 2 * h * config["router_width"] + held * expert
    dense_layer = 3 * 2 * h * config["intermediate_size"]

    total = 2 * h * config["vocab_size"]
    for kind, dense in layer_kinds(config):
        total += attn_op if kind == "full_attention" else conv_op
        total += dense_layer if dense else moe_layer
    return 3 * total
