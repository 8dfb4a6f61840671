"""Operations the Qwen3-Next language-model step requires, from shapes.

Matmul operations only, a multiply-add is 2, forward + backward = 3 x
forward, nothing counted for recomputation (the program recomputes its mixers
in its backward pass: PERF.md section 4). Per token:

- a Gated DeltaNet layer: the fused ``[q | k | v | z]`` projection, ``[b |
  a]``, the output projection, and the chunked delta rule as
  ``flops/gdn_core.py`` counts it at the op's chunk size;
- a full-attention layer: the query-and-gate, key, value and output
  projections, and the causal scores and context at half the square with 256
  channels;
- every layer's experts: the router over all ``router_width`` experts, the
  shared expert and its scalar gate, and the assignments that fell on the
  experts held here, as the runner's probe counted them on the reference
  sample (it leaves them in ``config["probe"]``); before any probe, their
  expectation under a uniform router, ``experts per token x held /
  router_width``;
- the head over the slice of the vocabulary, on every position. The embedding
  lookup, the convolution's 4 taps and the rotary arithmetic count nothing.
"""

from chipbench.flops import gdn_core


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h, s = config["hidden_size"], traffic["seq_len"]
    layers = config["num_hidden_layers"]
    full = layers // config["full_attention_interval"]

    nv = config["linear_num_value_heads"]
    kw = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    vw = nv * config["linear_value_head_dim"]
    gdn_layer = 2 * h * (2 * kw + 2 * vw) + 2 * h * 2 * nv + 2 * vw * h

    n, kv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    attn_layer = 2 * h * (2 * n * d + 2 * kv * d) + 2 * n * d * h \
        + n * (s // 2) * 4 * d

    expert = 3 * 2 * h * config["moe_intermediate_size"]
    probe = config.get("probe")
    if probe:
        held = sum(probe["held_rows"]) / len(probe["held_rows"]) \
            / probe["tokens"]
    else:
        held = config["num_experts_per_tok"] * config["experts_held"][1] \
            / config["router_width"]
    moe_layer = 2 * h * config["router_width"] + held * expert \
        + 3 * 2 * h * config["shared_expert_intermediate_size"] + 2 * h

    total = 2 * h * config["vocab_size"] + (layers - full) * gdn_layer \
        + full * attn_layer + layers * moe_layer
    # the delta rule of all the linear layers, forward and backward, a token
    scan = (gdn_core.flops_per_step(config, traffic) or 0) \
        / (traffic["batch"] * traffic["seq_len"])
    return 3 * total + scan
