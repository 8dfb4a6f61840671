"""Operations the Kimi Linear language-model step requires, from shapes.

Matmul operations only, a multiply-add is 2, forward + backward = 3 x
forward, nothing counted for recomputation (the program recomputes every KDA
mixer in its backward pass: PERF.md section 4). Per token:

- a KDA layer: the q, k, v and output projections, the two low-rank pairs
  (decay and output gate), beta, and the chunked scan as ``flops/kda_core.py``
  counts it at the op's chunk size;
- an MLA layer: the query, latent, expansion and output projections, and the
  causal scores and context at half the square with 192 and 128 channels;
- the dense feed-forward of the leading layers: three matmuls;
- an expert layer: the router over all ``router_width`` experts, the shared
  expert, and the assignments that fell on the experts held here, as the
  runner's probe counted them on the reference sample (it leaves them in
  ``config["probe"]``); before any probe, their expectation under a uniform
  router, ``experts per token x held / router_width``;
- the head over the slice of the vocabulary, on every position. The embedding
  lookup and the convolutions' 4 taps count nothing.
"""


from chipbench.flops import kda_core


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h = config["hidden_size"]
    linear = config["linear_attn_config"]
    layers = config["num_hidden_layers"]
    s = traffic["seq_len"]

    n, d = linear["num_heads"], linear["head_dim"]
    width = n * d
    kda_layer = (4 * 2 * h * width + 2 * (2 * h * d + 2 * d * width)
                 + 2 * h * n)

    heads = config["num_attention_heads"]
    score = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    value = config["v_head_dim"]
    rank = config["kv_lora_rank"]
    mla_layer = (2 * h * heads * score
                 + 2 * h * (rank + config["qk_rope_head_dim"])
                 + 2 * rank * heads * (config["qk_nope_head_dim"] + value)
                 + 2 * heads * value * h
                 + heads * (s // 2) * 2 * (score + value))

    expert = 3 * 2 * h * config["moe_intermediate_size"]
    probe = config.get("probe")
    if probe:
        held = sum(probe["held_rows"]) / len(probe["held_rows"]) \
            / probe["tokens"]
    else:
        held = config["num_experts_per_token"] * config["experts_held"][1] \
            / config["router_width"]
    moe_layer = 2 * h * config["router_width"] \
        + (config["num_shared_experts"] + held) * expert
    dense_layer = 3 * 2 * h * config["intermediate_size"]

    total = 2 * h * config["vocab_size"]
    for layer in range(1, layers + 1):
        total += kda_layer if layer in linear["kda_layers"] else mla_layer
        total += dense_layer if layer <= config["first_k_dense_replace"] \
            else moe_layer
    # the scans of all the KDA layers, forward and backward, a token
    scan = kda_core.flops_per_step(config, traffic) \
        / (traffic["batch"] * traffic["seq_len"])
    return 3 * total + scan
