"""Operations the step of a DeepSeek-V3-shaped decoder (Kanana-2) requires,
from shapes.

Matmul operations only, a multiply-add is 2, forward + backward = 3 x
forward, nothing counted for recomputation (the program forms every mixer's
projections, rotation and expansion twice a step: PERF.md section 4). Per
token, over ``num_hidden_layers`` layers:

- the mixer, every layer: the query, latent, expansion and output
  projections, and the causal scores and context at half the square with
  192 and 128 channels; the rotation (the 64 x 64 matrix of 0 and +-1 that
  fetches a pair's partner included), the norms and the softmax count nothing;
- the dense feed-forward of the first ``first_k_dense_replace`` layers: three
  matmuls of ``intermediate_size``;
- an expert layer: the router over all ``router_width`` experts, the shared
  feed-forward of ``n_shared_experts`` experts' width, and the assignments
  that fell on the experts held here, as the runner's probe counted them on
  the reference sample (it leaves them in ``config["probe"]``); before any
  probe, their expectation under a uniform router, ``experts per token x
  held / router_width``;
- the head over the slice of the vocabulary, on every position. The embedding
  lookup counts nothing.
"""


def mixer_flops(config, traffic):
    """A mixer's forward operations a token: (its four projections, its
    scores and context over the causal half)."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    score = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    value, rank = config["v_head_dim"], config["kv_lora_rank"]
    projections = (2 * h * heads * score
                   + 2 * h * (rank + config["qk_rope_head_dim"])
                   + 2 * rank * heads * (config["qk_nope_head_dim"] + value)
                   + 2 * heads * value * h)
    return projections, heads * (traffic["seq_len"] // 2) * 2 * (score
                                                                 + value)


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h = config["hidden_size"]
    expert = 3 * 2 * h * config["moe_intermediate_size"]
    probe = config.get("probe")
    if probe:
        held = sum(probe["held_rows"]) / len(probe["held_rows"]) \
            / probe["tokens"]
    else:
        held = config["num_experts_per_tok"] * config["experts_held"][1] \
            / config["router_width"]
    moe_layer = 2 * h * config["router_width"] \
        + (config["n_shared_experts"] + held) * expert
    dense_layer = 3 * 2 * h * config["intermediate_size"]
    dense = config["first_k_dense_replace"]
    layers = config["num_hidden_layers"]
    total = 2 * h * config["vocab_size"] \
        + layers * sum(mixer_flops(config, traffic)) \
        + dense * dense_layer + (layers - dense) * moe_layer
    return 3 * total
