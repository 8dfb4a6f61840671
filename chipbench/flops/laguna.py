"""Operations the Laguna language-model step requires, from shapes.

Matmul operations only, a multiply-add is 2, forward + backward = 3 x
forward, nothing counted for recomputation (the program recomputes its mixers
and the dense feed-forward in its backward pass: PERF.md section 4). Per
token:

- a layer's attention: the query, key, value, gate and output projections at
  the layer's own head count, and the scores and context over the keys a
  query sees: half the sequence on a full layer, the band counted as a band
  on a sliding one (``flops/swa_flash.band_pairs`` over the positions);
- the dense feed-forward where ``mlp_layer_types`` says so: three matmuls;
- an expert layer: the router over all ``router_width`` experts, the shared
  expert, and the assignments that fell on the experts held here, as the
  runner's probe counted them on the reference sample (it leaves them in
  ``config["probe"]``); before any probe, their expectation under a uniform
  router, ``experts per token x held / router_width``;
- the head over the slice of the vocabulary, on every position. The embedding
  lookup and the rotary arithmetic count nothing.
"""

from chipbench.flops import swa_flash


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    s = traffic["seq_len"]
    layers = config["num_hidden_layers"]
    keys = {"full_attention": s // 2,
            "sliding_attention":
                swa_flash.band_pairs(s, config["sliding_window"]) / s}

    expert = 3 * 2 * h * config["moe_intermediate_size"]
    probe = config.get("probe")
    if probe:
        held = sum(probe["held_rows"]) / len(probe["held_rows"]) \
            / probe["tokens"]
    else:
        held = config["num_experts_per_tok"] * config["experts_held"][1] \
            / config["router_width"]
    moe_layer = 2 * h * config["router_width"] + held * expert \
        + 3 * 2 * h * config["shared_expert_intermediate_size"]
    dense_layer = 3 * 2 * h * config["intermediate_size"]

    total = 2 * h * config["vocab_size"]
    for n, kind, mlp in zip(
            config["num_attention_heads_per_layer"][:layers],
            config["layer_types"][:layers],
            config["mlp_layer_types"][:layers]):
        total += 2 * h * (2 * n * d + 2 * kv * d + n) + n * keys[kind] * 4 * d
        total += dense_layer if mlp == "dense" else moe_layer
    return 3 * total
