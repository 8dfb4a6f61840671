"""Operations the step of a Nemotron-H decoder requires, from shapes.

Matmul operations only, a multiply-add is 2, forward + backward = 3 x
forward, nothing counted for recomputation (the program forms every M and
``*`` mixer twice a step: PERF.md section 4). Every product of the share the
configuration holds (its head counts, its experts held, its slice of the
vocabulary). Per token, by the letters of ``hybrid_override_pattern`` and of
the MTP module's ``mtp_hybrid_override_pattern``:

- an ``M`` layer: the input product into ``z | x | B | C | dt`` and the
  output product; the scan as ``flops/ssd_core.py`` counts it at the
  configuration's chunk (added once for all the M layers); the convolution's
  4 taps, the gate and the norms count nothing;
- a ``*`` layer: the query, key, value and output products, and the causal
  scores and context at half the square;
- an ``E`` layer: the router over all ``router_width`` experts, the latent's
  two projections, the shared expert's two products on the hidden, and the
  assignments that fell on the experts held here, two products of the latent
  each, as the runner's probe counted them on the reference sample (it
  leaves them in ``config["probe"]``, the module's layer last); before any
  probe, their expectation under a uniform router, ``experts per token x
  held / router_width``;
- the MTP module: the merge ``W_eh`` [2 hidden, hidden] and its layers;
- the head over the slice of the vocabulary, on every position, once for the
  main model and once for the module. The embedding lookups count nothing.
"""

from chipbench.flops import ssd_core


def layer_flops(config, traffic, held=None):
    """{letter: a layer's forward operations a token}; ``held`` the rows a
    token sends to the experts held here (default: a uniform router's)."""
    h = config["hidden_size"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    bc = config["n_groups"] * config["ssm_state_size"]
    mamba = 2 * h * (2 * inner + 2 * bc + config["mamba_num_heads"]) \
        + 2 * inner * h
    heads, kv, d = (config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    attention = 2 * h * (heads + 2 * kv) * d + 2 * heads * d * h \
        + heads * (traffic["seq_len"] // 2) * 2 * 2 * d
    if held is None:
        held = config["num_experts_per_tok"] * config["experts_held"][1] \
            / config["router_width"]
    latent = config["moe_latent_size"]
    experts = 2 * h * config["router_width"] + 2 * 2 * h * latent \
        + 2 * 2 * h * config["moe_shared_expert_intermediate_size"] \
        + held * 2 * 2 * latent * config["moe_intermediate_size"]
    return {"M": mamba, "*": attention, "E": experts}


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h = config["hidden_size"]
    main = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    module = config["mtp_hybrid_override_pattern"] \
        if config["num_nextn_predict_layers"] else ""
    probe = config.get("probe")
    held = sum(probe["held_rows"]) / len(probe["held_rows"]) \
        / probe["tokens"] if probe else None
    layer = layer_flops(config, traffic, held)
    head = 2 * h * config["vocab_size"]
    total = head + sum(layer[kind] for kind in main)
    if module:
        total += 2 * 2 * h * h + head + sum(layer[kind] for kind in module)
    # the scans of all the M layers, forward and backward, a token
    scan = ssd_core.flops_per_step(config, traffic) \
        / (traffic["batch"] * traffic["seq_len"])
    return 3 * total + scan
