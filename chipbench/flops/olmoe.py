"""Operations the OLMoE language-model step requires, from shapes alone.

Matmul operations only, a multiply-add is 2, forward + backward = 3 x
forward, nothing counted for recomputation. Per layer and token: the four
attention projections, the causal scores and context at half the square (a
query sees the keys up to its own), the router, and ``experts per token``
gated experts of three matmuls each: dropless, so exactly that many. The head
runs on every position. The embedding lookup counts nothing.
"""


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h, f = config["hidden_size"], config["intermediate_size"]
    qkv = config["num_attention_heads"] * config["head_dim"]
    s = traffic["seq_len"]
    per_layer = (2 * h * 3 * qkv + 2 * qkv * h          # q, k, v and output
                 + 2 * 2 * (s // 2) * qkv               # scores and context
                 + 2 * h * config["num_experts"]        # router
                 + config["num_experts_per_tok"] * 3 * 2 * h * f)
    head = 2 * h * config["vocab_size"]
    return 3 * (config["num_hidden_layers"] * per_layer + head)
