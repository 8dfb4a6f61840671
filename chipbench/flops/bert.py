"""Operations the BERT masked-LM step requires, from shapes alone.

Copied from ``paddle_tpu.models.bert.flops_per_token`` (the original is
listed in PERF.md for a later PR to delete): matmul operations only, a
multiply-add is 2, forward + backward = 3 x forward, the vocabulary head
counted on the gathered positions, nothing counted for recomputation. The
768 x 768 transform of the head is left out, as in the original.
"""


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h, f = config["hidden_size"], config["intermediate_size"]
    s = traffic["seq_len"]
    per_layer = (2 * h * 3 * h + 2 * h * h      # qkv and output projections
                 + 2 * h * f + 2 * f * h        # feed-forward
                 + 2 * 2 * s * h)               # scores and context
    head = 2 * h * config["vocab_size"] * traffic["max_predictions"] / s
    return 3 * (config["num_hidden_layers"] * per_layer + head)
