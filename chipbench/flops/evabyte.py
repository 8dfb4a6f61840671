"""Operations the EvaByte language-model step requires, from shapes.

Matmul operations only, a multiply-add is 2, forward + backward = 3 x
forward, nothing counted for recomputation (the program recomputes its mixers
and its feed-forwards in its backward pass: the configuration's
``program.recomputation``). Per token:

- a layer's projections (query, key, value, output: 4 hidden^2) and its
  SiLU-gated feed-forward (3 hidden x intermediate);
- the head over all ``num_pred_heads`` x ``vocab_size`` columns, on every
  position; the embedding lookup, the norms and the rotary arithmetic count
  nothing;
- the chunk summaries: a key's score against ``mu`` and against ``phi`` and
  its weighted sum into ``ksum``, a value's into ``vsum``: four products of 2
  x head_dim a position and head;
- the aggregation over the visible pairs only (``flops/eva_core.py``, which
  counts forward and backward itself).
"""

from chipbench.flops import eva_core


def flops_per_token(config, traffic):
    """Training operations per input position (the cell's token)."""
    h = config["hidden_size"]
    layer = 2 * 4 * h * h + 2 * 3 * h * config["intermediate_size"] \
        + config["num_attention_heads"] * 4 * 2 * eva_core.head_dim(config)
    head = 2 * h * config["num_pred_heads"] * config["vocab_size"]
    aggregation = eva_core.flops_per_step(config, traffic) \
        / (traffic["batch"] * traffic["seq_len"])
    return 3 * (config["num_hidden_layers"] * layer + head) + aggregation
