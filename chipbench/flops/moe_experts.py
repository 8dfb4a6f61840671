"""Operations the experts' matmuls of a training step must do, from shapes.

Every token is multiplied by ``num_experts_per_tok`` experts, each of three
matmuls of 2 x hidden x expert width operations (gate, up, down), forward,
and twice that backward (the rows' gradient and the weights'). The routing
drops nothing, so the count is exact whatever the experts' load is.

The bound is FLOP/s, not bytes: a group of R rows reads its three matrices
once, 3 x hidden x width bf16 values, for 3 x 2 x R x hidden x width
operations, R operations a byte of weights: 1024 at the mean load of the
cell ``olmoe_1b_7b.lm_s4096``, and near 500 with the rows read and written,
against the 240 at which a v5e's 197 TFLOP/s and 819 GB/s balance.
"""


def flops_per_step(config, traffic):
    """Matmul operations of the expert layers in one training step."""
    return (3 * traffic["batch"] * traffic["seq_len"]
            * config["num_experts_per_tok"] * 3 * 2 * config["hidden_size"]
            * config["intermediate_size"] * config["num_hidden_layers"])
