"""Operations and bytes the held experts' matmuls of a training step must do,
from the rows they took: 8 of 64 experts of ``moe_intermediate_size`` a
layer, no shared expert.

A row (one (token, expert) assignment that fell on an expert held here) is
three matmuls of 2 x hidden x width operations (gate, up, down), forward, and
twice that backward (the rows' gradient and the weights'); the forward the
expert layer's own backward repeats is not counted. Nothing is dropped, so
the count is exact for the rows given. ``rows`` is their sum over the expert
layers in one step; without it, the expectation under a uniform router,
``tokens x experts per token x held / router width`` a layer.

Bytes: each held expert's three matrices read once forward and once backward
and their gradients written (bfloat16 operands, float32 gradients), and a
row read and written at both widths each way. At 2048 rows an expert the
operations are 680 a byte: the bound is FLOP/s.
"""


def expert_layers(config):
    return config["num_hidden_layers"] - config["num_dense_layers"]


def rows_at_par(config, traffic):
    return expert_layers(config) * traffic["batch"] * traffic["seq_len"] \
        * config["num_experts_per_tok"] * config["experts_held"][1] \
        // config["router_width"]


def flops_per_step(config, traffic, rows=None):
    if rows is None:
        rows = rows_at_par(config, traffic)
    return 3 * rows * 3 * 2 * config["hidden_size"] \
        * config["moe_intermediate_size"]


def bytes_per_step(config, traffic, rows=None):
    if rows is None:
        rows = rows_at_par(config, traffic)
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    weights = expert_layers(config) * config["experts_held"][1] * 3 * h * f
    # forward: x in, two products of f out, their gate in, y out; backward
    # the same again with the gradients
    return weights * (2 + 2 + 4) + rows * 2 * 2 * (2 * h + 4 * f)
