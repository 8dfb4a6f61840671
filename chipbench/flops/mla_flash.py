"""Operations and bytes the flash attention kernels of the MLA layers of a
training step must do, from shapes: score heads of ``qk_nope_head_dim +
qk_rope_head_dim`` (192) and value heads of ``v_head_dim`` (128), causal.

A layer's attention is six matmuls over the S x S square of a head: the
scores and two of the backward's products (the queries' and the keys'
gradients) contract or produce the 192 score channels, the context and the
other two (the values' gradient, the probabilities') the 128 value channels:
``2 x (3 x 192 + 3 x 128)`` operations a (query, key) pair. Half of the
square is counted (a query sees the keys up to its own: S^2 / 2 where S (S +
1) / 2 are needed, so the share reads a little low and never high). The
scores the backward call forms again are recomputation and are not counted;
nor is anything for padding: the kernels lay 192 out on two lane tiles of 128
and the MXU contracts 256 rows where 192 carry data, which is the kernel's
cost, not the algorithm's.

The bound is FLOP/s: a head reads q, k (192) and v (128) and writes o (128),
640 bf16 values a position, for S / 2 x 2 x 320 operations: S / 4 = 2048
operations a byte at S = 8192, against the 240 at which a v5e balances.
"""


def _layers(config):
    return sum(1 for layer in config["linear_attn_config"]["full_attn_layers"]
               if layer <= config["num_hidden_layers"])


def _heads_squares(config, traffic):
    s = traffic["seq_len"]
    return (_layers(config) * traffic["batch"]
            * config["num_attention_heads"]), s * s // 2


def flops_per_step(config, traffic):
    heads, square = _heads_squares(config, traffic)
    score = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return heads * square * 2 * 3 * (score + config["v_head_dim"])


def bytes_per_step(config, traffic):
    """bf16 reads and writes of the two calls: forward q, k, v in and o out;
    backward q, k, v, o, do in and dq, dk, dv out."""
    score = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    value = config["v_head_dim"]
    heads = _layers(config) * traffic["batch"] * config["num_attention_heads"]
    return heads * traffic["seq_len"] * 2 * (
        (2 * score + 2 * value) + (4 * score + 4 * value))
