"""Operations and bytes the flash attention kernels of the full-attention
layers of a training step must do where ``num_attention_heads`` query heads
(16) share ``num_key_value_heads`` key/value heads (2) of ``head_dim`` 256,
causal: every ``full_attention_interval``-th layer of the layers held.

Six matmuls of ``2 x head_dim`` operations a (query, key) pair over half of
each head's S x S square, as ``flops/flash.py`` counts a causal call (S^2 / 2
where S (S + 1) / 2 are needed, so the share reads a little low and never
high); nothing for the scores the backward forms again, nor for the forward
call the backward pass repeats where the program recomputes its mixers. The
bytes are the algorithm's, as ``flops/swa_flash.py`` counts them: a query head
reads q and writes o, and in the backward call reads q, o and dO and writes
dQ; a key/value head is read once a call and its gradient written once. The
bound is FLOP/s by far.
"""


def _layers(config):
    return config["num_hidden_layers"] // config["full_attention_interval"]


def flops_per_step(config, traffic):
    s = traffic["seq_len"]
    return _layers(config) * config["num_attention_heads"] \
        * traffic["batch"] * (s * s // 2) * 6 * 2 * config["head_dim"]


def bytes_per_step(config, traffic):
    return _layers(config) * (6 * config["num_attention_heads"]
                              + 6 * config["num_key_value_heads"]) \
        * traffic["batch"] * traffic["seq_len"] * config["head_dim"] * 2
