"""Operations and bytes the flash attention kernels of the full-attention
layers of a training step must do where ``num_attention_heads`` query heads
(32) share ``num_key_value_heads`` key/value heads (8) of ``hidden_size /
num_attention_heads`` channels (64), causal: the ``full_attention`` entries
among the layers the stage runs.

Six matmuls of ``2 x head size`` operations a (query, key) pair over half of
each head's S x S square, as ``flops/flash.py`` counts a causal call (S^2 / 2
where S (S + 1) / 2 are needed, so the share reads a little low and never
high); nothing for the scores the backward forms again, nor for a forward
call the backward pass repeats where the program recomputes the operator. The
bytes are the algorithm's, as ``flops/swa_flash.py`` counts them: a query head
reads q and writes o, and in the backward call reads q, o and dO and writes
dQ; a key/value head is read once a call and its gradient written once. The
bound is FLOP/s by far; a head of 64 fills half of the MXU's contraction and
half a lane tile, which is what the share shows.
"""

from chipbench.flops import lfm2


def _layers(config):
    return sum(kind == "full_attention"
               for kind, _ in lfm2.layer_kinds(config))


def _head(config):
    return config["hidden_size"] // config["num_attention_heads"]


def flops_per_step(config, traffic):
    s = traffic["seq_len"]
    return _layers(config) * config["num_attention_heads"] \
        * traffic["batch"] * (s * s // 2) * 6 * 2 * _head(config)


def bytes_per_step(config, traffic):
    return _layers(config) * (6 * config["num_attention_heads"]
                              + 6 * config["num_key_value_heads"]) \
        * traffic["batch"] * traffic["seq_len"] * _head(config) * 2
