"""Operations and bytes the flash attention kernels of the full layers of a
training step must do where a group of query heads shares a key/value head:
``num_attention_heads_per_layer`` query heads (48) over
``num_key_value_heads`` (8), causal, ``head_dim`` 128.

Six matmuls of ``2 x head_dim`` operations a (query, key) pair over half of
each head's S x S square, as ``flops/flash.py`` counts a causal call (S^2 / 2
where S (S + 1) / 2 are needed, so the share reads a little low and never
high); nothing for the scores the backward forms again, nor for the forward
call the backward pass repeats where the program recomputes its mixers. The
bytes are ``flops/swa_flash.py``'s: a key/value head is moved once a call,
not once a query head. The bound is FLOP/s by far (S / 4 operations a byte a
query head).
"""

from chipbench.flops import swa_flash

FULL = "full_attention"


def flops_per_step(config, traffic):
    s = traffic["seq_len"]
    return sum(swa_flash.layers(config, FULL)) * traffic["batch"] \
        * (s * s // 2) * 6 * 2 * config["head_dim"]


def bytes_per_step(config, traffic):
    return swa_flash.bytes_per_step(config, traffic, FULL)
