"""Operations the flash attention kernels of a BERT step must do, from shapes.

A layer's attention is six matmuls of 2 x batch x heads x S^2 x head_dim
operations each: scores and context in the forward call, and in the backward
calls the gradients of the values, the probabilities, the queries and the keys.
The scores that the two backward calls compute again from the saved logsumexp
are recomputation and are not counted, so a kernel that recomputes less does
not read worse for it.

The bound is FLOP/s, not bytes: per batch row and head the forward call reads
q, k, v and writes o, 4 x S x head_dim bf16 values, for 4 x S^2 x head_dim
operations, S / 2 operations a byte. At S = 4096 that is 2048, against the 240
at which a v5e's 197 TFLOP/s and 819 GB/s balance.
"""


def flops_per_step(config, traffic):
    """Matmul operations of the flash calls in one training step."""
    heads = config["num_attention_heads"]
    head_dim = config["hidden_size"] // heads
    s = traffic["seq_len"]
    return (config["num_hidden_layers"] * 6 * 2 * traffic["batch"] * heads
            * s * s * head_dim)
