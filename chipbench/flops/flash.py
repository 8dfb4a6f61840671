"""Operations the flash attention kernels of a training step must do, from
shapes.

A layer's attention is six matmuls of 2 x batch x heads x S^2 x head_dim
operations each: scores and context in the forward call, and in the backward
call the gradients of the values, the probabilities, the queries and the keys.
The scores that the backward call computes again from the saved logsumexp are
recomputation and are not counted, so a kernel that recomputes less does not
read worse for it. Where the configuration says ``"attention": "causal"`` a
query attends to the keys up to its own, and half of each S x S square is
counted: S^2 / 2 where S (S + 1) / 2 are needed, so the share reads a little
low and never high. The head size is the configuration's ``head_dim`` where it
gives one, else ``hidden_size`` over the heads.

The bound is FLOP/s, not bytes: per batch row and head the forward call reads
q, k, v and writes o, 4 x S x head_dim bf16 values, for 4 x S^2 x head_dim
operations, S / 2 operations a byte (S / 4 where causal). At S = 4096 that is
2048, against the 240 at which a v5e's 197 TFLOP/s and 819 GB/s balance.
"""


def flops_per_step(config, traffic):
    """Matmul operations of the flash calls in one training step."""
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    s = traffic["seq_len"]
    square = s * s // 2 if config.get("attention") == "causal" else s * s
    return (config["num_hidden_layers"] * 6 * 2 * traffic["batch"] * heads
            * square * head_dim)
