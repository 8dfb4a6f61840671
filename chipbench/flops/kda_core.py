"""Operations and bytes the chunked gated delta rule (``paddle_tpu/ops/kda.py``,
scope ``kda_core``) of a training step must do, from shapes, at the chunk size
the op uses.

Per chunk of C positions and head of size d, forward, a multiply-add as 2:
the two score matrices inside the chunk at half the square (keys up to the
query's own), ``C^2 d`` each; the triangular solve of ``(I + A)`` against the
2d columns of ``[V | K * decay]``, ``2 C^2 d``; and the four products with the
d x d state (``W S``, ``q S``, ``k^T U``: ``2 C d^2`` each) and the scores
times U (``C^2 d``): ``5 C^2 d + 6 C d^2``. Backward twice that; the chunk
bodies and the exponentials the backward forms again are recomputation and
are not counted. The elementwise work (the exponentials of the diagonal
sub-blocks, ``C x 16 x d`` a chunk) is not counted either, so the share reads
low where that work, not the matmuls, takes the time.

Bytes: what the op must read and write once in each direction, a position and
head: q, k, v (bf16) and the log decay (float32) read, the output (bf16)
written; backward the same read again with the output's gradient, and the
four gradients written; beta and its gradient are 8 bytes. The states a chunk
starts from, which this implementation keeps for the backward pass, are its
own choice and are not counted.

At d = 128 and C = 32 that is 118 784 x 3 operations and 4 364 bytes a
position and head: 82 operations a byte, under the 240 at which a v5e's 197
TFLOP/s and 819 GB/s balance, so the bound is bytes.
"""


def _layers(config):
    linear = config["linear_attn_config"]
    return sum(1 for layer in linear["kda_layers"]
               if layer <= config["num_hidden_layers"])


def _positions_heads(config, traffic):
    linear = config["linear_attn_config"]
    return (_layers(config) * traffic["batch"] * traffic["seq_len"]
            * linear["num_heads"]), linear["head_dim"]


def chunk_size():
    """The op's constant, or None where the program has no such op."""
    try:
        from paddle_tpu.ops import kda
    except ImportError:
        return None
    return kda.CHUNK


def flops_per_step(config, traffic):
    c = chunk_size()
    if c is None:
        return None
    n, d = _positions_heads(config, traffic)
    return 3 * n * (5 * c * d + 6 * d * d)


def bytes_per_step(config, traffic):
    n, d = _positions_heads(config, traffic)
    forward = 3 * 2 * d + 4 * d + 4 + 2 * d
    backward = 3 * 2 * d + 4 * d + 4 + 2 * d + 3 * 2 * d + 4 * d + 4
    return n * (forward + backward)
