"""Operations and bytes the chunked gated delta rule with a decay a head
(``paddle_tpu/ops/kda.py`` on a rank-3 decay, scope ``gdn_core``) of a training
step must do, from shapes, at the chunk size the op uses: Gated DeltaNet's
core, ``linear_num_key_heads`` heads of q and k under
``linear_num_value_heads`` of v.

Per chunk of C positions, forward, a multiply-add as 2. A key head's two raw
score products at half the square (keys up to the query's own), ``C^2 d``
each, serve its ``n`` value heads; a value head has the triangular solve of
``(I + A)`` against the 2d columns of ``[V | K * decay]``, ``2 C^2 d``, the
scores times U, ``C^2 d``, and three products with the d x d state (``[W; q]
S`` as two, ``k^T U``: ``2 C d^2`` each): ``(3 + 2 / n) C^2 d + 6 C d^2`` a
value head. Backward twice that; what the backward forms again (the forward
under the mixer's recomputation included) is not counted, nor the masks'
exponentials, so the share reads low where that work takes the time, and
never high.

Bytes: what the op must read and write once in each direction, a position and
value head: its share of q and k (bf16, ``1 / n`` of a key head each), v
(bf16), the log decay and beta (float32 scalars) read, the output (bf16)
written; backward the same read again with the output's gradient, and the
five gradients written. What an implementation keeps for its backward pass is
its own choice and is not counted.

At d = 128, n = 2 and C = 64 that is 131 072 x 3 operations and 2 328 bytes a
position and value head: 169 operations a byte, under the 240 at which a
v5e's 197 TFLOP/s and 819 GB/s balance, so the bound is bytes.
"""


def _layers(config):
    n = config["num_hidden_layers"]
    return n - n // config["full_attention_interval"]


def _positions_heads(config, traffic):
    return (_layers(config) * traffic["batch"] * traffic["seq_len"]
            * config["linear_num_value_heads"])


def chunk_size():
    """The op's constant, or None where the program has no such op."""
    try:
        from paddle_tpu.ops import kda
    except ImportError:
        return None
    return getattr(kda, "CHUNK_HEAD", None)


def flops_per_step(config, traffic):
    c = chunk_size()
    if c is None:
        return None
    d = config["linear_key_head_dim"]
    n = config["linear_num_value_heads"] // config["linear_num_key_heads"]
    return 3 * _positions_heads(config, traffic) \
        * ((3 + 2 / n) * c * d + 6 * d * d)


def bytes_per_step(config, traffic):
    d = config["linear_key_head_dim"]
    n = config["linear_num_value_heads"] // config["linear_num_key_heads"]
    shared = 2 * 2 * d / n                    # q and k, a value head's share
    forward = shared + 2 * d + 8 + 2 * d
    backward = forward + 2 * d + shared + 2 * d + 8
    return _positions_heads(config, traffic) * (forward + backward)
