"""Operations and bytes the windowed flash attention kernels of a training
step must do, from shapes: the sliding layers of a configuration with
``layer_types``, ``num_attention_heads_per_layer`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim``, each query seeing the
``sliding_window`` keys that end at its own.

Only the (query, key) pairs inside the band are counted, and they are counted
exactly: ``w (w + 1) / 2`` for the first ``w`` queries and ``w`` for each of
the others. A pair is six matmuls of ``2 x head_dim`` operations, as
``flops/flash.py`` counts them; the scores the backward call forms again are
recomputation and are not counted, nor is the forward call the backward pass
repeats where the program recomputes its mixers (the time the reader divides
by holds both, so the share reads low there and never high), nor the masked
part of the tiles the band cuts through: a kernel that visits fewer tiles
reads better, one that computes less inside a tile cannot.

The bytes are the algorithm's: a query head reads q and writes o, and in the
backward call reads q, o and dO and writes dQ; a key/value head is read once
a call and its gradient written once, however many query heads share it.
"""

SLIDING = "sliding_attention"


def layers(config, kind):
    """The query heads of each layer of ``kind`` among the layers held."""
    n = config["num_hidden_layers"]
    return [heads for heads, k in zip(
        config["num_attention_heads_per_layer"][:n],
        config["layer_types"][:n]) if k == kind]


def band_pairs(seq_len, window):
    """(query, key) pairs with ``query - window < key <= query``."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def flops_per_step(config, traffic):
    pairs = band_pairs(traffic["seq_len"], config["sliding_window"])
    return sum(layers(config, SLIDING)) * traffic["batch"] * pairs \
        * 6 * 2 * config["head_dim"]


def bytes_per_step(config, traffic, kind=SLIDING):
    """bf16 reads and writes of the two calls of every layer of ``kind``:
    forward q in and o out a query head, k and v in a key/value head;
    backward q, o, dO in and dQ out a query head, k and v in and dK and dV
    out a key/value head."""
    kv = config["num_key_value_heads"]
    return sum(6 * n + 6 * kv for n in layers(config, kind)) \
        * traffic["batch"] * traffic["seq_len"] * config["head_dim"] * 2
