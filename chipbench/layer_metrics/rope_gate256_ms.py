"""Layer: functional trainers. ``rope_gate_ms`` for a cell whose attention has
heads of 256: the same reader (the scopes ``rope``, rotary positions on the
first 64 channels of queries and keys, and ``attn_gate``, the sigmoid a
channel and its product with the context, forward, recomputed forward and
backward together), under a name of its own because that entry's
``workloads`` list is the accepted benchmark's."""


def metric(facts):
    return facts["catalog"].module("layer_metrics",
                                   "rope_gate_ms").metric(facts)
