"""Layer: functional trainers. Milliseconds of device time a step in the
expert layers, whole, where 16 of 128 experts are held, a token takes 6 and a
shared feed-forward of two experts' width runs beside them: what
``moe_e32_layer_ms`` reads (the scopes ``moe_router``, ``moe_dispatch``,
``moe_experts`` and ``moe_shared``, forward and backward together), under a
name of its own because that entry's ``workloads`` list is the accepted
benchmark's. A balanced router sends an eighth of the assignments here,
12 288 rows a layer: one pass of ``moe._held_row_tile``'s 24 576, and a
second from a share of 25% on. None where the trace lacks one of the
scopes."""


def metric(facts):
    return facts["catalog"].module("layer_metrics",
                                   "moe_e32_layer_ms").metric(facts)
