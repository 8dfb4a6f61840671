"""Layer: functional trainers. Milliseconds of device time a step in the
expert layers, whole, where 32 of 512 experts are held and a token takes 10:
``moe_e32_layer_ms``'s reader (the scopes ``moe_router``, ``moe_dispatch``,
``moe_experts`` and ``moe_shared``, forward and backward together) under a
name of its own. A balanced router sends a sixteenth of the assignments here,
10 240 rows a layer: one pass of ``moe._held_row_tile``'s 24 576, and a second
from a share of 15% on."""


def metric(facts):
    return facts["catalog"].module("layer_metrics",
                                   "moe_e32_layer_ms").metric(facts)
