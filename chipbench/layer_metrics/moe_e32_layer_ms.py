"""Layer: functional trainers. Milliseconds of device time a step in the
expert layers, whole, where 32 of 256 experts are held: the scopes
``moe_router`` (scores, top-k, counts), ``moe_dispatch`` (the order, each
pass's gather and its sum back), ``moe_experts`` (the grouped matmuls on the
rows held) and ``moe_shared`` (the shared expert on every token), forward and
backward together. A balanced router sends an eighth of the assignments here,
16 384 rows a layer: one pass of ``moe._held_row_tile``'s 32 768, and a
second from a share of 25% on."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("moe_router", "moe_dispatch", "moe_experts",
                           "moe_shared")]
    return None if None in parts else sum(parts)
