"""Layer: functional trainers. Milliseconds of device time a step in the
expert layers, whole, where 8 of 64 experts are held, a token takes 4 and
there is no shared expert: what ``moe_e32_layer_ms`` reads, without the scope
``moe_shared`` this model's program never enters: ``moe_router`` (scores,
top-k, counts), ``moe_dispatch`` (the order, the pass's gather and its sum
back) and ``moe_experts`` (the grouped matmuls on the rows held), forward and
backward together. A balanced router sends an eighth of the assignments here,
16 384 rows a layer: one pass of ``moe._held_row_tile``'s 32 768, and a
second from a share of 25% on. None where the trace lacks one of the
scopes."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("moe_router", "moe_dispatch", "moe_experts")]
    return None if None in parts else sum(parts)
