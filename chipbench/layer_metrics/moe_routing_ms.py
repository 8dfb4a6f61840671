"""Layer: functional trainers. Milliseconds of device time a step that the
expert layer spends outside its matmuls: the scopes ``moe_router`` (router
matmul, softmax, top-k, the auxiliary terms) and ``moe_dispatch`` (the sort
by expert, the gather of the rows, the weighted sum back), forward and
backward together."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("moe_router", "moe_dispatch")]
    return None if None in parts else sum(parts)
