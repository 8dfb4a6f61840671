"""Layer: functional trainers. Milliseconds of device time a step that an
expert layer holding a share of its router's experts spends outside its
matmuls: the scopes ``moe_router`` (the router's float32 product over all
its experts, sigmoid, top-k, the counts) and ``moe_dispatch`` (the sort that
puts the held assignments first, and in every pass over them the gather of
the rows and the weighted sum back into the tokens' rows), forward and
backward together."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("moe_router", "moe_dispatch")]
    return None if None in parts else sum(parts)
