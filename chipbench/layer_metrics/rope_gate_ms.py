"""Layer: functional trainers. Milliseconds of device time a step that an
attention layer spends on the elementwise work around its core, outside its
projections: the scopes ``rope`` (rotary positions on queries and keys, on a
full head or on half of one) and ``attn_gate`` (the sigmoid a head and its
product with the head's context), forward, recomputed forward and backward
together: passes over [positions, heads x 128], bound by bytes."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("rope", "attn_gate")]
    return None if None in parts else sum(parts)
