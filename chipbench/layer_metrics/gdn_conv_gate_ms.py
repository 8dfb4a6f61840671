"""Layer: functional trainers. Milliseconds of device time a step that a Gated
DeltaNet layer spends around its delta rule, outside its projections: the
scopes ``short_conv`` (the causal depthwise convolution of [q | k | v] and its
SiLU) and ``gdn_gate`` (the L2 norms, the decay and beta, the RMSNorm of the
output times silu(z)), forward, recomputed forward and backward together:
elementwise passes over [positions, 8192] and [positions, 4096], bound by
bytes."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("short_conv", "gdn_gate")]
    return None if None in parts else sum(parts)
