"""Layer: functional trainers. Milliseconds of device time a step that a KDA
layer spends around its scan, outside its projections: the scopes
``short_conv`` (the causal depthwise convolutions of q, k and v and their
SiLU) and ``kda_gate`` (the L2 norms, the decay and beta, the gated RMSNorm of
the output), forward and backward together: elementwise passes over
[positions, 4096], bound by bytes."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("short_conv", "kda_gate")]
    return None if None in parts else sum(parts)
