"""Layer: functional trainers. Milliseconds of device time a step that a
Mamba-2 layer spends around its scan, outside its two products: the scopes
``short_conv`` (the causal depthwise convolution of ``x | B | C``, its bias
and SiLU) and ``ssd_gate`` (the step's softplus, the decay, the gate ``y *
silu(z)`` and the RMS norm a group), forward, recomputed forward and backward
together: elementwise passes over [positions, 2560] and [positions, 2048],
bound by bytes. None where the trace lacks one of the scopes."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("short_conv", "ssd_gate")]
    return None if None in parts else sum(parts)
