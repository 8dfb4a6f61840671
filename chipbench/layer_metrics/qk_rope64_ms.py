"""Layer: functional trainers. Milliseconds of device time a step that an
attention layer with heads of 64 spends on the elementwise work before its
core, outside its projections: the scopes ``qk_norm`` (the RMS norm a head of
queries and keys) and ``rope`` (rotary positions on all 64 channels of both),
forward, recomputed forward where the operator is, and backward together:
passes over [positions, 40 heads x 64], bound by bytes. None where the trace
lacks either scope."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("qk_norm", "rope")]
    return None if None in parts else sum(parts)
