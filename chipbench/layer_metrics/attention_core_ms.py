"""Layer: functional trainers. Milliseconds of device time a step inside the
named scope ``attention_core``, forward and backward together: scores, softmax
and context of the dense path, or the flash kernels; the projections around
them are ``attention`` and not in this number."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "attention_core", "total")
