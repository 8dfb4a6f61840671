"""Layer: optimizer. Milliseconds of device time a step inside the named
scope ``optimizer`` (``Optimizer.apply_gradients``): the update as it runs
inside the step, the flatten, cast and pad around the fused kernel included.
Read inside the step because XLA overlaps and fuses the update with the
backward pass: jitted alone it takes 2.3 to 12 times this (PERF.md, PR 23)."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "optimizer", "total")
