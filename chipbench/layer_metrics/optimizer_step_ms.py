"""Layer: optimizer. Milliseconds of device time a step inside the named
scope ``optimizer`` (``Optimizer.apply_gradients``): the update as it runs
inside the step, the flatten, cast and pad around the fused kernel included.
``optimizer_update_ms`` times the same update jitted alone, from outside."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "optimizer", "total")
