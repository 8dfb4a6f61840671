"""Layer: functional trainers. Milliseconds of device time a step in the
forward pass: operations whose name stack is under ``jvp(`` and not under
``transpose(`` (``scope_profile.py``), self times, averaged over the cell's
devices. With ``bwd_ms``, ``optimizer_step_ms`` and what has neither
direction, it is the whole of the device's busy time."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "direction_ns", "forward")
