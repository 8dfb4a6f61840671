"""Layer: functional trainers. Milliseconds of device time a step in the
backward pass: operations whose name stack is under ``transpose(jvp(``
(``scope_profile.py``), self times, averaged over the cell's devices. About
twice ``fwd_ms`` where nothing is recomputed: two matmuls for each of the
forward's."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "direction_ns", "backward")
