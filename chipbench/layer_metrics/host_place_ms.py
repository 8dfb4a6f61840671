"""Layer: functional trainers. Median host time of the program's span
``trainer/place`` (a step's batch put on the mesh, host to device) in the
traced steps: one half of a ``step_fn`` call, ``host_enqueue_ms`` being the
other. None where the program writes no such span."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.span_ms(facts, "trainer/place")
