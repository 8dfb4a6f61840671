"""Layer: functional trainers. Median host time of the program's span
``trainer/enqueue`` (the call of the jitted step: argument handling and the
dispatch to the device) in the traced steps. With ``host_place_ms`` it is
the host's time inside one ``step_fn`` call, which matters to the rate only
once it nears the step interval. None where the program writes no such
span."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.span_ms(facts, "trainer/enqueue")
