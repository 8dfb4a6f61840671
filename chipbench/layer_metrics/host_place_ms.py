"""Layer: functional trainers. Median host time of the program's span
``trainer/place`` (the ``device_put`` of a step's batch onto the mesh) in the
traced steps: one half of ``host_step_call_ms``, ``trainer/enqueue`` being the
other. None where the program writes no such span."""

import statistics

from chipbench import scope_profile


def metric(facts):
    reduced = scope_profile.profile(facts)
    if reduced is None or not reduced["host_span_ms"]["trainer/place"]:
        return None
    return statistics.median(reduced["host_span_ms"]["trainer/place"])
