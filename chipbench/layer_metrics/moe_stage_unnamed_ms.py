"""Layer: functional trainers. Milliseconds of device time a step whose
innermost name is ``moe_router`` or ``moe_dispatch`` itself once the seven
stage scopes of ``parallel/moe.py`` are on the vocabulary: operations under
the two scopes that no stage covers. The guard that the seven stage metrics
sum to the two scopes (``moe_routing_ms``, ``moe_held_routing_ms``): 0 while
every operation under them is under a stage. From the traced run's one trace
(``chipbench/moe_stages.py``); None where the trace names no stage."""

from chipbench import moe_stages


def metric(facts):
    return moe_stages.unnamed_ms(facts)
