"""Layer: functional trainers. Milliseconds of device time a step under the
stage scope ``dispatch_order`` of ``parallel/moe.py``, inside
``moe_dispatch``: the keys that put the held assignments first, every
``argsort``, the pad, and in each pass over held rows what the pass works on
(``moe._held_pass``: the slice of the order, the weights taken, the rows an
expert has in the pass). From the traced run's one trace
(``chipbench/moe_stages.py``); None where the trace names no stage."""

from chipbench import moe_stages


def metric(facts):
    return moe_stages.ms(facts, "dispatch_order")
