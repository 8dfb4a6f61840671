"""Layer: functional trainers. Milliseconds of device time a step under the
stage scope ``dispatch_gather`` of ``parallel/moe.py``, inside
``moe_dispatch``: the tokens' rows gathered into expert order
(``moe_combine.rows_held`` of the tokens and, in the backward, of the output's
gradient; ``_rows_in_expert_order`` and its gradient where every expert is
held). From the traced run's one trace (``chipbench/moe_stages.py``); None
where the trace names no stage."""

from chipbench import moe_stages


def metric(facts):
    return moe_stages.ms(facts, "dispatch_gather")
