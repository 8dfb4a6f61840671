"""Layer: functional trainers. Milliseconds of device time a step under the
stage scope ``dispatch_combine`` of ``parallel/moe.py``, inside
``moe_dispatch``: the experts' rows back into their tokens' rows, each times
its score (``moe._sum_back``: the kernel ``moe_combine`` or XLA's scatter-add,
forward and for the rows' gradient; ``_rows_in_token_order`` and the weighted
sum over a token's choices where every expert is held), and the scores'
gradient. From the traced run's one trace (``chipbench/moe_stages.py``); None
where the trace names no stage."""

from chipbench import moe_stages


def metric(facts):
    return moe_stages.ms(facts, "dispatch_combine")
