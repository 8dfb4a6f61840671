"""Layer: functional trainers. Milliseconds of device time a step under the
stage scope ``router_scores`` of ``parallel/moe.py``, inside ``moe_router``:
the softmax over the experts or the sigmoid of each logit, the renormalisation
of the chosen scores and their scale, forward and backward. From the traced
run's one trace (``chipbench/moe_stages.py``); None where the trace names no
stage."""

from chipbench import moe_stages


def metric(facts):
    return moe_stages.ms(facts, "router_scores")
