"""Layer: functional trainers. Milliseconds of device time a step under the
stage scope ``router_logits`` of ``parallel/moe.py``, inside ``moe_router``:
the router's float32 product ``x router_w`` at ``Precision.HIGHEST`` over all
the router's experts, with the cast of the tokens before it, and in the
backward its two gradient products. From the traced run's one trace
(``chipbench/moe_stages.py``); None where the trace names no stage."""

from chipbench import moe_stages


def metric(facts):
    return moe_stages.ms(facts, "router_logits")
