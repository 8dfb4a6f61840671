"""Layer: functional trainers. Milliseconds of device time a step under the
stage scope ``router_select`` of ``parallel/moe.py``, inside ``moe_router``:
the selection bias added, ``jax.lax.top_k`` over the router's width and the
chosen scores taken; in the backward the scatter of their gradient into the
scores'. From the traced run's one trace (``chipbench/moe_stages.py``); None
where the trace names no stage."""

from chipbench import moe_stages


def metric(facts):
    return moe_stages.ms(facts, "router_select")
