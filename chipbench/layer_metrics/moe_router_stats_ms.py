"""Layer: functional trainers. Milliseconds of device time a step under the
stage scope ``router_stats`` of ``parallel/moe.py``, inside ``moe_router``:
``jnp.bincount`` of the choices (the counts ``step_fn.aux`` hands out), the
balance term and the z term's ``logsumexp``, forward and backward. From the
traced run's one trace (``chipbench/moe_stages.py``); None where the trace
names no stage."""

from chipbench import moe_stages


def metric(facts):
    return moe_stages.ms(facts, "router_stats")
