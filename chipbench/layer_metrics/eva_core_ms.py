"""Layer: kernels. Milliseconds of device time a step under the named scope
``eva_core``: the EVA aggregation's two Mosaic calls (``flash_fwd_eva``,
``flash_bwd_eva``: a window's tokens and the earlier windows' chunk summaries
under one online softmax) and the backward's row sums of ``dO * o``, forward
and backward together; whichever body runs. It lies inside
``attention_core`` and takes its time out of it, so in a cell with this
scope ``attention_core_ms`` reads the head transposes around the calls. None
where the trace has no such scope."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "eva_core", "total")
