"""Layer: kernels. Milliseconds of device time a step under the named scope
``ssd_core``: the chunked state-space scan of the Mamba-2 layers
(``paddle_tpu/ops/ssd.py``: the scores and decays inside the chunks, the
scan over the chunks' states, the states' part of the output) and the views
a head around it, forward, recomputed forward and backward together;
whichever body runs. None where the trace has no such scope."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "ssd_core", "total")
