"""Layer: kernels. Milliseconds of device time a step under the named scope
``kda_core``: the chunked gated delta rule of the KDA layers
(``paddle_tpu/ops/kda.py``: the scores inside the chunks, the triangular
solve, the scan over the chunks) and the head transposes around it, forward,
recomputed forward and backward together."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "kda_core", "total")
