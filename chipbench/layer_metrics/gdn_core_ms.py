"""Layer: kernels. Milliseconds of device time a step under the named scope
``gdn_core``: the chunked gated delta rule with a decay a head of the Gated
DeltaNet layers (``paddle_tpu/ops/kda.py`` on a rank-3 decay and grouped key
heads), forward, recomputed forward and backward together. None where the
trace has no such scope."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "gdn_core", "total")
