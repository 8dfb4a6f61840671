"""Layer: kernels. Milliseconds of device time a step inside the named scope
``layer_norm``, forward and backward together, whichever body the registry
chose: the Pallas kernel and its plain backward on one chip, the reference
body under a mesh. The same per-chip shapes in ``mlm_s512`` and
``mlm_s512_dp4`` make the two comparable (ROADMAP A2)."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "layer_norm", "total")
