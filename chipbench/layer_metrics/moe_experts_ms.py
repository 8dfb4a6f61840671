"""Layer: kernels. Milliseconds of device time a step inside the named scope
``moe_experts``, forward and backward together: the grouped matmuls of the
dropless expert layer and the SiLU gate between them, whichever body the
registry chose for ``grouped_matmul``."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "moe_experts", "total")
