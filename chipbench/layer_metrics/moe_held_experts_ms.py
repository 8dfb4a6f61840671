"""Layer: kernels. Milliseconds of device time a step under the named scope
``moe_experts`` in a cell whose expert layers hold a share of the router's
experts: the three grouped matmuls over the rows that fell on the experts
held and the SiLU gate between them, forward, recomputed forward and
backward. The arrays are sized for every assignment, so what is not a product
here (the gate's elementwise pass, the zeroed tiles) is paid on all of them:
the number to watch against ``moe_held_rows_per_expert``."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "moe_experts", "total")
