"""Layer: functional trainers. Milliseconds of device time a step in the
LatentMoE layers, whole, where 8 of 512 experts are held, a token takes 22,
the routed experts work on a latent of 1024 and a shared expert of 5376 runs
on the hidden beside them: the scopes ``moe_latent`` (the latent's two
projections), ``moe_router`` (scores, top-k of 22, counts), ``moe_dispatch``
(the order, each pass's gather and its sum back), ``moe_experts`` (the two
grouped matmuls on the rows held) and ``moe_shared``, forward and backward
together. A balanced router sends a 64th of the assignments here, 2816 rows a
layer: one pass of ``moe._held_row_tile``'s 8192. None where the trace lacks
one of the scopes."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("moe_latent", "moe_router", "moe_dispatch",
                           "moe_experts", "moe_shared")]
    return None if None in parts else sum(parts)
