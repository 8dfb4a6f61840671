"""Layer: kernels. The expert layer's share of its roofline: the least time
the chip could take for the experts' matmuls (``flops/moe_experts.py``:
exact, the routing drops nothing; bound by FLOP/s) over the device time of
the named scope ``moe_experts``. The scope and not a kernel's name, so it
holds whichever body runs; the scope holds the SiLU gate and the weights'
casts beside the matmuls, so the share reads a little low and never high."""

from chipbench import scope_profile


def metric(facts):
    measured_ms = scope_profile.ms(facts, "scope_ns", "moe_experts", "total")
    if not measured_ms:
        return None
    flops = facts["catalog"].module("flops", "moe_experts").flops_per_step(
        facts["config"], facts["traffic"]) / facts["cell"]["chips"]
    least_s = flops / facts["peak"]["bf16_flops_per_s"]
    return 100.0 * least_s / (measured_ms / 1e3)
