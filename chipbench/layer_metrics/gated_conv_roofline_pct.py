"""Layer: kernels. The double-gated short convolution's share of its roofline:
the least time the chip could take to move what the op must read and write
(``flops/gated_conv.py``: 11 bfloat16 values a position and channel over the
HBM peak; the operations are 1.4 a byte and bound nothing) over the device
time of the named scope ``gated_conv``. The scope and not a kernel's name, so
it holds whichever body runs; where the operator is recomputed its forward
runs twice and is counted once, so the share reads low and never high. None
where the trace has no such scope."""

from chipbench import scope_profile


def metric(facts):
    measured_ms = scope_profile.ms(facts, "scope_ns", "gated_conv", "total")
    if not measured_ms:
        return None
    need = facts["catalog"].module("flops", "gated_conv")
    least_s = need.bytes_per_step(facts["config"], facts["traffic"]) \
        / facts["cell"]["chips"] / facts["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (measured_ms / 1e3)
