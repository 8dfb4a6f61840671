"""Layer: kernels. The chunked state-space scan's share of its roofline: the
least time the chip could take for what the scan must compute and move
(``flops/ssd_core.py`` at the configuration's chunk: the larger of its
operations over the bf16 peak and its bytes over the HBM peak; at a head of
64 with a state of 128 the bytes bound it) over the device time of the named
scope ``ssd_core``. The scope and not a kernel's name, so it holds whichever
body runs; the scope also holds the forward pass the backward recomputes,
the whole ``[chunk, chunk]`` squares and their exponentials, so the share
reads low and never high. None where the trace has no such scope."""

from chipbench import scope_profile


def metric(facts):
    measured_ms = scope_profile.ms(facts, "scope_ns", "ssd_core", "total")
    if not measured_ms:
        return None
    counts = facts["catalog"].module("flops", "ssd_core")
    chips, peak = facts["cell"]["chips"], facts["peak"]
    least_s = max(
        counts.flops_per_step(facts["config"], facts["traffic"]) / chips
        / peak["bf16_flops_per_s"],
        counts.bytes_per_step(facts["config"], facts["traffic"]) / chips
        / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
