"""Layer: kernels. The delta rule with a decay a head's share of its roofline:
the least time the chip could take for what it must compute and move
(``flops/gdn_core.py`` at the chunk size the op uses: the larger of its
operations over the bf16 peak and its bytes over the HBM peak; at head size
128 the bytes bound it) over the device time of the named scope ``gdn_core``:
``kda_core_roofline_pct``'s reader on the other scope and the other count.
The scope and not a kernel's name, so it holds whichever body runs; the scope
also holds the forward pass the backward recomputes, so the share reads low
and never high. None where the program has no such op or the trace no such
scope."""

from chipbench import scope_profile


def metric(facts):
    measured_ms = scope_profile.ms(facts, "scope_ns", "gdn_core", "total")
    counts = facts["catalog"].module("flops", "gdn_core")
    flops = counts.flops_per_step(facts["config"], facts["traffic"])
    if not measured_ms or flops is None:
        return None
    chips, peak = facts["cell"]["chips"], facts["peak"]
    least_s = max(
        flops / chips / peak["bf16_flops_per_s"],
        counts.bytes_per_step(facts["config"], facts["traffic"]) / chips
        / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
