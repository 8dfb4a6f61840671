"""Layer: kernels. The windowed flash attention kernels' share of their
roofline: the least time the chip could take for what they must do
(``flops/swa_flash.py``: the pairs inside the band only, and a key/value
head's bytes once a call; the larger of operations over the bf16 peak and
bytes over the HBM peak) over the device time of the Mosaic calls
``flash_fwd_window`` and ``flash_bwd_window`` in a step. Where the program
recomputes its mixers the forward call runs twice a layer and is counted
once, so the share reads low and never high. None where the step runs no
windowed call."""

from chipbench import scope_profile

KERNELS = ("flash_fwd_window", "flash_bwd_window")
COUNTS = "swa_flash"


def metric(facts, kernels=KERNELS, counts=COUNTS):
    reduced = scope_profile.profile(facts)
    if reduced is None:
        return None
    measured_ns = sum(reduced["kernel_ns"].get(k, 0) for k in kernels)
    if not measured_ns:
        return None
    need = facts["catalog"].module("flops", counts)
    chips = facts["cell"]["chips"]
    least_s = max(
        need.flops_per_step(facts["config"], facts["traffic"]) / chips
        / facts["peak"]["bf16_flops_per_s"],
        need.bytes_per_step(facts["config"], facts["traffic"]) / chips
        / facts["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ns / 1e9)
