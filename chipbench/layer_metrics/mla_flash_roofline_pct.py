"""Layer: kernels. The flash attention kernels' share of their roofline in a
cell whose attention scores on 192 channels and carries 128 (MLA): the least
time the chip could take for the matmuls they must do (``flops/mla_flash.py``:
unpadded 192 / 128, the causal half; bound by FLOP/s) over the device time of
the Mosaic calls ``flash_fwd`` and ``flash_bwd`` in a step: one call each a
layer, since the MLA mixer keeps what it computed (the configuration's
``program.recomputation``), so count and time cover the same work. The
backward contracts the scores over 256 rows of the MXU where 192 carry data;
only the 192 are counted. None where the step runs no flash call."""

from chipbench import scope_profile

KERNELS = ("flash_fwd", "flash_bwd")


def metric(facts):
    reduced = scope_profile.profile(facts)
    if reduced is None:
        return None
    measured_ns = sum(reduced["kernel_ns"].get(k, 0) for k in KERNELS)
    if not measured_ns:
        return None
    flops = facts["catalog"].module("flops", "mla_flash").flops_per_step(
        facts["config"], facts["traffic"]) / facts["cell"]["chips"]
    least_s = flops / facts["peak"]["bf16_flops_per_s"]
    return 100.0 * least_s / (measured_ns / 1e9)
