"""Layer: kernels. The flash attention kernels' share of their roofline: the
least time the chip could take for the matmuls they must do
(``flops/flash.py``: bound by FLOP/s) over the device time of the Mosaic
calls ``flash_fwd`` and the one backward call in a step. The backward call is
``flash_bwd_dkv`` in the program today; ``flash_bwd`` is the name it should
take (it yields dQ too since PR 24), so the PR that renames it edits nothing
here. None where the step runs no flash call: a cell whose step does lists
itself under this entry's ``workloads``, or brings an entry of its own whose
reader calls this one."""

from chipbench import scope_profile

KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd")


def metric(facts):
    reduced = scope_profile.profile(facts)
    if reduced is None:
        return None
    measured_ns = sum(reduced["kernel_ns"].get(k, 0) for k in KERNELS)
    if not measured_ns:
        return None
    flops = facts["catalog"].module("flops", "flash").flops_per_step(
        facts["config"], facts["traffic"]) / facts["cell"]["chips"]
    least_s = flops / facts["peak"]["bf16_flops_per_s"]
    return 100.0 * least_s / (measured_ns / 1e9)
