"""Layer: kernels. The flash attention kernels' share of their roofline in a
cell whose attention layer has 32 query heads of 64 over 8 key/value heads
(``flops/gqa64_flash.py``: the causal half, a key/value head's bytes once a
call) over the device time of the Mosaic calls ``flash_fwd`` and
``flash_bwd`` in a step: ``swa_flash_roofline_pct``'s reader on that pair of
kernels and that count. A head of 64 is half a lane tile and half of the
MXU's contraction in the heads-major blocks, so the share's ceiling there is
near half of what a head of 128 reaches. None where the step runs no such
call."""

KERNELS = ("flash_fwd", "flash_bwd")


def metric(facts):
    return facts["catalog"].module(
        "layer_metrics", "swa_flash_roofline_pct").metric(
            facts, kernels=KERNELS, counts="gqa64_flash")
