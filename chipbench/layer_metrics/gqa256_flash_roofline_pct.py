"""Layer: kernels. The flash attention kernels' share of their roofline in a
cell whose full layers have 16 query heads of 256 over 2 key/value heads
(``flops/gqa256_flash.py``: the causal half, a key/value head's bytes once a
call) over the device time of the Mosaic calls ``flash_fwd`` and
``flash_bwd`` in a step: ``swa_flash_roofline_pct``'s reader on that pair of
kernels and that count. Where the program recomputes its mixers the forward
call runs twice a layer and is counted once, so the share reads low and never
high. None where the step runs no such call."""

KERNELS = ("flash_fwd", "flash_bwd")


def metric(facts):
    return facts["catalog"].module(
        "layer_metrics", "swa_flash_roofline_pct").metric(
            facts, kernels=KERNELS, counts="gqa256_flash")
