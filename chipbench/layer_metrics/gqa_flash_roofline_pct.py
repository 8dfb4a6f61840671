"""Layer: kernels. The flash attention kernels' share of their roofline in a
cell whose full layers give a group of query heads one key/value head
(``flops/gqa_flash.py``: 48 query heads over 8, the causal half, head size
128, a key/value head's bytes once a call) over the device time of the Mosaic
calls ``flash_fwd`` and ``flash_bwd`` in a step: ``swa_flash_roofline_pct``'s
reader on the other pair of kernels and the other count. None where the step
runs no such call."""

KERNELS = ("flash_fwd", "flash_bwd")


def metric(facts):
    return facts["catalog"].module(
        "layer_metrics", "swa_flash_roofline_pct").metric(
            facts, kernels=KERNELS, counts="gqa_flash")
