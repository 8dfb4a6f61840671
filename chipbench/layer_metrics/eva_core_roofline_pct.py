"""Layer: kernels. The EVA aggregation kernels' share of their roofline: the
least time the chip could take for what they must do (``flops/eva_core.py``:
the visible pairs only, token pairs inside each window up to the diagonal
and summary pairs of earlier windows, six matmuls of 2 x head_dim a pair, and
each array's bytes once a call; the larger of operations over the bf16 peak
and bytes over the HBM peak) over the device time of the Mosaic calls
``flash_fwd_eva`` and ``flash_bwd_eva`` in a step:
``swa_flash_roofline_pct``'s reader on another pair of kernels and another
count. A forward call that recomputation ran twice would be counted once, so
the share reads low and never high. None where the step runs no such call."""

KERNELS = ("flash_fwd_eva", "flash_bwd_eva")


def metric(facts):
    return facts["catalog"].module(
        "layer_metrics", "swa_flash_roofline_pct").metric(
            facts, kernels=KERNELS, counts="eva_core")
