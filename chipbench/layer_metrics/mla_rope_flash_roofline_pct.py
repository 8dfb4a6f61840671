"""Layer: kernels. The flash attention kernels' share of their roofline in a
cell whose every layer is rotary latent attention, scores on 192 channels and
values of 128: the least time the chip could take for the matmuls they must
do (``flops/mla_rope_flash.py``: every layer, unpadded 192 / 128, the causal
half; bound by FLOP/s) over the device time of the Mosaic calls ``flash_fwd``
and ``flash_bwd`` in a step. That time holds one call of each a layer: the
mixers are recomputed but keep the forward call's outputs by name
(``blocks.recomputed``), so no forward kernel runs twice and count and time
cover the same work. ``swa_flash_roofline_pct``'s reader on that pair of
kernels and that count. None where the step runs no flash call."""

KERNELS = ("flash_fwd", "flash_bwd")


def metric(facts):
    return facts["catalog"].module(
        "layer_metrics", "swa_flash_roofline_pct").metric(
            facts, kernels=KERNELS, counts="mla_rope_flash")
