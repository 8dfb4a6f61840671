"""Operations and bytes the flash attention kernels of a training step must
do where every layer's mixer is rotary latent attention: ``num_hidden_layers``
layers of ``num_attention_heads`` heads that score on ``qk_nope_head_dim +
qk_rope_head_dim`` channels (192) and carry ``v_head_dim`` (128), causal.

``flops/mla_flash.py``'s count (six matmuls over half of a head's S x S
square at the unpadded 192 / 128, nothing for the scores the backward forms
again; that file has the reasons), which takes its layers from Kimi Linear's
list of them, over every layer here. Nothing for recomputation either: the
program's recomputed mixers keep the forward call's outputs, so the step
holds one ``flash_fwd`` and one ``flash_bwd`` a layer and the count and the
time cover the same calls. The rotation is outside the kernels and outside
this count.

The bound is FLOP/s: S / 4 = 4096 operations a byte at S = 16 384, against
the 240 at which a v5e balances.
"""

from chipbench.flops import mla_flash


def _every_layer(config):
    """``config`` with every layer in the list ``mla_flash`` reads."""
    return dict(config, linear_attn_config={"full_attn_layers": list(
        range(1, config["num_hidden_layers"] + 1))})


def flops_per_step(config, traffic):
    return mla_flash.flops_per_step(_every_layer(config), traffic)


def bytes_per_step(config, traffic):
    return mla_flash.bytes_per_step(_every_layer(config), traffic)
