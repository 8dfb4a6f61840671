"""Operations and bytes the double-gated short convolutions of a training step
must do, from shapes: the ``conv`` layers among the layers the stage runs,
``y = C * conv(B * u)`` on ``hidden_size`` channels with ``conv_L_cache``
taps.

Bytes, a position and channel, bfloat16: forward reads B, C and u and writes
y (4 values); backward reads B, C, u and dy and writes dB, dC and du (7
values): 22 bytes. The taps and their gradient are nothing beside them. The
forward a recomputed operator repeats is not counted, so a share of this
reads low there and never high. Operations, K taps: forward one product for
``B u``, K multiply-adds and the product by C (2 K + 2); backward the same
again, dC, dp, K multiply-adds for dz, dB and du, K for the taps' gradient
(6 K + 5): 31 at 3 taps, 1.4 a byte against the 240 at which a v5e's 197
TFLOP/s and 819 GB/s balance, so the bound is bytes whatever body runs.
"""

from chipbench.flops import lfm2


def _positions_channels(config, traffic):
    layers = sum(kind == "conv" for kind, _ in lfm2.layer_kinds(config))
    return layers * traffic["batch"] * traffic["seq_len"] \
        * config["hidden_size"]


def flops_per_step(config, traffic):
    k = config["conv_L_cache"]
    return _positions_channels(config, traffic) * (8 * k + 7)


def bytes_per_step(config, traffic):
    return _positions_channels(config, traffic) * 11 * 2
