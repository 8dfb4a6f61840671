"""Layer: kernels. Milliseconds of device time a step under the named scope
``gated_conv``: the double-gated short convolution between its two
projections, ``y = C * conv(B * u)`` on the three column ranges of the one
projected array, forward, recomputed forward where the operator is, and
backward together. The scope and not a kernel's name, so it holds whichever
body runs: the Mosaic calls ``gated_conv_fwd`` and ``gated_conv_bwd``, or
XLA's slices, products and the concatenate of the gradient. None where the
trace has no such scope."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "gated_conv", "total")
