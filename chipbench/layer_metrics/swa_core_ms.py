"""Layer: kernels. Milliseconds of device time a step under the named scope
``attention_window``: the core of the sliding layers' attention
(``blocks.causal_attention`` with a window: the kernels ``flash_fwd_window``
and ``flash_bwd_window``, the head transposes around them and the sum of a
group's dK and dV parts), forward, recomputed forward and backward together.
It lies inside ``attention_core`` and takes its time out of it, so in a cell
with this scope ``attention_core_ms`` reads the full layers alone."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "attention_window", "total")
