"""Layer: kernels. ``flash_roofline_pct`` for a cell whose attention is
causal at head size 128: the same reader (it takes ``head_dim`` and
``"attention": "causal"`` from the configuration through ``flops/flash.py``),
under a name of its own because that entry's ``workloads`` list is the
accepted benchmark's."""


def metric(facts):
    return facts["catalog"].module("layer_metrics",
                                   "flash_roofline_pct").metric(facts)
