"""Layer: functional trainers. ``moe_held_share_pct`` for a cell that holds
8 of 512 experts in six LatentMoE layers, the MTP module's the last: the same
reader (the trainer's counter of its last step, the fullest expert layer),
under a name of its own because that entry's ``workloads`` list is the
accepted benchmark's. 1.5625 for a balanced router."""


def metric(facts):
    return facts["catalog"].module("layer_metrics",
                                   "moe_held_share_pct").metric(facts)
