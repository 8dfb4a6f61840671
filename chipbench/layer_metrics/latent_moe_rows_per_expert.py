"""Layer: functional trainers. ``moe_e64_rows_per_expert`` for a cell that
holds 8 of 512 experts: the same reader (the trainer's counter of its last
step, the fullest expert layer's held rows over the experts held), under a
name of its own because that entry's ``workloads`` list is the accepted
benchmark's. 352 for a balanced router at 8192 tokens and 22 experts a
token; the deployment brings 16 times the tokens to the same experts."""


def metric(facts):
    return facts["catalog"].module("layer_metrics",
                                   "moe_e64_rows_per_expert").metric(facts)
