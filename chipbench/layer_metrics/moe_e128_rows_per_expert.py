"""Layer: functional trainers. ``moe_e64_rows_per_expert`` for a cell that
holds 16 of 128 experts: the same reader (the trainer's counter of its last
step, the fullest expert layer's held rows over the experts held), under a
name of its own because that entry's ``workloads`` list is the accepted
benchmark's. 768 for a balanced router at 16 384 tokens and 6 experts a
token; the deployment brings 8 times the tokens to the same experts."""


def metric(facts):
    return facts["catalog"].module("layer_metrics",
                                   "moe_e64_rows_per_expert").metric(facts)
