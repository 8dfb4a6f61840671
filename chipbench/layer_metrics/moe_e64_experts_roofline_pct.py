"""Layer: kernels. The held experts' share of their roofline where 8 of 64
are held: the least time the chip could take for their matmuls on the rows
they took (``flops/moe_e64_experts.py``, exact for those rows since nothing
is dropped; bound by FLOP/s) over the device time of the named scope
``moe_experts``. The rows are the trainer's counter of the last step it ran,
the last traced one (``step_fn.aux``: the assignments over all the router's
experts, a row an expert layer), which stands for each of the traced steps:
the selection biases are at rest and the batches follow one law, so a step's
rows differ from the next one's by a hundredth. The scope holds the forward
the expert layer's backward repeats, the SiLU gate and the weights' casts
beside the counted products, so the share reads low and never high. None
where the trainer keeps no such counter or the trace no such scope."""

from chipbench import scope_profile


def metric(facts):
    rows = facts["catalog"].module(
        "layer_metrics", "moe_e64_rows_per_expert").held_rows(facts)
    measured_ms = scope_profile.ms(facts, "scope_ns", "moe_experts", "total")
    if rows is None or not measured_ms:
        return None
    flops = facts["catalog"].module("flops", "moe_e64_experts") \
        .flops_per_step(facts["config"], facts["traffic"], int(rows.sum()))
    least_s = flops / facts["cell"]["chips"] \
        / facts["peak"]["bf16_flops_per_s"]
    return 100.0 * least_s / (measured_ms / 1e3)
