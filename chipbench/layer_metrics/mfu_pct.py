"""Layer: functional trainers. Model FLOP/s utilization of the traced run:
its tokens per second, times the operations a token requires
(``flops/<family>.py``), over the cell's chips times the bf16 peak of
``peaks.json``. An end-to-end utilization, not a kernel's roofline share."""


def metric(facts):
    config = facts["config"]
    flops = facts["catalog"].module("flops", config["flops"])
    per_token = flops.flops_per_token(config, facts["traffic"])
    peak = facts["cell"]["chips"] * facts["peak"]["bf16_flops_per_s"]
    return 100.0 * facts["tokens_per_s"] * per_token / peak
