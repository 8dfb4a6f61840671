"""Layer: functional trainers. Rows a step that fall on one expert held here:
the assignments of the reference sample to the held experts, as the runner's
probe counted them during set-up (``kimi_linear.stages``' counts), over the
experts held, the mean over the expert layers, scaled from the sample's
tokens to a step's. What the grouped matmul's row tiles are filled with; the
deployment brings 32 times the tokens to the same experts. None where the job
carries no such count."""


def metric(facts):
    rows = getattr(facts["job"], "held_rows", None)
    if rows is None:
        return None
    sample = facts["job"].sample_sequences * facts["traffic"]["seq_len"]
    return float(rows.mean()) / facts["config"]["experts_held"][1] \
        * facts["job"].tokens_per_step / sample
