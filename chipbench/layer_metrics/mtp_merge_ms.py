"""Layer: functional trainers. Milliseconds of device time a step under the
named scope ``mtp_merge``: the multi-token-prediction module's two norms
(of the main model's last state and of the next token's embedding) and the
merge ``W_eh`` [2 hidden, hidden], forward and backward together
(``lm_trainer.Decoder._predict_further``). None where the trace has no such
scope."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "mtp_merge", "total")
