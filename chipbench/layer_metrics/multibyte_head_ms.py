"""Layer: functional trainers. Milliseconds of device time a step under the
named scope ``multibyte_head``: the head's product over all ``num_pred_heads``
x ``vocab_size`` columns and the cross-entropy of each prediction head
against its shifted labels, forward and backward together
(``lm_trainer.Decoder._head_losses``). It lies inside ``loss`` and takes its
time out of it. None where the trace has no such scope."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "multibyte_head", "total")
