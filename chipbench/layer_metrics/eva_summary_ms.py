"""Layer: functional trainers. Milliseconds of device time a step under the
named scope ``eva_summary``: both chunk softmaxes and weighted sums of every
layer (``paddle_tpu/ops/eva.eva_summaries``: a key's score against a head's
two learned vectors, the softmax over each chunk of 16, the pooled key and
value), forward, recomputed forward and backward together. Plain XLA
fusions: what a kernel for them would have to beat. None where the trace has
no such scope."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "eva_summary", "total")
