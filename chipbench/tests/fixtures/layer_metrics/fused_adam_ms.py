"""A per-layer metric that exists only in the fixture: the device time under
``fused_adam``, a name that the fixture configuration ``bert_toy_scoped``
lists under ``"scopes"``. What a later PR does for its own model's parts: a
configuration file, this file and one entry, no trace of its own and no edit
to the harness."""

from chipbench import scope_profile


def metric(facts):
    return scope_profile.ms(facts, "scope_ns", "fused_adam", "total")
