"""Layer: device. The share of the device's busy time in operations whose
name stack holds no scope of the vocabulary (``scope_profile.SCOPES``), among
them those with no name stack at all (copies the compiler made): what the
by-scope metrics cannot see. The operations are listed by label in
``chiprun_out/chipbench/<cell>.scopes.json`` (``unscoped_ops``)."""

from chipbench import scope_profile


def metric(facts):
    reduced = scope_profile.profile(facts)
    if reduced is None:
        return None
    return 100.0 * reduced["unscoped_ns"] / reduced["busy_ns"]
