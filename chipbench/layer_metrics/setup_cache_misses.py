"""Layer: device. Of ``setup_programs``, the requests the cache did not hold
and the backend compiled for real: 0 in a run from the cache, all of them in a
cold one, so a half-cold traced run shows at a glance
(``chipbench/setup_profile.py``). None where the program keeps no compile
log."""

from chipbench import setup_profile


def metric(facts):
    return setup_profile.part(facts, "cache_misses")
