"""Layer: device. Seconds of the set-up inside jax's backend compile: the
union of the compile log's backend records before the window, each the
compile itself or, from the cache, the read and the load of the executable.
One of the four parts of a run's set-up (``chipbench/setup_profile.py``);
None where the program keeps no compile log."""

from chipbench import setup_profile


def metric(facts):
    return setup_profile.part(facts, "compile_s")
