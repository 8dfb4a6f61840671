"""Layer: functional trainers. Seconds of the set-up in which jax traced or
lowered a function: the union of the compile log's trace and lowering records
before the window (Python; the Mosaic lowering of the kernels is inside a
lowering record), less what the import and the backend's records cover. The
``[setup]`` lines name the five functions with most self seconds of it. One
of the four parts of a run's set-up (``chipbench/setup_profile.py``); None
where the program keeps no compile log."""

from chipbench import setup_profile


def metric(facts):
    return setup_profile.part(facts, "trace_lower_s")
