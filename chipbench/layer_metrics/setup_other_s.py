"""Layer: device. What is left of the set-up, process start to the window,
once the import, the trace and lowering and the backend's records are taken
out: the backend's start, the device running the initialisation, the probe,
the reference and the warm-up steps, and the harness's own host work. The
closure of the four parts (``chipbench/setup_profile.py``): they sum to the
window's start less the process's; None where the program keeps no start-up
timeline."""

from chipbench import setup_profile


def metric(facts):
    return setup_profile.part(facts, "other_s")
