"""Layer: functional trainers. Seconds from the process's start to the end of
the program's span ``startup/import``: the interpreter, ``import jax``, the
harness's own imports and then the package's import, first line to last. The
``[setup]`` line splits it at the package's first line. One of the four parts
of a run's set-up (``chipbench/setup_profile.py``); None where the program
keeps no start-up timeline."""

from chipbench import setup_profile


def metric(facts):
    return setup_profile.part(facts, "import_s")
