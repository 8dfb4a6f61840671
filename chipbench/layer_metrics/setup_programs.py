"""Layer: functional trainers. Compile requests that asked the cache before
the window, by the program's compile log: each is a program traced, lowered
and then compiled or read (``chipbench/setup_profile.py``). Equals the
``requests`` of the harness's ``compile_cache:`` log line. None where the
program keeps no compile log."""

from chipbench import setup_profile


def metric(facts):
    return setup_profile.part(facts, "programs")
