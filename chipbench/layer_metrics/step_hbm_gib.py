"""Layer: device. GiB one device needs to run the compiled step, by the
compiler's ``memory_analysis()`` on the chip: arguments + temporaries +
outputs less the donated. The runtime's ``peak_bytes_in_use`` does not see an
executable's temporaries (PERF.md, PR 21), so this is the level to watch; a
v5e refuses a step past 15.75."""


def metric(facts):
    return facts["step_bytes"] / 2**30
