"""Layer: sharding. Milliseconds a step spends in all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all operations while no other
operation runs on the device, averaged over the cell's devices
(``trace_reduce.py``). 0 by construction on one chip."""


def metric(facts):
    trace = facts["trace"]
    if not trace["devices"]:
        return None
    return trace["collective_exposed_ns"] / trace["steps"] / 1e6
