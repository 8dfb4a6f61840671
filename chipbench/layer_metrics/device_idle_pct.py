"""Layer: device. 100 x (1 - busy / window): busy is the union of the
intervals in which an operation runs on the device, the window spans the
traced whole steps; averaged over the cell's devices."""


def metric(facts):
    trace = facts["trace"]
    if not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_ns"] / trace["window_ns"])
