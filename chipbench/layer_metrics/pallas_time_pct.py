"""Layer: kernels. Share of the device's busy time spent inside Mosaic custom
calls (the Pallas bodies), from the trace."""


def metric(facts):
    trace = facts["trace"]
    if not trace["devices"]:
        return None
    return 100.0 * trace["mosaic_ns"] / trace["busy_ns"]
