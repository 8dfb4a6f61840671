"""Layer: functional trainers. Median host time inside one ``step_fn`` call
(placing the batch on the mesh and enqueueing the step), from the
``step_call`` spans of the measured window. It matters to the rate only once
it nears the step interval."""

import numpy as np


def metric(facts):
    calls = facts["spans"].durations_ms("step_call")
    return float(np.median(calls)) if calls else None
