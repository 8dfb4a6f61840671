"""A per-layer metric that exists only in the fixture: one more step from the
state ``facts`` holds. Listed after the metrics that take a trace of their
own through the donating step, it shows they left a state that still runs."""

import math

import numpy as np


def metric(facts):
    job = facts["job"]
    batch = job.draw_batch(np.random.RandomState(0), job.batch)
    loss, *facts["state"] = job.step_fn(*facts["state"], batch)
    return float(math.isfinite(float(loss)))
