"""Layer: functional trainers. The share of a step's (token, expert)
assignments that fell on the experts held here, in the last step the job
ran (after the window, so it shows what the window did to the router), the
fullest expert layer: the counter the trainer keeps of its last step
(``step_fn.aux``: ``dropless_moe_ffn``'s counts over all the router's
experts, a row an expert layer). A balanced router sends ``held / router
width`` of them here (3.125% for 8 of 256); the expert layer's passes, and
with them its time, follow this number, and a router drawn to the held
experts (one chip's part of its gradient does that; PERF.md section 6, PR
30) shows here before it shows in the rate. None where the trainer keeps no
such counter."""

import numpy as np


def metric(facts):
    aux = getattr(facts["job"].step_fn, "aux", None)
    if not aux or "experts_held" not in facts["config"]:
        return None
    counts = np.asarray(aux[0], dtype=np.float64)        # [layers, experts]
    first, held = facts["config"]["experts_held"]
    return float(100.0 * (counts[:, first:first + held].sum(axis=1)
                          / counts.sum(axis=1)).max())
