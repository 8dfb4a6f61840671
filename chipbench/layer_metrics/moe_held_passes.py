"""Layer: functional trainers. The passes over held rows the last step ran,
summed over its expert layers: ``moe.held_passes`` (the function that sets
the trip count of ``moe._held_experts``' loop) of the rows each layer held,
at the tile ``moe._held_row_tile`` gives that layer, from the counter the
trainer keeps of its last step (``step_fn.aux[0]``: ``dropless_moe_ffn``'s
counts over all the router's experts, a row an expert layer) and the
configuration's ``experts_held``. One a layer while the held share is under
twice par; the number between a step's held share and its time. The backward
runs as many again, and a pass costs whatever its fill. None where the
trainer keeps no such counter, the configuration holds every expert, or the
program has no ``held_passes``."""

import numpy as np


def metric(facts):
    from paddle_tpu.parallel import moe

    aux = getattr(facts["job"].step_fn, "aux", None)
    if not aux or "experts_held" not in facts["config"] \
            or not hasattr(moe, "held_passes"):
        return None
    counts = np.asarray(aux[0], dtype=np.int64)          # [layers, experts]
    first, held = facts["config"]["experts_held"]
    tiles = [moe._held_row_tile(int(assignments), held, counts.shape[1])
             for assignments in counts.sum(axis=1)]
    return float(moe.held_passes(counts[:, first:first + held].sum(axis=1),
                                 np.asarray(tiles)).sum())
