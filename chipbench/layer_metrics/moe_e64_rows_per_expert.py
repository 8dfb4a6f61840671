"""Layer: functional trainers. Rows that fell on one expert held here in the
last step the job ran (after the window), the fullest expert layer: the
trainer's counter of that step (``step_fn.aux``: the assignments over all the
router's experts, a row an expert layer), the held experts' sum over the
experts held. What the grouped matmul's row tiles are filled with: 2048 for a
balanced router at 4 x 8192 tokens, 4 experts a token and 8 of 64 held; the
deployment brings 8 times the tokens to the same experts. None where the
trainer keeps no such counter."""

import numpy as np


def held_rows(facts):
    """The held experts' assignments in the trainer's last step, an expert
    layer [layers], or None."""
    aux = getattr(facts["job"].step_fn, "aux", None)
    if not aux or "experts_held" not in facts["config"]:
        return None
    first, held = facts["config"]["experts_held"]
    counts = np.asarray(aux[0], dtype=np.float64)        # [layers, experts]
    return counts[:, first:first + held].sum(axis=1)


def metric(facts):
    rows = held_rows(facts)
    return None if rows is None \
        else float(rows.max() / facts["config"]["experts_held"][1])
