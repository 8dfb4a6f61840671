"""Layer: functional trainers. How uneven the experts' load is: the fullest
expert's assignments over the mean, the largest over the layers, from the
counts the runner's probe left on the job during set-up
(``olmoe.routing_stats`` on the reference sample). 1 is a perfectly balanced
router; the grouped matmul's work does not depend on it, its tiles' fill
does. None where the job carries no counts."""


def metric(facts):
    counts = getattr(facts["job"], "routing_counts", None)
    if counts is None:
        return None
    return float((counts.max(axis=-1) / counts.mean(axis=-1)).max())
