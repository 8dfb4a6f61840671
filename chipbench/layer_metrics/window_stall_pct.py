"""Layer: functional trainers. The share of the measured window that
``train_tokens_per_s`` does not see. That rate is taken over steady stretches
of the window (``run.step_seconds``), so a stall between two stretches (the
host was not run and the device idled, a save blocked the loop, a step
recompiled) does not move it. This is 1 minus the rate over the whole window,
first completion to last, over ``train_tokens_per_s``. Near 0 while every step
takes the same time; a PR that raises it has slowed the whole-window rate by
that share."""


def metric(facts):
    if not facts.get("window_tokens_per_s"):
        return None
    return 100.0 * (1.0 - facts["window_tokens_per_s"] / facts["tokens_per_s"])
