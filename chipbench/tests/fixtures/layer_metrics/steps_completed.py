"""A per-layer metric that exists only in the fixture: it shows that a
metric is one new file plus one entry, with no edit to the harness."""


def metric(facts):
    return len(facts["intervals_s"]) + 1
