"""Layer: kernels. The share of a causal flash call's score tiles, by area,
that the EVA kernels visit at the cell's sequence length, forward and
backward alike: the counter the op computes from the bounds its kernels'
loops run over (``ops/pallas/eva.eva_tiles_visited_pct``), left on the job by
the runner. 20.45 at 16 384 positions, a window of 2048, chunks of 16 and
query blocks of 512 (80 token tiles of 512 and 112 summary tiles of 128 keys
a head, against 528 tiles of 512); a kernel that masked the other windows'
tokens and did not skip them would read 100 and more. None where the job
carries no such counter."""


def metric(facts):
    return getattr(facts["job"], "eva_tiles_visited_pct", None)
