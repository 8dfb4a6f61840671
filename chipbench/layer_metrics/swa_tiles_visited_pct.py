"""Layer: kernels. The share of a causal call's score tiles that the windowed
flash kernels visit at the cell's sequence length, forward and backward: the
counter the op computes from the bounds its kernels' loops run over
(``ops/pallas/flash_attention.tiles_visited_pct``), left on the job by the
runner. 11.93 at 16 384 positions, a window of 512 and tiles of 512 (63 of
528 tiles each way); a kernel that masks the band's outside and does not skip
it reads 100. None where the job carries no such counter."""


def metric(facts):
    return getattr(facts["job"], "swa_tiles_visited_pct", None)
