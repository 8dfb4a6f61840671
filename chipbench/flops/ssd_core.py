"""Operations and bytes the chunked state-space scan
(``paddle_tpu/ops/ssd.py``, scope ``ssd_core``) of a training step must do,
from shapes, at the configuration's ``chunk_size``.

Per position and head of size P with a state of N and a chunk of C, forward,
a multiply-add as 2: the group's scores ``C_i . B_j`` inside the chunk at
half the square (the positions up to the query's own), ``C N`` shared by the
``heads / groups`` heads of a group; the decayed scores times ``dt x`` at
half the square, ``C P``; what the position adds to the chunk's state, ``2 P
N``; and the starting state's part of its output, ``2 P N``: ``C N / (heads
/ groups) + C P + 4 P N``. Backward twice that. The op forms the whole
``[C, C]`` squares and masks them, and a recomputed mixer runs the forward
twice: neither is counted, nor are the exponentials (``C`` a position and
head), so the share reads low where those take the time, and never high.

Bytes: what the op must read and write once in each direction, a position
and head: x (bf16, P), dt and the log-decay (float32), the head's share of
its group's B and C (bf16, ``2 N / (heads / groups)``) read, y (bf16, P)
written; backward the same read again with y's gradient, and the five
gradients written. The chunks' starting states and the ``[C, C]`` decays,
which this body keeps for autodiff, are its own choice and are not counted.

At P = 64, N = 128, C = 128 and 16 heads a group that is 41 984 x 3
operations and 760 bytes a position and head: 166 operations a byte, under
the 240 at which a v5e's 197 TFLOP/s and 819 GB/s balance, so the bound is
bytes.
"""


def _positions_heads(config, traffic):
    layers = config["hybrid_override_pattern"][
        :config["num_hidden_layers"]].count("M")
    return (layers * traffic["batch"] * traffic["seq_len"]
            * config["mamba_num_heads"])


def flops_per_step(config, traffic):
    c, p, n = (config[k] for k in ("chunk_size", "mamba_head_dim",
                                   "ssm_state_size"))
    per_group = config["mamba_num_heads"] // config["n_groups"]
    return 3 * _positions_heads(config, traffic) \
        * (c * n // per_group + c * p + 4 * p * n)


def bytes_per_step(config, traffic):
    p, n = config["mamba_head_dim"], config["ssm_state_size"]
    per_group = config["mamba_num_heads"] // config["n_groups"]
    operands = 2 * p + 4 + 4 + 2 * 2 * n // per_group
    forward = operands + 2 * p
    backward = operands + 2 * p + operands
    return _positions_heads(config, traffic) * (forward + backward)
