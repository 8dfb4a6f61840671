"""Operations the encoder-decoder translation step requires, from shapes.

After ``paddle_tpu.models.transformer.flops_per_step`` (the original is
listed in PERF.md for a later PR to delete): matmul operations only, a
multiply-add is 2, forward + backward = 3 x forward, decoder self-attention
counted in full (not the causal half), nothing counted for recomputation.
One correction: the original's comment names the cross-attention query and
output projections (4 h^2 per target position) and its formula leaves them
out, 6% of the step at the big model's sizes; they are counted here.
"""


def flops_per_token(config, traffic):
    """Training operations per target position (the cell's token)."""
    h, f = config["d_model"], config["d_ff"]
    s, t = traffic["src_len"], traffic["tgt_len"]
    enc = config["encoder_layers"] * (
        s * (8 * h * h + 4 * h * f) + 4 * s * s * h)
    dec = config["decoder_layers"] * (
        t * (8 * h * h + 4 * h * f) + 4 * t * t * h   # self-attention, ffn
        + t * 4 * h * h + s * 4 * h * h               # cross q,o and k,v
        + 4 * t * s * h)                              # cross scores, context
    logits = 2 * h * config["tgt_vocab_size"] * t
    return 3 * (enc + dec + logits) / t
