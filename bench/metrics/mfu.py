"""Model FLOP/s utilisation of the whole step: model FLOPs per token
(``bench.counts.flops_per_token``: no recomputation, tied embeddings
once) times the untraced window's tokens per second per chip, over the
chip's published bf16 peak, in percent."""


def read(ctx):
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s_per_chip"] \
        / ctx["peaks"].bf16_flops
