"""The decode step's share of the chip's bf16 peak: FLOPs of the tokens
decoded in the window (every matmul weight twice, plus attention over
each token's context) over the decode quanta's time."""
from bench import readers


def read(ctx):
    return readers.decode_mfu(ctx)
