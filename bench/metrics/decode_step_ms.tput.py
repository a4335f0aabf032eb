"""Host time of the engine's decode quanta (``decode.chunk`` spans, which
end at the tokens' readback) per decode step, over the window."""
from bench import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
