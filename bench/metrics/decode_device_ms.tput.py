"""Device time of the decode quantum (the ``jit_decode_quantum`` events
of the trace's "XLA Modules" line) per decode step, over the quanta of
the traced window.  ``decode_step_ms`` less this is the host's and
prefill's share of a step."""
from bench import programs


def read(ctx):
    return programs.decode_device_ms(ctx)
