"""Host time the window lost to the runtime: Python traces, lowerings
and compiles of JAX programs (``jax.*`` spans) and garbage collections
of 1 ms or more (``py.gc``), counted once where they nest."""
from bench import programs


def read(ctx):
    return programs.host_stall_ms(ctx)
