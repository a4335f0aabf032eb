"""Share of the decode slots that held a live row, over the window's
decode quanta, weighted by their steps: admission shows here as slots
standing empty while a prompt is prefilled."""
from bench import programs


def read(ctx):
    return programs.slot_occupancy(ctx)
