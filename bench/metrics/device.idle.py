"""Share of the traced window in which no operation ran on the device, in
percent."""
from bench import readers


def read(ctx):
    return readers.idle_percent(ctx)
