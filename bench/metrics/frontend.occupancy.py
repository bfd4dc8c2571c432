"""Real rows over padded rows of the frontend's dispatches in the window,
in percent (``FrontendStats`` counters)."""
from bench import readers


def read(ctx):
    return readers.occupancy_percent(ctx)
