"""Query rows served per second of the window, an answer in flight at the
window's close counted by the share of its service inside it
(``readers.window_rate``)."""
from bench import readers


def read(ctx):
    return readers.window_rate(ctx)
