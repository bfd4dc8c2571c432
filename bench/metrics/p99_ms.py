"""99th percentile latency of the requests scheduled in the window, from
the scheduled arrival to the answer, in ms; a failed request counts as
answered when the wait for answers ended."""
from bench import readers


def read(ctx):
    return readers.percentile_ms(ctx, 99)
